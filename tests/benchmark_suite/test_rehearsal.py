"""One toy-size CPU rehearsal of each traffic kind, end to end, each in a
process of its own: the output line's keys, the cell's metrics, and that
`correct` comes out true on sound code. They prove nothing about speed:
the device says `cpu`."""
import pytest

import _toy

KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}
E2E = {
    'toy-train': {'train_tokens_per_s_chip', 'setup_s'},
    'toy-chat': {'ttft_p95_ms', 'tpot_p50_ms', 'setup_s'},
    'toy-docs': {'out_tokens_per_s', 'tpot_p50_ms', 'setup_s'},
}
# what a traced run can report without a device trace (a CPU has none)
LAYER = {
    'toy-train': {'train_step_ms', 'train_steady_tokens_per_s_chip',
                  'train_compiles_in_window'},
    'toy-chat': {'loadgen_late_p95_ms', 'queue_wait_p50_ms',
                 'ttft_tail_queue_share',
                 'kv_real_rows_share', 'serve_compiles_in_window'},
    'toy-docs': {'kv_real_rows_share', 'serve_compiles_in_window'},
}


@pytest.fixture(scope='module')
def root(tmp_path_factory):
    return _toy.make_root(tmp_path_factory.mktemp('toy'))


@pytest.mark.parametrize('cell', sorted(E2E))
def test_end_to_end_run(root, cell):
    out, lines = _toy.run_toy(root, cell)
    assert set(out) == KEYS
    assert out['correct'] is True, lines[-12:]
    assert out['failed'] == 0 and out['attempted'] > 0
    assert set(out['metrics']) == E2E[cell]
    assert all(m['value'] > 0 for m in out['metrics'].values())
    assert out['device']['platform'] == 'cpu'
    assert set(out['device']) >= {'platform', 'kind', 'count',
                                  'memory_peak_bytes'}
    assert _toy.logged(lines, 'check ')          # numbers beside limits


@pytest.mark.parametrize('cell', sorted(LAYER))
def test_traced_run_reports_the_layers_metrics(root, cell):
    out, lines = _toy.run_toy(root, cell, seed=12, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert LAYER[cell] <= set(out['metrics'])
    assert not set(out['metrics']) & E2E[cell]
    for name in ('train_compiles_in_window', 'serve_compiles_in_window'):
        if name in out['metrics']:
            assert out['metrics'][name]['value'] == 0
