"""The trace reduction on small traces: a hand-made one with hand-worked
numbers, and (where the chip run left one) a trimmed recorded TPU trace."""
import os

import pytest

from benchmarks import xtrace as X

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, 'data', 'trace_recorded.json')


def _ev(name, start_us, dur_us, **stats):
    return [name, start_us * 1e-6, dur_us * 1e-6, stats]


def _trace():
    ops = [
        _ev('%fusion.1 = bf16[4,1024]{1,0:T(8,128)(2,1)} fusion(bf16[4,8]{1,0} %p.1), kind=kLoop', 0, 100),
        _ev('all-gather.2', 100, 50),
        _ev('fusion.3', 150, 100),
        # an async collective overlapped by compute: busy time counts once
        _ev('all-reduce-start.4', 250, 10),
        _ev('fusion.5', 260, 80),
        _ev('all-reduce-done.4', 340, 20),
        # a 200 us idle gap while the host was in bench.loss_read
        _ev('%jvp_jit_flash_attention__.6 = bf16[4,16,1024,128]{3,2,1,0} custom-call(bf16[4,16,1024,128]{3,2,1,0} %fusion.5), custom_call_target="tpu_custom_call"', 560, 40),
        # an op that only READS a custom call's result is not one
        # a 30 us gap: under the 50 us floor, not attributed
        _ev('%custom-call.7 = bf16[4,16]{1,0} custom-call(bf16[4,16]{0,1} %jvp_jit_flash_attention__.6), custom_call_target="ConcatBitcast"', 630, 70),
    ]
    ops.append(_ev('%while.9 = (s32[]{:T(128)}) while((s32[]) %t), body=%b', 0, 360))
    modules = [_ev('jit_decode_step(123)', 0, 360),
               _ev('jit_prefill_512(9)', 560, 140)]
    host = [_ev('bench.train_interval', 0, 800),
            _ev('bench.loss_read', 350, 220),
            _ev('unrelated', 0, 1000)]
    return {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Modules', 'events': modules},
            {'name': 'XLA Ops', 'events': ops},
            {'name': 'Steps', 'events': [_ev('step', 0, 700)]}]},
        {'name': '/host:CPU', 'lines': [{'name': 'python', 'events': host}]},
    ]}


def test_busy_union_idle_share_and_window():
    r = X.reduce(_trace())
    assert r['devices'] == 1
    assert r['window_s'] == pytest.approx(700e-6)
    assert r['busy_s'] == pytest.approx((360 + 40 + 70) * 1e-6)
    assert r['idle_share0'] == pytest.approx(1 - 470 / 700)


def test_gap_attribution_names_the_innermost_host_span():
    gaps = dict(X.reduce(_trace())['idle_gaps'])
    assert gaps == {'bench.loss_read': pytest.approx(200e-6)}


def test_per_op_labels_modules_and_custom_calls():
    r = X.reduce(_trace())
    ops = dict(r['device_ops'])
    assert ops['fusion.1_bf16_4_1024'] == pytest.approx(100e-6)
    assert not [k for k in ops if k.startswith('while')]   # a container
    t, n = X.module_time(r, 'decode')
    assert (t, n) == (pytest.approx(360e-6), 1)
    t, n = X.time_of(r['events0'], X.is_custom_call)
    assert (t, n) == (pytest.approx(40e-6), 1)
    flash = [e for e in r['events0'] if X.is_custom_call(e)]
    assert X.kernel_name(flash[0]) == 'jvp_jit_flash_attention__'
    assert len(r['device_ops']) <= 10


@pytest.mark.parametrize('intervals,want', [
    ([[3, 4], [0, 2], [1, 3]], [[0, 4]]),
    ([[0, 1], [2, 3]], [[0, 1], [2, 3]]),
    ([[0, 5], [1, 2], [5, 5]], [[0, 5]]),
    ([], [])])
def test_interval_union(intervals, want):
    assert X.union(intervals) == want
    assert X.total(X.union(intervals)) == sum(e - s for s, e in want)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        X.reduce({'planes': [{'name': '/host:CPU', 'lines': []}]})


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason='no recorded TPU trace kept with the tests')
def test_recorded_tpu_trace_reduces():
    r = X.reduce(X.load(RECORDED))
    assert r['busy_s'] > 0 and 0 <= r['idle_share0'] < 1
    assert r['device_ops'] and all(t > 0 for _, t in r['device_ops'])
