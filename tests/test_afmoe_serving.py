"""`nlp/afmoe.py` served: two slots at different positions against the
plain float32 reference, the engine's other layouts and modes (pages,
prefix cache, chunks, speculation, adapters), the expert and decode
kernels interpreted, and what the engine says about itself. The family,
its tolerance and its reason are `tests/test_afmoe.py`'s, the shared
cases `tests/family_harness.py`'s (a file of its own so that no worker
of the suite carries both)."""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.nlp import afmoe
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.serving.adapters import AdapterBank, make_adapter_factors

import family_harness as H
from family_harness import TOL
from test_afmoe import FAM

built, tiny = H.fixtures(FAM)


def _serve(model, requests, **extra):
    eng = H.engine(model, **extra)
    hs = [eng.submit(p, H.greedy(n), **sub) for p, n, sub in requests]
    eng.run()
    assert all(h.error is None for h in hs)
    return [list(h.tokens) for h in hs], eng


def _requests(seed=1):
    rs = np.random.RandomState(seed)
    return [(rs.randint(3, 128, n).tolist(), m, {})
            for n, m in ((5, 30), (19, 22), (11, 25))]


def test_through_the_engine_two_slots_at_different_positions(built):
    cfg, w, model = built
    reqs = _requests()
    toks, _ = _serve(model, reqs)
    for (prompt, n, _), got in zip(reqs, toks):
        assert len(got) == n
        assert FAM.served_gap(cfg, w, prompt, got) < TOL


# ---------------------------------------------------------------------------
# the engine's other layouts and modes with this model
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def base(tiny):
    """What the plain engine serves `_requests()`."""
    return _serve(tiny[2], _requests())[0]


@pytest.mark.parametrize('extra', [
    dict(kv_page_size=8), dict(prefix_cache=True),
    dict(prefill_chunk_tokens=16)],
    ids=['paged', 'prefix_cache', 'chunked_prefill'])
def test_engine_modes_serve_the_same_tokens(tiny, base, extra):
    """Paged pool, prefix cache and chunked prefill hand the model other
    masks, rows and offsets; every window layer narrows them by itself."""
    model = tiny[2]
    assert _serve(model, _requests(), **extra)[0] == base


def test_prefix_cache_hit_serves_the_same_tokens(tiny):
    cfg, w, model = tiny
    rs = np.random.RandomState(4)
    shared = rs.randint(3, 128, 20).tolist()
    reqs = [(shared + rs.randint(3, 128, 4).tolist(), 12, {})
            for _ in range(3)]
    eng = H.engine(model, num_slots=4, prefix_cache=True)
    toks = []
    for prompt, n, _ in reqs:       # one after another: the later ones hit
        h = eng.submit(prompt, H.greedy(n))
        eng.run()
        toks.append(list(h.tokens))
    assert eng.prefix_cache.stats()['hits'] >= 1
    for (prompt, _, _), got in zip(reqs, toks):
        assert FAM.served_gap(cfg, w, prompt, got) < TOL


def test_speculative_decoding_equals_plain_greedy(tiny, base):
    model = tiny[2]
    paddle.seed(3)
    draft = AfmoeForCausalLM(AfmoeConfig.tiny(
        num_hidden_layers=2,
        layer_types=[afmoe.SLIDING, afmoe.FULL])).eval()
    toks, eng = _serve(model, _requests(), draft_model=draft,
                       num_draft_tokens=3)
    assert toks == base and eng.stats()['spec']['rounds'] > 0


def test_adapters_on_the_attention_projections(tiny, base):
    """A bank over q/k/v/o: a request without an adapter is served as by
    a bank-less engine, one with an adapter differently, and alone as
    in company."""
    model = tiny[2]
    bank = AdapterBank(model, capacity=2, rank=4, targets=(
        'q_proj', 'k_proj', 'v_proj', 'o_proj'))
    bank.load('a', make_adapter_factors(bank, seed=1, scale=0.5))
    reqs = _requests()
    mixed = [reqs[0], (reqs[1][0], reqs[1][1], {'adapter_id': 'a'}),
             reqs[2]]
    toks, _ = _serve(model, mixed, adapter_bank=bank)
    alone, _ = _serve(model, [mixed[1]], adapter_bank=bank)
    assert toks[0] == base[0] and toks[2] == base[2]
    assert toks[1] != base[1] and toks[1] == alone[0]


# every slot busy: a block is dispatched before the one in flight is
# fetched (ISSUE 45), and the tokens are the serial order's
test_ahead_of_the_fetch_the_engine_serves_the_serial_orders_tokens = \
    H.ahead_serves_the_serial_tokens(FAM)


# ---------------------------------------------------------------------------
# what the engine says about itself
# ---------------------------------------------------------------------------
def test_decode_round_carries_routing_and_row_counts(tiny):
    cfg, _, model = tiny
    log = H.cleared_log()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_moe_experts_touched_total')
    _serve(model, _requests())
    rounds = H.rounds(log)
    assert rounds
    layers = cfg['num_hidden_layers'] - cfg['num_dense_layers']
    for a in rounds:
        assert a['experts'] == cfg['num_experts']
        assert a['expert_layer_substeps'] == 4 * layers
        assert a['expert_kernel_substeps'] == 0     # the CPU runs the loop
        # a token picks k distinct experts; active slots pick at most
        # active * k, and never more than there are
        lo = cfg['num_experts_per_tok'] * a['expert_layer_substeps']
        hi = min(a['active'] * cfg['num_experts_per_tok'],
                 cfg['num_experts']) * a['expert_layer_substeps']
        assert lo <= a['experts_touched'] <= hi
        assert a['rows'] in (32, 64)
        assert a['read_rows'] == 2 * a['rows'] * cfg['num_hidden_layers']
        assert 0 < a['needed_rows'] <= a['real_rows'] * 5
    # a lone slot past the window: four layers need 8 rows, one all
    lone = [a for a in rounds if a['active'] == 1 and a['real_rows'] > 8]
    assert lone and all(
        a['needed_rows'] == 4 * 8 + a['real_rows'] for a in lone)
    assert reg.value('paddle_serving_moe_experts_touched_total') - before \
        == sum(a['experts_touched'] for a in rounds)


test_the_kernel_serves_the_loops_tokens_and_says_it_ran = \
    H.expert_kernel_serves_the_loops_tokens(FAM)


def _walked(length, window, tile=16):
    """Rows a decoding slot of `length` rows walks on a layer that sees
    the newest `window` (None: all) in tiles of `tile`, by hand."""
    first = max(length - window, 0) if window else 0
    return ((length - 1) // tile - first // tile + 1) * tile


def _a_window_layer_walks_the_tiles_its_window_touches(cfg, eng, rounds,
                                                       calls):
    """On the full layer the decoding slot's length rounded up to the
    tile, on a window layer only the tiles its window of 8 touches —
    one, or two across an edge, wherever the slot stands — and ONE tile
    a layer of the slot that is not decoding."""
    windows = eng.model.attention_windows()
    assert eng._bounded_tiles(64).tolist() == [16] * len(windows)
    assert eng._bounded_tiles(32).tolist() == [16] * len(windows)
    assert len(calls) == 2 * len(windows)       # a call a layer, traced
    spans = set()
    for a in rounds:
        # needed: min(length, 8) on the window layers, length on the full
        n_win = sum(w_ is not None for w_ in windows)
        length = next(n for n in range(1, 65) if n_win * min(n, 8)
                      + (len(windows) - n_win) * n == a['needed_rows'])
        assert a['read_rows'] == sum(_walked(length, w_) + 16
                                     for w_ in windows)
        assert a['needed_rows'] <= a['read_rows'] \
            < 2 * a['rows'] * len(windows)
        spans.add(_walked(length, 8) // 16)
    assert spans == {1, 2}      # a window inside a tile, and across an edge


test_decode_through_the_kernel_agrees_with_the_reference = \
    H.decode_through_the_kernel(
        FAM, _a_window_layer_walks_the_tiles_its_window_touches,
        presets=('tiny_rep4',))


def test_a_model_without_experts_returns_what_it_returned():
    model = H.llama(5)
    log = H.cleared_log()
    _, eng = _serve(model, _requests())
    a = H.rounds(log)[-1]
    assert 'experts_touched' not in a and 'experts' not in a
    assert 'expert_kernel_substeps' not in a
    assert a['rows'] == 64 and a['read_rows'] == 2 * 64 * 2
    assert a['needed_rows'] == a['real_rows'] * 2      # no window layer
    out = jax.eval_shape(eng._decode_block_fn, *eng._decode_args())
    assert len(out) == 2                                # tokens, pool


def test_expert_scopes_are_on_the_decode_program(tiny):
    model = tiny[2]
    _serve(model, _requests())
    found = H.scopes_found('serving.decode_block')
    assert {'moe/router', 'moe/experts', 'moe/shared', 'attention',
            'kv_write', 'mlp', 'norm'} <= found
    assert programs.scope_path(
        'jit(f)/while/body/moe/experts/while/body/dot_general') \
        == ('moe/experts',)
