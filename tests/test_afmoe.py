"""`nlp/afmoe.py` against its plain float32 reference
(`benchmarks/reference/afmoe.py`) at the tiny presets, with seeded
weights whose `expert_bias` is not zero.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (sorted blocks of one expert against every expert
for every token; a cache against a full forward; grouped against
repeated KV heads). Observed at most 1e-5 on logits as large as 7; the
mildest departure from the published mathematics moves a logit by more
than 1e-2. 2e-4 lies between with room on both sides."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import _dispatch
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import functional_state
from paddle_tpu.nlp import afmoe
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import _attention_xla
from paddle_tpu.serving import InferenceEngine, SamplingParams
from paddle_tpu.serving.adapters import AdapterBank, make_adapter_factors

from benchmarks.models import adapter, fill
from benchmarks.reference import afmoe as R
from benchmarks.reference import common as C

TOL = 2e-4
AD = adapter('AfmoeForCausalLM')
PRESETS = ('tiny', 'tiny_rep4')


def _cfg(preset):
    conf = getattr(AfmoeConfig, preset)()
    return {k: getattr(conf, k) for k in AD._KEYS}


def _weights(cfg, seed=7):
    # std 0.3: logits of a few units, so a departure is not lost in them
    return C.make_weights(R.param_shapes(cfg), seed, 'float32', std=0.3)


def _model(cfg, w):
    return fill(AD.build(cfg), w, AD.name_map(cfg)).eval()


def _ref_logits(cfg, w, ids):
    ids = jnp.asarray(np.atleast_2d(ids), jnp.int32)
    return np.asarray(R.logits_of(cfg, w, R.hidden_states(cfg, w, ids)))


def _ids(shape, seed=0):
    return np.random.RandomState(seed).randint(3, 128, shape).astype('int32')


@pytest.fixture(scope='module', params=PRESETS)
def built(request):
    cfg = _cfg(request.param)
    w = _weights(cfg)
    return cfg, w, _model(cfg, w)


def test_full_forward_agrees_with_the_reference(built):
    cfg, w, model = built
    ids = _ids((2, 40))          # five windows long
    got = model(paddle.to_tensor(ids)).numpy()
    assert np.abs(got - _ref_logits(cfg, w, ids)).max() < TOL


def test_bucketed_prefill_then_decode_past_twice_the_window(built):
    """The engine's own order: a prompt right-padded to its bucket is
    prefilled into a zero row, the last prompt token is forwarded again
    at its slot, then one token at a time — past 1x and 2x the window
    of 8, under the engine's slot-causal mask."""
    cfg, w, model = built
    fwd = cached_forward(model, *functional_state(model))
    ids, n_prompt, bucket, length = _ids((1, 40), 3), 11, 16, 48
    ref = _ref_logits(cfg, w, ids)
    padded = np.zeros((1, bucket), 'int32')
    padded[:, :n_prompt] = ids[:, :n_prompt]
    _, cache = fwd(jnp.asarray(padded), model.init_cache(1, length),
                   jnp.int32(0), jnp.int32(0), None)
    k_slot = jnp.arange(length)
    worst = 0.0
    for t in range(n_prompt - 1, 40):
        pos = jnp.full((1,), t, jnp.int32)
        mask = (k_slot[None, :] <= pos[:, None])[:, None, None, :]
        lg, cache = fwd(jnp.asarray(ids[:, t:t + 1]), cache, pos, pos, mask)
        worst = max(worst, np.abs(np.asarray(lg)[0, 0] - ref[0, t]).max())
    assert worst < TOL


def _serve(model, requests, **extra):
    kw = dict(num_slots=2, max_length=64, decode_block=4,
              buckets=[16, 32], eos_token_id=-1)
    kw.update(extra)
    eng = InferenceEngine(model, **kw)
    hs = [eng.submit(p, SamplingParams(max_new_tokens=n, eos_token_id=-1),
                     **sub) for p, n, sub in requests]
    eng.run()
    assert all(h.error is None for h in hs)
    return [list(h.tokens) for h in hs], eng


def _requests(seed=1):
    rs = np.random.RandomState(seed)
    return [(rs.randint(3, 128, n).tolist(), m, {})
            for n, m in ((5, 30), (19, 22), (11, 25))]


def _served_gap(cfg, w, prompt, toks):
    """How far a served token's reference logit lies below the
    reference's best at its position: the benchmark's comparison."""
    lg = _ref_logits(cfg, w, prompt + toks[:-1])[0, len(prompt) - 1:]
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


def test_through_the_engine_two_slots_at_different_positions(built):
    cfg, w, model = built
    reqs = _requests()
    toks, _ = _serve(model, reqs)
    for (prompt, n, _), got in zip(reqs, toks):
        assert len(got) == n
        assert _served_gap(cfg, w, prompt, got) < TOL


# ---------------------------------------------------------------------------
# each departure from the published mathematics fails the tolerance
# ---------------------------------------------------------------------------
def _route_bias_in_weight(scores, bias, k, route_norm, route_scale, eps):
    biased = scores + bias.astype(jnp.float32)
    w, sel = jax.lax.top_k(biased, k)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * route_scale


def _experts_with_capacity(x, sel, w, gate_w, up_w, down_w):
    """Every expert keeps its first `capacity` picks and drops the rest,
    as a layer with `[T, E, C]` dispatch does."""
    t, k = sel.shape
    capacity = -(-t * k // gate_w.shape[0])
    flat = sel.reshape(-1)
    rank = jnp.sum((flat[None, :] == flat[:, None])
                   & (jnp.arange(t * k)[None, :] < jnp.arange(t * k)[:, None]),
                   axis=1)
    kept = jnp.where((rank < capacity).reshape(t, k), w, 0.0)
    return _GROUPED(x, sel, kept, gate_w, up_w, down_w)


_GROUPED = afmoe.grouped_experts       # the sound one, before any patch


def _no_window(model, mp):
    for layer in model.model.layers:
        layer.self_attn.window = None


def _rope_on_full(model, mp):
    model.model.layers[-1].self_attn.rotary = True


def _bias_in_weight(model, mp):
    mp.setattr(afmoe, 'route', _route_bias_in_weight)


def _no_route_norm(model, mp):
    model.config.route_norm = False


def _no_route_scale(model, mp):
    model.config.route_scale = 1.0


def _no_gate(model, mp):
    mp.setattr(afmoe.AfmoeAttention, '_gated',
               lambda self, out, hidden: out)


def _no_sqrt_h(model, mp):
    model.config.mup_enabled = False


def _dropped_token(model, mp):
    mp.setattr(afmoe, 'grouped_experts', _experts_with_capacity)


@pytest.fixture
def fresh_dispatch():
    """The eager dispatch cache keys an op by its code, not by the
    module globals a departure patches: empty it around such a test."""
    _dispatch.clear()
    yield
    _dispatch.clear()


DEPARTURES = [None, _no_window, _rope_on_full, _bias_in_weight,
              _no_route_norm, _no_route_scale, _no_gate, _no_sqrt_h,
              _dropped_token]


@pytest.mark.parametrize(
    'departure', DEPARTURES,
    ids=lambda d: 'sound' if d is None else d.__name__.strip('_'))
def test_each_departure_fails_the_tolerance_the_sound_model_passes(
        departure, monkeypatch, fresh_dispatch):
    """Under a bias that makes EVERY token pick expert 0 — the most
    uneven routing there is, where a layer with a capacity drops
    tokens — the sound model agrees with the reference, and each single
    departure does not."""
    cfg = _cfg('tiny')
    w = dict(_weights(cfg, seed=11))
    for i in range(cfg['num_dense_layers'], cfg['num_hidden_layers']):
        w[f'l{i}.expert_bias'] = w[f'l{i}.expert_bias'].at[0].set(100.0)
    ids = _ids((2, 40), 5)
    ref = _ref_logits(cfg, w, ids)
    model = _model(cfg, w)
    if departure is not None:
        departure(model, monkeypatch)
    err = np.abs(model(paddle.to_tensor(ids)).numpy() - ref).max()
    if departure is None:
        assert err < TOL
    else:
        assert err > 50 * TOL, (departure.__name__, err)


# ---------------------------------------------------------------------------
# the expert layer and the grouped attention, each against its plain form
# ---------------------------------------------------------------------------
def _experts_by_loop(x, sel, w, gate_w, up_w, down_w):
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for j in range(sel.shape[1]):
            e = int(sel[t, j])
            g, u = x[t] @ gate_w[e], x[t] @ up_w[e]
            out[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ down_w[e])
    return out


def _picks(rs, tokens, e, k, routing):
    """[tokens, k] distinct picks a row: `random`; `one_expert` (every
    row's first pick is expert 3); `distinct` (no expert picked twice in
    the batch); `few` (every row picks among the same k + 1 experts, so
    most of the layer is left untouched)."""
    if routing == 'distinct':
        return rs.permutation(e)[:tokens * k].reshape(tokens, k)
    pool = e if routing != 'few' else k + 1
    sel = np.stack([rs.permutation(pool)[:k] for _ in range(tokens)])
    if routing == 'one_expert':
        sel[:, 0] = 3
        sel[:, 1] = np.where(sel[:, 1] == 3, 4, sel[:, 1])
    return sel


@pytest.mark.parametrize('tokens,block_rows,routing,experts,kernel', [
    (5, 256, 'random', (8, 2), False),   # a decode batch: one block of 8
    (8, 256, 'one_expert', (8, 2), False),
    (70, 16, 'random', (8, 2), False),   # a prefill: several blocks an expert
    (70, 16, 'one_expert', (8, 2), False),
    # the kernel, interpreted, at the two cells' decode geometries
    (8, 256, 'random', (128, 8), True),      # serve-moe-docs: 64 picks of 128
    (32, 256, 'random', (64, 4), True),      # serve-hybrid-reason: 128 of 64
    (8, 256, 'one_expert', (128, 8), True),
    (8, 256, 'distinct', (128, 8), True),    # the grid's bound, all of it real
    (32, 256, 'few', (64, 4), True),         # 5 of 64 experts touched
    (5, 256, 'random', (8, 2), True)])       # rows padded to the kernel's 16
def test_grouped_experts_against_a_loop_over_picks(
        tokens, block_rows, routing, experts, kernel, monkeypatch,
        fresh_dispatch):
    """The loop over blocks and the kernel against a float64 loop over
    the picks. The kernel takes bf16 leaves under float32 activations,
    and is held to the tolerance the loop meets — also against the loop
    itself over the same leaves in float32 at `HIGHEST`."""
    monkeypatch.setattr(afmoe, 'BLOCK_ROWS', block_rows)
    rs = np.random.RandomState(tokens)
    (e, k), h, f = experts, 16, 12
    x = rs.randn(tokens, h).astype('float32')
    sel = _picks(rs, tokens, e, k, routing)
    w = rs.rand(tokens, k).astype('float32')
    gw, uw = (0.3 * rs.randn(e, h, f).astype('float32') for _ in range(2))
    dw = 0.3 * rs.randn(e, f, h).astype('float32')
    args = [jnp.asarray(a) for a in (x, sel.astype('int32'), w)]
    if not kernel:
        got = afmoe.grouped_experts(*args, *map(jnp.asarray, (gw, uw, dw)))
    else:
        leaves = [jnp.asarray(a, jnp.bfloat16) for a in (gw, uw, dw)]
        fn = pallas.expert_kernel(tokens, block_rows, leaves[0].dtype,
                                  interpret=True)
        got = fn(*args, *leaves)
        gw, uw, dw = (np.asarray(a.astype(jnp.float32)) for a in leaves)
        with jax.default_matmul_precision('highest'):
            loop = afmoe.grouped_experts(*args, *map(jnp.asarray,
                                                     (gw, uw, dw)))
        assert np.abs(np.asarray(got) - np.asarray(loop)).max() < 1e-5
    want = _experts_by_loop(x, sel, w, gw, uw, dw)
    assert np.abs(np.asarray(got) - want).max() < 1e-5


@pytest.mark.parametrize('tokens,dtype,interpret,takes', [
    (8, 'bfloat16', False, False),      # the CPU, not interpreted: the loop
    (8, 'bfloat16', True, True),
    (256, 'bfloat16', True, True),      # one block wide, to the row
    (257, 'bfloat16', True, False),     # a prefill's blocks keep the loop
    (8, 'float32', True, False)])       # the three parts want bf16 leaves
def test_expert_kernel_is_picked_by_backend_and_shape(tokens, dtype,
                                                      interpret, takes):
    fn = pallas.expert_kernel(tokens, afmoe.BLOCK_ROWS, jnp.dtype(dtype),
                              interpret=interpret)
    assert (fn is not None) == takes


def _attention_repeated(q, k, v, mask, causal):
    rep = q.shape[2] // k.shape[2]
    return _attention_xla(q, jnp.repeat(k, rep, axis=2),
                          jnp.repeat(v, rep, axis=2), mask=mask,
                          causal=causal)


@pytest.mark.parametrize('per_head_mask', [False, True],
                         ids=['mask_B1', 'mask_BH'])
@pytest.mark.parametrize('rep', [1, 2, 4, 8])
def test_attention_xla_grouped_equals_repeated_kv(rep, per_head_mask):
    rs = np.random.RandomState(rep)
    b, sq, sk, hkv, d = 2, 3, 12, 2, 8
    h = hkv * rep
    q = jnp.asarray(rs.randn(b, sq, h, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, sk, hkv, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, sk, hkv, d), jnp.float32)
    mask = rs.rand(b, h if per_head_mask else 1, sq, sk) > 0.3
    mask[..., 0] = True
    for m in (jnp.asarray(mask),
              jnp.where(jnp.asarray(mask), 0.0, -1e9).astype(jnp.float32),
              None):
        for causal in (False, True):
            got = _attention_xla(q, k, v, mask=m, causal=causal)
            want = _attention_repeated(q, k, v, m, causal)
            assert got.shape == (b, sq, h, d)
            assert np.abs(np.asarray(got - want)).max() < 1e-5


# ---------------------------------------------------------------------------
# the engine's other layouts and modes with this model
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def tiny():
    cfg = _cfg('tiny')
    w = _weights(cfg)
    model = _model(cfg, w)
    return cfg, w, model, _serve(model, _requests())[0]


@pytest.mark.parametrize('extra', [
    dict(kv_page_size=8), dict(prefix_cache=True),
    dict(prefill_chunk_tokens=16)],
    ids=['paged', 'prefix_cache', 'chunked_prefill'])
def test_engine_modes_serve_the_same_tokens(tiny, extra):
    """Paged pool, prefix cache and chunked prefill hand the model other
    masks, rows and offsets; every window layer narrows them by itself."""
    _, _, model, base = tiny
    assert _serve(model, _requests(), **extra)[0] == base


@pytest.mark.parametrize('rows', [16, 24, 32])
def test_a_shorter_mask_reads_fewer_rows_and_changes_nothing(tiny, rows):
    """A mask of `rows` columns over a cache of 32 rows: attention
    contracts over the first `rows` of each leaf, a window layer narrows
    against THAT length, and the write still lands in the whole leaf.
    Queries at rows 9 and 13, window 8; 32 is the cache's own length."""
    _, _, model, _ = tiny
    fwd = cached_forward(model, *functional_state(model))
    rs = np.random.RandomState(2)
    cache = jax.tree_util.tree_map(
        lambda c: jnp.asarray(rs.randn(*c.shape), c.dtype),
        model.init_cache(2, 32))
    pos = jnp.asarray([9, 13], jnp.int32)
    tok = jnp.asarray([[5], [77]], jnp.int32)
    mask = (jnp.arange(32)[None] <= pos[:, None])[:, None, None, :]
    want, wrote = fwd(tok, cache, pos, pos, mask)
    got, wrote_short = fwd(tok, cache, pos, pos, mask[..., :rows])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL
    for a, b, c in zip(*map(jax.tree_util.tree_leaves,
                            (wrote, wrote_short, cache))):
        assert a.shape == c.shape and (np.asarray(a) == np.asarray(b)).all()
        assert (np.asarray(a)[0, 9] != np.asarray(c)[0, 9]).any()


def test_prefix_cache_hit_serves_the_same_tokens(tiny):
    cfg, w, model, _ = tiny
    rs = np.random.RandomState(4)
    shared = rs.randint(3, 128, 20).tolist()
    reqs = [(shared + rs.randint(3, 128, 4).tolist(), 12, {})
            for _ in range(3)]
    eng = InferenceEngine(model, num_slots=4, max_length=64, decode_block=4,
                          buckets=[16, 32], eos_token_id=-1,
                          prefix_cache=True)
    toks = []
    for prompt, n, _ in reqs:       # one after another: the later ones hit
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n,
                                              eos_token_id=-1))
        eng.run()
        toks.append(list(h.tokens))
    assert eng.prefix_cache.stats()['hits'] >= 1
    for (prompt, _, _), got in zip(reqs, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL


def test_speculative_decoding_equals_plain_greedy(tiny):
    _, _, model, base = tiny
    paddle.seed(3)
    draft = AfmoeForCausalLM(AfmoeConfig.tiny(
        num_hidden_layers=2,
        layer_types=[afmoe.SLIDING, afmoe.FULL])).eval()
    toks, eng = _serve(model, _requests(), draft_model=draft,
                       num_draft_tokens=3)
    assert toks == base and eng.stats()['spec']['rounds'] > 0


def test_adapters_on_the_attention_projections(tiny):
    """A bank over q/k/v/o: a request without an adapter is served as by
    a bank-less engine, one with an adapter differently, and alone as
    in company."""
    _, _, model, base = tiny
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


# ---------------------------------------------------------------------------
# what the engine says about itself
# ---------------------------------------------------------------------------
def _rounds(log):
    return [e['attrs'] for e in log.events()
            if e['name'] == 'serving.decode_round']


def test_decode_round_carries_routing_and_row_counts(tiny):
    cfg, _, model, _ = tiny
    log = obs.get_event_log()
    log.clear()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_moe_experts_touched_total')
    _serve(model, _requests())
    rounds = _rounds(log)
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


def test_the_kernel_serves_the_loops_tokens_and_says_it_ran(
        monkeypatch, fresh_programs):
    """bf16 expert leaves: through the engine the interpreted kernel
    gives the greedy tokens the loop gives, and every expert-layer
    sub-step of every round is booked as the kernel's; on the CPU as it
    is, none."""
    cfg = _cfg('tiny')
    w = {name: v.astype(jnp.bfloat16) if 'experts_' in name else v
         for name, v in _weights(cfg).items()}
    log, reg = obs.get_event_log(), obs.get_registry()
    family = 'paddle_serving_moe_expert_kernel_substeps_total'
    log.clear()
    before = reg.value(family)
    base, _ = _serve(_model(cfg, w), _requests())
    assert all(a['expert_kernel_substeps'] == 0 for a in _rounds(log))
    assert reg.value(family) == before
    monkeypatch.setattr(afmoe, 'expert_kernel', functools.partial(
        pallas.expert_kernel, interpret=True))
    fresh_programs.clear_memory()
    log.clear()
    toks, _ = _serve(_model(cfg, w), _requests())
    assert toks == base
    rounds = _rounds(log)
    assert rounds and all(a['expert_kernel_substeps']
                          == a['expert_layer_substeps'] for a in rounds)
    assert reg.value(family) - before \
        == sum(a['expert_kernel_substeps'] for a in rounds)


def _walked(length, window, tile=16):
    """Rows a decoding slot of `length` rows walks on a layer that sees
    the newest `window` (None: all) in tiles of `tile`, by hand."""
    first = max(length - window, 0) if window else 0
    return ((length - 1) // tile - first // tile + 1) * tile


def test_decode_through_the_kernel_agrees_with_the_reference(
        kv_interpreted, preset='tiny_rep4'):
    """The decode block through `kv_decode_attention`, interpreted, 2
    slots x 64 rows in tiles of 16, one request at a time so a round's
    `read_rows` is exact: on the full layer the decoding slot's length
    rounded up to the tile, on a window layer only the tiles its window
    of 8 touches — one, or two across an edge, wherever the slot stands
    — and ONE tile a layer of the slot that is not decoding."""
    cfg = _cfg(preset)
    w = _weights(cfg)
    model = _model(cfg, w)
    windows = model.attention_windows()
    log = obs.get_event_log()
    log.clear()
    eng = InferenceEngine(model, num_slots=2, max_length=64, decode_block=4,
                          buckets=[16, 32], eos_token_id=-1)
    assert eng._bounded_tiles(64).tolist() == [16] * len(windows)
    assert eng._bounded_tiles(32).tolist() == [16] * len(windows)
    rs = np.random.RandomState(4)
    for n_prompt, n_new in ((3, 14), (21, 34)):
        prompt = rs.randint(3, 128, n_prompt).tolist()
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert _served_gap(cfg, w, prompt, list(h.tokens)) < TOL
    assert len(kv_interpreted) == 2 * len(windows)  # a call a layer, traced
    rounds = _rounds(log)
    assert {a['rows'] for a in rounds} == {32, 64}
    spans = set()
    for a in rounds:
        assert a['active'] == 1
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


def test_a_model_without_experts_returns_what_it_returned():
    paddle.seed(5)
    model = LlamaForCausalLM(LlamaConfig.tiny()).eval()
    log = obs.get_event_log()
    log.clear()
    _, eng = _serve(model, _requests())
    a = _rounds(log)[-1]
    assert 'experts_touched' not in a and 'experts' not in a
    assert 'expert_kernel_substeps' not in a
    assert a['rows'] == 64 and a['read_rows'] == 2 * 64 * 2
    assert a['needed_rows'] == a['real_rows'] * 2      # no window layer
    out = jax.eval_shape(eng._decode_block_fn, *eng._decode_args())
    assert len(out) == 2                                # tokens, pool


def test_expert_scopes_are_on_the_decode_program(tiny):
    _, _, model, _ = tiny
    _serve(model, _requests())
    table = programs.scope_table()['serving.decode_block']
    found = {s for op, *_ in table.values() for s in programs.scope_path(op)}
    assert {'moe/router', 'moe/experts', 'moe/shared', 'attention',
            'kv_write', 'mlp', 'norm'} <= found
    assert programs.scope_path(
        'jit(f)/while/body/moe/experts/while/body/dot_general') \
        == ('moe/experts',)


def test_config_presets_and_refusals():
    conf = AfmoeConfig.trinity_mini()
    assert conf.layer_types[:4] == [afmoe.SLIDING] * 3 + [afmoe.FULL]
    assert conf.layer_pattern == 'SSSF' * 8
    assert AfmoeConfig.tiny().layer_pattern == 'SSSSF'
    assert 'SSSSF' in programs.describe_statics(AfmoeConfig.tiny())
    with pytest.raises(ValueError, match='sigmoid'):
        AfmoeConfig.tiny(score_func='softmax')
    with pytest.raises(ValueError, match='layer_types'):
        AfmoeConfig.tiny(layer_types=['sliding_attention'])
    model = AfmoeForCausalLM(AfmoeConfig.tiny_rep4())
    assert model.attention_windows() == (8, 8, 8, 8, None)
