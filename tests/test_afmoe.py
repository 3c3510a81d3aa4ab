"""`nlp/afmoe.py` against its plain float32 reference
(`benchmarks/reference/afmoe.py`) at the tiny presets, with seeded
weights whose `expert_bias` is not zero. Model-level: what builds no
engine; the served half is `tests/test_afmoe_serving.py`, the shared
cases and helpers `tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (sorted blocks of one expert against every expert
for every token; a cache against a full forward; grouped against
repeated KV heads). Observed at most 1e-5 on logits as large as 7; the
mildest departure from the published mathematics moves a logit by more
than 1e-2. 2e-4 lies between with room on both sides."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import programs
from paddle_tpu.nlp import afmoe
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import _attention_xla

import family_harness as H
from family_harness import TOL

FAM = H.Family('AfmoeForCausalLM', AfmoeConfig, ('tiny', 'tiny_rep4'))
built, tiny = H.fixtures(FAM)

test_full_forward_agrees_with_the_reference = H.full_forward(FAM)


def test_bucketed_prefill_then_decode_past_twice_the_window(built):
    """The engine's own order: a prompt right-padded to its bucket is
    prefilled into a zero row, the last prompt token is forwarded again
    at its slot, then one token at a time — past 1x and 2x the window
    of 8, under the engine's slot-causal mask."""
    cfg, w, model = built
    fwd = H.cached_fwd(model)
    ids, n_prompt, bucket, length = H.ids((1, 40), 3), 11, 16, 48
    ref = FAM.ref_logits(cfg, w, ids)
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


@pytest.mark.parametrize('rows', [16, 24, 32])
def test_a_shorter_mask_reads_fewer_rows_and_changes_nothing(tiny, rows):
    """A mask of `rows` columns over a cache of 32 rows: attention
    contracts over the first `rows` of each leaf, a window layer narrows
    against THAT length, and the write still lands in the whole leaf.
    Queries at rows 9 and 13, window 8; 32 is the cache's own length."""
    model = tiny[2]
    fwd = H.cached_fwd(model)
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


# ---------------------------------------------------------------------------
# each departure from the published mathematics fails the tolerance
# ---------------------------------------------------------------------------
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


def _every_token_picks_expert_0(cfg, w):
    """A bias that makes EVERY token pick expert 0 — the most uneven
    routing there is, where a layer with a capacity drops tokens."""
    w = dict(w)
    for i in range(cfg['num_dense_layers'], cfg['num_hidden_layers']):
        w[f'l{i}.expert_bias'] = w[f'l{i}.expert_bias'].at[0].set(100.0)
    return w


test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(FAM, [
        _no_window, _rope_on_full, H.bias_in_weight, _no_route_norm,
        _no_route_scale, _no_gate, _no_sqrt_h, _dropped_token],
        reweigh=_every_token_picks_expert_0)


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
    (5, 256, 'random', (8, 2), True),        # rows padded to the kernel's 16
    # a call wider than a block: the grouped kernel, interpreted (PR 49)
    (70, 16, 'random', (8, 2), True),        # tiles of 128 hold several experts
    (70, 16, 'one_expert', (8, 2), True),    # half the picks one expert's
    (17, 16, 'random', (8, 2), True),        # one row over a block
    (70, 16, 'few', (64, 4), True)])         # 59 of 64 experts picked by nobody
def test_grouped_experts_against_a_loop_over_picks(
        tokens, block_rows, routing, experts, kernel, monkeypatch,
        fresh_dispatch):
    """The loop over blocks and the kernels against a float64 loop over
    the picks. A kernel takes bf16 leaves under float32 activations,
    and is held to the tolerance the loop meets (the grouped kernel's
    two bf16 parts to 16 bits of it) — also against the loop itself
    over the same leaves in float32 at `HIGHEST`."""
    monkeypatch.setattr(afmoe, 'BLOCK_ROWS', block_rows)
    tol = 1e-4 if kernel and tokens > block_rows else 1e-5
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
        assert np.abs(np.asarray(got) - np.asarray(loop)).max() < tol
    want = _experts_by_loop(x, sel, w, gw, uw, dw)
    assert np.abs(np.asarray(got) - want).max() < tol


@pytest.mark.parametrize('tokens,dtype,interpret,takes', [
    (8, 'bfloat16', False, None),       # the CPU, not interpreted: the loop
    (8, 'bfloat16', True, 'moe_decode_experts'),
    (256, 'bfloat16', True, 'moe_decode_experts'),  # one block, to the row
    (8, 'float32', True, None),         # the parts want bf16 leaves
    # wider than a block: the grouped kernel (PR 49)
    (257, 'bfloat16', True, 'moe_grouped_experts'),
    (10240, 'bfloat16', True, 'moe_grouped_experts'),
    (257, 'float32', True, None),
    (257, 'bfloat16', False, None)])    # another backend: the loop
def test_expert_kernel_is_picked_by_backend_and_shape(tokens, dtype,
                                                      interpret, takes):
    fn = pallas.expert_kernel(tokens, afmoe.BLOCK_ROWS, jnp.dtype(dtype),
                              interpret=interpret)
    assert (fn and fn.func.__name__) == takes


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


def test_config_presets_and_refusals():
    conf = AfmoeConfig.trinity_mini()
    assert conf.layer_types[:4] == [afmoe.SLIDING] * 3 + [afmoe.FULL]
    assert conf.layer_pattern == 'SSSF' * 8
    assert AfmoeConfig.tiny().layer_pattern == 'SSSSF'
    assert 'SSSSF' in programs.describe_statics(AfmoeConfig.tiny())
    H.refused(AfmoeConfig.tiny, (
        (dict(score_func='softmax'), 'sigmoid'),
        (dict(layer_types=['sliding_attention']), 'layer_types')))
    model = AfmoeForCausalLM(AfmoeConfig.tiny_rep4())
    assert model.attention_windows() == (8, 8, 8, 8, None)
