"""`ops.pallas_kernels.moe_decode_experts`, the routed experts of a
decode batch (PR 31), and `moe_grouped_experts`, those of a call more
than one block wide (PR 49), interpreted on the CPU: against the loop
over blocks, the first at the cells' real tiles, the second at their
widths cut. Moved whole out of `tests/test_pallas_kernels.py` (PR 42):
280 s of it are one test."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# routed experts of a decode batch (`moe_decode_experts`, PR 31)
# ---------------------------------------------------------------------------
def _expert_call(t=4, k=2, e=8, h=16, f=256, seed=0, **changed):
    rs = np.random.RandomState(seed)
    call = dict(
        x=jnp.asarray(rs.randn(t, h), jnp.float32),
        sel=jnp.asarray(np.stack([rs.permutation(e)[:k] for _ in range(t)]),
                        jnp.int32),
        w=jnp.asarray(rs.rand(t, k), jnp.float32),
        gate_w=jnp.asarray(0.3 * rs.randn(e, h, f), jnp.bfloat16),
        up_w=jnp.asarray(0.3 * rs.randn(e, h, f), jnp.bfloat16),
        down_w=jnp.asarray(0.3 * rs.randn(e, f, h), jnp.bfloat16))
    call.update(changed)
    return call


def _kernel(name, monkeypatch, tile=16, f_tile=None, h=16):
    """`decode`: the one-block kernel, cut by `f_tile`; `grouped`: the
    wide call's, which takes neither as an argument — its constants set
    to a tile of `tile` rows (the 4 x 2 picks lie in one of 16) and to
    the VMEM that holds `f_tile` of an expert of width `h` twice."""
    from paddle_tpu.ops import pallas_kernels as pk
    if name == 'decode':
        return functools.partial(pk.moe_decode_experts, f_tile=f_tile)
    monkeypatch.setattr(pk, '_GROUPED_ROW_TILE', tile)
    if f_tile:
        monkeypatch.setattr(pk, '_GROUPED_WEIGHT_VMEM', 12 * h * f_tile)
    return pk.moe_grouped_experts


@pytest.mark.parametrize('kernel', ['decode', 'grouped'])
@pytest.mark.parametrize('f_tile', [None, 128, 256])
def test_moe_decode_experts_sums_over_tiles_of_f(f_tile, kernel,
                                                 monkeypatch):
    """However an expert is cut into grid steps, the sum is the same:
    one whole tile, two of 128, and the tile the kernel picks."""
    from paddle_tpu.ops import pallas_kernels as pk
    call = _expert_call()
    got = np.asarray(_kernel(kernel, monkeypatch, f_tile=f_tile)(
        **call, interpret=True), np.float64)
    assert pk._grouped_f_tile(16, 256) == (f_tile or 256) \
        or kernel == 'decode'
    x, w = (np.asarray(call[n], np.float64) for n in ('x', 'w'))
    gw, uw, dw = (np.asarray(call[n].astype(jnp.float32), np.float64)
                  for n in ('gate_w', 'up_w', 'down_w'))
    want = np.zeros_like(x)
    for t, row in enumerate(np.asarray(call['sel'])):
        for j, ex in enumerate(row):
            g, u = x[t] @ gw[ex], x[t] @ uw[ex]
            want[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ dw[ex])
    # three bf16 parts of the activations, or the grouped kernel's two
    tol = 1e-5 if kernel == 'decode' else 5e-5
    assert np.abs(got - want).max() < tol * np.abs(want).max()


_REFUSED = {
    'bf16_rows': (dict(x=jnp.zeros((4, 16), jnp.bfloat16)),
                  'float32 activations'),
    'f32_leaves': (dict(gate_w=jnp.zeros((8, 16, 256), jnp.float32)),
                   'bf16 expert'),
    'down_not_transposed': (dict(down_w=jnp.zeros((8, 16, 256),
                                                  jnp.bfloat16)),
                            'against leaves'),
    'weights_shape': (dict(w=jnp.zeros((4, 3), jnp.float32)),
                      'against leaves'),
    'tile_off_the_lanes': (dict(f_tile=64), 'multiples of 128'),
    'tile_not_a_divisor': (dict(f_tile=192), 'multiples of 128')}


@pytest.mark.parametrize('kernel,case', [
    (kernel, case) for kernel in ('decode', 'grouped') for case in _REFUSED
    # the grouped kernel takes no tile from its caller to refuse
    if kernel == 'decode' or 'f_tile' not in _REFUSED[case][0]])
def test_moe_decode_experts_refuses(kernel, case, monkeypatch):
    changed, match = _REFUSED[case]
    call = _expert_call(**changed)
    with pytest.raises(ValueError, match=match):
        _kernel(kernel, monkeypatch, f_tile=call.pop('f_tile', None))(
            **call, interpret=True)


@pytest.mark.parametrize('h,f,budget,f_tile', [
    # the six cells' experts under the kernel's own budget: mimo's 50 MB
    # cannot sit whole, twice
    (2048, 1024, None, 1024),       # serve-moe-docs
    (3584, 1024, None, 1024),       # serve-mhc-agent
    (2048, 768, None, 768),         # serve-mla-long
    (2048, 1536, None, 1536),       # serve-hybrid-reason
    (4096, 2048, None, 1024),       # serve-swa-reason
    (16, 256, 12 * 16 * 128, 128),  # the most whole lanes that fit
    (16, 192, 12 * 16 * 128, 192)], # no whole lanes divide f: all of it
    ids=['trinity', 'xing4', 'kanana', 'lfm2', 'mimo', 'cut', 'no_divisor'])
def test_the_grouped_kernels_tile_of_f_comes_from_the_vmem(
        h, f, budget, f_tile, monkeypatch):
    from paddle_tpu.ops import pallas_kernels as pk
    if budget:
        monkeypatch.setattr(pk, '_GROUPED_WEIGHT_VMEM', budget)
    assert pk._grouped_f_tile(h, f) == f_tile


@pytest.mark.parametrize('tokens,k,routed,held,h,f', [
    (32, 8, 64, 4, 4096, 2048),     # serve-swa-reason: a share of the experts
    (8, 8, 32, 4, 2048, 1024),      # serve-moe-docs' expert
    (32, 4, 16, 4, 2048, 1536),     # serve-hybrid-reason's
    (16, 6, 128, 16, 2048, 768)],   # serve-mla-long's: top-6 of 128, f_tile 384
    ids=['4096x2048', '2048x1024', '2048x1536', '2048x768'])
def test_moe_decode_experts_and_the_loop_agree_on_picks_not_held(
        tokens, k, routed, held, h, f):
    """A layer that holds experts 0..held-1 of a router over `routed`
    hands both schedules its picks in its own numbering, `held` (one
    past the last) for a pick it does not hold, with weight zero: such a
    pick is no `hit` of the kernel and no row of the loop's sorted walk.
    At the real tiles of the four cells that run this kernel
    (interpreted), against the loop over the same leaves in float32 at
    `HIGHEST` and against a float64 sum over the held picks."""
    from paddle_tpu.nlp.afmoe import grouped_experts
    from paddle_tpu.ops.pallas_kernels import moe_decode_experts
    rs = np.random.RandomState(tokens + f)
    sel = np.stack([rs.permutation(routed)[:k] for _ in range(tokens)])
    mine = sel < held
    assert mine.any() and not mine.all() and not mine.all(axis=1).any()
    w = np.where(mine, rs.rand(tokens, k), 0.0).astype('float32')
    local = np.where(mine, sel, held).astype('int32')
    x = rs.randn(tokens, h).astype('float32')
    gw, uw = (jnp.asarray(0.02 * rs.randn(held, h, f), jnp.bfloat16)
              for _ in range(2))
    dw = jnp.asarray(0.02 * rs.randn(held, f, h), jnp.bfloat16)
    args = (jnp.asarray(x), jnp.asarray(local), jnp.asarray(w))
    got = np.asarray(moe_decode_experts(*args, gw, uw, dw, interpret=True))
    g32, u32, d32 = (a.astype(jnp.float32) for a in (gw, uw, dw))
    with jax.default_matmul_precision('highest'):
        loop = np.asarray(grouped_experts(*args, g32, u32, d32))
    want = np.zeros((tokens, h))
    for t, e_, j in zip(*np.nonzero(mine), local[mine]):
        g = x[t].astype('float64') @ np.asarray(g32[j], 'float64')
        u = x[t].astype('float64') @ np.asarray(u32[j], 'float64')
        want[t] += w[t, e_] * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(d32[j], 'float64'))
    scale = np.abs(want).max()
    assert scale > 0
    assert np.abs(got - want).max() < 2e-5 * scale
    assert np.abs(loop - want).max() < 2e-5 * scale
    # a row none of whose picks is held gets nothing from either
    none = ~mine.any(axis=1)
    assert (got[none] == 0).all() and (loop[none] == 0).all()


def _held_picks(rs, tokens, k, routed, held, routing):
    """[tokens, k] picks of a router over `routed` experts, distinct a
    row: `random`; `few` (every row picks among the first k + 1, so most
    held experts get no row); `one_expert` (every row's first pick is
    expert 1: its rows span several tiles); `none_held` (every pick
    past the experts held)."""
    pool = {'few': k + 1, 'none_held': routed - held}.get(routing, routed)
    sel = np.stack([rs.permutation(pool)[:k] for _ in range(tokens)])
    if routing == 'none_held':
        sel += held
    if routing == 'one_expert':
        sel[:, 1] = np.where(sel[:, 1] == 1, sel[:, 0], sel[:, 1])
        sel[:, 0] = 1
    return sel


@pytest.mark.parametrize('tokens,k,routed,held,h,f,tile,f_tile,routing', [
    # the six cells' expert layers, h and f cut by eight, a call just
    # over one block of 256 tokens, at tiles of 64, 128 and 256 rows
    (257, 8, 128, 128, 256, 128, 128, None, 'random'),  # serve-moe-docs
    (264, 4, 64, 64, 448, 128, 256, None, 'random'),    # serve-mhc-agent
    (260, 6, 128, 128, 256, 96, 128, None, 'random'),   # serve-mla-long
    (264, 8, 512, 64, 320, 96, 64, None, 'random'),     # serve-kda-reason
    (272, 4, 64, 64, 256, 192, 64, None, 'random'),     # serve-hybrid-reason
    (258, 8, 256, 16, 512, 256, 64, 128, 'random'),     # serve-swa-reason
    (300, 2, 8, 8, 32, 128, 64, None, 'one_expert'),    # 300 rows of one expert: five tiles
    (300, 2, 8, 8, 32, 128, 256, None, 'random'),       # 600 rows: no whole third tile
    (300, 4, 64, 64, 32, 128, 64, None, 'few'),         # 59 experts nobody picked
    (40, 2, 8, 8, 32, 128, 16, None, 'random'),         # a tile of one packed row
    (300, 4, 64, 8, 32, 128, 64, None, 'none_held'),    # every pick `sel == E`
    (300, 8, 64, 8, 32, 128, 128, None, 'random')],     # an eighth of the picks held
    ids=['trinity', 'xing4', 'kanana', 'ling', 'lfm2', 'mimo', 'spans_tiles',
         'no_whole_tile', 'nobody_picked', 'tile_16', 'none_held',
         'share_held'])
def test_moe_grouped_experts_and_the_loop_agree(tokens, k, routed, held, h,
                                                f, tile, f_tile, routing,
                                                monkeypatch):
    """The grouped kernel (interpreted) against the loop over the same
    leaves in float32 at `HIGHEST` and against a float64 sum over the
    held picks, to the 16 bits its two bf16 parts carry: no pick
    dropped, a pick not held (`sel == E`, weight zero) adding nothing,
    a tile no expert has rows in never written and never read."""
    from paddle_tpu.nlp.afmoe import grouped_experts
    moe_grouped_experts = _kernel('grouped', monkeypatch, tile, f_tile, h)
    rs = np.random.RandomState(tokens + f + tile)
    sel = _held_picks(rs, tokens, k, routed, held, routing)
    mine = sel < held
    assert mine.any() != (routing == 'none_held')
    w = np.where(mine, rs.rand(tokens, k), 0.0).astype('float32')
    local = np.where(mine, sel, held).astype('int32')
    x = rs.randn(tokens, h).astype('float32')
    gw, uw = (jnp.asarray(0.1 * rs.randn(held, h, f), jnp.bfloat16)
              for _ in range(2))
    dw = jnp.asarray(0.1 * rs.randn(held, f, h), jnp.bfloat16)
    args = (jnp.asarray(x), jnp.asarray(local), jnp.asarray(w))
    got = np.asarray(moe_grouped_experts(*args, gw, uw, dw, interpret=True))
    g32, u32, d32 = (a.astype(jnp.float32) for a in (gw, uw, dw))
    with jax.default_matmul_precision('highest'):
        loop = np.asarray(grouped_experts(*args, g32, u32, d32))
    want = np.zeros((tokens, h))
    for t, e_, j in zip(*np.nonzero(mine), local[mine]):
        g = x[t].astype('float64') @ np.asarray(g32[j], 'float64')
        u = x[t].astype('float64') @ np.asarray(u32[j], 'float64')
        want[t] += w[t, e_] * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(d32[j], 'float64'))
    scale = np.abs(want).max()
    assert (scale > 0) != (routing == 'none_held')
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 5e-5 * scale
    assert np.abs(loop - want).max() <= 2e-5 * scale
    none = ~mine.any(axis=1)
    assert (got[none] == 0).all() and (loop[none] == 0).all()


def test_a_tape_meets_a_refusal_by_name_behind_the_grouped_kernel(
        monkeypatch):
    """Under `jax.vjp` the forward is the kernel's and the pullback
    refuses by name, as the loop's does (a `while` has no reverse
    mode): what a differentiated call of an expert layer met before the
    kernel, said where it is met."""
    moe_grouped_experts = _kernel('grouped', monkeypatch)
    call = _expert_call(t=20)
    leaves = [call[n] for n in ('gate_w', 'up_w', 'down_w')]
    plain = moe_grouped_experts(**call, interpret=True)
    out, pull = jax.vjp(
        lambda x, w: moe_grouped_experts(x, call['sel'], w, *leaves,
                                         interpret=True),
        call['x'], call['w'])
    assert (np.asarray(out) == np.asarray(plain)).all()
    with pytest.raises(NotImplementedError, match='no reverse mode'):
        pull(out)
