"""`ops.pallas_kernels.moe_decode_experts`, the routed experts of a
decode batch (PR 31), interpreted on the CPU: against the loop over
blocks at the cells' real tiles. Moved whole out of
`tests/test_pallas_kernels.py` (PR 42): 280 s of it are one test."""
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


@pytest.mark.parametrize('f_tile', [None, 128, 256])
def test_moe_decode_experts_sums_over_tiles_of_f(f_tile):
    """However an expert is cut into grid steps, the sum is the same:
    one whole tile, two of 128, and the tile the kernel picks."""
    from paddle_tpu.ops.pallas_kernels import moe_decode_experts
    call = _expert_call()
    got = np.asarray(moe_decode_experts(**call, f_tile=f_tile,
                                        interpret=True), np.float64)
    x, w = (np.asarray(call[n], np.float64) for n in ('x', 'w'))
    gw, uw, dw = (np.asarray(call[n].astype(jnp.float32), np.float64)
                  for n in ('gate_w', 'up_w', 'down_w'))
    want = np.zeros_like(x)
    for t, row in enumerate(np.asarray(call['sel'])):
        for j, ex in enumerate(row):
            g, u = x[t] @ gw[ex], x[t] @ uw[ex]
            want[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ dw[ex])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


@pytest.mark.parametrize('changed,match', [
    (dict(x=jnp.zeros((4, 16), jnp.bfloat16)), 'float32 activations'),
    (dict(gate_w=jnp.zeros((8, 16, 256), jnp.float32)), 'bf16 expert'),
    (dict(down_w=jnp.zeros((8, 16, 256), jnp.bfloat16)), 'against leaves'),
    (dict(w=jnp.zeros((4, 3), jnp.float32)), 'against leaves'),
    (dict(f_tile=96), 'multiples of 128'),
    (dict(f_tile=192), 'multiples of 128')],
    ids=['bf16_rows', 'f32_leaves', 'down_not_transposed', 'weights_shape',
         'tile_off_the_lanes', 'tile_not_a_divisor'])
def test_moe_decode_experts_refuses(changed, match):
    from paddle_tpu.ops.pallas_kernels import moe_decode_experts
    with pytest.raises(ValueError, match=match):
        moe_decode_experts(**_expert_call(**changed), interpret=True)


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
