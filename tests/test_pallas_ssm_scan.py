"""`ssm_prefill_scan` (PR 47), interpreted on the CPU: a Mamba layer's
recurrence over a prompt as ONE kernel — a block of channels' state in
VMEM over the token axis, the tokens walked in order — against `nlp/
jamba.py::mamba_step` token by token, which stays the plain form; and
`ops.pallas.ssm_scan_kernel`, the dispatch that reads the call alone,
with `mamba_mix` on either side of it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nlp import jamba
from paddle_tpu.ops import pallas, pallas_kernels

# the draws of the scan's own tests: `long` is the long-memory regime (dt
# in [1e-3, 1e-1] against decays 1..N), `short` forgets in a token or two,
# `mixed` holds both; a state to start from that is not zero
from test_jamba import _operands

# kernel and recurrence make the same float32 products in the same
# order and differ in the order of the sum over the N states alone:
# observed at most 4e-6 on `y` as large as 36
TOL = 2e-5


@jax.jit
def _token_by_token(u, dt, b, c, a, d, h):
    def step(h, x):
        y, h = jamba.mamba_step(*x, a, d, h)
        return h, y
    h, ys = jax.lax.scan(step, h, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (u, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), h


_kernel = jax.jit(functools.partial(pallas_kernels.ssm_prefill_scan,
                                    interpret=True))


@pytest.fixture
def lane_blocks_of_128(monkeypatch):
    """A grid step holds 8,192 channels at most and these tests' widest
    state has 384: with blocks of 128 it is three."""
    monkeypatch.setattr(pallas_kernels, '_SSM_LANES', 128)
    pallas_kernels.ssm_prefill_scan.clear_cache()   # jitted: traced anew
    yield
    pallas_kernels.ssm_prefill_scan.clear_cache()


@pytest.mark.parametrize('tokens', [1, 63, 64, 65, 200])
@pytest.mark.parametrize('regime', ['long', 'mixed'])
def test_kernel_is_the_recurrence_token_by_token(regime, tokens):
    """One block of channels (128 of them, 8 states), from a state that
    is not zero: `y` of every token and the state after all of them, at
    lengths under, at and over the block of 8 tokens a walk unrolls and
    (200) over the 128 a grid step takes."""
    ops = _operands(3, 1, tokens, 128, 8, regime)
    want, end = _token_by_token(*ops)
    got, state = _kernel(*ops)
    assert got.shape == want.shape and np.abs(np.asarray(want)).max() > 1.0
    assert np.abs(np.asarray(got - want)).max() < TOL
    assert np.abs(np.asarray(state - end)).max() < TOL
    assert np.abs(np.asarray(end - ops[-1])).max() > 1e-3


@pytest.mark.parametrize('regime,tokens', [('long', 200), ('short', 65),
                                           ('mixed', 64)])
def test_several_blocks_of_channels_and_a_batch(lane_blocks_of_128, regime,
                                                tokens):
    """384 channels in three blocks of 16 states, two sequences: every
    block carries its own state over the token axis, and the next block
    starts from `h0` again."""
    assert pallas_kernels._ssm_blocks(16, 384) == (128, 128)
    ops = _operands(4, 2, tokens, 384, 16, regime)
    want, end = _token_by_token(*ops)
    got, state = jax.jit(functools.partial(          # the patched blocks
        pallas_kernels.ssm_prefill_scan, interpret=True))(*ops)
    assert np.abs(np.asarray(got - want)).max() < TOL
    assert np.abs(np.asarray(state - end)).max() < TOL


def test_the_blocks_a_grid_step_and_a_walk_take():
    """The cell's state in one block, walked 512 channels (8 vregs of
    state) at a time; a width no power of two; fewer states, wider
    walks; a state too tall for 8 vregs walks a lane group."""
    assert pallas_kernels._ssm_blocks(16, 5120) == (5120, 512)
    assert pallas_kernels._ssm_blocks(16, 1280) == (1280, 256)
    assert pallas_kernels._ssm_blocks(8, 2048) == (2048, 1024)
    assert pallas_kernels._ssm_blocks(128, 256) == (256, 128)
    assert pallas_kernels._ssm_blocks(16, 16384) == (8192, 512)


def test_a_state_carried_from_call_to_call_is_the_whole_sequence():
    """150 tokens in calls of 64, 1 and 85, each from the state the one
    before returned, against the 150 at once."""
    u, dt, b, c, a, d, h0 = _operands(5, 2, 150, 128, 8, 'long')
    want, end = _token_by_token(u, dt, b, c, a, d, h0)
    h, ys = h0, []
    for lo, hi in ((0, 64), (64, 65), (65, 150)):
        y, h = _kernel(u[:, lo:hi], dt[:, lo:hi], b[:, lo:hi], c[:, lo:hi],
                       a, d, h)
        ys.append(y)
    assert np.abs(np.asarray(jnp.concatenate(ys, 1) - want)).max() < TOL
    assert np.abs(np.asarray(h - end)).max() < TOL


def test_tokens_of_no_step_leave_the_state_bit_for_bit():
    """`dt = 0` is decay exactly one and input exactly nothing. A call
    of such tokens alone hands the state back as it got it; and what
    stands after the folded tokens — other tokens, the padding up to the
    block — changes not one bit of what they left."""
    u, dt, b, c, a, d, h0 = _operands(6, 2, 40, 128, 8, 'mixed')
    _, same = _kernel(u, 0.0 * dt, b, c, a, d, h0)
    assert (np.asarray(same) == np.asarray(h0)).all()
    enters = (jnp.arange(40) < 23)[None, :, None]
    _, first = _kernel(u, jnp.where(enters, dt, 0.0), b, c, a, d, h0)
    other = _operands(7, 2, 40, 128, 8, 'short')
    tail = lambda mine, theirs: jnp.where(enters, mine, theirs)   # noqa
    _, second = _kernel(tail(u, other[0]), jnp.where(enters, dt, 0.0),
                        tail(b, other[2]), tail(c, other[3]), a, d, h0)
    assert (np.asarray(first) == np.asarray(second)).all()
    _, exact = _kernel(u[:, :23], dt[:, :23], b[:, :23], c[:, :23], a, d, h0)
    assert (np.asarray(first) == np.asarray(exact)).all()
    assert np.abs(np.asarray(first - h0)).max() > 0.1


def test_operands_that_do_not_fit_are_refused_by_name():
    u, dt, b, c, a, d, h0 = _operands(8, 1, 8, 128, 8, 'short')
    with pytest.raises(ValueError, match='against state'):
        pallas_kernels.ssm_prefill_scan(u, dt, b, c[:, :4], a, d, h0,
                                        interpret=True)
    with pytest.raises(ValueError, match='float32 state'):
        pallas_kernels.ssm_prefill_scan(
            u, dt, b, c, a, d, h0.astype(jnp.bfloat16), interpret=True)


# ---------------------------------------------------------------------------
# the dispatch, and `mamba_mix` on either side of it
# ---------------------------------------------------------------------------
def _leaf(n, di, dtype=jnp.float32, bsz=2):
    return jax.ShapeDtypeStruct((bsz, n, di), dtype)


def _mix_operands(seed, bsz, s, di, n):
    u, dt, b, c, a, d, h0 = _operands(seed, bsz, s, di, n, 'mixed')
    rs = np.random.RandomState(seed + 100)
    z = jnp.asarray(rs.randn(bsz, s, di), jnp.float32)
    # `dt` before its softplus, `a_log` as published
    return (u, z, 3.0 * dt - 2.0, b, c, jnp.log(-a), d, h0)


_DISPATCH = pallas.ssm_scan_kernel


def _interpreted(monkeypatch):
    """`mamba_mix` asks the dispatch as on a TPU and runs what it is
    given interpreted."""
    asked = []

    def ask(h, tokens):
        asked.append(_DISPATCH(h, tokens, interpret=True))
        return asked[-1]
    monkeypatch.setattr(pallas, 'ssm_scan_kernel', ask)
    return asked


def test_the_dispatch_takes_the_call_on_a_tpu_alone(monkeypatch):
    leaf = _leaf(16, 5120)
    assert pallas.ssm_scan_kernel(leaf, 1024) is None           # no TPU
    assert pallas.ssm_scan_kernel(leaf, 1024, interpret=True).func \
        is pallas_kernels.ssm_prefill_scan
    monkeypatch.setattr(pallas, '_pallas_enabled', lambda: True)
    assert pallas.ssm_scan_kernel(leaf, 2).func \
        is pallas_kernels.ssm_prefill_scan
    assert pallas.ssm_scan_kernel(leaf, 1) is None              # a step


@pytest.mark.parametrize('why,leaf,tokens', [
    ('states no whole sublanes', _leaf(12, 128), 9),
    ('channels no whole lanes', _leaf(8, 192), 9),
    ('a bf16 state', _leaf(8, 128, jnp.bfloat16), 9),
    ('one token', _leaf(8, 128), 1)])
def test_what_the_dispatch_refuses_is_xlas_scan(monkeypatch, why, leaf,
                                                tokens):
    """Each refusal gives None, and `mamba_mix` then gives what it gave
    before there was a kernel: `mamba_scan`'s result to the bit (one
    token folded in whole: `mamba_step`'s; a bf16 state `mamba_scan`
    never took either, so there the answer is all there is to hold)."""
    assert _DISPATCH(leaf, tokens, interpret=True) is None, why
    if leaf.dtype != jnp.float32:
        return
    asked = _interpreted(monkeypatch)
    bsz, n, di = leaf.shape
    ops = _mix_operands(9, bsz, tokens, di, n)
    folded = jnp.int32(max(tokens - 2, 0))
    got = [jamba.mamba_mix(*ops, folded, chunk=4, fold_all=fold_all)
           for fold_all in (True, False)]
    assert asked and all(kernel is None for kernel in asked)
    monkeypatch.setattr(pallas, 'ssm_scan_kernel', lambda h, tokens: None)
    want = [jamba.mamba_mix(*ops, folded, chunk=4, fold_all=fold_all)
            for fold_all in (True, False)]
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (np.asarray(g) == np.asarray(w)).all(), why


@pytest.mark.parametrize('fold_all', [True, False])
def test_mamba_mix_through_the_kernel_is_mamba_mix_without(monkeypatch,
                                                           fold_all):
    """Batch 2 with a left-padded `keep`, 37 tokens of which (without
    `fold_all`) the first 29 enter the state: the gated output and the
    state, kernel against `mamba_scan`."""
    ops = _mix_operands(10, 2, 37, 128, 8)
    keep = jnp.asarray(np.arange(37)[None, :, None]
                       >= np.array([0, 11])[:, None, None], jnp.float32)
    folded = jnp.int32(29)
    want = jamba.mamba_mix(*ops, folded, keep, chunk=16, fold_all=fold_all)
    asked = _interpreted(monkeypatch)
    got = jamba.mamba_mix(*ops, folded, keep, chunk=16, fold_all=fold_all)
    assert asked and asked[0] is not None
    for g, w in zip(got, want):
        assert np.abs(np.asarray(g - w)).max() < TOL
    # the pads of the second sequence never entered its state
    alone = jamba.mamba_mix(*(t[1:, 11:] if t.ndim == 3 and t.shape[1] == 37
                              else t[1:] if t.ndim == 3 else t for t in ops),
                            folded - 11, chunk=16, fold_all=fold_all)
    assert np.abs(np.asarray(got[1][1:] - alone[1])).max() < TOL
