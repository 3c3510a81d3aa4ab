"""`kda_decode_step` (PR 44), interpreted on the CPU: a KDA layer's
recurrence of one token as ONE kernel — a block of heads' `[d_k, d_v]`
tiles read once, both sums over d_k and the update made from them,
written back once, the state aliased — against `nlp/ling3.py::kda_step`,
which stays the plain form; and `ops.pallas.kda_step_kernel`, the
dispatch that reads the call alone."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nlp import ling3
from paddle_tpu.ops import pallas, pallas_kernels

# (slots, heads, d_k, d_v): a toy, the cell's real head tile (not its 48
# x 32: an interpreted grid that size costs minutes), a head of two
# lanes' d_k
TOY, REAL, TALL = (3, 4, 8, 16), (2, 8, 128, 128), (1, 2, 256, 128)
# a float32 rounding of the compared array's range, and how many of them
# two orders of the same sums may lie apart
EPS, ROUNDINGS = 2.0 ** -23, 8


def _tokens(shape, steps, seed=0, beta=None, g=None):
    """`steps` tokens for `shape` as `kda_mix` hands them to the
    recurrence: q and k l2-normed a head, decays and betas as
    `kda_gates` gives them (`A_log` one, the argument's deviation the
    benchmark initializer's) -> per step (q, k, v, g, beta), and a
    state to start from."""
    b, h, dk, dv = shape
    rs = np.random.RandomState(seed)

    def draw(*dims):
        return jnp.asarray(rs.randn(steps, *dims), jnp.float32)
    gates = ling3.kda_gates(
        draw(b, h * dk).swapaxes(0, 1), draw(b, h).swapaxes(0, 1),
        jnp.ones((h,)), jnp.zeros((h * dk,)), -5.0)
    gs, betas = (jnp.swapaxes(t, 0, 1) for t in gates)
    if g is not None:
        gs = jnp.full_like(gs, g)
    if beta is not None:
        betas = jnp.full_like(betas, beta)
    return ((ling3.l2norm(draw(b, h, dk)), ling3.l2norm(draw(b, h, dk)),
             draw(b, h, dv), gs, betas),
            jnp.asarray(rs.randn(b, h, dk, dv), jnp.float32))


def _chain(step, tokens, state):
    """`step` over the tokens one after another -> (every o, the last
    state)."""
    def one(s, token):
        o, s = step(*token, s)
        return s, o
    state, o = jax.lax.scan(one, state, tokens)
    return o, state


def _close(got, want):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= ROUNDINGS * EPS * scale


@pytest.mark.parametrize('shape, heads, steps', [
    (TOY, None, 1), (TOY, 2, 1), (REAL, None, 1), (REAL, None, 64),
    (TALL, 1, 1), (TOY, 1, 64)])
def test_kda_decode_step_agrees_with_kda_step(shape, heads, steps):
    """After ONE token and after 64 chained, `o` and the state lie
    within a few float32 roundings of their range of the plain form's
    (the same multiply-adds; only the order of a sum over d_k
    differs)."""
    tokens, state = _tokens(shape, steps)
    kernel = functools.partial(pallas_kernels.kda_decode_step, heads=heads,
                               interpret=True)
    o, s = jax.jit(functools.partial(_chain, kernel))(tokens, state)
    want_o, want_s = jax.jit(functools.partial(_chain, ling3.kda_step))(
        tokens, state)
    assert o.shape == want_o.shape and s.shape == want_s.shape
    assert o.dtype == s.dtype == jnp.float32
    _close(o, want_o)
    _close(s, want_s)


@pytest.mark.parametrize('shape', [TOY, REAL])
def test_a_token_folded_out_leaves_the_state_bit_for_bit(shape):
    """`beta = 0, g = 0`: the identity update a token past
    `state_scope`'s count, and a chunk's padding, rely on."""
    tokens, state = _tokens(shape, 1, seed=3, beta=0.0, g=0.0)
    o, s = pallas_kernels.kda_decode_step(
        *(t[0] for t in tokens), state, interpret=True)
    assert np.array_equal(np.asarray(s).view(np.uint32),
                          np.asarray(state).view(np.uint32))
    # and what it reads is the state as it stood: `S^T q / sqrt(d_k)`
    q = tokens[0][0]
    _close(o, jnp.einsum('bhkv,bhk->bhv', state, q) / np.sqrt(shape[2]))


def test_the_state_is_aliased_input_to_output():
    """The lowered call names the state among its `input_output_
    aliases`: donated to a caller that carries it, the update is in
    place."""
    tokens, state = _tokens(TOY, 1)
    args = tuple(t[0] for t in tokens) + (state,)
    jaxpr = jax.make_jaxpr(functools.partial(
        pallas_kernels.kda_decode_step, interpret=True))(*args)
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == 'pallas_call']
    (src, dst), = call.params['input_output_aliases']
    assert call.invars[src].aval.shape == state.shape       # the state in
    assert call.outvars[dst].aval.shape == state.shape      # ... and out
    assert call.params['name'] == 'kda_decode_step'


@pytest.mark.parametrize('changed, match', [
    (dict(state=jnp.bfloat16), 'a float32 state'),
    (dict(beta=(3, 4, 1)), 'against state'),
    (dict(v=(3, 4, 8)), 'against state'),
    (dict(heads=3), 'must divide 4'),
])
def test_kda_decode_step_refuses(changed, match):
    tokens, state = _tokens(TOY, 1)
    q, k, v, g, beta = (t[0] for t in tokens)
    if 'state' in changed:
        state = state.astype(changed['state'])
    if 'beta' in changed:
        beta = jnp.zeros(changed['beta'])
    if 'v' in changed:
        v = jnp.zeros(changed['v'])
    with pytest.raises(ValueError, match=match):
        pallas_kernels.kda_decode_step(
            q, k, v, g, beta, state, heads=changed.get('heads'),
            interpret=True)


@pytest.mark.parametrize('heads, dk, dv, block', [
    (32, 128, 128, 32),     # the cell's: every head of a slot, 2 MiB
    (64, 128, 128, 32), (8, 128, 128, 8), (4, 128, 128, 4),
    (32, 256, 256, 8),      # the block's bytes bound it
    (12, 128, 128, 12), (20, 128, 128, 20),
    (44, 128, 128, None),   # no block of whole sublanes divides it
])
def test_kda_head_block(heads, dk, dv, block):
    assert pallas_kernels._kda_head_block(heads, dk, dv) == block


def _spec(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize('state, interpret, heads', [
    (_spec((48, 32, 128, 128)), True, 32),         # serve-kda-reason's
    (_spec((1, 32, 128, 128)), True, 32),          # `generate`, a row
    (_spec((48, 32, 256, 128)), True, 16),
    (_spec((48, 32, 128, 128)), False, None),      # not on a TPU
    (_spec((2, 4, 8, 8)), True, None),             # the tiny presets'
    (_spec((2, 4, 64, 128)), True, None),          # d_k no whole lanes
    (_spec((2, 4, 128, 64)), True, None),          # d_v neither
    (_spec((48, 32, 128, 128), jnp.bfloat16), True, None),
    (_spec((48, 44, 128, 128)), True, None),       # no head block
])
def test_kda_step_kernel_is_picked_by_the_call_alone(state, interpret, heads):
    kernel = pallas.kda_step_kernel(state, interpret=interpret)
    if heads is None:
        assert kernel is None
    else:
        assert kernel.func is pallas_kernels.kda_decode_step
        assert kernel.keywords == {'heads': heads, 'interpret': True}


def test_kda_mix_asks_the_dispatch_for_one_token_only(monkeypatch):
    """A call of one token goes where the dispatch says (its kernel
    gets `kda_step`'s arguments, the state last); a longer call never
    asks: the chunked scan is XLA's."""
    asked = []

    def dispatch(state):
        asked.append(state.shape)
        return functools.partial(pallas_kernels.kda_decode_step,
                                 interpret=True)
    monkeypatch.setattr(pallas, 'kda_step_kernel', dispatch)
    b, h, d, taps = 2, 4, 8, 4
    rs = np.random.RandomState(5)

    def mix(s):
        def draw(*dims):
            return jnp.asarray(rs.randn(*dims), jnp.float32)
        args = (draw(b, s, h * d), draw(b, s, h * d), draw(b, s, h * d),
                draw(b, s, h * d), draw(b, s, h),
                draw(h * d, taps), draw(h * d, taps), draw(h * d, taps),
                jnp.ones((h,)), jnp.zeros((h * d,)),
                draw(b, h, d, d), draw(b, taps - 1, 3 * h * d))
        kw = dict(folded=s, lower=-5.0, chunk=16, fold_all=True)
        got = ling3.kda_mix(*args, **kw)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(pallas, 'kda_step_kernel', lambda state: None)
            want = ling3.kda_mix(*args, **kw)
        return got, want
    got, want = mix(1)
    assert asked == [(b, h, d, d)]
    for a, w in zip(got, want):
        _close(a, w)
    mix(5)
    assert asked == [(b, h, d, d)]


def test_a_kernel_error_reaches_the_caller(monkeypatch):
    """No silent stand-in: what the kernel raises, `kda_mix` raises."""
    def broken(*args, **kw):
        raise RuntimeError('mosaic says no')
    monkeypatch.setattr(pallas, 'kda_step_kernel', lambda state: broken)
    z = jnp.zeros
    with pytest.raises(RuntimeError, match='mosaic says no'):
        ling3.kda_mix(z((1, 1, 8)), z((1, 1, 8)), z((1, 1, 8)), z((1, 1, 8)),
                      z((1, 1, 1)), z((8, 4)), z((8, 4)), z((8, 4)),
                      z((1,)), z((8,)), z((1, 1, 8, 8)), z((1, 3, 24)),
                      folded=1, lower=-5.0, chunk=16, fold_all=True)
