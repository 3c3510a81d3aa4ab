"""`nlp/jamba.py` against its plain float32 reference
(`benchmarks/reference/jamba.py`: the selective recurrence token by
token, the convolution as four shifted sums, attention as one masked
softmax) at the tiny presets, with seeded weights in the LONG-MEMORY
regime the benchmark's initializer cannot reach: `A_log` = log(1..N) a
row (states of one channel forget at rates 1..N), `b_dt` such that
softplus(b_dt) lies in [1e-3, 1e-1] (a state keeps its past over
hundreds of tokens), random taps and a convolution bias at std 0.3 (the
benchmark's are ones and zero: there a reversed tap order would not
show), the three inner norms' weights and `D` off one. Model-level: what
builds no engine; the served half is `tests/test_jamba_serving.py`, the
shared cases and helpers `tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (chunks of an associative scan against one token
after another; a state carried from call to call, held `[N, d_inner]`,
against the whole sequence from zeros, `[d_inner, N]`; a cache against a
full forward; grouped against repeated K,V heads). Observed at most 5e-5
on logits as large as 6; every departure from the published mathematics
below moves a logit by more than 50 x TOL. 2e-4 lies between with room
on both sides."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import programs
from paddle_tpu.nlp import generation, jamba
from paddle_tpu.nlp.jamba import JambaConfig, JambaForCausalLM

import family_harness as H


def _draw(R, cfg, seed):
    """The long-memory regime: see the module's docstring."""
    drawn = ('.conv_w', '.conv_b', '.dt_b', '.dt_norm', '.b_norm',
             '.c_norm', '.d')
    shapes = {k: (shape, 'normal' if k.endswith(drawn) else kind)
              for k, (shape, kind) in R.param_shapes(cfg).items()}
    out = {}
    for k, v in H.draw(shapes, seed).items():
        if k.endswith('.a_log'):
            v = jnp.broadcast_to(jnp.log(jnp.arange(
                1.0, v.shape[1] + 1.0)), v.shape)
        elif k.endswith('.dt_b'):       # N(0, 0.3) -> softplus in [1e-3, 1e-1]
            at = 0.5 * (1.0 + jnp.tanh(v / 0.3 * 1.2))
            v = jnp.log(jnp.expm1(jnp.exp(
                np.log(1e-3) + at * np.log(1e-1 / 1e-3))))
        elif k.endswith(('.dt_norm', '.b_norm', '.c_norm', '.d')):
            v = 1.0 + v
        out[k] = v
    return out


FAM = H.Family('JambaForCausalLM', JambaConfig,
               ('tiny', 'tiny_attention_last'), draw=_draw,
               one_position=True)
R = FAM.R
built, tiny = H.fixtures(FAM)


@pytest.fixture(autouse=True, scope='module')
def chunks_of_sixteen_tokens():
    """The scan's chunk is 64 tokens and these tests' sequences 40 and
    fewer: with chunks of 16 a forward is three, a bucket of 32 two."""
    patch = pytest.MonkeyPatch()
    patch.setattr(jamba, 'SSM_CHUNK', 16)
    yield
    patch.undo()


# ---------------------------------------------------------------------------
# (a) the whole forward: over its own tokens and against rows held
# ---------------------------------------------------------------------------
test_full_forward_agrees_with_the_reference_on_both_paths = \
    H.full_forward(FAM, H.paths)
test_a_left_padded_batch_forward_is_each_prompt_alone = \
    H.left_padded_forward(FAM)


def test_the_draw_is_the_long_memory_regime():
    cfg, w, _ = FAM.build()
    dt = np.asarray(jax.nn.softplus(w['l0.dt_b']))
    assert 1e-3 <= dt.min() < 3e-3 and 3e-2 < dt.max() <= 1e-1
    assert np.allclose(np.exp(np.asarray(w['l0.a_log']))[5],
                       np.arange(1, cfg['mamba_d_state'] + 1))
    # the slowest state of the slowest channel keeps 99.9% a token
    assert np.exp(-dt.min() * 1.0) > 0.997
    assert np.asarray(w['l0.conv_w']).std() > 0.2
    assert np.abs(np.asarray(w['l0.b_norm']) - 1).max() > 0.1


# ---------------------------------------------------------------------------
# (b) the chunked scan against the recurrence, token by token
# ---------------------------------------------------------------------------
def _operands(seed, bsz, s, di, n, regime):
    rs = np.random.RandomState(seed)
    f32 = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa
    dt = {'long': jnp.exp(jnp.asarray(rs.uniform(
              np.log(1e-3), np.log(1e-1), (bsz, s, di)), jnp.float32)),
          'short': jax.nn.softplus(2.0 + f32(bsz, s, di)),
          'mixed': jax.nn.softplus(3.0 * f32(bsz, s, di) - 2.0)}[regime]
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1.0), (di, n))
    return (f32(bsz, s, di), dt, f32(bsz, s, n), f32(bsz, s, n), a,
            1.0 + 0.3 * f32(di), f32(bsz, n, di))


@functools.cache
def _token_by_token(seed, regime, shape, upto):
    """`mamba_step` over the first `upto` tokens, one after another (a
    `lax.scan` of the step: one compile a length) -> (y, the state)."""
    u, dt, b, c, a, d, h = _operands(seed, *shape, regime)

    def step(h, x):
        y, h = jamba.mamba_step(*x, a, d, h)
        return h, y
    h, ys = jax.jit(lambda h, xs: jax.lax.scan(step, h, xs))(
        h, tuple(jnp.moveaxis(t[:, :upto], 1, 0) for t in (u, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), h


@pytest.mark.parametrize('chunk', [16, 50, 64, 150])
@pytest.mark.parametrize('regime', ['long', 'short', 'mixed'])
def test_chunked_scan_is_the_recurrence_token_by_token(regime, chunk):
    """150 tokens from a state that is not zero, in chunks that divide
    the length (50, 150) and chunks that do not (16, 64): `y` of every
    token and the state after all of them; then with 117 of them folded:
    the state AS OF those, whatever follows."""
    shape = (2, 150, 24, 8)
    ops = _operands(3, *shape, regime)
    scan = jax.jit(jamba.mamba_scan, static_argnums=8)
    want, end = _token_by_token(3, regime, shape, 150)
    got, state = scan(*ops, None, chunk)
    assert np.abs(np.asarray(want)).max() > 1.0
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    assert np.abs(np.asarray(state - end)).max() < 2e-5
    _, at_117 = _token_by_token(3, regime, shape, 117)
    got, state = scan(*ops, jnp.int32(117), chunk)
    assert np.abs(np.asarray(state - at_117)).max() < 2e-5
    assert np.abs(np.asarray(end - at_117)).max() > 1e-3
    # (what the folded tokens gave is what they gave)
    assert np.abs(np.asarray(got - want))[:, :117].max() < 2e-5


@pytest.mark.parametrize('chunk', [16, 64])
def test_the_pads_leave_the_state_bit_for_bit(chunk):
    """A pad is `dt = 0`: decay exactly one, input exactly nothing. A
    call that folds nothing hands the state back as it got it; and what
    stands after the folded tokens — other tokens, other lengths of
    padding inside the chunk — changes not one bit of what they left."""
    u, dt, b, c, a, d, h0 = _operands(5, 2, 40, 24, 8, 'mixed')
    scan = jax.jit(jamba.mamba_scan, static_argnums=8)
    _, same = scan(u, dt, b, c, a, d, h0, jnp.int32(0), chunk)
    assert (np.asarray(same) == np.asarray(h0)).all()
    _, first = scan(u, dt, b, c, a, d, h0, jnp.int32(23), chunk)
    other = _operands(6, 2, 40, 24, 8, 'short')
    tail = lambda mine, theirs: jnp.concatenate(     # noqa: E731
        [mine[:, :23], theirs[:, 23:]], axis=1)
    _, second = scan(tail(u, other[0]), tail(dt, other[1]),
                     tail(b, other[2]), tail(c, other[3]), a, d, h0,
                     jnp.int32(23), chunk)
    assert (np.asarray(first) == np.asarray(second)).all()
    assert np.abs(np.asarray(first - h0)).max() > 0.1


@pytest.mark.parametrize('tokens,carried', [(1, True), (7, True), (7, False),
                                            (2, True), (1, False)])
def test_causal_conv_against_a_direct_sum(tokens, carried):
    rs = np.random.RandomState(tokens)
    x, w, bias = rs.randn(2, tokens, 8), rs.randn(8, 4), rs.randn(8)
    state = rs.randn(2, 3, 8) if carried else np.zeros((2, 3, 8))
    past = np.concatenate([state, x], axis=1)
    want = np.zeros((2, tokens, 8))
    for t in range(tokens):
        for j in range(4):              # tap j reads the input 3 - j ago
            want[:, t] += w[:, j] * past[:, 3 + t - (3 - j)]
    want = np.asarray(jax.nn.silu(jnp.asarray(want + bias, jnp.float32)))
    for folded in range(tokens + 1):
        got, new = jamba.short_conv_silu(
            *(jnp.asarray(v, jnp.float32) for v in (x, w, state)),
            jnp.int32(folded), jnp.asarray(bias, jnp.float32))
        assert np.abs(np.asarray(got) - want).max() < 1e-5
        # the state after `folded` tokens: the last three inputs by then
        assert np.abs(np.asarray(new) - past[:, folded:folded + 3]).max() \
            < 1e-6
        assert new.dtype == jnp.float32


def test_a_state_carried_from_call_to_call_is_the_whole_sequence(tiny):
    """The layer itself: one call over 40 tokens against calls of 17, 1
    and 22, the entry handed from each to the next."""
    _, _, model = tiny
    mixer = model.model.layers[0].mamba
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 40, 32)
                         .astype('float32'))
    whole = mixer(x).numpy()
    state = jax.tree_util.tree_map(paddle.to_tensor, mixer.init_state(2))
    at = 0
    for n in (17, 1, 22):
        out, state = mixer(x[:, at:at + n], state=state)
        assert np.abs(out.numpy() - whole[:, at:at + n]).max() < 1e-5
        at += n
    # lanes-whole: d_inner 64 minor on both leaves, the 8 states above it
    assert set(state) == {'h', 'conv'}
    assert tuple(state['h'].shape) == (2, 8, 64)
    assert tuple(state['conv'].shape) == (2, 3, 64)


@pytest.mark.parametrize('length', [1, 9, 16, 17, 31])
def test_a_padded_buckets_state_is_the_exact_lengths(tiny, length):
    """Under `state_scope(n)` a call of 32 tokens returns the entry as
    the first n leave it — whatever follows them — and that is the
    entry a call of exactly n tokens returns."""
    _, _, model = tiny
    mixer = model.model.layers[0].mamba
    x = np.random.RandomState(2).randn(1, 32, 32).astype('float32')
    zero = jax.tree_util.tree_map(paddle.to_tensor, mixer.init_state(1))
    _, exact = mixer(paddle.to_tensor(x[:, :length]), state=zero)
    with generation.state_scope(jnp.int32(length)):
        _, padded = mixer(paddle.to_tensor(x), state=zero)
    for leaf in ('h', 'conv'):
        assert np.abs(padded[leaf].numpy() - exact[leaf].numpy()).max() \
            < 1e-6


# ---------------------------------------------------------------------------
# each departure from the published mathematics fails the tolerance
# ---------------------------------------------------------------------------
def _mixers(model):
    return [l.mamba for l in model.model.layers if not l.is_attention]


def _attention(model):
    return [l.self_attn for l in model.model.layers if l.is_attention]


def _no_inner_norms(model, mp):
    for mixer in _mixers(model):
        mixer.dt_layernorm = mixer.b_layernorm = mixer.c_layernorm = \
            lambda t: t


def _dt_without_its_bias(model, mp):
    for mixer in _mixers(model):
        mixer.dt_proj.bias._data = jnp.zeros_like(mixer.dt_proj.bias._data)


def _d_dropped(model, mp):
    for mixer in _mixers(model):
        mixer.D._data = jnp.zeros_like(mixer.D._data)


def _gate_on_u(model, mp):
    """`y * silu(u)`: the gate on the convolved input, not on `z`."""
    real = jamba.mamba_mix
    mp.setattr(jamba, 'mamba_mix',
               lambda u, z, *rest, **kw: real(u, u, *rest, **kw))


def _taps_reversed(model, mp):
    for mixer in _mixers(model):
        mixer.conv_weight._data = mixer.conv_weight._data[:, ::-1]


def _no_conv_bias(model, mp):
    for mixer in _mixers(model):
        mixer.conv_bias._data = jnp.zeros_like(mixer.conv_bias._data)


def _decay_a_channel_not_a_state(model, mp):
    """Every state of a channel forgetting alike: `A` its row's mean."""
    for mixer in _mixers(model):
        a = -jnp.exp(mixer.A_log._data)
        mixer.A_log._data = jnp.log(-jnp.broadcast_to(
            jnp.mean(a, -1, keepdims=True), a.shape))


def _rope_on_attention(model, mp):
    model.config.rope_theta = 10000.0
    for attn in _attention(model):
        attn.rotary = True


def _qk_norm_on_attention(model, mp):
    from paddle_tpu.nn import functional as F
    ones = paddle.to_tensor(np.ones(8, 'float32'))
    for attn in _attention(model):
        attn.q_norm = attn.k_norm = \
            lambda t: F.rms_norm(t, ones, epsilon=1e-6)


def _bf16_operands(model, mp):
    H.bf16_operands(model, mp, ('input_layernorm', 'pre_ff_layernorm'),
                    'final_layernorm')


test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(FAM, [
        _no_inner_norms, _dt_without_its_bias, _d_dropped, _gate_on_u,
        _taps_reversed, _no_conv_bias, _decay_a_channel_not_a_state,
        _rope_on_attention, _qk_norm_on_attention, _bf16_operands])


# ---------------------------------------------------------------------------
# generate: the batch path builds no engine
# ---------------------------------------------------------------------------
test_generate_gives_the_references_greedy_tokens = H.generate_greedy(FAM, 10)
test_generate_refuses_padded_prompts_and_speculation = \
    H.generate_refuses(FAM, 'Mamba')


def test_config_presets_and_refusals():
    conf = JambaConfig()        # the defaults are the published file's
    assert [i for i, t in enumerate(conf.layer_types)
            if t == 'full_attention'] == [7, 21]
    assert conf.layer_pattern == 'MMMMMMMAMMMMMM' * 2
    assert (conf.head_dim, conf.mamba_d_inner) == (128, 5120)
    assert conf.rope_theta is None and conf.sliding_window is None
    assert JambaConfig.tiny().layer_pattern == 'MAMMM'
    assert JambaConfig.tiny_attention_last().layer_pattern == 'MMA'
    assert 'MAMMM' in programs.describe_statics(JambaConfig.tiny())
    H.refused(JambaConfig.tiny, (
        (dict(num_experts=16, num_experts_per_tok=2), 'num_experts 16'),
        (dict(num_experts_per_tok=2), 'num_experts 1 .top 2'),
        (dict(sliding_window=4096), 'sliding_window'),
        (dict(mamba_proj_bias=True), 'mamba_proj_bias'),
        (dict(mamba_conv_bias=False), 'mamba_conv_bias'),
        (dict(tie_word_embeddings=False), 'tie_word_embeddings'),
        (dict(hidden_act='gelu'), 'hidden_act'),
        (dict(num_attention_heads=5), 'num_attention_heads')))
    from paddle_tpu.nlp import transformers
    assert transformers.JambaForCausalLM is JambaForCausalLM
    # every parameter the published file's shapes imply, at the tiny size
    model = JambaForCausalLM(JambaConfig.tiny())
    shapes = {n: tuple(p.shape) for n, p in model.named_parameters()}
    assert shapes['model.layers.0.mamba.A_log'] == (64, 8)
    assert shapes['model.layers.0.mamba.x_proj.weight'] == (64, 6 + 16)
    assert shapes['model.layers.0.mamba.dt_proj.bias'] == (64,)
    assert not any('norm' in n for n in shapes
                   if n.startswith('model.layers.1.self_attn'))
    assert not any(n.startswith('lm_head') for n in shapes)
