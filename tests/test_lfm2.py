"""`nlp/lfm2.py` against its plain float32 reference
(`benchmarks/reference/lfm2.py`) at the tiny presets, with seeded
weights whose `expert_bias` is not zero and whose convolution taps are
random (the benchmark's are one: there a reversed tap order would not
show, here it does). Model-level: what builds no engine; the served half
is `tests/test_lfm2_serving.py`, the shared cases and helpers
`tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (a state carried from call to call against shifted
copies of the whole sequence; sorted blocks of one expert against every
expert for every token; a cache against a full forward; grouped against
repeated KV heads). Observed at most 1.3e-5 on logits as large as 6;
every departure from the published mathematics below moves a logit by
more than 2, and operands rounded to bfloat16 — what one bf16 pass of
the MXU would make of the float32 activations — by 1.1, an expert choice
flipped. 2e-4 lies between with room on both sides."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import programs
from paddle_tpu.nlp import afmoe, lfm2
from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM

import family_harness as H


def _draw(R, cfg, seed):
    """The taps random too (the reference's shapes say ones)."""
    return H.draw({k: (shape, 'normal' if k.endswith('conv_w') else kind)
                   for k, (shape, kind) in R.param_shapes(cfg).items()}, seed)


FAM = H.Family(
    'Lfm2MoeForCausalLM', Lfm2MoeConfig, ('tiny', 'tiny_conv_first'),
    cfg_adds=lambda conf: dict(
        rope_parameters={'rope_theta': conf.rope_theta}),
    draw=_draw, one_position=True)
built, tiny = H.fixtures(FAM)


# ---------------------------------------------------------------------------
# (a) the whole forward
# ---------------------------------------------------------------------------
test_full_forward_agrees_with_the_reference = H.full_forward(FAM)
test_a_left_padded_batch_forward_is_each_prompt_alone = \
    H.left_padded_forward(FAM)


# ---------------------------------------------------------------------------
# (b) the conv operator alone, against a direct sum
# ---------------------------------------------------------------------------
def _conv_by_loop(bcz, w, state):
    b, s, h3 = bcz.shape
    h, taps = h3 // 3, w.shape[1]
    u = np.concatenate([state, bcz[..., :h] * bcz[..., 2 * h:]], axis=1)
    out = np.zeros((b, s, h))
    for t in range(s):
        for j in range(taps):       # tap j reads the input taps-1-j ago
            out[:, t] += w[:, j] * u[:, taps + t - (taps - 1 - j)]
    return bcz[..., h:2 * h] * out, u


@pytest.mark.parametrize('tokens,carried', [(1, True), (7, True), (7, False),
                                            (2, True), (1, False)])
def test_short_conv_against_a_direct_sum(tokens, carried):
    rs = np.random.RandomState(tokens)
    bcz, w = rs.randn(2, tokens, 3 * 8), rs.randn(8, 3)
    state = rs.randn(2, 3, 8) if carried else np.zeros((2, 3, 8))
    want, u = _conv_by_loop(bcz, w, state)
    for folded in range(tokens + 1):
        got, new = lfm2.short_conv(
            jnp.asarray(bcz, jnp.float32), jnp.asarray(w, jnp.float32),
            jnp.asarray(state, jnp.float32), jnp.int32(folded))
        assert np.abs(np.asarray(got) - want).max() < 1e-5
        # the state after `folded` tokens: the last three inputs by then
        assert np.abs(np.asarray(new) - u[:, folded:folded + 3]).max() < 1e-6
        assert new.dtype == jnp.float32


def test_a_state_carried_token_by_token_is_the_whole_sequence(tiny):
    """The layer itself: one call over 9 tokens against 9 calls of one,
    the state handed from each to the next."""
    _, _, model = tiny
    conv = model.model.layers[0].conv
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 9, 32)
                         .astype('float32'))
    whole = conv(x).numpy()
    state = paddle.to_tensor(np.zeros((2, 3, 32), 'float32'))
    for t in range(9):
        out, state = conv(x[:, t:t + 1], state=state)
        assert np.abs(out.numpy()[:, 0] - whole[:, t]).max() < 1e-5


# ---------------------------------------------------------------------------
# (c) the router
# ---------------------------------------------------------------------------
def test_route_bias_selects_and_is_not_in_the_weight():
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]], jnp.float32)
    none = jnp.zeros(4)
    sel, w = afmoe.route(scores, none, 2, True, 1.0, 1e-6)
    assert sel.tolist() == [[0, 1]]
    assert np.allclose(w, [[0.9 / 1.700001, 0.8 / 1.700001]], atol=1e-7)
    # a bias that changes the selection: expert 2 in, expert 1 out — and
    # its weight is its SCORE's share, the bias nowhere in it
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0])
    sel, w = afmoe.route(scores, bias, 2, True, 1.0, 1e-6)
    assert sel.tolist() == [[2, 0]]
    assert np.allclose(w, [[0.1 / 1.000001, 0.9 / 1.000001]], atol=1e-7)
    # unnormalised and scaled
    _, w = afmoe.route(scores, bias, 2, False, 2.0, 1e-6)
    assert np.allclose(w, [[0.2, 1.8]])


def test_route_epsilon_is_an_argument_and_each_family_has_its_own():
    tiny_scores = jnp.asarray([[1e-6, 1e-6, 0.0]], jnp.float32)
    _, w6 = afmoe.route(tiny_scores, jnp.zeros(3), 2, True, 1.0, 1e-6)
    _, w20 = afmoe.route(tiny_scores, jnp.zeros(3), 2, True, 1.0, 1e-20)
    assert np.allclose(w6, [[1 / 3, 1 / 3]]) and np.allclose(w20, [[.5, .5]])
    assert lfm2.Lfm2SparseMLP.route_norm_eps == 1e-6
    assert afmoe.AfmoeSparseMLP.route_norm_eps == 1e-20


# ---------------------------------------------------------------------------
# each departure from the published mathematics fails the tolerance
# ---------------------------------------------------------------------------
def _no_route_norm(model, mp):
    model.config.route_norm = False


def _no_expert_bias(model, mp):
    for layer in model.model.layers[1:]:
        layer.feed_forward.expert_bias._data = jnp.zeros(8, jnp.float32)


def _taps_reversed(model, mp):
    for layer in model.model.layers:
        if not layer.is_attention:
            layer.conv.conv_weight._data = \
                layer.conv.conv_weight._data[:, ::-1]


def _gates_swapped(model, mp):
    real = lfm2.short_conv

    def swapped(bcz, w, state, folded):
        b, c, z = jnp.split(bcz, 3, axis=-1)
        return real(jnp.concatenate([c, b, z], axis=-1), w, state, folded)
    mp.setattr(lfm2, 'short_conv', swapped)


def _silu_on_the_conv(model, mp):
    real = lfm2.short_conv

    def activated(bcz, w, state, folded):
        out, new = real(bcz, w, state, folded)
        c = jnp.split(bcz, 3, axis=-1)[1]
        return c * jax.nn.silu(out / c), new
    mp.setattr(lfm2, 'short_conv', activated)


def _no_qk_norm(model, mp):
    for layer in model.model.layers:
        if layer.is_attention:
            layer.self_attn.q_norm = layer.self_attn.k_norm = lambda t: t


def _no_rope(model, mp):
    for layer in model.model.layers:
        if layer.is_attention:
            layer.self_attn.rotary = False


def _bf16_operands(model, mp):
    H.bf16_operands(model, mp, ('operator_norm', 'ffn_norm'),
                    'embedding_norm')


test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(FAM, [
        H.bias_in_weight, _no_route_norm, _no_expert_bias, _taps_reversed,
        _gates_swapped, _silu_on_the_conv, _no_qk_norm, _no_rope,
        _bf16_operands])


# ---------------------------------------------------------------------------
# generate: the batch path builds no engine
# ---------------------------------------------------------------------------
test_generate_gives_the_references_greedy_tokens = H.generate_greedy(FAM, 10)
test_generate_refuses_padded_prompts_and_speculation = \
    H.generate_refuses(FAM, 'conv')


def test_config_presets_and_refusals():
    conf = Lfm2MoeConfig()      # the defaults are the published file's
    assert conf.layer_types[:6] == ['conv', 'conv', 'full_attention',
                                    'conv', 'conv', 'conv']
    assert conf.layer_types.count('full_attention') == 10
    assert conf.layer_pattern == 'CC' + 'ACCC' * 9 + 'AC'
    assert conf.head_dim == 64 and conf.num_shared_experts == 0
    assert Lfm2MoeConfig.tiny().layer_pattern == 'CACCC'
    assert Lfm2MoeConfig.tiny_conv_first().layer_pattern == 'CCAC'
    assert 'CACCC' in programs.describe_statics(Lfm2MoeConfig.tiny())
    H.refused(Lfm2MoeConfig.tiny, (
        (dict(conv_bias=True), 'conv_bias'),
        (dict(use_expert_bias=False), 'use_expert_bias'),
        (dict(tie_word_embeddings=False), 'ties its head'),
        (dict(layer_types=['conv']), 'layer_types')))
    from paddle_tpu.nlp import transformers
    assert transformers.Lfm2MoeForCausalLM is Lfm2MoeForCausalLM
