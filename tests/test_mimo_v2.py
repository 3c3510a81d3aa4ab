"""`nlp/mimo_v2.py` against its plain float32 reference
(`benchmarks/reference/mimo_v2.py`) at the tiny presets, with seeded
weights whose sinks are drawn at std 2 and whose selection bias is large
enough to change picks (the benchmark's are 0.02: there a dropped sink
would not show, here it does). Model-level: what builds no engine; the
served half is `tests/test_mimo_v2_serving.py`, the shared cases and
helpers `tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (a ring of the window's rows against a banded mask
over the whole sequence; a sink folded into the softmax's denominator
against an appended column; sorted blocks of the held experts against
every held expert for every token; grouped against repeated KV heads).
Observed at most 1.2e-5 on logits as large as 7; every departure from
the published mathematics below moves a logit by more than 0.05, and
operands rounded to bfloat16 — what one bf16 pass of the MXU would make
of the float32 activations — by 0.3, an expert choice flipped. 2e-4 lies
between with room on both sides."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import programs
from paddle_tpu.nlp import afmoe, generation
from paddle_tpu.nlp.llama import _rope
from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.ops import pallas, pallas_kernels

from benchmarks.reference import common as C

import family_harness as H
from family_harness import TOL

WINDOW = 4


def _draw(R, cfg, seed):
    """The sinks at std 2."""
    w = H.draw(R.param_shapes(cfg), seed)
    return {k: v * (2.0 / 0.3) if k.endswith('.sink') else v
            for k, v in w.items()}


FAM = H.Family(
    'MiMoV2ForCausalLM', MiMoV2Config, ('tiny', 'tiny_window_first'),
    cfg_adds=lambda conf: dict(
        n_shared_experts=None, scoring_func='sigmoid', n_group=1,
        topk_group=1, tie_word_embeddings=False,
        expert_share={'routed': conf.num_routed_experts,
                      'first': conf.first_expert}),
    draw=_draw, one_position=True)
R = FAM.R
built, tiny = H.fixtures(FAM)


# ---------------------------------------------------------------------------
# (a) the whole forward
# ---------------------------------------------------------------------------
test_full_forward_agrees_with_the_reference = H.full_forward(FAM)
test_a_left_padded_batch_forward_is_each_prompt_alone = \
    H.left_padded_forward(FAM)


@pytest.mark.parametrize('chunks', [(7,) + (1,) * 13, (5, 6, 9), (1, 19),
                                    (3, 3, 3, 3, 8)])
def test_a_cache_carried_from_call_to_call_is_the_whole_sequence(built,
                                                                 chunks):
    """Calls of one token write the ring and then attend over it; a
    longer call attends over the ring as it found it beside its own
    tokens: 20 tokens through a window of 4, in pieces."""
    cfg, w, model = built
    ids = H.ids((2, 20), 9)
    ref = FAM.ref_logits(cfg, w, ids)
    fwd, cache, at = H.cached_fwd(model), model.init_cache(2, 64), 0
    for n in chunks:
        lg, cache = fwd(jnp.asarray(ids[:, at:at + n]), cache, jnp.int32(at),
                        jnp.int32(at), None)
        assert np.abs(np.asarray(lg) - ref[:, at:at + n]).max() < TOL, (at, n)
        at += n


# ---------------------------------------------------------------------------
# (b) attention alone
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('kv_heads', [4, 2, 1])
def test_sink_against_the_closed_form_and_v_narrower_than_qk(kv_heads):
    """With a sink the output is the sinkless output times
    `sigmoid(lse - s_h)`: the sink takes `exp(s) / (exp(s) + sum exp(l))`
    of the mass and gives nothing. q and k 12 wide, v 8."""
    rs = np.random.RandomState(kv_heads)
    q = jnp.asarray(rs.randn(2, 5, 4, 12), jnp.float32)
    k = jnp.asarray(rs.randn(2, 9, kv_heads, 12), jnp.float32)
    v = jnp.asarray(rs.randn(2, 9, kv_heads, 8), jnp.float32)
    sink = jnp.asarray(2.0 * rs.randn(4), jnp.float32)
    mask = jnp.asarray(rs.rand(2, 1, 5, 9) < 0.7).at[..., 0].set(True)
    plain = pallas._attention_xla(q, k, v, mask=mask)
    assert plain.shape == (2, 5, 4, 8)
    kr = jnp.repeat(k, 4 // kv_heads, axis=2)
    logits = jnp.einsum('bqhd,bkhd->bhqk', q, kr) / np.sqrt(12.0)
    lse = jax.nn.logsumexp(jnp.where(mask, logits, -1e30), axis=-1)
    want = plain * jnp.transpose(
        jax.nn.sigmoid(lse - sink[None, :, None]), (0, 2, 1))[..., None]
    got = pallas._attention_xla(q, k, v, mask=mask, sink=sink)
    assert np.abs(np.asarray(got - want)).max() < 1e-6
    assert np.abs(np.asarray(got - plain)).max() > 1e-2
    # the dispatch: such a call is XLA's by the conditions alone
    assert np.abs(np.asarray(pallas.flash_attention(
        q, k, v, mask=mask, sink=sink) - want)).max() < 1e-6


def test_a_square_call_with_a_sink_or_a_narrow_v_never_takes_the_kernel(
        monkeypatch):
    """On a TPU a cache-free square call without a mask is the flash
    kernel's; with a sink, or a v of another width, it is XLA's."""
    monkeypatch.setattr(pallas, '_pallas_enabled', lambda: True)
    called = []
    monkeypatch.setattr(pallas_kernels, 'flash_attention',
                        lambda *a, **k: called.append(a) or a[0])
    q = jnp.zeros((1, 128, 4, 64), jnp.float32)
    pallas.flash_attention(q, q, q, causal=True)
    assert len(called) == 1
    pallas.flash_attention(q, q, q, causal=True, sink=jnp.zeros(4))
    pallas.flash_attention(q, q, q[..., :32], causal=True)
    assert len(called) == 1


@pytest.mark.parametrize('theta', [1e7, 1e4])
def test_partial_rotary_against_a_hand_written_rotation(theta):
    """The first 4 of 12 dims rotate, rotate-half WITHIN them (dim 0
    with dim 2, dim 1 with dim 3); the other 8 pass as they are."""
    rs = np.random.RandomState(0)
    x = rs.randn(1, 6, 2, 12).astype('float32')
    pos = np.array([0, 1, 2, 50, 51, 1000], 'int32')
    got = np.asarray(_rope(jnp.asarray(x), jnp.asarray(pos), theta, 4))
    want = x.copy()
    for t, p in enumerate(pos):
        for pair, freq in ((0, 1.0), (1, theta ** -0.5)):
            a, b = x[0, t, :, pair], x[0, t, :, pair + 2]
            c, s = np.cos(p * freq), np.sin(p * freq)
            want[0, t, :, pair] = a * c - b * s
            want[0, t, :, pair + 2] = b * c + a * s
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())
    assert (got[..., 4:] == x[..., 4:]).all()
    # all of the head rotating is the call it was
    assert (np.asarray(_rope(jnp.asarray(x), jnp.asarray(pos), theta, 12))
            == np.asarray(_rope(jnp.asarray(x), jnp.asarray(pos),
                                theta))).all()


def test_each_kind_of_layer_has_its_own_theta_heads_and_sink(tiny):
    _, _, model = tiny
    full, window = (model.model.layers[i].self_attn for i in (0, 1))
    assert (full.theta, window.theta) == (1e7, 1e4)
    assert (full.num_key_value_heads, window.num_key_value_heads) == (1, 2)
    assert (full.window, window.window) == (None, WINDOW)
    assert full.sink is None and tuple(window.sink.shape) == (4,)
    assert model.attention_windows() == (None, WINDOW, WINDOW, None)
    other = MiMoV2ForCausalLM(MiMoV2Config.tiny_window_first())
    assert other.attention_windows() == (WINDOW, None, WINDOW)
    assert other.model.layers[0].self_attn.sink is None
    assert other.model.layers[1].self_attn.sink is not None


# ---------------------------------------------------------------------------
# (c) the router
# ---------------------------------------------------------------------------
def test_route_bias_selects_and_weights_are_normalised_over_all_picks():
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2, 0.7]], jnp.float32)
    sel, w = afmoe.route(scores, jnp.zeros(5), 3, True, 1.0, 1e-20)
    assert sel.tolist() == [[0, 1, 4]]
    assert np.allclose(w, np.array([[0.9, 0.8, 0.7]]) / 2.4, atol=1e-7)
    # a bias that changes the selection: expert 2 in, expert 4 out — and
    # its weight is its SCORE's share, the bias nowhere in it
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0, 0.0])
    sel, w = afmoe.route(scores, bias, 3, True, 1.0, 1e-20)
    assert sel.tolist() == [[2, 0, 1]]
    assert np.allclose(w, np.array([[0.1, 0.9, 0.8]]) / 1.8, atol=1e-7)


def test_a_share_normalises_over_picks_it_does_not_hold(tiny):
    """The layer holds experts 4-7 of 16: a token's weights on them are
    its scores over the sum of BOTH its picks, held or not."""
    _, _, model = tiny
    layer = model.model.layers[1].mlp
    assert layer.holds_share and layer.first_expert == 4
    assert tuple(layer.router.weight.shape) == (32, 16)
    assert tuple(layer.expert_bias.shape) == (16,)
    assert tuple(layer.gate_w.shape) == (4, 32, 16)
    with pytest.raises(ValueError, match='not among the router'):
        MiMoV2ForCausalLM(MiMoV2Config.tiny(first_expert=14))


# ---------------------------------------------------------------------------
# (d) the share tied to the model: the shares add up to the whole layer
# ---------------------------------------------------------------------------
def _layer_and_reference(first, held, dtype='float32', seed=3):
    """The PROGRAM's expert layer holding experts first..first+held-1 of
    16, and the uncut reference's weights it was cut from."""
    cfg = FAM.cfg('tiny', n_routed_experts=16, first_expert=0)
    shapes = {k[3:]: v for k, v in R.param_shapes(cfg).items()
              if k.startswith('l1.') and ('expert' in k or 'router' in k)}
    lp = H.draw(shapes, seed)
    layer = afmoe.AfmoeSparseMLP(MiMoV2Config.tiny(
        n_routed_experts=held, first_expert=first))
    layer.router.weight._data = lp['router_w']
    layer.expert_bias._data = lp['expert_bias']
    for name, leaf in (('gate_w', 'experts_gate'), ('up_w', 'experts_up'),
                       ('down_w', 'experts_down')):
        getattr(layer, name)._data = \
            lp[leaf][first:first + held].astype(dtype)
    return cfg, lp, layer.eval()


@pytest.mark.parametrize('kernel', [False, True],
                         ids=['grouped_experts', 'moe_decode_experts'])
def test_the_four_shares_add_up_to_the_uncut_reference(kernel, monkeypatch,
                                                       fresh_dispatch):
    """Experts 0-3, 4-7, 8-11, 12-15, each through the program's layer
    (the loop over blocks; with bf16 leaves, the interpreted kernel):
    their partial sums add up to what the reference gives for the whole
    layer of 16, and each is what the reference gives for that share."""
    if kernel:
        monkeypatch.setattr(afmoe, 'expert_kernel', functools.partial(
            pallas.expert_kernel, interpret=True))
    dtype = 'bfloat16' if kernel else 'float32'
    m = jnp.asarray(np.random.RandomState(5).randn(1, 24, 32), jnp.float32)
    total = 0.0
    for first in (0, 4, 8, 12):
        cfg, lp, layer = _layer_and_reference(first, 4, dtype)
        if kernel:      # the reference on the same rounded leaves
            lp = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                  if k.startswith('experts_') else v for k, v in lp.items()}
        # (no tape: the kernel, as the loop, has no reverse mode)
        with generation.routing_scope() as picks, paddle.no_grad():
            part = np.asarray(layer(paddle.to_tensor(m)).numpy())[0]
        assert picks[0][1:] == (4, kernel, True)
        share = dict(cfg, n_routed_experts=4,
                     expert_share={'routed': 16, 'first': first})
        own = {k: v[first:first + 4] if k.startswith('experts_') else v
               for k, v in lp.items()}
        assert np.abs(part - np.asarray(
            R.experts(C.Ref(), share, own, m[0]))).max() < TOL
        total = total + part
    whole = np.asarray(R.experts(C.Ref(), cfg, lp, m[0]))
    assert np.abs(whole).max() > 0.5
    assert np.abs(total - whole).max() < TOL


@pytest.mark.parametrize('kernel', [False, True],
                         ids=['grouped_experts', 'moe_decode_experts'])
def test_a_batch_none_of_whose_picks_is_held_gives_zeros(kernel):
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(6, 32), jnp.float32)
    leaves = [jnp.asarray(rs.randn(*s), jnp.bfloat16)
              for s in ((4, 32, 16), (4, 32, 16), (4, 16, 32))]
    sel = jnp.full((6, 2), 4, jnp.int32)        # 4 = one past the last
    w = jnp.zeros((6, 2), jnp.float32)
    fn = functools.partial(pallas_kernels.moe_decode_experts,
                           interpret=True) if kernel \
        else afmoe.grouped_experts
    out = np.asarray(fn(x, sel, w, *leaves))
    assert out.shape == (6, 32) and (out == 0).all()


# ---------------------------------------------------------------------------
# each departure from the published mathematics fails the tolerance
# ---------------------------------------------------------------------------
def _no_sink(model, mp):
    for layer in model.model.layers:
        layer.self_attn.sink = None


def _no_value_scale(model, mp):
    model.config.attention_value_scale = 1.0


def _all_dims_rotate(model, mp):
    model.config.rotary_dim = model.config.head_dim


def _one_theta(model, mp):
    for layer in model.model.layers:
        layer.self_attn.theta = model.config.rope_theta


def _window_one_longer(model, mp):
    for layer in model.model.layers:
        if layer.self_attn.window is not None:
            layer.self_attn.window += 1


def _normalised_over_the_held_picks_only(model, mp):
    real = afmoe.route

    def held_only(scores, bias, k, route_norm, route_scale, eps):
        sel, w = real(scores, bias, k, False, route_scale, eps)
        mine = (sel >= 4) & (sel < 8)
        return sel, w / (jnp.sum(jnp.where(mine, w, 0.0), axis=-1,
                                 keepdims=True) + eps)
    mp.setattr(afmoe, 'route', held_only)


def _no_expert_bias(model, mp):
    for layer in model.model.layers[1:]:
        layer.mlp.expert_bias._data = jnp.zeros(16, jnp.float32)


def _another_share(model, mp):
    for layer in model.model.layers[1:]:
        layer.mlp.first_expert = 5


test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(FAM, [
        _no_sink, _no_value_scale, _all_dims_rotate, _one_theta,
        _window_one_longer, H.bias_in_weight,
        _normalised_over_the_held_picks_only, _no_expert_bias,
        _another_share, H.bf16_operands])


# ---------------------------------------------------------------------------
# generate: the batch path builds no engine
# ---------------------------------------------------------------------------
test_generate_gives_the_references_greedy_tokens = H.generate_greedy(FAM, 14)
test_generate_refuses_padded_prompts_and_speculation = \
    H.generate_refuses(FAM, 'ring')


def test_config_presets_and_refusals():
    conf = MiMoV2Config()       # the defaults are the published file's
    assert conf.layer_pattern == 'FWWWWF' + 'WWWWWF' * 7
    assert conf.moe_pattern == 'D' + 'E' * 47
    assert conf.rotary_dim == 64 and conf.num_routed_experts == 256
    assert conf.route_scale == 1.0 and conf.num_shared_experts == 0
    assert MiMoV2Config.tiny().layer_pattern == 'FWWF'
    assert MiMoV2Config.tiny_window_first().layer_pattern == 'WFW'
    assert 'FWWF' in programs.describe_statics(MiMoV2Config.tiny())
    H.refused(MiMoV2Config.tiny, (
        (dict(scoring_func='softmax'), 'scoring_func'),
        (dict(n_group=2), 'n_group'),
        (dict(n_shared_experts=1), 'n_shared_experts'),
        (dict(tie_word_embeddings=True), 'untied head'),
        (dict(hybrid_layer_pattern=[0]), 'hybrid_layer_pattern'),
        (dict(partial_rotary_factor=0.3), 'rotate-half')))
    from paddle_tpu.nlp import transformers
    assert transformers.MiMoV2ForCausalLM is MiMoV2ForCausalLM
