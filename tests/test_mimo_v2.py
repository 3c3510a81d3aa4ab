"""`nlp/mimo_v2.py` against its plain float32 reference
(`benchmarks/reference/mimo_v2.py`) at the tiny presets, with seeded
weights whose sinks are drawn at std 2 and whose selection bias is large
enough to change picks (the benchmark's are 0.02: there a dropped sink
would not show, here it does).

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
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import _dispatch
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import functional_state
from paddle_tpu.nlp import afmoe, generation, mimo_v2
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM, _rope
from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.ops import pallas, pallas_kernels
from paddle_tpu.serving import (InferenceEngine, ReplicaSet, Router,
                                SamplingParams)

from benchmarks.models import adapter, fill
from benchmarks.reference import common as C
from benchmarks.reference import mimo_v2 as R

TOL = 2e-4
AD = adapter('MiMoV2ForCausalLM')
PRESETS = ('tiny', 'tiny_window_first')
BUCKET, BLOCK, WINDOW = 16, 4, 4


def _cfg(preset, **over):
    conf = getattr(MiMoV2Config, preset)(**over)
    cfg = {k: getattr(conf, k, None) for k in AD._KEYS}
    cfg.update(n_shared_experts=None, scoring_func='sigmoid', n_group=1,
               topk_group=1, tie_word_embeddings=False)
    cfg['expert_share'] = {'routed': conf.num_routed_experts,
                           'first': conf.first_expert}
    return cfg


def _weights(cfg, seed=7):
    # std 0.3: logits of a few units, so a departure is not lost in
    # them; a selection bias of 0.3 beside sigmoid scores changes picks;
    # the sinks at std 2
    w = C.make_weights(R.param_shapes(cfg), seed, 'float32', std=0.3)
    return {k: v * (2.0 / 0.3) if k.endswith('.sink') else v
            for k, v in w.items()}


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


@pytest.fixture(scope='module')
def tiny():
    cfg = _cfg('tiny')
    w = _weights(cfg)
    return cfg, w, _model(cfg, w)


@pytest.fixture
def fresh_dispatch():
    """The eager dispatch cache keys an op by its code, not by the
    module globals a departure patches: empty it around such a test."""
    _dispatch.clear()
    yield
    _dispatch.clear()


# ---------------------------------------------------------------------------
# (a) the whole forward
# ---------------------------------------------------------------------------
def test_full_forward_agrees_with_the_reference(built):
    cfg, w, model = built
    ids = _ids((2, 40))
    got = model(paddle.to_tensor(ids)).numpy()
    ref = _ref_logits(cfg, w, ids)
    assert np.abs(ref).max() > 3.0          # logits of a few units
    assert np.abs(got - ref).max() < TOL


def test_a_left_padded_batch_forward_is_each_prompt_alone(tiny):
    cfg, w, model = tiny
    ids = _ids((1, 12), 4)
    padded = np.concatenate([np.zeros((1, 5), 'int32'), ids], axis=1)
    keep = np.concatenate([np.zeros((1, 5)), np.ones((1, 12))], axis=1)
    off = paddle.to_tensor(np.array([-5], 'int32'))
    got = model(paddle.to_tensor(padded), attention_mask=keep,
                position_offset=off).numpy()[0, 5:]
    assert np.abs(got - _ref_logits(cfg, w, ids)[0]).max() < TOL


@pytest.mark.parametrize('chunks', [(7,) + (1,) * 13, (5, 6, 9), (1, 19),
                                    (3, 3, 3, 3, 8)])
def test_a_cache_carried_from_call_to_call_is_the_whole_sequence(built,
                                                                 chunks):
    """Calls of one token write the ring and then attend over it; a
    longer call attends over the ring as it found it beside its own
    tokens: 20 tokens through a window of 4, in pieces."""
    cfg, w, model = built
    ids = _ids((2, 20), 9)
    ref = _ref_logits(cfg, w, ids)
    cache, at = model.init_cache(2, 64), 0
    for n in chunks:
        lg, cache = model(paddle.to_tensor(ids[:, at:at + n]), cache=cache,
                          use_cache=True, position_offset=at, cache_offset=at)
        assert np.abs(lg.numpy() - ref[:, at:at + n]).max() < TOL, (at, n)
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
    cfg = _cfg('tiny', n_routed_experts=16, first_expert=0)
    shapes = {k[3:]: v for k, v in R.param_shapes(cfg).items()
              if k.startswith('l1.') and ('expert' in k or 'router' in k)}
    lp = C.make_weights(shapes, seed, 'float32', std=0.3)
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


def _route_bias_in_weight(scores, bias, k, route_norm, route_scale, eps):
    w, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * route_scale


def _bias_in_weight(model, mp):
    mp.setattr(afmoe, 'route', _route_bias_in_weight)


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


def _bf16_operands(model, mp):
    """What a single bf16 pass makes of the float32 activations: every
    norm's output, the operand of every projection, rounded."""
    def rounded(norm):
        real = norm.forward
        norm.forward = lambda x: real(x).astype('bfloat16').astype('float32')
    for layer in model.model.layers:
        rounded(layer.input_layernorm)
        rounded(layer.post_attention_layernorm)
    rounded(model.model.norm)


DEPARTURES = [None, _no_sink, _no_value_scale, _all_dims_rotate, _one_theta,
              _window_one_longer, _bias_in_weight,
              _normalised_over_the_held_picks_only, _no_expert_bias,
              _another_share, _bf16_operands]


@pytest.mark.parametrize(
    'departure', DEPARTURES,
    ids=lambda d: 'sound' if d is None else d.__name__.strip('_'))
def test_each_departure_fails_the_tolerance_the_sound_model_passes(
        departure, monkeypatch, fresh_dispatch):
    cfg = _cfg('tiny')
    w = _weights(cfg, seed=11)
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
# (e) prefill by bucket, then decode: the hand-off of a ring
# ---------------------------------------------------------------------------
LENGTHS = (1, 2, WINDOW - 1, WINDOW, WINDOW + 1, BUCKET - 1, BUCKET)


def _engine(model, **extra):
    kw = dict(num_slots=2, max_length=64, decode_block=BLOCK,
              buckets=[BUCKET, 32], eos_token_id=-1)
    kw.update(extra)
    return InferenceEngine(model, **kw)


@pytest.mark.parametrize('n_prompt', LENGTHS)
def test_prefill_program_then_decode_logits_at_every_position(built,
                                                              n_prompt):
    """The engine's own prefill program on a prompt right-padded to its
    bucket, the last prompt token forwarded again at its slot (the same
    values into the same ring row), then one token at a time for three
    blocks and more than 3 x window tokens — the ring wraps three times:
    the LOGITS at every position against the reference's full forward."""
    cfg, w, model = built
    eng = _engine(model)
    assert eng.pool.stands_at_one_position
    fwd = cached_forward(model, *functional_state(model))
    n_new = 3 * WINDOW + 2
    ids = _ids((1, n_prompt + n_new), 3 + n_prompt)
    ref = _ref_logits(cfg, w, ids)
    padded = np.zeros((1, BUCKET), 'int32')
    padded[:, :n_prompt] = ids[:, :n_prompt]
    cache = eng._state_prefill_fn(eng._params, eng._frozen, eng._buffers,
                                  jnp.asarray(padded), jnp.int32(n_prompt))
    k_slot = jnp.arange(64)
    worst = 0.0
    for t in range(n_prompt - 1, n_prompt + n_new):
        pos = jnp.full((1,), t, jnp.int32)
        mask = (k_slot[None, :] <= pos[:, None])[:, None, None, :]
        lg, cache = fwd(jnp.asarray(ids[:, t:t + 1]), cache, pos, pos, mask)
        worst = max(worst, np.abs(np.asarray(lg)[0, 0] - ref[0, t]).max())
    assert worst < TOL


def test_the_reforward_of_the_last_prompt_token_rewrites_its_row_alike(tiny):
    """The prefill seats the ring as of token `s - 2`; the decode
    block's re-forward of token `s - 1` writes row `(s - 1) mod window`.
    A prefill that had written that token too would have put the SAME
    values there (to the rounding of a forward of one token against a
    forward of sixteen: observed 2.3e-6 on values of 3): harmless,
    unlike a state's second fold."""
    _, _, model = tiny
    eng = _engine(model)
    state = (eng._params, eng._frozen, eng._buffers)
    ids = jnp.asarray(_ids((1, BUCKET), 2))
    upto = {n: eng._state_prefill_fn(*state, ids, jnp.int32(n))
            for n in (9, 10)}
    fwd = cached_forward(model, *functional_state(model))
    pos = jnp.full((1,), 8, jnp.int32)
    mask = (jnp.arange(64)[None, :] <= pos[:, None])[:, None, None, :]
    for n in (9, 10):       # the ring with and without token 8 in it
        _, after = fwd(ids[:, 8:9], upto[n], pos, pos, mask)
        for i in eng.pool.ring_layers:
            for got, want in zip(after[i], upto[10][i]):
                assert np.abs(np.asarray(got - want)).max() < 1e-5
    ring = eng.pool.ring_layers[0]
    assert np.abs(np.asarray(upto[9][ring][0]
                             - upto[10][ring][0])).max() > 0.1


def _served_gap(cfg, w, prompt, toks):
    """How far a served token's reference logit lies below the
    reference's best at its position: the benchmark's comparison."""
    lg = _ref_logits(cfg, w, prompt + toks[:-1])[0, len(prompt) - 1:]
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, 128, n).tolist() for n in lengths]


def _through_the_router(model, prompts, n_new, num_slots=2):
    router = Router(ReplicaSet(
        model, 1, num_slots=num_slots, max_length=64, decode_block=BLOCK,
        buckets=[BUCKET, 32], eos_token_id=-1))
    hs = [router.submit(p, SamplingParams(max_new_tokens=n_new,
                                          eos_token_id=-1))
          for p in prompts]
    router.run()
    assert all(h.error is None and len(h.tokens) == n_new for h in hs)
    return [list(h.tokens) for h in hs], router.replicas[0].engine


def test_through_router_and_engine_prompts_shorter_than_their_bucket(built):
    cfg, w, model = built
    prompts = _prompts(LENGTHS)
    toks, eng = _through_the_router(model, prompts, 3 * WINDOW + 2)
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL, len(prompt)
    assert eng._counts['prefills'] == len(LENGTHS)
    assert eng._counts['chunked_prefills'] == 0


def _ring_at_the_buckets_end(mp):
    """The prefill that does not know the prompt's length: the padding
    is written into the ring, over rows the window still needs."""
    mp.setattr(generation, 'folded_tokens', lambda s: s)


def _ring_never_written_by_prefill(mp):
    mp.setattr(generation, 'folded_tokens', lambda s: 0 if s > 1 else 1)


@pytest.mark.parametrize('fault,slots', [(_ring_at_the_buckets_end, 3),
                                         (_ring_never_written_by_prefill, 4)],
                         ids=lambda f: getattr(f, '__name__', '').strip('_'))
def test_a_faulty_hand_off_fails_the_tolerance(tiny, fault, slots,
                                               monkeypatch):
    """(A slot count of its own: the program store keys a program by the
    engine's geometry, not by what a test patched, and must trace the
    faulty prefill anew.)"""
    cfg, w, model = tiny
    fault(monkeypatch)
    prompts = _prompts(LENGTHS)
    toks, _ = _through_the_router(model, prompts, 3 * WINDOW + 2, slots)
    gaps = [_served_gap(cfg, w, p, t) for p, t in zip(prompts, toks)]
    assert max(gaps) > 50 * TOL, gaps


# ---------------------------------------------------------------------------
# (f) continuous batching: more requests than slots, slots reseated
# ---------------------------------------------------------------------------
def test_more_requests_than_slots_every_one_against_the_reference(built):
    cfg, w, model = built
    lengths = (5, 19, 1, 11, 16, 2, 27)
    prompts = _prompts(lengths, seed=2)
    eng = _engine(model)
    hs = [eng.submit(p, SamplingParams(max_new_tokens=6 + 3 * i,
                                       eos_token_id=-1))
          for i, p in enumerate(prompts)]
    eng.run()
    for h, prompt in zip(hs, prompts):
        assert h.error is None
        assert _served_gap(cfg, w, prompt, list(h.tokens)) < TOL
    # two slots, seven requests: each slot was seated over a used ring
    assert eng._counts['prefills'] == 7 and eng.pool.num_slots == 2


def test_a_reseated_slot_holds_the_new_requests_ring_whole(tiny):
    """After a long request the slot's rings are its garbage; the next
    prefill seats every row of them (zeros where the short prompt has
    not reached), and the short request then served from that slot is
    the one served from a fresh engine."""
    _, _, model = tiny
    long_one, short_one = _prompts((27, 3), seed=5)
    eng = _engine(model, num_slots=1)
    a = eng.submit(long_one, SamplingParams(max_new_tokens=20,
                                            eos_token_id=-1))
    eng.run()
    assert all(np.abs(np.asarray(eng.pool.rows[i][0])).min() > 0
               for i in eng.pool.ring_layers)
    b = eng.submit(short_one, SamplingParams(max_new_tokens=10,
                                             eos_token_id=-1))
    eng.run()
    fresh = _engine(model, num_slots=1)
    c = fresh.submit(short_one, SamplingParams(max_new_tokens=10,
                                               eos_token_id=-1))
    fresh.run()
    assert a.error is None and list(b.tokens) == list(c.tokens)
    for i in eng.pool.ring_layers:
        for got, want in zip(eng.pool.rows[i], fresh.pool.rows[i]):
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# ---------------------------------------------------------------------------
# (g) both decode programs
# ---------------------------------------------------------------------------
def test_both_decode_programs_agree_with_the_reference(built):
    """max_length 64: rounds attend over 32 rows of the full layers
    while every active position allows it, then over 64; a ring is read
    whole by both. One request stays inside the half program, one
    crosses over, one starts past it."""
    cfg, w, model = built
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model, num_slots=1)
    for n_prompt, n_new in ((3, 12), (20, 24), (30, 12)):
        prompt = _prompts((n_prompt,), seed=n_prompt)[0]
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert _served_gap(cfg, w, prompt, list(h.tokens)) < TOL
    rounds = [e['attrs'] for e in log.events()
              if e['name'] == 'serving.decode_round']
    assert {a['rows'] for a in rounds} == {32, 64}
    n_ring, n_full = len(eng.pool.ring_layers), \
        len(eng.pool.row_spec) - len(eng.pool.ring_layers)
    for a in rounds:        # one slot: a ring is WINDOW rows whichever
        assert a['read_rows'] == n_full * a['rows'] + n_ring * WINDOW
    assert eng._trace_counts['decode_step'] == 1
    assert eng._trace_counts['decode_step_half'] == 1


def test_decode_through_the_kernel_agrees_with_the_reference(
        built, kv_interpreted):
    """The decode block through `kv_decode_attention`, interpreted (K
    wider than V, 2 slots x 64 rows in tiles of 16), one request at a
    time so a round's `read_rows` is exact: on a full layer WITHOUT a
    sink the decoding slot's length rounded up to the tile and one tile
    of the slot that is not decoding; a full layer with a sink
    (`tiny_window_first`) and every ring keep XLA and are read whole."""
    cfg, w, model = built
    sinks = cfg['add_full_attention_sink_bias']
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model)
    full = [i for i in range(len(eng.pool.row_spec))
            if i not in eng.pool.ring_layers]
    want = [0 if sinks or i in eng.pool.ring_layers else 16
            for i in range(len(eng.pool.row_spec))]
    assert eng._bounded_tiles(64).tolist() == want
    assert eng._bounded_tiles(32).tolist() == want
    for n_prompt, n_new in ((3, 14), (21, 34)):
        prompt = _prompts((n_prompt,), seed=n_prompt)[0]
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert _served_gap(cfg, w, prompt, list(h.tokens)) < TOL
    assert len(kv_interpreted) == (0 if sinks else 2 * len(full))
    rounds = [e['attrs'] for e in log.events()
              if e['name'] == 'serving.decode_round']
    assert {a['rows'] for a in rounds} == {32, 64}
    rings = 2 * len(eng.pool.ring_layers) * WINDOW
    for a in rounds:
        assert a['active'] == 1
        if sinks:
            assert a['read_rows'] == 2 * len(full) * a['rows'] + rings
            continue
        length = (a['needed_rows'] - a['needed_rows_window']) // len(full)
        assert a['read_rows'] \
            == len(full) * (-(-length // 16) * 16 + 16) + rings
        assert a['needed_rows'] <= a['read_rows']


def test_attended_rows_leaves_a_ring_whole(tiny):
    """The half program's mask has 32 columns: a full layer's leaves are
    sliced to it, a ring's 4 rows are not its business."""
    _, _, model = tiny
    cache = model.init_cache(1, 64)
    sliced = []
    real = generation.attended_rows

    def spy(k, v, mask):
        sliced.append((k.shape[1], mask.shape[-1]))
        return real(k, v, mask)
    import unittest.mock as mock
    with mock.patch.object(mimo_v2, '_attended_rows', spy):
        pos = jnp.zeros((1,), jnp.int32)
        mask = (jnp.arange(32)[None, :] <= pos[:, None])[:, None, None, :]
        model(paddle.to_tensor(_ids((1, 1))), cache=cache, use_cache=True,
              position_offset=pos, cache_offset=pos, attention_mask=mask)
    assert sliced == [(64, 32), (64, 32)]       # the two FULL layers only


# ---------------------------------------------------------------------------
# (h) what cannot share or rewind a ring is refused, with its reason
# ---------------------------------------------------------------------------
def _llama():
    paddle.seed(3)
    return LlamaForCausalLM(LlamaConfig.tiny()).eval()


@pytest.mark.parametrize('extra,names', [
    (dict(prefix_cache=True), 'prefix_cache.*END of its donor'),
    (dict(prefix_cache=0.5), 'prefix_cache'),
    (dict(prefill_chunk_tokens=16), 'prefill_chunk_tokens.*chunk'),
    (dict(kv_page_size=8), 'kv_page_size.*one page geometry'),
    (dict(kv_pages=40), 'kv_pages'),
    (dict(kv_quant='int8'), 'kv_quant.*int8'),
    (dict(draft_model='llama'), 'draft_model.*moved back'),
], ids=['prefix_cache', 'prefix_cache_fraction', 'chunked_prefill', 'paged',
        'kv_pages', 'int8_kv', 'speculative'])
def test_engine_modes_that_cannot_hold_a_ring_are_refused(tiny, extra,
                                                          names):
    _, _, model = tiny
    if extra.get('draft_model') == 'llama':
        extra = dict(draft_model=_llama())
    with pytest.raises(ValueError, match='MiMoV2ForCausalLM keeps a ring '
                                         'of a window.*' + names):
        _engine(model, **extra)


def test_a_draft_model_with_a_ring_is_refused_too(tiny):
    _, _, model = tiny
    with pytest.raises(ValueError, match='MiMoV2ForCausalLM keeps a ring.*'
                                         'draft_model'):
        InferenceEngine(_llama(), num_slots=2, max_length=64,
                        draft_model=model)


def test_a_window_as_long_as_the_slot_is_no_ring():
    """`init_cache` gives a window layer min(window, max_length) rows: at
    max_length 4 nothing is shorter than the slot, nothing is a ring,
    and the plain prefill serves it."""
    paddle.seed(1)
    model = MiMoV2ForCausalLM(MiMoV2Config.tiny()).eval()
    eng = InferenceEngine(model, num_slots=1, max_length=WINDOW,
                          decode_block=1, buckets=[2])
    assert eng.pool.ring_layers == () and not eng.pool.stands_at_one_position
    assert generation.ring_layers(model.init_cache(1, 64), 64) == (1, 2)


def test_generate_gives_the_references_greedy_tokens(built):
    cfg, w, model = built
    ids = _ids((2, 9), 8)
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=14,
                            eos_token_id=-1)
    for row, got in zip(ids, out.numpy()):
        assert _served_gap(cfg, w, row.tolist(), got.tolist()) < TOL
    # all-ones mask: nothing is padded, nothing refused
    same, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=14,
                             eos_token_id=-1,
                             attention_mask=np.ones((2, 9), 'int32'))
    assert (same.numpy() == out.numpy()).all()


def test_generate_refuses_padded_prompts_and_speculation(tiny):
    _, _, model = tiny
    ids = _ids((2, 9), 8)
    keep = np.ones((2, 9), 'int32')
    keep[1, :4] = 0
    with pytest.raises(ValueError, match='no padded prompts.*ring'):
        model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                       attention_mask=keep)
    with pytest.raises(NotImplementedError, match='moved back'):
        model.speculative_generate(_llama(), paddle.to_tensor(ids[:1]))


# ---------------------------------------------------------------------------
# (i) the other families compile to the programs they had
# ---------------------------------------------------------------------------
_FAMILIES = {'gpt': (GPTForCausalLM, GPTConfig),
             'llama': (LlamaForCausalLM, LlamaConfig),
             'afmoe': (AfmoeForCausalLM, AfmoeConfig),
             'lfm2': (Lfm2MoeForCausalLM, Lfm2MoeConfig)}

# sha256 (first 16 hex digits) of the StableHLO text of each program of
# a tiny engine (2 slots x 64, block 4, bucket 16): the prefills' taken
# on the PARENT of PR 32 (commit 25ee4df) by the very code of
# `_program_texts` below; the decode programs' re-taken AT PR 36, which
# made the slot state one buffer that they unpack (they are the
# engine's own functions on `_decode_args()`, the pins of
# `tests/test_pool_layout.py`); jax 0.9.0, which the repository is
# written for (the verify skill)
_PARENT_PROGRAMS = {
    ('afmoe', 'decode'): '81008fe4d4edb6d9',
    ('afmoe', 'decode_half'): '8e312056c151a0a2',
    ('afmoe', 'prefill'): '6782a117cd64283e',
    ('gpt', 'decode'): '5e706a44cb430fe1',
    ('gpt', 'decode_half'): '4a4e6ee67293bb7c',
    ('gpt', 'prefill'): '365eec42133d1ab2',
    ('lfm2', 'decode'): '611c2975c6cfa539',
    ('lfm2', 'decode_half'): '6df5d3a5564cc3bd',
    ('lfm2', 'prefill'): '1a02dff7d8263eae',
    ('llama', 'decode'): '0b25e1d31f4c9b75',
    ('llama', 'decode_half'): '8a7f5153ef78c81d',
    ('llama', 'prefill'): '8b4c79aa8dc443ef',
}


def _program_texts(eng):
    state = (eng._params, eng._frozen, eng._buffers)
    dec = eng._decode_args()
    ids = jnp.zeros((1, 16), jnp.int32)
    pre = (ids, jnp.int32(5)) if eng.pool.state_layers else (ids,)
    prefill = eng._state_prefill_fn if eng.pool.state_layers \
        else eng._prefill_fn
    return {
        'decode': jax.jit(eng._decode_block_fn).lower(*dec),
        'decode_half': jax.jit(eng._decode_block_half_fn).lower(*dec),
        'prefill': jax.jit(prefill).lower(*state, *pre)}


@pytest.mark.parametrize('family', sorted(_FAMILIES))
def test_the_other_families_programs_are_the_parents(family):
    cls, conf = _FAMILIES[family]
    paddle.seed(0)
    eng = InferenceEngine(cls(conf.tiny()).eval(), num_slots=2,
                          max_length=64, decode_block=4, buckets=[16])
    assert eng.pool.ring_layers == ()
    for name, lowered in _program_texts(eng).items():
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
        assert digest == _PARENT_PROGRAMS[family, name], (family, name)


# ---------------------------------------------------------------------------
# (j) what a decode round's span carries
# ---------------------------------------------------------------------------
def _rounds(log):
    return [e['attrs'] for e in log.events()
            if e['name'] == 'serving.decode_round']


def test_decode_round_carries_the_ring_and_the_share(tiny):
    cfg, _, model = tiny
    log = obs.get_event_log()
    log.clear()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_moe_picks_held_total')
    _, eng = _through_the_router(model, _prompts((5, 19, 11)), 14)
    rounds = _rounds(log)
    assert rounds
    for a in rounds:
        # two full layers at the round's rows, two rings of 4, two slots
        assert a['read_rows'] == 2 * (2 * a['rows'] + 2 * WINDOW)
        # a ring entry needs at most the window of a slot
        assert 0 < a['needed_rows_window'] <= a['active'] * 2 * WINDOW
        assert a['needed_rows_window'] < a['needed_rows'] \
            <= 2 * a['real_rows'] + 4 * a['active'] + a['needed_rows_window']
        assert a['expert_layer_substeps'] == BLOCK * 3
        assert a['experts'] == 4            # the experts HELD a layer
        assert a['experts_touched'] <= BLOCK * 3 * 4
        # 2 picks a token on each of 3 expert layers in each sub-step
        assert a['picks'] == a['active'] * 2 * 3 * BLOCK
        assert 0 <= a['picks_held'] <= a['picks']
    held, made = (sum(a[k] for a in rounds) for k in ('picks_held', 'picks'))
    assert 0.05 < held / made < 0.6         # 4 of 16, give or take the bias
    assert reg.value('paddle_serving_moe_picks_held_total') - before == held
    late = [a for a in rounds if a['real_rows'] >= a['active'] * 2 * WINDOW]
    assert late and all(a['needed_rows_window'] == a['active'] * 2 * WINDOW
                        for a in late)


def test_a_model_that_holds_every_expert_carries_no_picks(built):
    cfg, _, model = built
    log = obs.get_event_log()
    log.clear()
    _through_the_router(model, _prompts((5,)), 6)
    share = cfg['n_routed_experts'] < cfg['expert_share']['routed']
    for a in _rounds(log):
        assert ('picks' in a) == ('picks_held' in a) == share
        assert 'needed_rows_window' in a


def test_a_model_with_one_geometry_carries_what_it_carried():
    log = obs.get_event_log()
    log.clear()
    eng = InferenceEngine(_llama(), num_slots=2, max_length=64,
                          decode_block=BLOCK, buckets=[BUCKET])
    eng.submit([5, 6, 7], SamplingParams(max_new_tokens=6, eos_token_id=-1))
    eng.run()
    a = _rounds(log)[-1]
    assert not {'needed_rows_window', 'picks', 'picks_held'} & set(a)
    assert a['read_rows'] == 2 * a['rows'] * len(eng.pool.row_spec)
    stats = eng.pool.stats()
    assert stats['ring_layers'] == 0 and len(stats['entry_bytes']) == 1


def test_pool_books_bytes_by_entry_geometry(tiny):
    _, _, model = tiny
    pool = _engine(model).pool
    assert pool.ring_layers == (1, 2) and pool.state_layers == ()
    full = 2 * 64 * 1 * (12 + 8) * 4        # slots x rows x heads x (K + V)
    ring = 2 * WINDOW * 2 * (12 + 8) * 4
    assert pool.stats()['entry_bytes'] == {'64x1x(12+8)': 2 * full,
                                           '4x2x(12+8)': 2 * ring}
    assert pool.pool_bytes == 2 * full + 2 * ring
    assert pool.rows[1][0].shape == (2, WINDOW, 2, 12)
    assert pool.rows[1][1].shape == (2, WINDOW, 2, 8)
    assert pool.rows[0][0].shape == (2, 64, 1, 12)


def test_the_pool_of_the_timed_size():
    """32 slots x 4096 at the published widths: 128 rows x 8 heads on a
    window layer, 4096 x 4 on a full one, K 192 and V 128 wide."""
    conf = MiMoV2Config(num_hidden_layers=7, vocab_size=64,
                        hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 0],
                        moe_layer_freq=[0, 1, 1, 1, 1, 1, 1])
    with paddle.LazyGuard():
        model = MiMoV2ForCausalLM(conf)
    cache = jax.eval_shape(lambda: model.init_cache(32, 4096, 'float32'))
    assert [(k.shape, v.shape) for k, v in cache[:2]] == [
        ((32, 4096, 4, 192), (32, 4096, 4, 128)),
        ((32, 128, 8, 192), (32, 128, 8, 128))]
    assert sum(leaf.size * 4 for entry in cache for leaf in entry) \
        == 1_551_892_480
    assert generation.ring_layers(cache, 4096) == (1, 2, 3, 4, 5)


def test_scopes_are_on_the_decode_and_prefill_programs(tiny):
    _, _, model = tiny
    _through_the_router(model, _prompts((5,)), 6)
    table = programs.scope_table()
    for prog, more in (('serving.decode_block', {'lm_head', 'sample'}),
                       (f'serving.prefill_{BUCKET}', set())):
        found = {s for op, *_ in table[prog].values()
                 for s in programs.scope_path(op)}
        assert {'attention', 'kv_write', 'mlp', 'moe/router', 'moe/experts',
                'norm'} | more <= found
        assert 'moe/shared' not in found
        # the ring's write is under `kv_write`: its `p mod rows`
        assert any('kv_write' in programs.scope_path(op)
                   and op.endswith('/rem')
                   for op, *_ in table[prog].values())


def test_config_presets_and_refusals():
    conf = MiMoV2Config()       # the defaults are the published file's
    assert conf.layer_pattern == 'FWWWWF' + 'WWWWWF' * 7
    assert conf.moe_pattern == 'D' + 'E' * 47
    assert conf.rotary_dim == 64 and conf.num_routed_experts == 256
    assert conf.route_scale == 1.0 and conf.num_shared_experts == 0
    assert MiMoV2Config.tiny().layer_pattern == 'FWWF'
    assert MiMoV2Config.tiny_window_first().layer_pattern == 'WFW'
    assert 'FWWF' in programs.describe_statics(MiMoV2Config.tiny())
    for bad, what in ((dict(scoring_func='softmax'), 'scoring_func'),
                      (dict(n_group=2), 'n_group'),
                      (dict(n_shared_experts=1), 'n_shared_experts'),
                      (dict(tie_word_embeddings=True), 'untied head'),
                      (dict(hybrid_layer_pattern=[0]), 'hybrid_layer_pattern'),
                      (dict(partial_rotary_factor=0.3), 'rotate-half')):
        with pytest.raises(ValueError, match=what):
            MiMoV2Config.tiny(**bad)
    from paddle_tpu.nlp import transformers
    assert transformers.MiMoV2ForCausalLM is MiMoV2ForCausalLM
