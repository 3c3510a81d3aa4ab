"""`nlp/deepseek_v3.py` against its plain float32 reference
(`benchmarks/reference/deepseek_v3.py`, latent attention WRITTEN OUT: K
and V by head, no cache, no absorbed product) at the tiny presets, with
seeded weights whose selection bias is large enough to change picks and
whose latent norm's weight is not ones (the benchmark's are 0.02 and
ones: there a dropped norm weight would not show, here it does).

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (the absorbed products `q_nope W_UK^T . c` against
`q_nope . (c W_UK)`; a cache of rows against a full-sequence forward;
sorted blocks of the experts against every expert for every token).
Observed at most 1.5e-5 on logits as large as 9; every departure from
the published mathematics below moves a logit by more than 0.03, and
operands rounded to bfloat16 — what one bf16 pass of the MXU would make
of the float32 activations — by 0.1 and more. 2e-4 lies between with
room on both sides."""
import hashlib
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import _dispatch
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import functional_call, functional_state
from paddle_tpu.nlp import afmoe, deepseek_v3, generation
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.nlp.deepseek_v3 import (DeepseekV3Config,
                                        DeepseekV3ForCausalLM)
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp.mimo_v2 import MiMoV2Config, MiMoV2ForCausalLM
from paddle_tpu.serving import (InferenceEngine, ReplicaSet, Router,
                                SamplingParams)
from paddle_tpu.serving.kv_pool import wants_own_layout

from benchmarks.models import adapter, fill
from benchmarks.reference import common as C
from benchmarks.reference import deepseek_v3 as R

from test_own_tokens_attention import _shapes

TOL = 2e-4
AD = adapter('DeepseekV3ForCausalLM')
PRESETS = ('tiny', 'tiny_wide_v')
BUCKET, BLOCK, MAX_LEN = 16, 4, 64


def _cfg(preset, **over):
    conf = getattr(DeepseekV3Config, preset)(**over)
    cfg = {k: getattr(conf, k, None) for k in AD._KEYS}
    cfg.update(moe_layer_freq=1, scoring_func='sigmoid',
               topk_method='noaux_tc', n_group=1, topk_group=1,
               attention_bias=False, tie_word_embeddings=False)
    return cfg


def _weights(cfg, seed=7):
    # std 0.3: logits of a few units, so a departure is not lost in
    # them; a selection bias of 0.3 beside sigmoid scores changes picks;
    # the latent norm's weight 1 + what the generator drew (0.4-1.6)
    w = C.make_weights(R.param_shapes(cfg), seed, 'float32', std=0.3)
    noise = C.make_weights({k: (v.shape, 'normal') for k, v in w.items()
                            if k.endswith('.kv_norm')}, seed + 1,
                           'float32', std=0.3)
    return {k: v + noise[k] if k in noise else v for k, v in w.items()}


def _model(cfg, w):
    return fill(AD.build(cfg), w, AD.name_map(cfg)).eval()


REF_LEN = 64
_REF = {}


def _ref_logits(cfg, w, ids, ref_len=REF_LEN):
    """The reference's logits, one compile a set of weights: every row
    goes through alone, right-padded to `ref_len` (the reference is
    causal: what follows a position does not reach it)."""
    if id(w) not in _REF:
        _REF[id(w)] = (w, jax.jit(lambda wt, row: R.logits_of(
            cfg, wt, R.hidden_states(cfg, wt, row))))
    fn = _REF[id(w)][1]
    ids = np.atleast_2d(np.asarray(ids, 'int32'))
    padded = np.zeros((ids.shape[0], ref_len), 'int32')
    padded[:, :ids.shape[1]] = ids
    return np.stack([np.asarray(fn(w, jnp.asarray(row[None])))[0]
                     for row in padded])[:, :ids.shape[1]]


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


def _both_paths(model, ids, **kwargs):
    """-> (logits of a plain forward: the path over the call's own
    tokens; logits of the whole sequence in ONE call against rows held:
    a traced slot, so the absorbed path over the cache it has just
    written), one compile for the two."""
    state = functional_state(model)
    cache = model.init_cache(ids.shape[0], ids.shape[1] + 8)

    def both(ids, cache, zero):
        own, _ = functional_call(model, *state, (ids,), dict(kwargs))
        (held, _), _ = functional_call(
            model, *state, (ids,),
            dict(cache=cache, use_cache=True, position_offset=zero,
                 cache_offset=zero))
        return own, held
    own, held = jax.jit(both)(jnp.asarray(ids), cache,
                              jnp.zeros((), jnp.int32))
    return np.asarray(own), np.asarray(held)


# ---------------------------------------------------------------------------
# (a) the whole forward
# ---------------------------------------------------------------------------
def test_full_forward_agrees_with_the_reference_on_both_paths(built):
    cfg, w, model = built
    ids = _ids((2, 24))
    ref = _ref_logits(cfg, w, ids)
    assert np.abs(ref).max() > 3
    own, held = _both_paths(model, ids)
    assert np.abs(own - ref).max() < TOL
    assert np.abs(held - ref).max() < TOL


def test_a_padded_batch_forward_is_each_prompt_alone(tiny):
    """A [B, S] padding mask on the path over a call's own tokens: a
    right-padded row's real positions are the row alone."""
    cfg, w, model = tiny
    ids = _ids((2, 24), 4)
    keep = np.ones((2, 24), 'int32')
    keep[1, 7:] = 0
    got, _ = _both_paths(model, ids, attention_mask=jnp.asarray(keep))
    assert np.abs(got[0] - _ref_logits(cfg, w, ids[0])[0]).max() < TOL
    assert np.abs(got[1, :7] - _ref_logits(cfg, w, ids[1, :7])[0]).max() \
        < TOL


# ---------------------------------------------------------------------------
# (b) attention alone: both paths, the rotary, the scale
# ---------------------------------------------------------------------------
def _attention_alone(cfg, w, model, layer=1, s=24, seed=2):
    """-> (reference, path (i), path (ii)) of one layer's attention on
    the same normed input, every position."""
    a = np.random.RandomState(seed).standard_normal(
        (1, s, cfg['hidden_size'])).astype('float32')
    lp = {k[len(f'l{layer}.'):]: v for k, v in w.items()
          if k.startswith(f'l{layer}.')}
    ref = np.asarray(R.self_attention(C.Ref('f32'), cfg, lp,
                                      jnp.asarray(a[0])))
    attn = model.model.layers[layer].self_attn
    own = attn(paddle.to_tensor(a)).numpy()[0]
    cache = tuple(map(paddle.to_tensor, model.init_cache(1, s + 8)[layer]))
    zero = jnp.zeros((), jnp.int32)
    held, _ = attn(paddle.to_tensor(a), cache=cache, position_offset=zero,
                   cache_offset=zero)
    return ref, own, held.numpy()[0]


def test_both_attention_paths_agree_with_the_reference_and_each_other(
        built):
    cfg, w, model = built
    ref, own, held = _attention_alone(cfg, w, model)
    assert np.abs(ref).max() > 1
    assert np.abs(own - ref).max() < TOL
    assert np.abs(held - ref).max() < TOL
    assert np.abs(held - own).max() < TOL


def test_a_whole_prefill_is_told_by_the_literal_slot_and_no_mask():
    lit = deepseek_v3._starts_empty_slot
    assert lit(0, None) and lit(np.int32(0), None)
    assert not lit(jnp.int32(0), None)          # an array may be traced
    assert not lit(0, object()) and not lit(1, None) and not lit(None, None)


@pytest.mark.parametrize('theta', [1e6, 1e4])
def test_interleaved_rotary_against_a_hand_written_pairwise_rotation(theta):
    """Pairs `(x_2i, x_2i+1)` rotated by `p * theta^(-2i/d)`, written
    out; the program's result holds the same numbers with the even
    members first. A product of two rotated vectors is the pairwise
    one's."""
    d, s = 8, 11
    rs = np.random.RandomState(0)
    x, y = rs.standard_normal((2, 1, s, 3, d)).astype('float32')
    pos = np.arange(5, 5 + s)

    def pairwise(v):
        out = np.empty_like(v)
        for i in range(d // 2):
            ang = pos * theta ** (-2.0 * i / d)
            c, sn = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
            out[..., 2 * i] = v[..., 2 * i] * c - v[..., 2 * i + 1] * sn
            out[..., 2 * i + 1] = v[..., 2 * i + 1] * c + v[..., 2 * i] * sn
        return out
    rot = lambda v: np.asarray(deepseek_v3._rotary(
        jnp.asarray(v), jnp.asarray(pos, jnp.int32), theta, True))
    want = pairwise(x)
    assert np.abs(rot(x) - np.concatenate(
        [want[..., 0::2], want[..., 1::2]], -1)).max() < 1e-5
    assert np.abs((rot(x) * rot(y)).sum(-1)
                  - (want * pairwise(y)).sum(-1)).max() < 1e-5
    # stored as halves, nothing is permuted
    plain = np.asarray(deepseek_v3._rotary(
        jnp.asarray(x), jnp.asarray(pos, jnp.int32), theta, False))
    assert np.abs(plain - rot(np.stack(
        [x[..., :d // 2], x[..., d // 2:]], -1).reshape(x.shape))).max() \
        < 1e-5


# ---------------------------------------------------------------------------
# (c) the router, and every departure against the tolerance
# ---------------------------------------------------------------------------
def test_route_bias_selects_scores_weigh_and_the_scale_multiplies():
    scores = jnp.asarray([[0.9, 0.5, 0.4, 0.1]], jnp.float32)
    bias = jnp.asarray([-1.0, 0.0, 0.0, 0.45], jnp.float32)
    sel, wt = afmoe.route(scores, bias, 2, True, 2.448, 1e-20)
    assert sorted(np.asarray(sel)[0].tolist()) == [1, 3]     # by s + b
    got = dict(zip(np.asarray(sel)[0].tolist(), np.asarray(wt)[0].tolist()))
    assert got[1] == pytest.approx(2.448 * 0.5 / 0.6, rel=1e-6)   # by s
    assert got[3] == pytest.approx(2.448 * 0.1 / 0.6, rel=1e-6)


def _no_latent_norm(model, mp):
    for layer in model.model.layers:
        layer.self_attn.kv_a_layernorm.forward = lambda x: x


def _scaled_by_the_absorbed_width(model, mp):
    real = deepseek_v3._latent_attention
    cfg = model.config
    wrong = 1.0 / math.sqrt(cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    mp.setattr(deepseek_v3, '_latent_attention',
               lambda *a: real(*a[:-1], wrong))


def _rotary_half_of_the_score_dropped(model, mp):
    real = deepseek_v3._latent_attention
    mp.setattr(deepseek_v3, '_latent_attention',
               lambda qn, qr, *a: real(qn, jnp.zeros_like(qr), *a))


def _rotary_dims_taken_as_halves(model, mp):
    model.config.rope_interleave = False


def _route_bias_in_weight(scores, bias, k, route_norm, route_scale, eps):
    w, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * route_scale


def _bias_in_weight(model, mp):
    mp.setattr(afmoe, 'route', _route_bias_in_weight)


def _weights_not_normalised(model, mp):
    real = afmoe.route
    mp.setattr(afmoe, 'route',
               lambda s, b, k, norm, scale, eps: real(s, b, k, False,
                                                      scale, eps))


def _no_routing_scale(model, mp):
    model.config.route_scale = 1.0


def _shared_mlp_weighted(model, mp):
    scale = model.config.route_scale
    for layer in model.model.layers:
        if layer.moe_enabled:
            real = layer.mlp.shared_experts.forward
            layer.mlp.shared_experts.forward = \
                lambda x, real=real: real(x) * scale


def _no_selection_bias(model, mp):
    for layer in model.model.layers:
        if layer.moe_enabled:
            layer.mlp.expert_bias._data = jnp.zeros(8, jnp.float32)


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


DEPARTURES = [None, _no_latent_norm, _scaled_by_the_absorbed_width,
              _rotary_half_of_the_score_dropped,
              _rotary_dims_taken_as_halves, _bias_in_weight,
              _weights_not_normalised, _no_routing_scale,
              _shared_mlp_weighted, _no_selection_bias, _bf16_operands]


@pytest.mark.parametrize(
    'departure', DEPARTURES,
    ids=lambda d: 'sound' if d is None else d.__name__.strip('_'))
def test_each_departure_fails_the_tolerance_the_sound_model_passes(
        departure, monkeypatch, fresh_dispatch):
    """Both paths, each against the reference: a departure that lives
    in one of them alone (the absorbed path's scale, its rotary half)
    shows there."""
    cfg = _cfg('tiny', num_hidden_layers=2)     # one dense, one expert
    w = _weights(cfg, seed=11)
    ids = _ids((2, 24), 5)
    ref = _ref_logits(cfg, w, ids)
    model = _model(cfg, w)
    if departure is not None:
        departure(model, monkeypatch)
    err = max(np.abs(got - ref).max() for got in _both_paths(model, ids))
    if departure is None:
        assert err < TOL
    else:
        assert err > 50 * TOL, (departure.__name__, err)


# ---------------------------------------------------------------------------
# (d) prefill by bucket, then decode, at every position
# ---------------------------------------------------------------------------
LENGTHS = (1, 2, BUCKET - 1, BUCKET, BUCKET + 11)
N_NEW = 3 * BLOCK + 1


def _engine(model, **extra):
    kw = dict(num_slots=2, max_length=MAX_LEN, decode_block=BLOCK,
              buckets=[BUCKET, 32], eos_token_id=-1)
    kw.update(extra)
    return InferenceEngine(model, **kw)


@pytest.fixture(scope='module')
def programs_of(tiny):
    """The engine's own prefill program and one cached forward of a
    token, compiled once for every length below."""
    _, _, model = tiny
    eng = _engine(model)
    assert not eng.pool.stands_at_one_position
    fwd = cached_forward(model, *functional_state(model))
    return eng, jax.jit(eng._prefill_fn), jax.jit(fwd)


@pytest.mark.parametrize('n_prompt', LENGTHS)
def test_prefill_program_then_decode_logits_at_every_position(
        tiny, programs_of, n_prompt):
    """The engine's own prefill program on a prompt right-padded to its
    bucket (the path over its own tokens; latent rows past the prompt's
    end are garbage a mask hides), the last prompt token forwarded again
    at its slot, then one token at a time over the rows held: the LOGITS
    at every position against the reference's full forward."""
    cfg, w, model = tiny
    eng, prefill, fwd = programs_of
    ids = _ids((1, n_prompt + N_NEW), 3 + n_prompt)
    ref = _ref_logits(cfg, w, ids)
    bucket = eng.pool.bucket_for(n_prompt)
    padded = np.zeros((1, bucket), 'int32')
    padded[:, :n_prompt] = ids[:, :n_prompt]
    cache = prefill(eng._params, eng._frozen, eng._buffers,
                    jnp.asarray(padded))
    assert [tuple(leaf.shape) for leaf in cache[0]] == [
        (1, MAX_LEN, 16), (1, MAX_LEN, 4)]
    k_slot = jnp.arange(MAX_LEN)
    worst = 0.0
    for t in range(n_prompt - 1, n_prompt + N_NEW):
        pos = jnp.full((1,), t, jnp.int32)
        mask = (k_slot[None, :] <= pos[:, None])[:, None, None, :]
        lg, cache = fwd(jnp.asarray(ids[:, t:t + 1]), cache, pos, pos, mask)
        worst = max(worst, np.abs(np.asarray(lg)[0, 0] - ref[0, t]).max())
    assert worst < TOL


def _served_gap(cfg, w, prompt, toks, ref_len=REF_LEN):
    """How far a served token's reference logit lies below the
    reference's best at its position: the benchmark's comparison."""
    lg = _ref_logits(cfg, w, prompt + toks[:-1],
                     ref_len)[0, len(prompt) - 1:]
    return float((lg.max(-1) - lg[np.arange(len(toks)), toks]).max())


def _prompts(lengths, seed=1):
    rs = np.random.RandomState(seed)
    return [rs.randint(3, 128, n).tolist() for n in lengths]


def _through_the_router(model, prompts, n_new, **extra):
    kw = dict(num_slots=2, max_length=MAX_LEN, decode_block=BLOCK,
              buckets=[BUCKET, 32], eos_token_id=-1)
    kw.update(extra)
    router = Router(ReplicaSet(model, 1, **kw))
    hs = [router.submit(p, SamplingParams(max_new_tokens=n_new,
                                          eos_token_id=-1))
          for p in prompts]
    router.run()
    assert all(h.error is None and len(h.tokens) == n_new for h in hs)
    return [list(h.tokens) for h in hs], router.replicas[0].engine


@pytest.fixture(scope='module')
def served(tiny):
    """One run through `Router(ReplicaSet(model, 1))` that several tests
    read: the prompts of (d), its events, its engine."""
    _, _, model = tiny
    log = obs.get_event_log()
    log.clear()
    prompts = _prompts(LENGTHS)
    toks, eng = _through_the_router(model, prompts, N_NEW)
    rounds = [e['attrs'] for e in log.events()
              if e['name'] == 'serving.decode_round']
    return prompts, toks, eng, rounds


def test_through_router_and_engine_every_prompt_length(tiny, served):
    cfg, w, _ = tiny
    prompts, toks, eng, _ = served
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL, len(prompt)
    assert eng._counts['prefills'] == len(LENGTHS)
    assert eng._counts['chunked_prefills'] == 0
    # the plain prefill program: a latent row is hidden by position
    assert not eng.pool.stands_at_one_position


def test_the_other_preset_through_the_router():
    cfg = _cfg('tiny_wide_v')
    w = _weights(cfg)
    prompts = _prompts((3, BUCKET + 5), seed=4)
    log = obs.get_event_log()
    log.clear()
    toks, _ = _through_the_router(_model(cfg, w), prompts, N_NEW)
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL
    # a whole prefill over its own tokens says what its attention
    # computes a layer beside what a causal mask lets through (PR 41):
    # a bucket under one block of queries is scored whole
    prefills = [e['attrs'] for e in log.events()
                if e['name'] == 'serving.prefill']
    assert [(a['attn_pairs_scored'], a['attn_pairs_causal'])
            for a in prefills] == [
        (a['bucket'] ** 2, len(p) * (len(p) + 1) // 2)
        for a, p in zip(prefills, prompts)]


def test_generate_gives_the_references_greedy_tokens(tiny):
    cfg, w, model = tiny
    ids = _ids((2, 9), 8)
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=14,
                            eos_token_id=-1)
    for row, got in zip(ids, out.numpy()):
        assert _served_gap(cfg, w, row.tolist(), got.tolist()) < TOL


def test_generate_with_left_padded_prompts_is_each_prompt_alone(tiny):
    """Unlike a ring or a state, latent rows hide behind a mask of
    positions: the batch path's padded prompts are served."""
    cfg, w, model = tiny
    ids = _ids((2, 9), 9)
    keep = np.ones((2, 9), 'int32')
    keep[1, :4] = 0
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                            eos_token_id=-1, attention_mask=keep)
    assert _served_gap(cfg, w, ids[0].tolist(), out.numpy()[0].tolist()) \
        < TOL
    assert _served_gap(cfg, w, ids[1, 4:].tolist(),
                       out.numpy()[1].tolist()) < TOL


# ---------------------------------------------------------------------------
# (e) continuous batching: more requests than slots, slots reseated
# ---------------------------------------------------------------------------
def test_more_requests_than_slots_every_one_against_the_reference(tiny):
    cfg, w, model = tiny
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
    # two slots, seven requests: each slot was seated over used rows
    assert eng._counts['prefills'] == 7 and eng.pool.num_slots == 2


# ---------------------------------------------------------------------------
# (f) both decode programs
# ---------------------------------------------------------------------------
def test_both_decode_programs_agree_with_the_reference(tiny):
    """max_length 64: rounds attend over 32 latent rows while every
    active position allows it, then over 64. One request stays inside
    the half program, one crosses over, one starts past it."""
    cfg, w, model = tiny
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model)
    for n_prompt, n_new in ((3, 12), (20, 24), (30, 12)):
        prompt = _prompts((n_prompt,), seed=n_prompt)[0]
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert _served_gap(cfg, w, prompt, list(h.tokens)) < TOL
    rounds = [e['attrs'] for e in log.events()
              if e['name'] == 'serving.decode_round']
    assert {a['rows'] for a in rounds} == {32, 64}
    for a in rounds:        # every layer's rows, latent as K and V
        assert a['read_rows'] == 2 * 3 * a['rows']


def test_attended_rows_slices_both_latent_leaves(tiny):
    _, _, model = tiny
    cache = model.init_cache(1, 64)
    sliced = []
    real = generation.attended_rows

    def spy(c, r, mask):
        out = real(c, r, mask)
        sliced.append(tuple(tuple(leaf.shape) for leaf in out))
        return out
    import unittest.mock as mock
    with mock.patch.object(deepseek_v3, '_attended_rows', spy):
        pos = jnp.zeros((1,), jnp.int32)
        mask = (jnp.arange(32)[None, :] <= pos[:, None])[:, None, None, :]
        model(paddle.to_tensor(_ids((1, 1))), cache=cache, use_cache=True,
              position_offset=pos, cache_offset=pos, attention_mask=mask)
    assert sliced == [((1, 32, 16), (1, 32, 4))] * 3


# ---------------------------------------------------------------------------
# (g) the engine's modes: served against the reference, or refused
# ---------------------------------------------------------------------------
def _llama():
    paddle.seed(3)
    return LlamaForCausalLM(LlamaConfig.tiny()).eval()


def test_prefix_cache_serves_latent_rows(tiny):
    """Latent rows can be shared up to a position: a retained row is
    copied (`copy_slot` maps over any leaf) and the suffix prefilled
    against it — the absorbed path."""
    cfg, w, model = tiny
    shared = _prompts((20,), seed=6)[0]
    prompts = [shared + tail for tail in _prompts((3, 7, 1, 9), seed=7)]
    toks, eng = _through_the_router(model, prompts, 9, prefix_cache=True)
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL
    # a hit's retained row IS the row its suffix is prefilled against
    assert eng.prefix_cache.stats()['hits'] >= 2


def test_chunked_prefill_serves_latent_rows(tiny):
    cfg, w, model = tiny
    prompts = _prompts((27, 5, 30, 17), seed=8)
    toks, eng = _through_the_router(model, prompts, 9,
                                    prefill_chunk_tokens=8)
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL
    assert eng._counts['chunked_prefills'] == 3


def test_speculation_serves_latent_rows(tiny):
    """A verify of k+1 rows is a call against rows held, and a rejected
    draft's latent rows lie above the live position, where the mask
    hides them until they are overwritten."""
    cfg, w, model = tiny
    prompts = _prompts((5, 19, 11), seed=9)
    toks, eng = _through_the_router(model, prompts, 11,
                                    draft_model=_llama(),
                                    num_draft_tokens=3)
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL
    assert eng._counts['spec_rounds'] > 0
    plain, _ = _through_the_router(model, prompts, 11)
    assert toks == plain


@pytest.mark.parametrize('extra,names', [
    (dict(kv_page_size=8), 'kv_page_size / kv_pages.*no head axis'),
    (dict(kv_pages=9), 'kv_page_size / kv_pages'),
    (dict(kv_quant='int8'), 'kv_quant.*no heads'),
])
def test_engine_modes_that_reason_by_head_are_refused(tiny, extra, names):
    _, _, model = tiny
    with pytest.raises(ValueError, match='DeepseekV3ForCausalLM keeps '
                       'latent rows.*' + names):
        _engine(model, **extra)


def test_a_draft_model_with_latent_rows_is_refused_the_paged_pool(tiny):
    _, _, model = tiny
    with pytest.raises(ValueError, match='latent rows'):
        InferenceEngine(_llama(), num_slots=2, max_length=MAX_LEN,
                        draft_model=model, kv_page_size=8)


# ---------------------------------------------------------------------------
# (h) the other families compile to the programs they had
# ---------------------------------------------------------------------------
_FAMILIES = {'gpt': (GPTForCausalLM, GPTConfig),
             'llama': (LlamaForCausalLM, LlamaConfig),
             'afmoe': (AfmoeForCausalLM, AfmoeConfig),
             'lfm2': (Lfm2MoeForCausalLM, Lfm2MoeConfig),
             'mimo_v2': (MiMoV2ForCausalLM, MiMoV2Config)}

# sha256 (first 16 hex digits) of the StableHLO text of each program of
# a tiny engine (2 slots x 64, block 4, bucket 16), taken on the PARENT
# of PR 37 (commit bbd1fb5) by the very code of `_program_texts` below;
# the first twelve are the pins of `tests/test_mimo_v2.py` too; jax
# 0.9.0, which the repository is written for (the verify skill)
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
    ('mimo_v2', 'decode'): '8ea7c6f267237b5a',
    ('mimo_v2', 'decode_half'): 'cf1b7410b8762beb',
    ('mimo_v2', 'prefill'): '83c5267b24fdfe6b',
}


def _program_texts(eng):
    state = (eng._params, eng._frozen, eng._buffers)
    dec = eng._decode_args()
    ids = jnp.zeros((1, 16), jnp.int32)
    one = eng.pool.stands_at_one_position
    pre = (ids, jnp.int32(5)) if one else (ids,)
    prefill = eng._state_prefill_fn if one else eng._prefill_fn
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
    assert eng.pool.latent_layers == ()
    for name, lowered in _program_texts(eng).items():
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
        assert digest == _PARENT_PROGRAMS[family, name], (family, name)


@pytest.mark.parametrize('family', sorted(_FAMILIES))
def test_through_the_kv_kernel_only_the_decode_blocks_are_other_programs(
        family, monkeypatch):
    """`ops.pallas.kv_decode_kernel` lifted off its backend condition
    (PR 40; tiles of 16 rows, the toy length has no whole lanes): the
    families that ask it — float32 queries over K and V by head, in
    their cached branch — get other decode blocks and the same prefill;
    gpt and llama never ask, and every program of theirs is the
    parent's. (Lowered here and not through the program store, whose
    memory the module's `served` engines still need.)"""
    from paddle_tpu.ops import pallas, pallas_kernels
    kv_interpreted = []
    real = pallas.kv_decode_kernel

    def asked(*args, **kw):
        kv_interpreted.append(args)
        return real(*args, interpret=True, **kw)
    monkeypatch.setattr(pallas, 'kv_decode_kernel', asked)
    monkeypatch.setattr(pallas_kernels, '_mla_row_tile',
                        lambda rows: 16 if rows % 16 == 0 else None)
    cls, conf = _FAMILIES[family]
    paddle.seed(0)
    eng = InferenceEngine(cls(conf.tiny()).eval(), num_slots=2,
                          max_length=64, decode_block=4, buckets=[16])
    changed = {name for name, lowered in _program_texts(eng).items()
               if hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
               != _PARENT_PROGRAMS[family, name]}
    assert changed == ({'decode', 'decode_half'}
                       if family in ('afmoe', 'lfm2', 'mimo_v2') else set())
    assert bool(kv_interpreted) == bool(changed)


# ---------------------------------------------------------------------------
# (i) what a decode round's span and the pool's book carry
# ---------------------------------------------------------------------------
def test_decode_round_carries_the_latent_counts(tiny, served):
    _, _, eng, rounds = served
    assert rounds
    for a in rounds:
        # three latent layers, (16 + 4) float32 numbers a row a layer
        assert a['latent_layers'] == 3 and a['latent_row_bytes'] == 240
        # a latent entry is a row entry: two slots, three layers
        assert a['read_rows'] == 2 * 3 * a['rows']
        assert 0 < a['needed_rows'] <= 3 * (a['real_rows'] + 2 * a['active'])
        assert a['expert_layer_substeps'] == BLOCK * 2
        assert a['experts'] == 8
        assert not {'needed_rows_window', 'state_bytes', 'picks'} & set(a)
    stats = eng.pool.stats()
    assert stats['latent_layers'] == 3 and stats['latent_row_bytes'] == 240
    assert stats['state_layers'] == stats['ring_layers'] == 0
    assert stats['entry_bytes'] == {
        f'{MAX_LEN}xlatent(16+4)': 2 * MAX_LEN * 240}
    assert stats['entry_layouts'] == {f'{MAX_LEN}xlatent(16+4)': 'default'}
    assert stats['row_bytes'] == MAX_LEN * 240


def test_the_gauge_reads_the_newest_engines_latent_row_bytes(tiny):
    _, _, model = tiny
    reg = obs.get_registry()
    _engine(model)
    assert reg.value('paddle_serving_pool_latent_row_bytes') == 240
    InferenceEngine(_llama(), num_slots=2, max_length=64)
    assert reg.value('paddle_serving_pool_latent_row_bytes') == 0


def test_a_model_without_a_latent_entry_carries_what_it_carried():
    log = obs.get_event_log()
    log.clear()
    eng = InferenceEngine(_llama(), num_slots=2, max_length=64,
                          decode_block=BLOCK, buckets=[BUCKET])
    eng.submit([5, 6, 7], SamplingParams(max_new_tokens=6, eos_token_id=-1))
    eng.run()
    a = [e['attrs'] for e in log.events()
         if e['name'] == 'serving.decode_round'][-1]
    assert not {'latent_layers', 'latent_row_bytes'} & set(a)
    assert [set(e['attrs']) for e in log.events()
            if e['name'] == 'serving.prefill'] == [
        {'request_id', 'bucket', 'slot', 'prompt_len'}]
    stats = eng.pool.stats()
    assert stats['latent_layers'] == 0 and stats['latent_row_bytes'] == 0


def test_the_pool_of_the_timed_size():
    """16 slots x 16,384 at the published widths, shapes only: 576
    numbers a row a layer and no head axis."""
    conf = DeepseekV3Config(num_hidden_layers=5, vocab_size=64,
                            max_position_embeddings=16384)
    with paddle.LazyGuard():
        model = DeepseekV3ForCausalLM(conf)
    cache = jax.eval_shape(lambda: model.init_cache(16, 16384, 'float32'))
    assert [(c.shape, r.shape) for c, r in cache] == [
        ((16, 16384, 512), (16, 16384, 64))] * 5
    assert sum(leaf.size * 4 for entry in cache for leaf in entry) \
        == 3_019_898_880
    assert generation.latent_layers(cache) == (0, 1, 2, 3, 4)
    assert generation.state_layers(cache) == ()
    assert generation.ring_layers(cache, 16384) == ()


@pytest.mark.parametrize('what, shape, backend, wanted', [
    ('the latent c: whole lanes', (16, 16384, 512), 'tpu', False),
    ('the shared rotary key: 64 of 128 lanes', (16, 16384, 64), 'tpu', True),
    ('the shared rotary key on the CPU', (16, 16384, 64), 'cpu', False),
])
def test_a_latent_leaf_asks_for_a_layout_by_its_width(what, shape, backend,
                                                      wanted):
    assert wants_own_layout(shape, backend) is wanted, what


# ---------------------------------------------------------------------------
# (j) what a prefill may build
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('as_draft', [False, True],
                         ids=['the_model', 'a_latent_draft'])
def test_a_prefill_scores_a_block_of_queries_against_the_keys_up_to_its_end(
        tiny, monkeypatch, as_draft):
    """max_length 256, bucket 48, blocks of 16 queries: the three
    differ (and differ from the hidden size, 64). Block `i` of the
    prefill is scored against the keys up to its own last row, `(i + 1)
    x 16` of them (PR 41), so the largest array with keys in its last
    axis is heads x block x bucket, the last block's; nothing is bucket
    x max_length (the absorbed path over the slab) nor bucket x bucket
    (the own-tokens path unblocked). So for the draft's whole prefill,
    where the draft keeps latent rows: every whole prefill has one body
    (`engine._whole_prefill`)."""
    _, _, model = tiny
    ids = jnp.zeros((1, 48), jnp.int32)
    if as_draft:
        eng = _engine(_llama(), max_length=256, buckets=[48],
                      draft_model=model, num_draft_tokens=2)
        assert eng.draft_pool.latent_layers
        prefill, state = eng._draft_prefill_fn, eng._draft_state
    else:
        eng = _engine(model, max_length=256, buckets=[48])
        prefill = eng._prefill_fn
        state = (eng._params, eng._frozen, eng._buffers)

    def shapes(block):
        monkeypatch.setattr(deepseek_v3, 'PREFILL_QUERY_BLOCK', block)
        # a function of its own each time: jax remembers a trace by the
        # function traced, and the block size is no argument of it
        return _shapes(jax.make_jaxpr(lambda *args: prefill(*args))(
            *state, ids).jaxpr, [])
    blocked = shapes(16)
    scores = [s for s in blocked
              if len(s) == 4 and s[-1] in (16, 32, 48, 256)]
    assert {(1, 4, 16, 16), (1, 4, 16, 32), (1, 4, 16, 48)} <= set(scores)
    assert max(math.prod(s) for s in scores) == 4 * 16 * 48
    assert not [s for s in blocked
                if len(s) >= 4 and s[-2:] in ((48, 256), (48, 48))]
    # unblocked, the same walk does find bucket x bucket
    assert (1, 4, 48, 48) in shapes(48)


def test_blocks_of_queries_give_the_unblocked_result(tiny, monkeypatch,
                                                     fresh_dispatch):
    cfg, w, model = tiny
    ids = _ids((2, 27), 12)             # 27: the last block is short
    ref = _ref_logits(cfg, w, ids)
    monkeypatch.setattr(deepseek_v3, 'PREFILL_QUERY_BLOCK', 8)
    assert np.abs(model(paddle.to_tensor(ids)).numpy() - ref).max() < TOL


# ---------------------------------------------------------------------------
# scopes, presets, refusals
# ---------------------------------------------------------------------------
def test_scopes_are_on_the_decode_and_prefill_programs(served):
    table = programs.scope_table()
    for prog, more in (('serving.decode_block',
                        {'lm_head', 'sample', 'latent_absorb'}),
                       (f'serving.prefill_{BUCKET}', set())):
        paths = [programs.scope_path(op) for op, *_ in table[prog].values()]
        found = {s for p in paths for s in p}
        assert {'attention', 'kv_write', 'mlp', 'moe/router', 'moe/experts',
                'moe/shared', 'norm'} | more <= found
        # nested: the OUTERMOST scope of the absorbed products and of
        # the rows' write stays `attention`
        for inner in ('latent_absorb', 'kv_write'):
            assert all(p[0] == 'attention' for p in paths if inner in p)
    # a whole prefill never takes the absorbed path
    assert 'latent_absorb' not in {
        s for op, *_ in table[f'serving.prefill_{BUCKET}'].values()
        for s in programs.scope_path(op)}


def test_config_presets_and_refusals():
    conf = DeepseekV3Config()       # the defaults are the published file's
    assert (conf.qk_head_dim, conf.kv_lora_rank, conf.v_head_dim) \
        == (192, 512, 128)
    assert conf.num_experts == 128 and conf.num_shared_experts == 2
    assert conf.route_scale == 2.448 and conf.rope_interleave
    tiny, wide = DeepseekV3Config.tiny(), DeepseekV3Config.tiny_wide_v()
    assert (tiny.num_hidden_layers, tiny.first_k_dense_replace) == (3, 1)
    assert tiny.v_head_dim == tiny.qk_nope_head_dim == 8
    assert wide.v_head_dim > wide.qk_nope_head_dim
    assert not wide.rope_interleave and wide.first_k_dense_replace == 2
    # accepted since PR 39: a compressed query and YaRN positions
    # (tests/test_xing4.py holds both to the reference)
    yarn = DeepseekV3Config.tiny_yarn()
    assert yarn.q_lora_rank == 12 and yarn.rope_scaling['factor'] == 8
    assert tiny.q_lora_rank is None and tiny.rope_scaling is None
    assert tiny.softmax_gain == 1.0 \
        and tiny.softmax_scale == 1.0 / math.sqrt(12)
    assert yarn.softmax_scale == pytest.approx(
        (0.1 * 0.5 * math.log(8) + 1) ** 2 / math.sqrt(12))
    for bad, what in ((dict(q_lora_rank=0), 'q_lora_rank'),
                      (dict(rope_scaling={'type': 'linear', 'factor': 2}),
                       "rope_scaling type 'linear'"),
                      (dict(rope_scaling={'rope_type': 'llama3'}),
                       "rope_scaling type 'llama3'"),
                      (dict(scoring_func='softmax'), 'scoring_func'),
                      (dict(topk_method='greedy'), 'topk_method'),
                      (dict(n_group=8), 'n_group'),
                      (dict(moe_layer_freq=2), 'moe_layer_freq'),
                      (dict(tie_word_embeddings=True), 'tie_word'),
                      (dict(num_key_value_heads=1), 'num_key_value_heads'),
                      (dict(qk_rope_head_dim=3), 'even')):
        with pytest.raises(ValueError, match=what):
            DeepseekV3Config.tiny(**bad)


# ---------------------------------------------------------------------------
# (k) decode attention through the kernel (PR 38): which calls take it,
# and the engine with it interpreted
# ---------------------------------------------------------------------------
def _call(queries=1, slots=16, rows=16384, held=16384, width=512,
          mask='bool', rows_dtype='float32', heads=1):
    spec = jax.ShapeDtypeStruct
    return (spec((slots, queries, 32, 128), jnp.float32),
            spec((slots, held, width), rows_dtype),
            spec((slots, heads, queries, rows), mask))


@pytest.mark.parametrize('what,call,interpret,tile', [
    ('a decode sub-step', _call(), True, 512),
    ('... over bf16 rows', _call(rows_dtype='bfloat16'), True, 512),
    ('... of the half-length program', _call(rows=8192), True, 512),
    ('... with one mask for every slot', _call(slots=1), True, 512),
    ('... over 768 rows: the largest tile that divides', _call(
        rows=768, held=768), True, 256),
    ('... half of them', _call(rows=384, held=768), True, 128),
    ('the CPU', _call(), False, None),
    ("speculation's k+1 rows", _call(queries=5), True, None),
    ('a prefill chunk', _call(queries=512), True, None),
    ('an additive mask', _call(mask='float32'), True, None),
    ('a mask by head', _call(heads=32), True, None),
    ('rows of 16 numbers', _call(width=16), True, None),
    ('rows of 576: not whole lanes', _call(width=576), True, None),
    ('rows in float16', _call(rows_dtype='float16'), True, None),
    ('64 rows: no tile of whole lanes', _call(rows=64, held=64), True, None),
    ('192 of 384 rows: no tile', _call(rows=192, held=384), True, None),
], ids=lambda v: v.replace(' ', '_') if isinstance(v, str) else None)
def test_the_kernel_takes_a_call_by_what_the_call_is(what, call, interpret,
                                                     tile):
    """`ops.pallas.latent_decode_kernel`'s conditions one by one: one
    query a slot, a boolean mask shared by the heads, a TPU or
    interpret, float32 or bf16 rows of whole lanes, whole tiles."""
    from paddle_tpu.ops import pallas, pallas_kernels
    kernel = pallas.latent_decode_kernel(*call, interpret=interpret)
    if tile is None:
        assert kernel is None, what
    else:
        assert kernel.func is pallas_kernels.mla_decode_attention
        assert kernel.keywords == dict(tile=tile, interpret=True), what


# sha256 (first 16 hex digits) of the StableHLO text of this family's own
# programs at the tiny presets (2 slots x 64, block 4, bucket 16), taken
# on the PARENT of PR 38 (commit 90423b8) by `_own_program_texts` below
_PARENT_OWN_PROGRAMS = {
    ('tiny', 'decode'): '7f1fc5826be26a90',
    ('tiny', 'decode_half'): 'cf4185e6d5cf00af',
    ('tiny', 'prefill'): 'feba32510e9fd5d1',
    ('tiny', 'chunk'): '338c89c25e5b4dce',
    ('tiny_wide_v', 'decode'): '79b7ba536389d5cb',
    ('tiny_wide_v', 'decode_half'): '8464664a0954bc96',
    ('tiny_wide_v', 'prefill'): '683ac209cf3b7b56',
    ('tiny_wide_v', 'chunk'): '0af2fdcdd9919cab',
}


def _own_program_texts(eng):
    texts = _program_texts(eng)
    row = jax.tree_util.tree_map(lambda v: jnp.zeros(v.shape, v.dtype),
                                 eng.pool.row_spec)
    texts['chunk'] = jax.jit(eng._chunk_prefill_fn).lower(
        eng._params, eng._frozen, eng._buffers, row,
        jnp.zeros((1, 16), jnp.int32), jnp.int32(3))
    return {name: hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
            for name, lowered in texts.items()}


def test_on_the_cpu_this_familys_programs_are_the_parents_too(built):
    """Where the kernel does not take the call — here the CPU — every
    program is the parent's, byte for byte: both decode blocks, the
    whole prefill, a chunk against rows held."""
    cfg, _, model = built
    preset = 'tiny' if cfg['v_head_dim'] == 8 else 'tiny_wide_v'
    for name, digest in _own_program_texts(_engine(model)).items():
        assert digest == _PARENT_OWN_PROGRAMS[preset, name], name


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel wherever its conditions hold but the backend's:
    `interpret=True` for the model's dispatch and the engine's count."""
    import functools
    from paddle_tpu.ops import pallas
    monkeypatch.setattr(pallas, 'latent_decode_kernel', functools.partial(
        pallas.latent_decode_kernel, interpret=True))


WIDE_LEN = 768      # whole program: 3 tiles of 256; half: 3 of 128


@pytest.fixture(scope='module')
def wide():
    """`tiny` with a latent of 128, whole lanes, and positions for
    `WIDE_LEN` rows: a call the kernel takes."""
    cfg = _cfg('tiny', kv_lora_rank=128, max_position_embeddings=WIDE_LEN)
    w = _weights(cfg, seed=9)
    return cfg, w, _model(cfg, w)


def test_with_the_kernel_only_one_query_a_slot_leaves_the_einsums(
        wide, interpreted):
    """A latent of whole lanes and the kernel interpreted: the two
    decode blocks are other programs than the einsums'; a chunk against
    rows held and the whole prefill are the very programs they are
    without it."""
    _, _, model = wide
    kw = dict(max_length=256, buckets=[16])
    with_kernel = _own_program_texts(_engine(model, **kw))
    with pytest.MonkeyPatch.context() as mp:
        from paddle_tpu.ops import pallas
        mp.setattr(pallas, 'latent_decode_kernel', lambda *a: None)
        without = _own_program_texts(_engine(model, **kw))
    assert {n for n in without if with_kernel[n] != without[n]} \
        == {'decode', 'decode_half'}


def _rounds(log):
    return [e['attrs'] for e in log.events()
            if e['name'] == 'serving.decode_round']


def test_both_decode_programs_agree_with_the_reference_through_the_kernel(
        wide, interpreted):
    """`test_both_decode_programs_agree_with_the_reference` with the
    kernel interpreted, 2 slots x 768: the half program's rounds walk
    tiles of 128 rows, the whole program's of 256. One request at a
    time, so a round's `read_rows` is exact: over the three latent
    layers, the decoding slot's length rounded up to the tile, and ONE
    tile of the slot that is not decoding (whatever stale position it
    holds) — not `slots x rows`."""
    cfg, w, model = wide
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model, max_length=WIDE_LEN, buckets=[16, 320, 640])
    assert eng._bounded_tiles(WIDE_LEN).tolist() == [256] * 3
    assert eng._bounded_tiles(WIDE_LEN // 2).tolist() == [128] * 3
    for n_prompt, n_new in ((3, 12), (250, 24), (370, 16), (600, 12)):
        prompt = _prompts((n_prompt,), seed=n_prompt)[0]
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert _served_gap(cfg, w, prompt, list(h.tokens), WIDE_LEN) < TOL
    rounds = _rounds(log)
    assert {a['rows'] for a in rounds} == {WIDE_LEN // 2, WIDE_LEN}
    walked = set()
    for a in rounds:
        assert a['active'] == 1 and a['needed_rows'] % 3 == 0
        tile = 256 if a['rows'] == WIDE_LEN else 128
        length = a['needed_rows'] // 3
        tiles = -(-length // tile)
        walked.add((tile, tiles))
        assert a['read_rows'] == 3 * (tiles * tile + tile)
        assert a['needed_rows'] <= a['read_rows'] < 2 * 3 * a['rows']
    # one, two and three tiles of each size were walked
    assert walked >= {(128, 1), (128, 2), (128, 3), (256, 2), (256, 3)}


def test_through_router_and_engine_every_prompt_length_through_the_kernel(
        wide, interpreted):
    """`test_through_router_and_engine_every_prompt_length` with the
    kernel interpreted: two slots decoding side by side at lengths that
    differ, each bounded by its own."""
    cfg, w, model = wide
    log = obs.get_event_log()
    log.clear()
    lengths = (1, 2, BUCKET, 127, 128, 129, 300)
    prompts = _prompts(lengths)
    toks, eng = _through_the_router(model, prompts, N_NEW,
                                    max_length=WIDE_LEN,
                                    buckets=[BUCKET, 160, 320])
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got, WIDE_LEN) < TOL, len(prompt)
    assert eng._counts['prefills'] == len(lengths)
    rounds = _rounds(log)
    assert any(a['active'] == 2 for a in rounds)
    for a in rounds:
        tile = int(eng._bounded_tiles(a['rows'])[0])
        assert a['needed_rows'] <= a['read_rows'] \
            <= a['needed_rows'] + 3 * 2 * tile
        assert a['read_rows'] % (3 * tile) == 0


def test_decode_round_reads_slots_x_rows_where_the_einsums_run(wide):
    """The same engine on the CPU, the kernel not interpreted: what a
    round reads is what it was, every row of every slot."""
    _, _, model = wide
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model, max_length=WIDE_LEN, buckets=[16])
    assert not eng._bounded_tiles(WIDE_LEN).any()
    eng.submit([5, 6, 7], SamplingParams(max_new_tokens=6, eos_token_id=-1))
    eng.run()
    rounds = _rounds(log)
    assert rounds and all(a['read_rows'] == 2 * 3 * a['rows']
                          for a in rounds)


def test_a_model_without_a_latent_entry_is_asked_nothing(interpreted):
    eng = InferenceEngine(_llama(), num_slots=2, max_length=256,
                          decode_block=BLOCK, buckets=[BUCKET])
    assert not eng._bounded_tiles(256).any()
    assert eng._read_rows(256) == 2 * 256 * len(eng.pool.row_spec)
