"""`nlp/lfm2.py` against its plain float32 reference
(`benchmarks/reference/lfm2.py`) at the tiny presets, with seeded
weights whose `expert_bias` is not zero and whose convolution taps are
random (the benchmark's are one: there a reversed tap order would not
show, here it does).

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (a state carried from call to call against shifted
copies of the whole sequence; sorted blocks of one expert against every
expert for every token; a cache against a full forward; grouped against
repeated KV heads). Observed at most 1.3e-5 on logits as large as 6;
every departure from the published mathematics below moves a logit by
more than 2, and operands rounded to bfloat16 — what one bf16 pass of
the MXU would make of the float32 activations — by 1.1, an expert choice
flipped. 2e-4 lies between with room on both sides."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import _dispatch
from paddle_tpu import observability as obs
from paddle_tpu import programs
from paddle_tpu.jit import functional_state
from paddle_tpu.nlp import afmoe, generation, lfm2
from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.nlp.generation import cached_forward
from paddle_tpu.nlp.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.nlp.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from paddle_tpu.nlp.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import pallas
from paddle_tpu.serving import (InferenceEngine, ReplicaSet, Router,
                                SamplingParams)

from benchmarks.models import adapter, fill
from benchmarks.reference import common as C
from benchmarks.reference import lfm2 as R

TOL = 2e-4
AD = adapter('Lfm2MoeForCausalLM')
PRESETS = ('tiny', 'tiny_conv_first')
BUCKET, BLOCK = 16, 4


def _cfg(preset):
    conf = getattr(Lfm2MoeConfig, preset)()
    cfg = {k: getattr(conf, k) for k in AD._KEYS}
    cfg['rope_parameters'] = {'rope_theta': conf.rope_theta}
    return cfg


def _weights(cfg, seed=7):
    # std 0.3: logits of a few units, so a departure is not lost in them;
    # the taps random too (the reference's shapes say ones)
    shapes = {k: (shape, 'normal' if k.endswith('conv_w') else kind)
              for k, (shape, kind) in R.param_shapes(cfg).items()}
    return C.make_weights(shapes, seed, 'float32', std=0.3)


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
    """A [B, S] padding mask: attention masks the pads, a conv layer
    zeroes their inputs — zeros before a sequence are what its state
    starts from."""
    cfg, w, model = tiny
    ids = _ids((1, 12), 4)
    padded = np.concatenate([np.zeros((1, 5), 'int32'), ids], axis=1)
    keep = np.concatenate([np.zeros((1, 5)), np.ones((1, 12))], axis=1)
    off = paddle.to_tensor(np.array([-5], 'int32'))
    got = model(paddle.to_tensor(padded), attention_mask=keep,
                position_offset=off).numpy()[0, 5:]
    assert np.abs(got - _ref_logits(cfg, w, ids)[0]).max() < TOL


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
def _route_bias_in_weight(scores, bias, k, route_norm, route_scale, eps):
    biased = scores + bias.astype(jnp.float32)
    w, sel = jax.lax.top_k(biased, k)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * route_scale


def _bias_in_weight(model, mp):
    mp.setattr(afmoe, 'route', _route_bias_in_weight)


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
    """What a single bf16 pass makes of the float32 activations: every
    norm's output, the operand of every projection, rounded."""
    def rounded(norm):
        real = norm.forward
        norm.forward = lambda x: real(x).astype('bfloat16').astype('float32')
    for layer in model.model.layers:
        rounded(layer.operator_norm)
        rounded(layer.ffn_norm)
    rounded(model.model.embedding_norm)


@pytest.fixture
def fresh_dispatch():
    """The eager dispatch cache keys an op by its code, not by the
    module globals a departure patches: empty it around such a test."""
    _dispatch.clear()
    yield
    _dispatch.clear()


DEPARTURES = [None, _bias_in_weight, _no_route_norm, _no_expert_bias,
              _taps_reversed, _gates_swapped, _silu_on_the_conv,
              _no_qk_norm, _no_rope, _bf16_operands]


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
# (d) prefill by bucket, then decode: the hand-off of a state
# ---------------------------------------------------------------------------
LENGTHS = (1, 2, 3, BUCKET - 1, BUCKET)


def _engine(model, **extra):
    kw = dict(num_slots=2, max_length=64, decode_block=BLOCK,
              buckets=[BUCKET, 32], eos_token_id=-1)
    kw.update(extra)
    return InferenceEngine(model, **kw)


@pytest.mark.parametrize('n_prompt', LENGTHS)
def test_prefill_program_then_decode_logits_at_every_position(built,
                                                              n_prompt):
    """The engine's own prefill program on a prompt right-padded to its
    bucket, the last prompt token forwarded again at its slot, then one
    token at a time for three blocks: the LOGITS at every position
    against the reference's full forward."""
    cfg, w, model = built
    eng = _engine(model)
    fwd = cached_forward(model, *functional_state(model))
    n_new = 3 * BLOCK
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
    toks, eng = _through_the_router(model, prompts, 3 * BLOCK)
    for prompt, got in zip(prompts, toks):
        assert _served_gap(cfg, w, prompt, got) < TOL, len(prompt)
    assert eng._counts['prefills'] == len(LENGTHS)
    assert eng._counts['chunked_prefills'] == 0


def _state_at_the_buckets_end(mp):
    """The prefill that does not know the prompt's length: the padding
    is folded into the state."""
    mp.setattr(lfm2, 'folded_tokens', lambda s: s)


def _last_token_twice(mp):
    """The state as of the prompt's END: the decode block's re-forward
    of the last prompt token then folds it in a second time."""
    real = generation.folded_tokens
    mp.setattr(lfm2, 'folded_tokens', lambda s: real(s) + (
        0 if generation._routing.folded is None else 1))


@pytest.mark.parametrize('fault,slots', [(_state_at_the_buckets_end, 3),
                                         (_last_token_twice, 4)],
                         ids=lambda f: getattr(f, '__name__', '').strip('_'))
def test_a_faulty_hand_off_fails_the_tolerance(tiny, fault, slots,
                                               monkeypatch):
    """(A slot count of its own: the program store keys a program by the
    engine's geometry, not by what a test patched, and must trace the
    faulty prefill anew.)"""
    cfg, w, model = tiny
    fault(monkeypatch)
    prompts = _prompts(LENGTHS)
    toks, _ = _through_the_router(model, prompts, 3 * BLOCK, slots)
    gaps = [_served_gap(cfg, w, p, t) for p, t in zip(prompts, toks)]
    assert max(gaps) > 50 * TOL, gaps


# ---------------------------------------------------------------------------
# (e) continuous batching: more requests than slots, slots reseated
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
    # two slots, seven requests: each slot was seated over a used state
    assert eng._counts['prefills'] == 7 and eng.pool.num_slots == 2


def test_a_reseated_slot_holds_the_new_requests_state_whole(tiny):
    """After a long request the slot's state leaves are its garbage; the
    next prefill seats every one of them, and the short request then
    served from that slot is the one served from a fresh engine."""
    _, _, model = tiny
    long_one, short_one = _prompts((27, 3), seed=5)
    eng = _engine(model, num_slots=1)
    a = eng.submit(long_one, SamplingParams(max_new_tokens=20,
                                            eos_token_id=-1))
    eng.run()
    used = [np.asarray(eng.pool.rows[i]) for i in eng.pool.state_layers]
    assert all(np.abs(u).max() > 0 for u in used)
    b = eng.submit(short_one, SamplingParams(max_new_tokens=10,
                                             eos_token_id=-1))
    eng.run()
    fresh = _engine(model, num_slots=1)
    c = fresh.submit(short_one, SamplingParams(max_new_tokens=10,
                                               eos_token_id=-1))
    fresh.run()
    assert a.error is None and list(b.tokens) == list(c.tokens)
    for i in eng.pool.state_layers:
        assert np.abs(np.asarray(eng.pool.rows[i])
                      - np.asarray(fresh.pool.rows[i])).max() < 1e-5


# ---------------------------------------------------------------------------
# (f) both decode programs
# ---------------------------------------------------------------------------
def test_both_decode_programs_agree_with_the_reference(built):
    """max_length 64: rounds attend over 32 rows while every active
    position allows it, then over 64. One request stays inside the half
    program, one crosses over, one starts past it."""
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
    rows = [e['attrs']['rows'] for e in log.events()
            if e['name'] == 'serving.decode_round']
    assert set(rows) == {32, 64}
    assert eng._trace_counts['decode_step'] == 1
    assert eng._trace_counts['decode_step_half'] == 1


# ---------------------------------------------------------------------------
# (g) what cannot share or rewind a state is refused, with its reason
# ---------------------------------------------------------------------------
def _llama():
    paddle.seed(3)
    return LlamaForCausalLM(LlamaConfig.tiny()).eval()


@pytest.mark.parametrize('extra,names', [
    (dict(prefix_cache=True), 'prefix_cache.*END of its donor'),
    (dict(prefix_cache=0.5), 'prefix_cache'),
    (dict(prefill_chunk_tokens=16), 'prefill_chunk_tokens.*chunk'),
    (dict(kv_page_size=8), 'kv_page_size.*no rows to page'),
    (dict(kv_pages=40), 'kv_pages'),
    (dict(kv_quant='int8'), 'kv_quant.*int8'),
    (dict(draft_model='llama'), 'draft_model.*moved back'),
], ids=['prefix_cache', 'prefix_cache_fraction', 'chunked_prefill', 'paged',
        'kv_pages', 'int8_kv', 'speculative'])
def test_engine_modes_that_cannot_hold_a_state_are_refused(tiny, extra,
                                                           names):
    _, _, model = tiny
    if extra.get('draft_model') == 'llama':
        extra = dict(draft_model=_llama())
    with pytest.raises(ValueError, match='recurrent slot state.*' + names):
        _engine(model, **extra)


def test_a_draft_model_with_a_state_is_refused_too(tiny):
    _, _, model = tiny
    with pytest.raises(ValueError, match='Lfm2MoeForCausalLM keeps '
                                         'recurrent.*draft_model'):
        InferenceEngine(_llama(), num_slots=2, max_length=64,
                        draft_model=model)


def test_generate_gives_the_references_greedy_tokens(built):
    cfg, w, model = built
    ids = _ids((2, 9), 8)
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                            eos_token_id=-1)
    for row, got in zip(ids, out.numpy()):
        assert _served_gap(cfg, w, row.tolist(), got.tolist()) < TOL
    # all-ones mask: nothing is padded, nothing refused
    same, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=10,
                             eos_token_id=-1,
                             attention_mask=np.ones((2, 9), 'int32'))
    assert (same.numpy() == out.numpy()).all()


def test_generate_refuses_padded_prompts_and_speculation(tiny):
    _, _, model = tiny
    ids = _ids((2, 9), 8)
    keep = np.ones((2, 9), 'int32')
    keep[1, :4] = 0
    with pytest.raises(ValueError, match='no padded prompts.*conv'):
        model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                       attention_mask=keep)
    with pytest.raises(NotImplementedError, match='moved back'):
        model.speculative_generate(_llama(), paddle.to_tensor(ids[:1]))


# ---------------------------------------------------------------------------
# (h) models that keep K and V only compile to the programs they had
# ---------------------------------------------------------------------------
_KV_ONLY = {'gpt': (GPTForCausalLM, GPTConfig),
            'llama': (LlamaForCausalLM, LlamaConfig),
            'afmoe': (AfmoeForCausalLM, AfmoeConfig)}


@pytest.mark.parametrize('family', sorted(_KV_ONLY))
def test_a_model_without_state_keeps_the_programs_it_had(family):
    """The state path is picked by what the cache holds: a model with K
    and V only has no state booked, every layer counted as attending,
    and the plain prefill — ids alone, no length."""
    cls, conf = _KV_ONLY[family]
    paddle.seed(0)
    eng = InferenceEngine(cls(conf.tiny()).eval(), num_slots=2,
                          max_length=64, decode_block=4, buckets=[16])
    assert eng.pool.state_layers == () and eng.pool.state_bytes == 0
    assert len(eng._layer_rows) == len(eng.pool.row_spec)
    assert eng._prefill_jit._fn_token \
        == programs.code_token(eng._prefill_fn) \
        != programs.code_token(eng._state_prefill_fn)
    state = (eng._params, eng._frozen, eng._buffers)
    slab = jax.eval_shape(eng._prefill_fn, *state,
                          jnp.zeros((1, 16), jnp.int32))
    assert not generation.state_layers(slab)


# ---------------------------------------------------------------------------
# (i) what a decode round's span carries
# ---------------------------------------------------------------------------
def _rounds(log):
    return [e['attrs'] for e in log.events()
            if e['name'] == 'serving.decode_round']


def test_decode_round_carries_state_and_counts_one_attention_layer(tiny):
    cfg, _, model = tiny
    log = obs.get_event_log()
    log.clear()
    reg = obs.get_registry()
    before = reg.value('paddle_serving_slot_state_bytes_total')
    _through_the_router(model, _prompts((5, 19, 11)), 14)
    rounds = _rounds(log)
    assert rounds
    leaf = 3 * 32 * 4                   # conv_L_cache x hidden x float32
    for a in rounds:
        assert (a['attn_layers'], a['state_layers']) == (1, 4)
        assert a['state_bytes'] == a['active'] * 4 * leaf * 2 * BLOCK
        assert a['rows'] in (32, 64)
        # ONE attention layer of the five: rows of one layer only
        assert a['read_rows'] == 2 * a['rows']
        assert 0 < a['needed_rows'] <= a['real_rows'] + a['active']
        assert a['expert_layer_substeps'] == BLOCK * 4
        assert a['experts'] == cfg['num_experts']
    assert reg.value('paddle_serving_slot_state_bytes_total') - before \
        == sum(a['state_bytes'] for a in rounds)


def test_decode_through_the_kernel_agrees_with_the_reference(
        tiny, kv_interpreted):
    """The decode block through `kv_decode_attention`, interpreted (4
    query heads a KV head, 2 slots x 64 rows in tiles of 16), one
    request at a time so a round's `read_rows` is exact: on the ONE
    attention layer the decoding slot's length rounded up to the tile,
    and one tile of the slot that is not decoding; a conv layer reads
    no row."""
    cfg, w, model = tiny
    log = obs.get_event_log()
    log.clear()
    eng = _engine(model)
    assert eng._bounded_tiles(64).tolist() == [16]
    assert eng._bounded_tiles(32).tolist() == [16]
    for n_prompt, n_new in ((3, 14), (21, 34)):
        prompt = _prompts((n_prompt,), seed=n_prompt)[0]
        h = eng.submit(prompt, SamplingParams(max_new_tokens=n_new,
                                              eos_token_id=-1))
        eng.run()
        assert _served_gap(cfg, w, prompt, list(h.tokens)) < TOL
    assert len(kv_interpreted) == 2         # a call a program, traced
    rounds = _rounds(log)
    assert {a['rows'] for a in rounds} == {32, 64}
    walked = set()
    for a in rounds:
        assert a['active'] == 1
        tiles = -(-a['needed_rows'] // 16)
        walked.add(tiles)
        assert a['read_rows'] == tiles * 16 + 16
        assert a['needed_rows'] <= a['read_rows'] <= 2 * a['rows']
    assert walked == {1, 2, 3, 4}


def test_the_expert_kernel_serves_the_loops_tokens(monkeypatch,
                                                   fresh_programs):
    """bf16 expert leaves, four picks of eight experts, a state beside
    K and V: the interpreted kernel gives the loop's greedy tokens and
    books every expert-layer sub-step as its own."""
    cfg = _cfg('tiny')
    w = {name: v.astype(jnp.bfloat16) if 'experts_' in name else v
         for name, v in _weights(cfg).items()}
    prompts, log = _prompts((5, 19, 11)), obs.get_event_log()
    log.clear()
    base, _ = _through_the_router(_model(cfg, w), prompts, 14)
    assert all(a['expert_kernel_substeps'] == 0 for a in _rounds(log))
    monkeypatch.setattr(afmoe, 'expert_kernel', functools.partial(
        pallas.expert_kernel, interpret=True))
    fresh_programs.clear_memory()
    log.clear()
    toks, _ = _through_the_router(_model(cfg, w), prompts, 14)
    assert toks == base
    rounds = _rounds(log)
    assert rounds and all(a['expert_kernel_substeps']
                          == a['expert_layer_substeps'] == BLOCK * 4
                          for a in rounds)


def test_a_model_without_state_carries_none_of_it():
    log = obs.get_event_log()
    log.clear()
    eng = InferenceEngine(_llama(), num_slots=2, max_length=64,
                          decode_block=BLOCK, buckets=[BUCKET])
    eng.submit([5, 6, 7], SamplingParams(max_new_tokens=6, eos_token_id=-1))
    eng.run()
    a = _rounds(log)[-1]
    assert not {'attn_layers', 'state_layers', 'state_bytes'} & set(a)
    assert eng.pool.stats()['state_layers'] == 0


def test_pool_books_state_apart_from_rows(tiny):
    _, _, model = tiny
    eng = _engine(model)
    pool = eng.pool
    assert pool.state_layers == (0, 2, 3, 4)
    assert pool.state_bytes == 4 * 3 * 32 * 4
    kv = 2 * 64 * 2 * 8 * 4             # K and V, 64 rows, 2 heads x 8
    assert pool.row_bytes == kv + pool.state_bytes
    assert pool.stats()['state_bytes'] == pool.state_bytes
    assert list(eng._layer_rows) == [64]
    # a bf16 pool keeps its state leaves float32
    half = _engine(model, dtype='bfloat16').pool.rows
    assert half[1][0].dtype == jnp.bfloat16 and half[0].dtype == jnp.float32


def test_conv_scopes_are_on_the_decode_and_prefill_programs(tiny):
    _, _, model = tiny
    _through_the_router(model, _prompts((5,)), 6)
    table = programs.scope_table()
    # (a prefill returns rows and state, no logits: no `lm_head` there)
    for prog, more in (('serving.decode_block', {'lm_head', 'sample'}),
                       (f'serving.prefill_{BUCKET}', set())):
        found = {s for op, *_ in table[prog].values()
                 for s in programs.scope_path(op)}
        assert {'conv', 'state_write', 'attention', 'kv_write', 'mlp',
                'moe/router', 'moe/experts', 'norm'} | more <= found
        assert 'moe/shared' not in found
    assert programs.scope_path(
        'jit(f)/while/body/conv/state_write/dynamic_slice') \
        == ('conv', 'state_write')


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
    with pytest.raises(ValueError, match='conv_bias'):
        Lfm2MoeConfig.tiny(conv_bias=True)
    with pytest.raises(ValueError, match='use_expert_bias'):
        Lfm2MoeConfig.tiny(use_expert_bias=False)
    with pytest.raises(ValueError, match='ties its head'):
        Lfm2MoeConfig.tiny(tie_word_embeddings=False)
    with pytest.raises(ValueError, match='layer_types'):
        Lfm2MoeConfig.tiny(layer_types=['conv'])
    from paddle_tpu.nlp import transformers
    assert transformers.Lfm2MoeForCausalLM is Lfm2MoeForCausalLM
