"""`nlp/ling3.py` against its plain float32 reference
(`benchmarks/reference/ling3.py`: KDA as the recurrence token by token,
latent attention written out with its head-wise gate, the group-limited
router from the rule) at the tiny presets, with seeded weights whose
`dt_bias` lies in [-8, -3] (decays near 1: the long-memory regime the
benchmark's initializer cannot reach), whose taps are random (the
benchmark's are one) and whose selection bias is drawn at std 1 (it
decides groups and picks). Model-level: what builds no engine; the
served half is `tests/test_ling3_serving.py`, the shared cases and
helpers `tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (chunks of 16 or 64 tokens with a triangular solve
against one token after another; a state carried from call to call
against the whole sequence; the absorbed products against K and V by
head; sorted blocks of one expert against every expert for every token).
Observed at most 4e-5 on logits as large as 6; every departure from the
published mathematics below moves a logit by more than 50 x TOL. 2e-4
lies between with room on both sides."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import programs
from paddle_tpu.jit import functional_call, functional_state
from paddle_tpu.nlp import afmoe, generation, ling3
from paddle_tpu.nlp.ling3 import (KDA, MLA, Ling3Config, Ling3ForCausalLM)

from benchmarks.reference import common as C

import family_harness as H
from family_harness import TOL


def _draw(R, cfg, seed):
    """Random taps; `dt_bias` uniform in [-8, -3] and `A_log` at std
    0.3: decays of 0.9-0.999 a token beside a few strong ones; the
    selection bias at std 1; the output norm's weight 1 + noise."""
    shapes = {k: (shape, 'normal' if k.endswith(('_conv', '.a_log',
                                                  '.dt_bias', '.o_norm'))
                  else kind)
              for k, (shape, kind) in R.param_shapes(cfg).items()}
    w = H.draw(shapes, seed)
    out = {}
    for k, v in w.items():
        if k.endswith('.dt_bias'):      # N(0, 0.3) -> uniform [-8, -3]
            v = -5.5 + 2.5 * jnp.tanh(v / 0.3 * 1.2)
        elif k.endswith('.expert_bias'):
            v = v / 0.3
        elif k.endswith('.o_norm'):
            v = 1.0 + v
        out[k] = v
    return out


def _adds(conf):
    return dict(layer_types=conf.layer_types, q_lora_rank=None,
                rope_scaling=None, tie_word_embeddings=False,
                expert_share={'routed': conf.num_routed_experts,
                              'first': conf.first_expert})


FAM = H.Family('Ling3ForCausalLM', Ling3Config,
               ('tiny', 'tiny_latent_first'), cfg_adds=_adds, draw=_draw,
               one_position=True)
R = FAM.R
built, tiny = H.fixtures(FAM)


@pytest.fixture(autouse=True, scope='module')
def chunks_of_sixteen_tokens():
    """The scan's chunk is 64 tokens and these tests' sequences 40 and
    fewer: with chunks of 16 a forward is three, a bucket of 32 two."""
    patch = pytest.MonkeyPatch()
    patch.setattr(ling3, 'KDA_CHUNK', 16)
    yield
    patch.undo()


# ---------------------------------------------------------------------------
# (a) the whole forward: over its own tokens and against rows held
# ---------------------------------------------------------------------------
test_full_forward_agrees_with_the_reference_on_both_paths = \
    H.full_forward(FAM, H.paths)
test_a_left_padded_batch_forward_is_each_prompt_alone = \
    H.left_padded_forward(FAM)


def test_one_chunk_of_four_blocks_is_the_same_forward(monkeypatch,
                                                       fresh_dispatch):
    """The chunk as served, 64 tokens: 40 are one chunk of three blocks'
    rows, the pair products of a block against the blocks before it."""
    monkeypatch.setattr(ling3, 'KDA_CHUNK', 64)
    cfg, w, _ = FAM.build('tiny_latent_first')
    tokens = H.ids((2, 40))
    ref = FAM.ref_logits(cfg, w, tokens)
    for got in H.own_path(FAM.model(cfg, w), tokens):
        assert np.abs(got - ref).max() < TOL


# ---------------------------------------------------------------------------
# (b) the chunked scan against the recurrence, decays at both ends
# ---------------------------------------------------------------------------
def _operands(seed, b, s, h, d, gate):
    rs = np.random.RandomState(seed)
    f32 = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)  # noqa
    q, k = ling3.l2norm(f32(b, s, h, d)), ling3.l2norm(f32(b, s, h, d))
    g = {'strong': jnp.full((b, s, h, d), -4.999, jnp.float32),
         'weak': jnp.full((b, s, h, d), -1e-4, jnp.float32),
         'mixed': -5 * jax.nn.sigmoid(2.7 * f32(b, s, h, d)),
         # a channel that forgets at once beside one that never does
         'both_ends': jnp.where(jnp.arange(d) % 2 == 0, -4.999, -1e-5)
         * jnp.ones((b, s, h, d), jnp.float32)}[gate]
    return q, k, f32(b, s, h, d), g, jax.nn.sigmoid(f32(b, s, h)), \
        f32(b, h, d, d)


def _token_by_token(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = ling3.kda_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


@pytest.mark.parametrize('chunk', [16, 64])
@pytest.mark.parametrize('gate', ['strong', 'weak', 'mixed', 'both_ends'])
def test_chunked_scan_is_the_recurrence_token_by_token(gate, chunk):
    """150 tokens (whole chunks and a part of one) from a state that is
    not zero: g near -5 for 64 tokens running would be e^-320 as one
    factor and e^320 as the other; in blocks of 16 nothing is ever
    not finite."""
    ops = _operands(3, 2, 150, 3, 8, gate)
    want, end = _token_by_token(*ops)
    got, state = jax.jit(ling3.kda_chunked, static_argnums=6)(*ops, chunk)
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(state)).all()
    assert np.abs(np.asarray(want)).max() > 0.1
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    assert np.abs(np.asarray(state - end)).max() < 2e-5


def test_the_pair_products_factors_stay_inside_float32():
    """What `_pair_products` multiplies: with every log decay at the
    bound the two factors of a block stay within e^-40 and e^40 of the
    block's middle, so that keys a MILLIONTH of a unit still count, and
    a key of a later block is an exact zero."""
    cum = jnp.cumsum(jnp.full((64, 4), -5.0, jnp.float32), axis=0)
    small = jnp.full((64, 4), 1e-6, jnp.float32)
    got = np.asarray(ling3._pair_products((small,), small, cum, 16)[0])
    i, j = np.tril_indices(64)
    want = 4e-12 * np.exp(-5.0 * (i - j))
    assert np.isfinite(got).all()
    near = i - j <= 4           # e^-20 and more: further apart is nothing
    assert np.abs(got[i, j][near] / want[near] - 1).max() < 1e-5
    assert np.abs(got[i, j] - want).max() < 1e-17
    assert (got[np.arange(16)[:, None], np.arange(16, 64)[None]] == 0).all()


def test_a_state_carried_from_call_to_call_is_the_whole_sequence(tiny):
    """The layer itself: one call over 40 tokens against calls of 17, 1,
    16, 5 and 1, the entry handed from each to the next."""
    _, _, model = tiny
    kda = model.model.layers[0].self_attn
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 40, 32)
                         .astype('float32'))
    whole = kda(x).numpy()
    state = jax.tree_util.tree_map(paddle.to_tensor, kda.init_state(2))
    at = 0
    for n in (17, 1, 16, 5, 1):
        out, state = kda(x[:, at:at + n], state=state)
        assert np.abs(out.numpy() - whole[:, at:at + n]).max() < 1e-5
        at += n
    assert set(state) == {'S', 'conv'}
    assert tuple(state['S'].shape) == (2, 4, 8, 8)
    assert tuple(state['conv'].shape) == (2, 3, 96)


@pytest.mark.parametrize('length', [1, 2, 9, 16, 17, 31])
def test_a_padded_buckets_state_is_the_exact_lengths(tiny, length):
    """Under `state_scope(n)` a call of 32 tokens returns the entry as
    the first n leave it — whatever follows them — and that is the
    entry a call of exactly n tokens returns."""
    _, _, model = tiny
    kda = model.model.layers[0].self_attn
    x = np.random.RandomState(2).randn(1, 32, 32).astype('float32')
    zero = jax.tree_util.tree_map(paddle.to_tensor, kda.init_state(1))
    _, exact = kda(paddle.to_tensor(x[:, :length]), state=zero)
    with generation.state_scope(jnp.int32(length)):
        _, padded = kda(paddle.to_tensor(x), state=zero)
    for leaf in ('S', 'conv'):
        assert np.abs(padded[leaf].numpy() - exact[leaf].numpy()).max() \
            < 1e-6


# ---------------------------------------------------------------------------
# (c) the router: groups first
# ---------------------------------------------------------------------------
def _route_by_loop(scores, bias, k, n_group, topk_group):
    picks = []
    for s in np.asarray(scores, np.float64):
        c = s + np.asarray(bias, np.float64)
        size = len(c) // n_group
        group = [np.sort(c[g * size:(g + 1) * size])[-2:].sum()
                 for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: -group[g])[:topk_group]
        inside = [e for e in range(len(c)) if e // size in kept]
        picks.append(sorted(inside, key=lambda e: -c[e])[:k])
    return picks


@pytest.mark.parametrize('n_group, topk_group', [(4, 2), (8, 4), (2, 1),
                                                 (4, 4)])
def test_group_limited_route_against_a_plain_loop(n_group, topk_group):
    rs = np.random.RandomState(n_group)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(40, 32), jnp.float32))
    bias = jnp.asarray(rs.randn(32), jnp.float32)
    sel, w = afmoe.route(scores, bias, 3, True, 2.5, 1e-20, n_group,
                         topk_group)
    want = _route_by_loop(scores, bias, 3, n_group, topk_group)
    assert sel.tolist() == want
    picked = np.take_along_axis(np.asarray(scores), np.asarray(sel), -1)
    assert np.allclose(w, 2.5 * picked / picked.sum(-1, keepdims=True),
                       atol=1e-6)
    groups = {tuple(sorted({e // (32 // n_group) for e in row}))
              for row in want}
    assert all(len(g) <= topk_group for g in groups)
    free, _ = afmoe.route(scores, bias, 3, True, 2.5, 1e-20)
    if topk_group < n_group:        # the limit changes picks
        assert free.tolist() != want
    else:                           # every group kept: no limit
        assert free.tolist() == want


def test_one_group_is_the_parents_router():
    """`n_group` 1: the picks and the program `route` always gave."""
    rs = np.random.RandomState(0)
    scores = jax.nn.sigmoid(jnp.asarray(rs.randn(9, 16), jnp.float32))
    bias = jnp.asarray(rs.randn(16), jnp.float32)

    def parent(scores, bias):
        _, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), 4)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return sel.astype(jnp.int32), w * 2.0
    now = lambda s, b: afmoe.route(s, b, 4, True, 2.0, 1e-20, 1, 1)  # noqa
    assert str(jax.make_jaxpr(now)(scores, bias)) \
        == str(jax.make_jaxpr(parent)(scores, bias))
    assert [a.tolist() for a in now(scores, bias)] \
        == [a.tolist() for a in parent(scores, bias)]


# ---------------------------------------------------------------------------
# (d) the share tied to the model: the eight shares add up
# ---------------------------------------------------------------------------
def _layer_and_reference(first, held, seed=3):
    """The PROGRAM's expert layer holding experts first..first+held-1 of
    16 (group `first // 4` whole), and the uncut reference's weights."""
    cfg = FAM.cfg('tiny', num_experts=16, first_expert=0, n_group=8,
                  topk_group=4, num_experts_per_tok=4)
    shapes = {k[3:]: v for k, v in R.param_shapes(cfg).items()
              if k.startswith('l1.') and ('expert' in k or 'router' in k
                                          or 'shared' in k)}
    lp = H.draw(shapes, seed)
    lp['expert_bias'] = lp['expert_bias'] / 0.3
    layer = afmoe.AfmoeSparseMLP(Ling3Config.tiny(
        num_experts=held, num_routed_experts=16, first_expert=first,
        n_group=8, topk_group=4, num_experts_per_tok=4))
    layer.router.weight._data = lp['router_w']
    layer.expert_bias._data = lp['expert_bias']
    for name, leaf in (('gate_w', 'experts_gate'), ('up_w', 'experts_up'),
                       ('down_w', 'experts_down')):
        getattr(layer, name)._data = lp[leaf][first:first + held]
    for name in ('gate', 'up', 'down'):
        getattr(layer.shared_experts, name + '_proj').weight._data = \
            lp['shared_' + name]
    return cfg, lp, layer.eval()


def test_the_eight_shares_add_up_to_the_uncut_reference(fresh_dispatch):
    """Experts 0-1, 2-3, ... 14-15 — a routing group a chip — each
    through the program's layer: their partial sums, the shared expert
    counted ONCE, add up to what the reference gives for the whole layer
    of 16; each is what the reference gives for that share; and no
    token's picks land on more than 4 of the 8."""
    m = jnp.asarray(np.random.RandomState(5).randn(1, 24, 32), jnp.float32)
    total, shared = 0.0, None
    landed = np.zeros((24, 8), bool)
    for chip, first in enumerate(range(0, 16, 2)):
        cfg, lp, layer = _layer_and_reference(first, 2)
        state = functional_state(layer)

        def chip_s(x):      # one compile a chip, not one an op
            with generation.routing_scope() as picks:
                part = functional_call(layer, *state, (x,), {})[0]
            assert picks[0][1:] == (2, False, True)
            return part, picks[0][0], functional_call(
                layer.shared_experts, *functional_state(
                    layer.shared_experts), (x,), {})[0]
        part, sel, shared = (np.asarray(a)[0] for a in jax.jit(chip_s)(m))
        landed[:, chip] = (sel < 2).any(-1)
        own = dict(cfg, num_experts=2,
                   expert_share={'routed': 16, 'first': first})
        mine = {k: v[first:first + 2] if k.startswith('experts_') else v
                for k, v in lp.items()}
        assert np.abs(part - np.asarray(
            R.experts(C.Ref(), own, mine, m[0]))).max() < TOL
        total = total + part - shared
    whole = np.asarray(R.experts(C.Ref(), cfg, lp, m[0]))
    assert np.abs(whole - shared).max() > 0.5
    assert np.abs(total + shared - whole).max() < TOL
    assert landed.sum(-1).max() <= 4 and landed.any(-1).all()


# ---------------------------------------------------------------------------
# each departure from the published mathematics fails the tolerance
# ---------------------------------------------------------------------------
def _kda_layers(model):
    return [l.self_attn for l in model.model.layers if not l.is_attention]


def _patched(mp, name, make):
    real = getattr(ling3, name)
    mp.setattr(ling3, name, make(real))


def _no_group_limit(model, mp):
    model.config.n_group = model.config.topk_group = 1


def _bias_in_weight(model, mp):
    real = afmoe.route

    def route(scores, bias, *args):
        sel, _ = real(scores, bias, *args)
        k, norm, scale, eps = args[:4]
        w = jnp.take_along_axis(scores + bias, sel, axis=-1)
        return sel, w / (jnp.sum(w, -1, keepdims=True) + eps) * scale
    mp.setattr(afmoe, 'route', route)


def _no_erase_term(model, mp):
    """`S = diag(alpha) S + beta k v^T`: plain gated linear attention."""
    def chunked(real):
        def f(q, k, v, g, beta, state, chunk):
            outs = []
            for t in range(q.shape[1]):
                state = state * jnp.exp(g[:, t])[..., None] \
                    + (beta[:, t, :, None] * k[:, t])[..., None] \
                    * v[:, t][..., None, :]
                outs.append(jnp.sum(state * q[:, t][..., None], -2)
                            / np.sqrt(q.shape[-1]))
            return jnp.stack(outs, 1), state
        return f
    _patched(mp, 'kda_chunked', chunked)


def _taps_reversed(model, mp):
    for kda in _kda_layers(model):
        for name in ('q_conv', 'k_conv', 'v_conv'):
            leaf = getattr(kda, name)
            leaf._data = leaf._data[:, ::-1]


def _no_l2norm(model, mp):
    mp.setattr(ling3, 'l2norm', lambda x, eps=1e-6: x)


def _decay_a_head_not_a_channel(model, mp):
    def gates(real):
        def f(*args):
            g, beta = real(*args)
            return jnp.broadcast_to(jnp.mean(g, -1, keepdims=True),
                                    g.shape), beta
        return f
    _patched(mp, 'kda_gates', gates)


def _unsafe_gate(model, mp):
    """`g = -softplus(.)`, the gate without its lower bound."""
    def gates(real):
        def f(f_, b, a_log, dt_bias, lower):
            g, beta = real(f_, b, a_log, dt_bias, 1.0)
            return -jax.nn.softplus(jnp.log(g / (1 - g))), beta
        return f
    _patched(mp, 'kda_gates', gates)


def _no_beta(model, mp):
    def gates(real):
        def f(*args):
            g, beta = real(*args)
            return g, jnp.ones_like(beta)
        return f
    _patched(mp, 'kda_gates', gates)


def _no_output_norm(model, mp):
    for kda in _kda_layers(model):
        kda.o_norm = lambda t: t


def _no_head_gate(model, mp):
    for layer in model.model.layers:
        if layer.is_attention:
            layer.self_attn._gated = lambda out, hidden: out


def _bf16_operands(model, mp):
    H.bf16_operands(model, mp)


test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(FAM, [
        _no_group_limit, _bias_in_weight, _no_erase_term, _taps_reversed,
        _no_l2norm, _decay_a_head_not_a_channel, _unsafe_gate, _no_beta,
        _no_output_norm, _no_head_gate, _bf16_operands])


# ---------------------------------------------------------------------------
# generate: the batch path builds no engine
# ---------------------------------------------------------------------------
test_generate_gives_the_references_greedy_tokens = H.generate_greedy(FAM, 10)
test_generate_refuses_padded_prompts_and_speculation = \
    H.generate_refuses(FAM, 'KDA')


def test_config_presets_and_refusals():
    conf = Ling3Config()        # the defaults are the published file's
    assert conf.layer_types.count(MLA) == 7
    assert [i for i, t in enumerate(conf.layer_types) if t == MLA] \
        == [5, 11, 17, 23, 29, 35, 41]
    assert conf.layer_pattern == 'KKKKKA' * 7
    assert (conf.n_group, conf.topk_group, conf.num_routed_experts) \
        == (8, 4, 512)
    assert conf.attention_output_gate == 'head_wise'
    assert abs(conf.softmax_scale - 192 ** -0.5) < 1e-12 \
        and conf.q_lora_rank is None
    assert Ling3Config.tiny().layer_pattern == 'KKA'
    assert Ling3Config.tiny_latent_first().layer_pattern == 'AKK'
    assert 'KKA' in programs.describe_statics(Ling3Config.tiny())
    H.refused(Ling3Config.tiny, (
        (dict(expert_swiglu_limit_list=[0, 0, 4]),
         'expert_swiglu_limit_list'),
        (dict(share_expert_swiglu_limit_list=[0, 5, 0]),
         'share_expert_swiglu_limit_list'),
        (dict(use_kda_lora=True), 'use_kda_lora'),
        (dict(kda_safe_gate=False), 'kda_safe_gate'),
        (dict(use_mla_nope=True), 'use_mla_nope'),
        (dict(q_lora_rank=768), 'q_lora_rank'),
        (dict(num_kv_heads_for_linear_attn=4),
         'num_kv_heads_for_linear_attn'),
        (dict(rope_scaling={'type': 'yarn'}), 'rope_scaling'),
        (dict(use_nGPT=True), 'use_nGPT'),
        (dict(value_norm=True), 'value_norm'),
        (dict(up_proj_norm=True), 'up_proj_norm'),
        (dict(n_group=3), 'n_group'),
        (dict(topk_group=5), 'topk_group'),
        (dict(layer_types=[KDA]), 'layer_types'),
        (dict(gated_attention_proj_granularity_type='elementwise'),
         'gated_attention_proj_granularity_type')))
    # zeros are no clamp: the layers this repository's cut keeps
    assert Ling3Config.tiny(expert_swiglu_limit_list=[0, 0, 0])
    from paddle_tpu.nlp import transformers
    assert transformers.Ling3ForCausalLM is Ling3ForCausalLM
