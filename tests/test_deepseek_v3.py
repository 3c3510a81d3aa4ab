"""`nlp/deepseek_v3.py` against its plain float32 reference
(`benchmarks/reference/deepseek_v3.py`, latent attention WRITTEN OUT: K
and V by head, no cache, no absorbed product) at the tiny presets, with
seeded weights whose selection bias is large enough to change picks and
whose latent norm's weight is not ones (the benchmark's are 0.02 and
ones: there a dropped norm weight would not show, here it does).
Model-level: what builds no engine; the served half is
`tests/test_deepseek_v3_serving.py`, the shared cases and helpers
`tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (the absorbed products `q_nope W_UK^T . c` against
`q_nope . (c W_UK)`; a cache of rows against a full-sequence forward;
sorted blocks of the experts against every expert for every token).
Observed at most 1.5e-5 on logits as large as 9; every departure from
the published mathematics below moves a logit by more than 0.03, and
operands rounded to bfloat16 — what one bf16 pass of the MXU would make
of the float32 activations — by 0.1 and more. 2e-4 lies between with
room on both sides."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import afmoe, deepseek_v3, generation
from paddle_tpu.nlp.deepseek_v3 import (DeepseekV3Config,
                                        DeepseekV3ForCausalLM)
from paddle_tpu.serving.kv_pool import wants_own_layout

from benchmarks.reference import common as C

import family_harness as H
from family_harness import TOL


def _draw(R, cfg, seed):
    """The latent norm's weight 1 + what the generator drew (0.4-1.6)."""
    w = H.draw(R.param_shapes(cfg), seed)
    noise = H.draw({k: (v.shape, 'normal') for k, v in w.items()
                    if k.endswith('.kv_norm')}, seed + 1)
    return {k: v + noise[k] if k in noise else v for k, v in w.items()}


FAM = H.Family(
    'DeepseekV3ForCausalLM', DeepseekV3Config, ('tiny', 'tiny_wide_v'),
    cfg_adds=lambda conf: dict(
        moe_layer_freq=1, scoring_func='sigmoid', topk_method='noaux_tc',
        n_group=1, topk_group=1, attention_bias=False,
        tie_word_embeddings=False),
    draw=_draw)
R = FAM.R
built, tiny = H.fixtures(FAM)


# ---------------------------------------------------------------------------
# (a) the whole forward
# ---------------------------------------------------------------------------
test_full_forward_agrees_with_the_reference_on_both_paths = \
    H.full_forward(FAM, H.paths, shape=(2, 24))


def test_a_padded_batch_forward_is_each_prompt_alone(tiny):
    """A [B, S] padding mask on the path over a call's own tokens: a
    right-padded row's real positions are the row alone."""
    cfg, w, model = tiny
    ids = H.ids((2, 24), 4)
    keep = np.ones((2, 24), 'int32')
    keep[1, 7:] = 0
    got, _ = H.paths(model, ids, attention_mask=jnp.asarray(keep))
    assert np.abs(got[0] - FAM.ref_logits(cfg, w, ids[0])[0]).max() < TOL
    assert np.abs(got[1, :7] - FAM.ref_logits(cfg, w, ids[1, :7])[0]).max() \
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


def test_attended_rows_slices_both_latent_leaves(tiny):
    sliced = H.attended_rows_under_the_half_mask(tiny[2], deepseek_v3)
    assert [out for _, out in sliced] == [((1, 32, 16), (1, 32, 4))] * 3


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


test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(
        FAM, [_no_latent_norm, _scaled_by_the_absorbed_width,
              _rotary_half_of_the_score_dropped,
              _rotary_dims_taken_as_halves, H.bias_in_weight,
              _weights_not_normalised, _no_routing_scale,
              _shared_mlp_weighted, _no_selection_bias, H.bf16_operands],
        # both paths, each against the reference: a departure that lives
        # in one of them alone (the absorbed path's scale, its rotary
        # half) shows there; one dense layer, one expert layer
        H.paths, shape=(2, 24), num_hidden_layers=2)


# ---------------------------------------------------------------------------
# (d) generate: the batch path builds no engine
# ---------------------------------------------------------------------------
test_generate_gives_the_references_greedy_tokens = H.generate_greedy(FAM, 14)


def test_generate_with_left_padded_prompts_is_each_prompt_alone(tiny):
    """Unlike a ring or a state, latent rows hide behind a mask of
    positions: the batch path's padded prompts are served."""
    cfg, w, model = tiny
    ids = H.ids((2, 9), 9)
    keep = np.ones((2, 9), 'int32')
    keep[1, :4] = 0
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                            eos_token_id=-1, attention_mask=keep)
    assert FAM.served_gap(cfg, w, ids[0].tolist(), out.numpy()[0].tolist()) \
        < TOL
    assert FAM.served_gap(cfg, w, ids[1, 4:].tolist(),
                          out.numpy()[1].tolist()) < TOL


# ---------------------------------------------------------------------------
# (e) the pool of the timed size, by shapes; blocks of queries
# ---------------------------------------------------------------------------
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


def test_blocks_of_queries_give_the_unblocked_result(tiny, monkeypatch,
                                                     fresh_dispatch):
    cfg, w, model = tiny
    ids = H.ids((2, 27), 12)             # 27: the last block is short
    ref = FAM.ref_logits(cfg, w, ids)
    monkeypatch.setattr(deepseek_v3, 'PREFILL_QUERY_BLOCK', 8)
    assert np.abs(model(paddle.to_tensor(ids)).numpy() - ref).max() < TOL


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
    H.refused(DeepseekV3Config.tiny, (
        (dict(q_lora_rank=0), 'q_lora_rank'),
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
        (dict(qk_rope_head_dim=3), 'even')))


# ---------------------------------------------------------------------------
# (f) decode attention through the kernel (PR 38): which calls take it
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
