"""`nlp/xing4.py` against its plain float32 reference
(`benchmarks/reference/xing4.py`: four residual streams mixed by
manifold-constrained hyper-connections, latent attention WRITTEN OUT
with a compressed query under YaRN positions, Sinkhorn a plain loop) at
the tiny preset: YaRN stretches 16 original positions 8 times and every
test below runs past them; the hyper-connection leaves are drawn at std
1 (every map far from its start) and, in one case, at a published-like
start (gates 0.01, no bias). The served half is
`tests/test_xing4_serving.py`, the shared cases and helpers
`tests/family_harness.py`'s.

TOL: both sides compute in float32 on the CPU and differ only in the
order of their sums (the flat norm after the product against before it;
the mixes stream by stream against an einsum; the absorbed products; a
cache of rows against a full-sequence forward). Observed at most 4e-5 on
logits as large as 11; every departure from the published mathematics
below moves a logit by more than 0.01, and operands rounded to bfloat16
— what ONE bf16 pass of the MXU would make of the float32 activations
the configuration states — by 0.05 and more. 2e-4 lies between with
room on both sides."""
import math

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import deepseek_v3, llama, xing4
from paddle_tpu.nlp.deepseek_v3 import (DeepseekV3Config,
                                        DeepseekV3DecoderLayer,
                                        DeepseekV3ForCausalLM)
from paddle_tpu.nlp.xing4 import (HyperConnection, Xing4Config,
                                  Xing4DecoderLayer, Xing4ForCausalLM)

import family_harness as H
from family_harness import TOL

ORIGINAL = 16       # positions YaRN stretches from, in the tiny preset


def _is_hc(name, leaf=None):
    return '.hc_' in name and (leaf is None or name.endswith('.' + leaf))


def _draw(R, cfg, seed, start=False):
    """The selection bias at 0.3 changes picks; the two attention norms'
    weights are 1 + what the generator drew. The hyper-connections:
    `Phi` at 0.3 (arguments of deviation 4.8 before the gates); gates
    and biases at std 1 — or, `start`, the gates 0.01 and no bias: a
    published-like start."""
    w = H.draw(R.param_shapes(cfg), seed)
    noisy = {k: (v.shape, 'normal') for k, v in w.items()
             if k.endswith(('.kv_norm', '.q_norm'))
             or (_is_hc(k) and not _is_hc(k, 'phi'))}
    noise = H.draw(noisy, seed + 1, std=1.0)
    out = {}
    for k, v in w.items():
        if k.endswith(('.kv_norm', '.q_norm')):
            v = v + 0.3 * noise[k]
        elif _is_hc(k) and not _is_hc(k, 'phi'):
            if not start:
                v = noise[k]
            elif k.split('.')[-1].startswith('a_'):
                v = jnp.full(v.shape, 0.01, jnp.float32)
        out[k] = v
    return out


# one dense and one expert layer, four sublayers: the suite's time is
# short (every compile here is paid in every run of it)
FAM = H.Family(
    'Xing4ForCausalLM', Xing4Config, ('tiny',),
    cfg_adds=lambda conf: dict(
        moe_layer_freq=1, scoring_func='sigmoid', topk_method='noaux_tc',
        n_group=1, topk_group=1, attention_bias=False,
        tie_word_embeddings=False,
        num_key_value_heads=conf.num_attention_heads),
    draw=_draw, over=dict(num_hidden_layers=2))
AD, R = FAM.adapter, FAM.R


# ---------------------------------------------------------------------------
# (a) the whole forward, both attention paths, past the original positions
# ---------------------------------------------------------------------------
test_full_forward_agrees_with_the_reference_on_both_paths = \
    H.full_forward(FAM, H.paths, shape=(1, 2 * ORIGINAL + 8))


# ---------------------------------------------------------------------------
# (b) the maps: Sinkhorn, the clamp
# ---------------------------------------------------------------------------
def _maps_of(x, **leaves):
    """`connection_maps` on streams x [T, 4, 8] with made-up leaves."""
    n, c = x.shape[-2:]
    rs = np.random.RandomState(3)
    given = dict(
        phi=rs.standard_normal((n * c, 2 * n + n * n)).astype('float32'),
        a_pre=np.ones(1, 'float32'), a_post=np.ones(1, 'float32'),
        a_res=np.ones(1, 'float32'), b_pre=np.zeros(n, 'float32'),
        b_post=np.zeros(n, 'float32'), b_res=np.zeros((n, n), 'float32'))
    given.update(leaves)
    return [np.asarray(m) for m in xing4.connection_maps(
        jnp.asarray(x), *(jnp.asarray(given[k]) for k in (
            'phi', 'a_pre', 'a_post', 'a_res', 'b_pre', 'b_post', 'b_res')),
        iters=20, eps=1e-6, norm_eps=1e-6, lo=-30.0, hi=30.0)]


@pytest.mark.parametrize('std, columns', [(0.09, 1e-5), (0.42, 0.06)],
                         ids=['small_arguments', 'the_benchmarks'])
def test_h_res_after_twenty_rounds(std, columns):
    """Twenty rounds are the published count, not convergence. The LAST
    division is the rows', so every row sums to 1 within 1e-5 whatever
    the arguments (1 - `hc_eps`, in fact). The columns follow within
    1e-5 while the arguments are small (deviation 0.5: `Phi` at 0.09
    against a unit-rms `u` of 32 numbers); at the deviation the
    benchmark's weights give, 2.4, half the tokens' columns are within
    1e-4 and the worst of 256 is off by a few percent — measured, and
    what the reference does too."""
    x = np.random.RandomState(1).standard_normal((256, 4, 8)) \
        .astype('float32')
    phi = (np.random.RandomState(3).standard_normal((32, 24)) * std) \
        .astype('float32')
    h_pre, h_post, h_res = _maps_of(x, phi=phi)
    assert h_res.shape == (256, 4, 4) and (h_res > 0).all()
    assert np.abs(h_res.sum(-1) - 1).max() < 1e-5
    off = np.abs(h_res.sum(-2) - 1).max(-1)
    assert off.max() < columns and np.median(off) < 1e-4
    if std > 0.4:
        assert np.abs(h_res - 0.25).max() > 0.5       # far from uniform
        assert off.max() > 1e-3
    assert ((0 < h_pre) & (h_pre < 1)).all()
    assert ((0 < h_post) & (h_post < 2)).all()
    # one round alone leaves the columns tenths off: the rounds are the
    # constraint
    once = np.asarray(xing4.sinkhorn(jnp.exp(jnp.asarray(
        np.random.RandomState(2).standard_normal((64, 4, 4)) * 3)), 1,
        1e-6))
    assert np.abs(once.sum(-2) - 1).max() > 0.05


def test_the_clamp_holds_at_thirty():
    """A gate of 1000 asks for `exp` of thousands: the clamp keeps every
    argument in [-30, 30], so nothing overflows and the map is still
    doubly stochastic; it equals the map of the clamped arguments."""
    x = np.random.RandomState(4).standard_normal((16, 4, 8)) \
        .astype('float32')
    _, _, wild = _maps_of(x, a_res=np.full(1, 1000.0, 'float32'))
    assert np.isfinite(wild).all()
    assert np.abs(wild.sum(-1) - 1).max() < 1e-5
    _, _, r = _maps_of(x, a_res=np.full(1, 1.0, 'float32'),
                       b_res=np.zeros((4, 4), 'float32'))
    flat = x.reshape(16, -1)
    u = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-6)
    phi = np.random.RandomState(3).standard_normal((32, 24)) \
        .astype('float32')
    arg = np.clip(1000.0 * (u @ phi)[:, 8:].reshape(16, 4, 4), -30, 30)
    assert set(np.unique(np.abs(arg))) == {30.0}
    want = np.asarray(xing4.sinkhorn(jnp.exp(jnp.asarray(arg)), 20, 1e-6))
    assert np.abs(wild - want).max() < 1e-6


# ---------------------------------------------------------------------------
# (c) one stream, maps at one: DeepseekV3DecoderLayer
# ---------------------------------------------------------------------------
def test_one_stream_with_maps_at_one_is_the_deepseek_layer(layer_idx=0):
    """`hc_mult` 1, gates 0, `b_pre` and `b_res` at the clamp, `b_post`
    0: `H_pre` = `H_post` = `H_res` = 1, and the layer is
    `DeepseekV3DecoderLayer` on the same leaves.
    A lone stream's `H_res` is `1 - hc_eps`, Sinkhorn's fixed point
    (0.999999), so the two agree to 1e-6 of the residual a sublayer:
    observed 7e-6 on outputs of 3.7, where a map off by a hundredth
    would read 0.04."""
    conf = Xing4Config.tiny(hc_mult=1)
    paddle.seed(3)
    ours = Xing4DecoderLayer(conf, layer_idx).eval()
    theirs = DeepseekV3DecoderLayer(conf, layer_idx).eval()
    mine = dict(ours.named_parameters())
    for name, p in theirs.named_parameters():
        p._data = mine[name]._data
    for hc in (ours.hc_attn, ours.hc_mlp):
        for leaf, value in (('a_pre', 0.0), ('a_post', 0.0), ('a_res', 0.0),
                            ('b_pre', 30.0), ('b_post', 0.0),
                            ('b_res', 30.0)):
            p = getattr(hc, leaf)
            p._data = jnp.full(p.shape, value, jnp.float32)
    assert set(mine) - set(dict(theirs.named_parameters())) == {
        f'{hc}.{leaf}' for hc in ('hc_attn', 'hc_mlp')
        for leaf in AD._HC}
    x = np.random.RandomState(5).standard_normal((2, 24, 64)) \
        .astype('float32')
    want = theirs(paddle.to_tensor(x)).numpy()
    got = ours(paddle.to_tensor(x[:, :, None])).numpy()
    assert got.shape == (2, 24, 1, 64)
    assert np.abs(got[:, :, 0] - want).max() < 2e-5


# ---------------------------------------------------------------------------
# (d) YaRN and the shared rotary; the uncompressed query
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('theta, dim', [(1e4, 64), (1e6, 128), (1e7, 64),
                                        (100.0, 4)])
def test_yarn_at_factor_one_is_the_plain_table_bit_for_bit(theta, dim):
    """What the five other rotary configurations read is `_rope` as it
    was: the plain table where no frequencies are given, and YaRN's at
    factor 1 equal to it to the last bit."""
    x = np.random.RandomState(0).standard_normal((2, 9, 3, dim)) \
        .astype('float32')
    pos = jnp.arange(9, dtype=jnp.int32) + 12_000
    plain = llama._rope(jnp.asarray(x), pos, theta)
    inv = deepseek_v3.yarn_inv_freq(dim, theta, 1, 4096)
    want = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    assert np.array_equal(np.asarray(inv), np.asarray(want))
    given = llama._rope(jnp.asarray(x), pos, theta, inv_freq=inv)
    assert np.array_equal(np.asarray(plain), np.asarray(given))
    assert deepseek_v3._yarn_mscale(1, 1) == 1.0


def test_yarn_frequencies_at_the_published_sizes_against_the_formula():
    """theta 1e4, 64 rotary dims, factor 64 over 4096: pairs 0-10 keep
    their angle, pairs 23-31 take a 64th, a ramp between; the program's
    table against the reference's (written from the public formula);
    the logits' scale 192^-0.5 x 1.41589^2."""
    conf = Xing4Config()
    rs = conf.rope_scaling
    got = np.asarray(deepseek_v3.yarn_inv_freq(64, conf.rope_theta, **rs))
    cfg = dict(qk_rope_head_dim=64, rope_theta=1e4, rope_scaling=rs,
               qk_nope_head_dim=128)
    want = np.asarray(R.yarn_inv_freq(cfg))
    plain = 1e4 ** (-np.arange(0, 64, 2) / 64)
    assert R.find_correction_range(32, 1, 64, 1e4, 4096) == (10, 23)
    assert np.abs(got / want - 1).max() < 2e-6
    assert np.abs(got[:11] / plain[:11] - 1).max() < 2e-6
    assert np.abs(got[23:] * 64 / plain[23:] - 1).max() < 2e-6
    assert (np.diff(got) < 0).all()
    mscale = 0.1 * math.log(64) + 1
    assert round(mscale, 5) == 1.41589 and round(mscale ** 2, 5) == 2.00474
    assert conf.softmax_gain == pytest.approx(mscale ** 2)
    assert conf.softmax_gain == pytest.approx(R.softmax_gain(cfg))
    assert (conf.q_lora_rank, conf.hc_mult, conf.num_experts,
            conf.num_shared_experts, conf.route_scale) == (768, 4, 64, 1, 2)


def test_without_query_compression_the_leaves_are_kananas():
    """`q_lora_rank` null: `q_proj` and no `q_a_*` (its logits are held
    to PR 37's reference, and its programs to the parent's digests, in
    `tests/test_deepseek_v3.py`); with a rank, the three in its place."""
    def attention_leaves(model):
        return {n.split('.')[-2] for n, _ in model.named_parameters()
                if '.self_attn.' in n}
    names = attention_leaves(DeepseekV3ForCausalLM(
        DeepseekV3Config.tiny(num_hidden_layers=1)))
    assert names == {'q_proj', 'kv_a_proj_with_mqa', 'kv_a_layernorm',
                     'kv_b_proj', 'o_proj'}
    assert attention_leaves(Xing4ForCausalLM(Xing4Config.tiny(
        num_hidden_layers=1))) == (names - {'q_proj'}) | {
            'q_a_proj', 'q_a_layernorm', 'q_b_proj'}


# ---------------------------------------------------------------------------
# (e) departures: each fails the tolerance the sound model passes
# ---------------------------------------------------------------------------
def _no_sinkhorn(model, mp):
    mp.setattr(xing4, 'sinkhorn', lambda m, iters, eps: m)


def _no_mscale_on_the_logits(model, mp):
    cfg = model.config
    cfg.softmax_gain = 1.0
    cfg.softmax_scale = 1.0 / math.sqrt(cfg.qk_head_dim)


def _no_query_norm(model, mp):
    for layer in model.model.layers:
        layer.self_attn.q_a_layernorm.forward = lambda x: x


def _plain_positions(model, mp):
    model.config.rope_scaling = None


def _res_map_transposed(model, mp):
    real = xing4.connection_maps
    def transposed(*a, **k):
        h_pre, h_post, h_res = real(*a, **k)
        return h_pre, h_post, jnp.swapaxes(h_res, -1, -2)
    mp.setattr(xing4, 'connection_maps', transposed)


def _bf16_maps(model, mp):
    """The residual path in bfloat16: the streams rounded as a
    sublayer reads them."""
    real = xing4.connection_maps
    mp.setattr(xing4, 'connection_maps', lambda x, *a, **k: real(
        x.astype(jnp.bfloat16).astype(jnp.float32), *a, **k))


def _held(model, ids):
    return H.paths(model, ids, own=False)


# One layer (two sublayers, attention and MLP), 40 positions (the YaRN
# of the preset stretches 16), the absorbed path. The first three are
# the three faulty programs the chip's limit has to refuse; the last two
# are the precision test: computing in bfloat16 what the configuration
# states in float32 fails TOL by more than an order of magnitude.
test_each_departure_fails_the_tolerance_the_sound_model_passes = \
    H.each_departure(
        FAM, [_no_sinkhorn, _no_mscale_on_the_logits, _no_query_norm,
              _plain_positions, _res_map_transposed, H.bf16_operands,
              _bf16_maps], _held, shape=(1, 40), num_hidden_layers=1)


def test_a_published_like_start_agrees_too_and_its_maps_are_constants():
    """Gates 0.01 and no bias: the maps are within a few hundredths of
    1/2, 1 and 1/4 for every token, which is why the benchmark's
    weights do NOT start there (a dropped Sinkhorn would not show)."""
    cfg = FAM.cfg(num_hidden_layers=1)
    w = FAM.weights(cfg, seed=9, start=True)
    ids = H.ids((1, 24), 6)
    model = FAM.model(cfg, w)
    assert np.abs(_held(model, ids)[0]
                  - FAM.ref_logits(cfg, w, ids)).max() < TOL
    hc = model.model.layers[0].hc_mlp
    x = paddle.to_tensor(np.random.RandomState(0).standard_normal(
        (1, 5, 4, 64)).astype('float32'))
    _, (h_post, h_res) = hc.enter(x)
    assert np.abs(h_post.numpy() - 1.0).max() < 0.1
    assert np.abs(h_res.numpy() - 0.25).max() < 0.05


# ---------------------------------------------------------------------------
# (f) presets and refusals
# ---------------------------------------------------------------------------
def test_config_presets_and_refusals():
    conf = Xing4Config()        # the defaults are the published file's
    assert (conf.hidden_size, conf.num_hidden_layers, conf.vocab_size,
            conf.first_k_dense_replace) == (3584, 40, 131072, 2)
    assert (conf.hc_mult, conf.hc_sinkhorn_iters, conf.hc_eps,
            conf.mhc_h_res_clamp_min, conf.mhc_h_res_clamp_max) \
        == (4, 20, 1e-6, -30.0, 30.0)
    tiny = Xing4Config.tiny()
    assert (tiny.hidden_size, tiny.q_lora_rank, tiny.hc_mult) == (64, 12, 4)
    assert tiny.rope_scaling['original_max_position_embeddings'] == ORIGINAL
    hc = HyperConnection(tiny)
    assert tuple(hc.phi.shape) == (256, 24)
    assert [tuple(getattr(hc, k).shape) for k in AD._HC[1:]] \
        == [(1,), (1,), (1,), (4,), (4,), (4, 4)]
    assert Xing4ForCausalLM(Xing4Config.tiny(
        num_hidden_layers=1)).residual_streams == 4
    H.refused(Xing4Config.tiny, (
        (dict(hc_mult=0), 'hc_mult'),
        (dict(rope_scaling={'type': 'dynamic', 'factor': 2}),
         "rope_scaling type 'dynamic'"),
        (dict(n_group=8), 'n_group')))
