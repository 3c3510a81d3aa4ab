"""Plain reference of the Xing4.0 block as Xing4.0-29B-A4B publishes it
(`XingChen-AGI/Xing4.0-29B-A4B` `config.json`, `model_type: xing4_0`):
a residual path of `hc_mult` streams mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880) around latent attention with a
COMPRESSED query under YaRN positions, and around a dense SwiGLU (the
first `first_k_dense_replace` layers) or a sigmoid router over sparse
SwiGLU experts with a selection bias plus one shared expert.

A token's state between blocks is `x [n, C]` (n streams of the hidden
size). Around a block `F` with its own RMSNorm in front:

    u       = vec(x) / rms(vec(x))                      [nC], no weight
    [p|q|R] = u Phi,  Phi [nC, n + n + n*n]
    H_pre   = sigmoid(a_pre  * p + b_pre)               [n]
    H_post  = 2 * sigmoid(a_post * q + b_post)          [n]
    H_res   = SK(exp(clip(a_res * R + b_res, lo, hi)))  [n, n], row-major
    y       = F(RMSNorm(H_pre . x))                     [C]
    x'      = H_res x + H_post (outer) y                [n, C]

`SK`: `hc_sinkhorn_iters` times, every column over (its sum + `hc_eps`),
then every row over (its sum + `hc_eps`). `x_0` is the embedding copied
to the n streams; the logits are `RMSNorm(sum of the streams) W_head`.

Attention is the DeepSeek-V3 one with `q = RMSNorm(a W_qa) W_qb`; YaRN
is the public DeepSeek-V3 rule, written out in `yarn_inv_freq` from the
formula: `find_correction_range(beta_fast, beta_slow, dim, theta,
original)` gives the pairs between which the angle goes from `f =
theta^(-2i/dim)` to `f / factor` along a linear ramp; cos and sin times
`mscale(factor, mscale) / mscale(factor, mscale_all_dim)`; the logits
times `qk_head_dim^-0.5 * mscale(factor, mscale_all_dim)^2`, `mscale(s,
a) = 0.1 a ln s + 1` (causal attention by blocks of queries is
`reference/deepseek_v3.py`'s, which scores over `sqrt(qk)`: the
`mscale^2` is put on the queries).

Written for reading, not for speed: a full-sequence forward with NO
cache and NO absorbed product (K and V by head for every position),
Sinkhorn a plain loop, every expert for every token in blocks of
experts (`reference/deepseek_v3.py`'s, which this model's expert layer
is), attention in blocks of queries so that 12,288 positions fit.

What `config.json` alone does not fix is listed in the configuration
file's `assumed`: where `hc_eps` goes and the order of Sinkhorn's two
divisions, the flat norm's eps (`rms_norm_eps`) and absent weight, how
the streams start and end, `rope_interleave` true. `param_shapes` gives
`Phi` normal, the three `a` ONES and the three `b` zeros: with `u` of
unit rms over nC numbers and `Phi` at 0.02 the maps' arguments have a
deviation of `0.02 sqrt(nC)` (2.4 at the published widths), so the maps
differ by token and by seed and a wrong one shows in the logits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C
from .afmoe import swiglu
from .deepseek_v3 import attention, experts, is_expert_layer


def hc_shapes(cfg, prefix):
    n, c = cfg['hc_mult'], cfg['hidden_size']
    return {prefix + 'phi': ((n * c, 2 * n + n * n), 'normal'),
            prefix + 'a_pre': ((1,), 'ones'),
            prefix + 'a_post': ((1,), 'ones'),
            prefix + 'a_res': ((1,), 'ones'),
            prefix + 'b_pre': ((n,), 'zeros'),
            prefix + 'b_post': ((n,), 'zeros'),
            prefix + 'b_res': ((n, n), 'zeros')}


def param_shapes(cfg):
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    ql, lat = cfg['q_lora_rank'], cfg['kv_lora_rank']
    nope, rd, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                    cfg['v_head_dim'])
    e, f = cfg['n_routed_experts'], cfg['moe_intermediate_size']
    shared = f * cfg['n_shared_experts']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones'),
           'head': ((h, cfg['vocab_size']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        p = f'l{i}.'
        out.update({
            p + 'in_norm': ((h,), 'ones'), p + 'post_norm': ((h,), 'ones'),
            p + 'qa_w': ((h, ql), 'normal'), p + 'q_norm': ((ql,), 'ones'),
            p + 'qb_w': ((ql, nh * (nope + rd)), 'normal'),
            p + 'kva_w': ((h, lat + rd), 'normal'),
            p + 'kv_norm': ((lat,), 'ones'),
            p + 'kvb_w': ((lat, nh * (nope + vd)), 'normal'),
            p + 'o_w': ((nh * vd, h), 'normal')})
        out.update(hc_shapes(cfg, p + 'hc_attn.'))
        out.update(hc_shapes(cfg, p + 'hc_mlp.'))
        if is_expert_layer(cfg, i):
            out.update({
                p + 'router_w': ((h, e), 'normal'),
                # seeded, not zero: selection and weight then differ
                p + 'expert_bias': ((e,), 'normal'),
                p + 'experts_gate': ((e, h, f), 'normal'),
                p + 'experts_up': ((e, h, f), 'normal'),
                p + 'experts_down': ((e, f, h), 'normal'),
                p + 'shared_gate': ((h, shared), 'normal'),
                p + 'shared_up': ((h, shared), 'normal'),
                p + 'shared_down': ((shared, h), 'normal')})
        else:
            ff = cfg['intermediate_size']
            out.update({p + 'mlp_gate': ((h, ff), 'normal'),
                        p + 'mlp_up': ((h, ff), 'normal'),
                        p + 'mlp_down': ((ff, h), 'normal')})
    return out


# ---------------------------------------------------------------------------
# YaRN, from the formula
# ---------------------------------------------------------------------------
def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def find_correction_dim(rotations, dim, base, original):
    """The rotary pair that turns `rotations` times over `original`
    positions."""
    return dim * math.log(original / (rotations * 2 * math.pi)) \
        / (2 * math.log(base))


def find_correction_range(low_rot, high_rot, dim, base, original):
    low = math.floor(find_correction_dim(low_rot, dim, base, original))
    high = math.ceil(find_correction_dim(high_rot, dim, base, original))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(cfg):
    """[rope/2] float32: pair i turns `inv[i]` radians a position."""
    dim, base = cfg['qk_rope_head_dim'], float(cfg['rope_theta'])
    rs = cfg['rope_scaling']
    extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    inter = extra / rs['factor']
    low, high = find_correction_range(
        rs['beta_fast'], rs['beta_slow'], dim, base,
        rs['original_max_position_embeddings'])
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp              # 1: the pair keeps its own angle
    return inter * (1.0 - mask) + extra * mask


def rotary(cfg, x):
    """x [S, H, rope]: positions 0..S-1 under YaRN; stored as interleaved
    pairs where `rope_interleave`, brought to halves first."""
    if cfg['rope_interleave']:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    rs = cfg['rope_scaling']
    gain = yarn_mscale(rs['factor'], rs['mscale']) \
        / yarn_mscale(rs['factor'], rs['mscale_all_dim'])
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang)[:, None] * gain, jnp.sin(ang)[:, None] * gain
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_gain(cfg):
    """What the logits carry beside `qk_head_dim^-0.5`: mscale^2."""
    rs = cfg['rope_scaling']
    return yarn_mscale(rs['factor'], rs['mscale_all_dim']) ** 2


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def self_attention(ref, cfg, lp, a):
    """a [S, h] (normed) -> [S, h]: latent attention written out, the
    query through its own compression, K and V by head."""
    s = a.shape[0]
    nh, lat = cfg['num_attention_heads'], cfg['kv_lora_rank']
    nope, rd, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                    cfg['v_head_dim'])
    eps = cfg['rms_norm_eps']
    qc = C.rms_norm(ref.mm(a, lp['qa_w']), lp['q_norm'], eps)
    q = ref.mm(qc, lp['qb_w']).reshape(s, nh, nope + rd)
    kva = ref.mm(a, lp['kva_w'])
    c = C.rms_norm(kva[:, :lat], lp['kv_norm'], eps)
    r = rotary(cfg, kva[:, None, lat:])                   # [S, 1, rope]
    kv = ref.mm(c, lp['kvb_w']).reshape(s, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], rotary(cfg, q[..., nope:])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(r, (s, nh, rd))], -1)
    # `attention` scores over sqrt(qk): the gain goes on the queries
    return ref.mm(attention(ref, q * softmax_gain(cfg), k, kv[..., nope:]),
                  lp['o_w'])


# ---------------------------------------------------------------------------
# the residual path
# ---------------------------------------------------------------------------
def sinkhorn(cfg, m):
    """m [S, n, n] positive -> rows and columns (nearly) summing to 1."""
    for _ in range(cfg['hc_sinkhorn_iters']):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg['hc_eps'])
        m = m / (jnp.sum(m, axis=2, keepdims=True) + cfg['hc_eps'])
    return m


def hc_maps(ref, cfg, hp, x):
    """x [S, n, C] -> H_pre [S, n], H_post [S, n], H_res [S, n, n]. The
    one product goes through `ref.mm`, so a control rounds it too; the
    rest is float32."""
    n = cfg['hc_mult']
    f32 = lambda t: t.astype(jnp.float32)     # noqa: E731
    flat = x.reshape(x.shape[0], -1)
    u = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                             + cfg['rms_norm_eps'])
    z = ref.mm(u, hp['phi'])
    h_pre = jax.nn.sigmoid(f32(hp['a_pre']) * z[:, :n] + f32(hp['b_pre']))
    h_post = 2.0 * jax.nn.sigmoid(f32(hp['a_post']) * z[:, n:2 * n]
                                  + f32(hp['b_post']))
    arg = f32(hp['a_res']) * z[:, 2 * n:].reshape(-1, n, n) \
        + f32(hp['b_res'])
    h_res = sinkhorn(cfg, jnp.exp(jnp.clip(
        arg, cfg['mhc_h_res_clamp_min'], cfg['mhc_h_res_clamp_max'])))
    return h_pre, h_post, h_res


def hyper_connected(ref, cfg, hp, x, block):
    """One sublayer: x [S, n, C] -> x' [S, n, C] around `block` ([S, C]
    -> [S, C], its own norm inside)."""
    h_pre, h_post, h_res = hc_maps(ref, cfg, hp, x)
    y = block(jnp.einsum('sn,snc->sc', h_pre, x, precision=C.HIGHEST))
    return jnp.einsum('sij,sjc->sic', h_res, x, precision=C.HIGHEST) \
        + h_post[:, :, None] * y[:, None, :]


def _sub(tree, prefix):
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    eps, n = cfg['rms_norm_eps'], cfg['hc_mult']

    def one(seq):
        e = params['embed'].astype(jnp.float32)[seq]
        x = jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))
        for i in range(cfg['num_hidden_layers']):
            lp = _sub(params, f'l{i}.')

            def attn(h, lp=lp):
                return self_attention(ref, cfg, lp,
                                      C.rms_norm(h, lp['in_norm'], eps))

            def mlp(h, lp=lp, i=i):
                m = C.rms_norm(h, lp['post_norm'], eps)
                if is_expert_layer(cfg, i):
                    return experts(ref, cfg, lp, m)
                return swiglu(ref, m, lp['mlp_gate'], lp['mlp_up'],
                              lp['mlp_down'])
            x = hyper_connected(ref, cfg, _sub(lp, 'hc_attn.'), x, attn)
            x = hyper_connected(ref, cfg, _sub(lp, 'hc_mlp.'), x, mlp)
        return C.rms_norm(jnp.sum(x, axis=1), params['norm'], eps)
    return jnp.stack([one(seq) for seq in ids])


def logits_of(cfg, params, hidden, mode='f32'):
    return C.Ref(mode).mm(hidden, params['head'])
