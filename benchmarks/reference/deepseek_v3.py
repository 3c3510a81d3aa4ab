"""Plain reference of the DeepSeek-V3-style block as Kanana-2-30B-A3B
publishes it (`kakaocorp/kanana-2-30b-a3b-instruct-2601` `config.json`,
`model_type: deepseek_v3`): multi-head latent attention WRITTEN OUT —
`q = a W_q` a head `[q_nope ; q_rope]`; `[c' ; r'] = a W_kva`, one of
each a token; `c = RMSNorm_kv(c')`; `[k_nope_h ; v_h] = c W_kvb`; rotary
positions on `q_rope` and `r'`; `k_h = [k_nope_h ; r]`; logits over
`sqrt(qk_nope_head_dim + qk_rope_head_dim)`; two RMSNorms a layer; a
dense SwiGLU on the first `first_k_dense_replace` layers, else a sigmoid
router over sparse SwiGLU experts with a selection bias, plus the
shared experts as one MLP; the head untied.

Written for reading, not for speed: a full-sequence forward with NO
cache and NO absorbed product — K and V are made by head for every
position, which is what a latent cache exists to avoid; every expert is
computed for every token, in blocks of experts, and combined with the
routing weight, which is zero where the router did not select.
Attention in blocks of queries so that 16,384 positions x 32 heads fit.

What `config.json` alone does not show, taken from the public
`deepseek_v3` modeling code and listed in the configuration file's
`assumed` (a dagger in ISSUE 37): `rope_scaling` null means no mscale on
the softmax scale, which is `qk_head_dim ** -0.5`; `rope_interleave`
true means the rotary dims are stored as interleaved pairs and
de-interleaved (`[x0, x2, .., x1, x3, ..]`) before a rotate-half rotary,
on `q_rope` and `r'` alike — done here as the public code does it; the
1e-20 beside the routing weights' sum; the `n_shared_experts` shared
experts are ONE MLP of `n_shared_experts * moe_intermediate_size`,
unweighted; `kv_a_layernorm` is an RMSNorm with `rms_norm_eps`;
`n_group` = `topk_group` = 1 limits no group.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C
from .afmoe import swiglu

EXPERT_BLOCK = 4       # experts computed at once: [4, T, 2048] float32
QUERY_BLOCK = 512      # queries scored at once: [32, 512, S] float32


def is_expert_layer(cfg, i):
    return i >= cfg['first_k_dense_replace'] \
        and i % cfg['moe_layer_freq'] == 0


def param_shapes(cfg):
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    lat, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    rd, vd = cfg['qk_rope_head_dim'], cfg['v_head_dim']
    e, f = cfg['n_routed_experts'], cfg['moe_intermediate_size']
    shared = f * cfg['n_shared_experts']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones'),
           'head': ((h, cfg['vocab_size']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        p = f'l{i}.'
        out.update({
            p + 'in_norm': ((h,), 'ones'), p + 'post_norm': ((h,), 'ones'),
            p + 'q_w': ((h, nh * (nope + rd)), 'normal'),
            p + 'kva_w': ((h, lat + rd), 'normal'),
            p + 'kv_norm': ((lat,), 'ones'),
            p + 'kvb_w': ((lat, nh * (nope + vd)), 'normal'),
            p + 'o_w': ((nh * vd, h), 'normal')})
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


def rotary(cfg, x):
    """x [S, H, rope]: positions 0..S-1; stored as interleaved pairs
    where `rope_interleave`, brought to halves first."""
    if cfg['rope_interleave']:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return C.rope(x[None], cfg['rope_theta'])[0]


def attention(ref, q, k, v):
    """q, k [S, H, D], v [S, H, Dv] -> [S, H*Dv]; key j is visible from
    query i iff j <= i; logits over sqrt(D). Blocks of queries."""
    s, h, d = q.shape
    dv = v.shape[-1]
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, h, d)
    j = jnp.arange(s)

    def one(args):
        qi, i0 = args
        i = i0 + jnp.arange(blk)
        sc = ref.einsum('qhd,shd->hqs', qi, k) / jnp.sqrt(jnp.float32(d))
        sc = jnp.where(j[None, :] <= i[:, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return ref.einsum('hqs,shd->qhd', p, v).reshape(blk, h * dv)
    out = jax.lax.map(one, (qb, jnp.arange(qb.shape[0]) * blk))
    return out.reshape(-1, h * dv)[:s]


def routing(cfg, lp, m):
    """-> [T, E] float32: the weight of every expert for every token,
    zero where the router did not select it."""
    s = jax.nn.sigmoid(jnp.matmul(m, lp['router_w'].astype(jnp.float32),
                                  precision=C.HIGHEST))
    _, sel = jax.lax.top_k(s + lp['expert_bias'].astype(jnp.float32),
                           cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, sel, axis=-1)     # the bias selects only
    if cfg['norm_topk_prob']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg['routed_scaling_factor']
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(w)


def experts(ref, cfg, lp, m):
    """shared(m) + sum_e weight[t, e] * expert_e(m): every expert for
    every token, EXPERT_BLOCK experts at a time."""
    weight = routing(cfg, lp, m)
    e = cfg['n_routed_experts']
    blk = min(EXPERT_BLOCK, e)
    assert e % blk == 0

    def some(acc, args):
        gate, up, down, w = args        # [blk, h, f] x2, [blk, f, h], [blk, T]
        a = jax.nn.silu(ref.einsum('th,ehf->etf', m, gate)) \
            * ref.einsum('th,ehf->etf', m, up)
        y = ref.einsum('etf,efh->eth', a, down)
        return acc + jnp.sum(y * w[:, :, None], axis=0), None
    cut = lambda x: x.reshape(e // blk, blk, *x.shape[1:])
    routed, _ = jax.lax.scan(
        some, jnp.zeros_like(m),
        (cut(lp['experts_gate']), cut(lp['experts_up']),
         cut(lp['experts_down']), cut(weight.T)))
    return routed + swiglu(ref, m, lp['shared_gate'], lp['shared_up'],
                           lp['shared_down'])


def self_attention(ref, cfg, lp, a):
    """a [S, h] (normed) -> [S, h]: latent attention written out, K and
    V by head for every position."""
    s = a.shape[0]
    nh, lat = cfg['num_attention_heads'], cfg['kv_lora_rank']
    nope, rd, vd = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                    cfg['v_head_dim'])
    q = ref.mm(a, lp['q_w']).reshape(s, nh, nope + rd)
    kva = ref.mm(a, lp['kva_w'])
    c = C.rms_norm(kva[:, :lat], lp['kv_norm'], cfg['rms_norm_eps'])
    r = rotary(cfg, kva[:, None, lat:])                   # [S, 1, rope]
    kv = ref.mm(c, lp['kvb_w']).reshape(s, nh, nope + vd)
    q = jnp.concatenate([q[..., :nope], rotary(cfg, q[..., nope:])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(r, (s, nh, rd))], -1)
    return ref.mm(attention(ref, q, k, kv[..., nope:]), lp['o_w'])


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    eps = cfg['rms_norm_eps']

    def one(seq):
        x = params['embed'].astype(jnp.float32)[seq]
        for i in range(cfg['num_hidden_layers']):
            lp = {k[len(f'l{i}.'):]: v for k, v in params.items()
                  if k.startswith(f'l{i}.')}
            x = x + self_attention(ref, cfg, lp,
                                   C.rms_norm(x, lp['in_norm'], eps))
            m = C.rms_norm(x, lp['post_norm'], eps)
            if is_expert_layer(cfg, i):
                x = x + experts(ref, cfg, lp, m)
            else:
                x = x + swiglu(ref, m, lp['mlp_gate'], lp['mlp_up'],
                               lp['mlp_down'])
        return C.rms_norm(x, params['norm'], eps)
    return jnp.stack([one(seq) for seq in ids])


def logits_of(cfg, params, hidden, mode='f32'):
    return C.Ref(mode).mm(hidden, params['head'])
