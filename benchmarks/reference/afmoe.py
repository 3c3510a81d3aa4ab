"""Plain reference of the AFMoE block (`arcee-ai/Trinity-Mini`
`config.json`, `model_type: afmoe`; the public modeling code is
`transformers/models/afmoe/modeling_afmoe.py`): a sigmoid router over
sparse SwiGLU experts plus a shared one, sliding-window layers with
rotary positions and full-attention layers with none, q/k RMS-normed per
head, the attention output gated, four norms a layer, the embedding
scaled by sqrt(h).

Written for reading, not for speed: a Python loop over the layers; every
expert computed for every token, in blocks of experts, and combined with
a [T, E] weight that is zero where the router did not select; attention
in blocks of queries so that 4096 positions x 32 heads fit. Canonical
leaves are per layer and have the program's own shapes (experts stacked
[E, h, f]), so no float32 leaf is larger than E x h x f.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

SLIDING = 'sliding_attention'
EXPERT_BLOCK = 16      # experts computed at once: [T, 16, f] float32
QUERY_BLOCK = 512      # queries scored at once: [H, 512, S] float32


def _dims(cfg):
    hd = cfg['head_dim']
    return (cfg['hidden_size'], cfg['num_attention_heads'] * hd,
            cfg['num_key_value_heads'] * hd, hd)


def is_expert_layer(cfg, i):
    return i >= cfg['num_dense_layers']


def param_shapes(cfg):
    h, nq, nkv, hd = _dims(cfg)
    e, f = cfg['num_experts'], cfg['moe_intermediate_size']
    fs = f * cfg['num_shared_experts']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones'),
           'head': ((h, cfg['vocab_size']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        p = f'l{i}.'
        out.update({
            p + 'in_norm': ((h,), 'ones'),
            p + 'q_w': ((h, nq), 'normal'), p + 'k_w': ((h, nkv), 'normal'),
            p + 'v_w': ((h, nkv), 'normal'), p + 'g_w': ((h, nq), 'normal'),
            p + 'o_w': ((nq, h), 'normal'),
            p + 'q_norm': ((hd,), 'ones'), p + 'k_norm': ((hd,), 'ones'),
            p + 'post_attn_norm': ((h,), 'ones'),
            p + 'pre_mlp_norm': ((h,), 'ones'),
            p + 'post_mlp_norm': ((h,), 'ones')})
        if is_expert_layer(cfg, i):
            out.update({
                p + 'router_w': ((h, e), 'normal'),
                # seeded, not zero: selection and weight then differ
                p + 'expert_bias': ((e,), 'normal'),
                p + 'experts_gate': ((e, h, f), 'normal'),
                p + 'experts_up': ((e, h, f), 'normal'),
                p + 'experts_down': ((e, f, h), 'normal'),
                p + 'shared_gate': ((h, fs), 'normal'),
                p + 'shared_up': ((h, fs), 'normal'),
                p + 'shared_down': ((fs, h), 'normal')})
        else:
            ff = cfg['intermediate_size']
            out.update({p + 'mlp_gate': ((h, ff), 'normal'),
                        p + 'mlp_up': ((h, ff), 'normal'),
                        p + 'mlp_down': ((ff, h), 'normal')})
    return out


def attention(ref, q, k, v, window):
    """q [S, H, D], k / v [S, Hkv, D] -> [S, H*D]; key j is visible from
    query i iff 0 <= i - j (< window on a sliding layer); each KV head
    is shared by H / Hkv query heads. Blocks of queries."""
    s, h, d = q.shape
    nkv = k.shape[1]
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, blk, nkv, h // nkv, d)
    j = jnp.arange(s)

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(blk)
        sc = ref.einsum('qkgd,skd->kgqs', qb, k) / jnp.sqrt(jnp.float32(d))
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - j[None, :] < window
        p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
        return ref.einsum('kgqs,skd->qkgd', p, v).reshape(blk, h * d)
    out = jax.lax.map(one, (qg, jnp.arange(qg.shape[0]) * blk))
    return out.reshape(-1, h * d)[:s]


def swiglu(ref, x, gate, up, down):
    return ref.mm(jax.nn.silu(ref.mm(x, gate)) * ref.mm(x, up), down)


def routing(cfg, lp, m):
    """-> [T, E] float32: the weight of every expert for every token,
    zero where the router did not select it."""
    s = jax.nn.sigmoid(jnp.matmul(m, lp['router_w'].astype(jnp.float32),
                                  precision=C.HIGHEST))
    _, sel = jax.lax.top_k(s + lp['expert_bias'].astype(jnp.float32),
                           cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, sel, axis=-1)     # the bias selects only
    if cfg['route_norm']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg['route_scale']
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(w)


def experts(ref, cfg, lp, m):
    """shared(m) + sum_e weight[t, e] * expert_e(m): every expert for
    every token, EXPERT_BLOCK experts at a time."""
    weight = routing(cfg, lp, m)
    e = cfg['num_experts']
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


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    h, _, _, hd = _dims(cfg)
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    nh, nk = cfg['num_attention_heads'], cfg['num_key_value_heads']

    def one(seq):
        s = seq.shape[0]
        x = params['embed'].astype(jnp.float32)[seq]
        if cfg['mup_enabled']:
            x = x * jnp.sqrt(jnp.float32(h))
        for i in range(cfg['num_hidden_layers']):
            lp = {k[len(f'l{i}.'):]: v for k, v in params.items()
                  if k.startswith(f'l{i}.')}
            sliding = cfg['layer_types'][i] == SLIDING
            a = C.rms_norm(x, lp['in_norm'], eps)
            q = C.rms_norm(ref.mm(a, lp['q_w']).reshape(s, nh, hd),
                           lp['q_norm'], eps)
            k = C.rms_norm(ref.mm(a, lp['k_w']).reshape(s, nk, hd),
                           lp['k_norm'], eps)
            v = ref.mm(a, lp['v_w']).reshape(s, nk, hd)
            if sliding:                 # a full layer has no positions
                q, k = C.rope(q[None], theta)[0], C.rope(k[None], theta)[0]
            o = attention(ref, q, k, v,
                          cfg['sliding_window'] if sliding else None)
            o = o * jax.nn.sigmoid(ref.mm(a, lp['g_w']))
            x = x + C.rms_norm(ref.mm(o, lp['o_w']),
                               lp['post_attn_norm'], eps)
            m = C.rms_norm(x, lp['pre_mlp_norm'], eps)
            if is_expert_layer(cfg, i):
                f = experts(ref, cfg, lp, m)
            else:
                f = swiglu(ref, m, lp['mlp_gate'], lp['mlp_up'],
                           lp['mlp_down'])
            x = x + C.rms_norm(f, lp['post_mlp_norm'], eps)
        return C.rms_norm(x, params['norm'], eps)
    return jnp.stack([one(seq) for seq in ids])


def logits_of(cfg, params, hidden, mode='f32'):
    return C.Ref(mode).mm(hidden, params['head'])
