"""Plain reference of the MiMo-V2 block (`XiaomiMiMo/MiMo-V2.5`
`config.json`, `model_type: mimo_v2`, text path): window and full
attention layers mixed as `hybrid_layer_pattern` says (1 = window), with
KV head counts of their own (4 full, 8 window), K 192 and V 128 wide,
rotary positions on the first `int(192 * 0.334)` = 64 dims of a head
with a theta per kind, V scaled by `attention_value_scale`, a learned
sink on the layers whose `add_*_attention_sink_bias` says so; two
RMSNorms a layer; a dense SwiGLU where `moe_layer_freq` is 0, else a
sigmoid router over sparse SwiGLU experts with a selection bias and no
shared expert; the head untied.

Written for reading, not for speed: a full-sequence forward with no
cache and NO RING — a window layer is a banded mask over the whole
sequence; the sink is a column appended to the scores and dropped after
the softmax; every HELD expert is computed for every token, in blocks
of experts, and combined with the routing weight over ALL of the
router's experts (`expert_share.routed`), which is zero where the router
did not select — a pick that is not held (`n_routed_experts` experts
from `expert_share.first` on are) is simply absent, as it is in the
program: the partial sum goes on to the next layer. Attention in blocks
of queries so that 4096 positions x 64 heads fit.

What `config.json` alone does not show, taken from the public modeling
code and listed in the configuration file's `assumed` (a dagger in
ISSUE 32): no embedding scale; two norms a layer and no QK norm; the
value scale applied to V; rotate-half within the rotated dims, the
others passing unrotated; logits over sqrt(192); the window counts the
query's own position (`0 <= i - j < sliding_window`);
`attention_chunk_size` is an implementation switch, not mathematics;
the 1e-20 beside the routing weights' sum; `routed_scaling_factor` null
is 1. `attention_projection_layout: fused_qkv` is storage: three leaves
here. The multi-token-prediction layers and the vision and audio towers
are left out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C
from .afmoe import swiglu

WINDOW = 1
EXPERT_BLOCK = 4       # experts computed at once: [4, T, 2048] float32
QUERY_BLOCK = 512      # queries scored at once: [64, 512, S + 1] float32


def kv_heads(cfg, i):
    return cfg['swa_num_key_value_heads'] \
        if cfg['hybrid_layer_pattern'][i] == WINDOW \
        else cfg['num_key_value_heads']


def has_sink(cfg, i):
    return bool(cfg['add_swa_attention_sink_bias']
                if cfg['hybrid_layer_pattern'][i] == WINDOW
                else cfg['add_full_attention_sink_bias'])


def is_expert_layer(cfg, i):
    return bool(cfg['moe_layer_freq'][i])


def param_shapes(cfg):
    h, nh = cfg['hidden_size'], cfg['num_attention_heads']
    hd, vd = cfg['head_dim'], cfg['v_head_dim']
    held, f = cfg['n_routed_experts'], cfg['moe_intermediate_size']
    routed = cfg['expert_share']['routed']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones'),
           'head': ((h, cfg['vocab_size']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        p, nkv = f'l{i}.', kv_heads(cfg, i)
        out.update({
            p + 'in_norm': ((h,), 'ones'), p + 'post_norm': ((h,), 'ones'),
            p + 'q_w': ((h, nh * hd), 'normal'),
            p + 'k_w': ((h, nkv * hd), 'normal'),
            p + 'v_w': ((h, nkv * vd), 'normal'),
            p + 'o_w': ((nh * vd, h), 'normal')})
        if has_sink(cfg, i):
            # seeded, not zero (at the generator's one deviation, 0.02:
            # the configuration file's `assumed.initializer`)
            out[p + 'sink'] = ((nh,), 'normal')
        if is_expert_layer(cfg, i):
            out.update({
                p + 'router_w': ((h, routed), 'normal'),
                # seeded, not zero: selection and weight then differ
                p + 'expert_bias': ((routed,), 'normal'),
                p + 'experts_gate': ((held, h, f), 'normal'),
                p + 'experts_up': ((held, h, f), 'normal'),
                p + 'experts_down': ((held, f, h), 'normal')})
        else:
            ff = cfg['intermediate_size']
            out.update({p + 'mlp_gate': ((h, ff), 'normal'),
                        p + 'mlp_up': ((h, ff), 'normal'),
                        p + 'mlp_down': ((ff, h), 'normal')})
    return out


def partial_rope(x, theta, rotary_dim):
    """x [S, H, D]: rotate-half over positions 0..S-1 within the first
    `rotary_dim` dims of a head; the others pass."""
    rot = C.rope(x[None, ..., :rotary_dim], theta)[0]
    return jnp.concatenate([rot, x[..., rotary_dim:]], axis=-1)


def attention(ref, q, k, v, window, sink):
    """q [S, H, D], k [S, Hkv, D], v [S, Hkv, Dv] -> [S, H*Dv]; key j is
    visible from query i iff 0 <= i - j (< window on a window layer);
    each KV head is shared by H / Hkv query heads; `sink` None or [H]:
    a column of scores that takes its share of the softmax and is then
    dropped. Blocks of queries."""
    s, h, d = q.shape
    nkv, dv = k.shape[1], v.shape[-1]
    g = h // nkv
    blk = min(QUERY_BLOCK, s)
    pad = -s % blk
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, blk, nkv, g, d)
    j = jnp.arange(s)

    def one(args):
        qb, i0 = args
        i = i0 + jnp.arange(blk)
        sc = ref.einsum('qkgd,skd->kgqs', qb, k) / jnp.sqrt(jnp.float32(d))
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - j[None, :] < window
        sc = jnp.where(seen, sc, -1e30)
        if sink is not None:
            col = jnp.broadcast_to(
                sink.astype(jnp.float32).reshape(nkv, g, 1, 1),
                (nkv, g, blk, 1))
            sc = jnp.concatenate([sc, col], axis=-1)
        p = jax.nn.softmax(sc, axis=-1)[..., :s]
        return ref.einsum('kgqs,skd->qkgd', p, v).reshape(blk, h * dv)
    out = jax.lax.map(one, (qg, jnp.arange(qg.shape[0]) * blk))
    return out.reshape(-1, h * dv)[:s]


def routing(cfg, lp, m):
    """-> [T, routed] float32: the weight of every expert the router
    scores for every token, zero where it did not select it."""
    s = jax.nn.sigmoid(jnp.matmul(m, lp['router_w'].astype(jnp.float32),
                                  precision=C.HIGHEST))
    _, sel = jax.lax.top_k(s + lp['expert_bias'].astype(jnp.float32),
                           cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, sel, axis=-1)     # the bias selects only
    if cfg['norm_topk_prob']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    scale = cfg['routed_scaling_factor']
    w = w * (1.0 if scale is None else scale)
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(w)


def experts(ref, cfg, lp, m):
    """sum over the HELD experts e of weight[t, e] * expert_e(m): every
    held expert for every token, EXPERT_BLOCK at a time; the weight is
    the router's over all it scores, so a token whose picks lie
    elsewhere gets nothing here."""
    held, first = cfg['n_routed_experts'], cfg['expert_share']['first']
    weight = routing(cfg, lp, m)[:, first:first + held]
    blk = min(EXPERT_BLOCK, held)
    assert held % blk == 0

    def some(acc, args):
        gate, up, down, w = args        # [blk, h, f] x2, [blk, f, h], [blk, T]
        a = jax.nn.silu(ref.einsum('th,ehf->etf', m, gate)) \
            * ref.einsum('th,ehf->etf', m, up)
        y = ref.einsum('etf,efh->eth', a, down)
        return acc + jnp.sum(y * w[:, :, None], axis=0), None
    cut = lambda x: x.reshape(held // blk, blk, *x.shape[1:])
    routed, _ = jax.lax.scan(
        some, jnp.zeros_like(m),
        (cut(lp['experts_gate']), cut(lp['experts_up']),
         cut(lp['experts_down']), cut(weight.T)))
    return routed


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    eps = cfg['layernorm_epsilon']
    nh, hd, vd = cfg['num_attention_heads'], cfg['head_dim'], \
        cfg['v_head_dim']
    rotary_dim = int(hd * cfg['partial_rotary_factor'])

    def one(seq):
        s = seq.shape[0]
        x = params['embed'].astype(jnp.float32)[seq]
        for i in range(cfg['num_hidden_layers']):
            lp = {k[len(f'l{i}.'):]: v for k, v in params.items()
                  if k.startswith(f'l{i}.')}
            window = cfg['hybrid_layer_pattern'][i] == WINDOW
            nk = kv_heads(cfg, i)
            theta = cfg['swa_rope_theta'] if window else cfg['rope_theta']
            a = C.rms_norm(x, lp['in_norm'], eps)
            q = partial_rope(ref.mm(a, lp['q_w']).reshape(s, nh, hd),
                             theta, rotary_dim)
            k = partial_rope(ref.mm(a, lp['k_w']).reshape(s, nk, hd),
                             theta, rotary_dim)
            v = ref.mm(a, lp['v_w']).reshape(s, nk, vd) \
                * cfg['attention_value_scale']
            o = attention(ref, q, k, v,
                          cfg['sliding_window'] if window else None,
                          lp.get('sink'))
            x = x + ref.mm(o, lp['o_w'])
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
