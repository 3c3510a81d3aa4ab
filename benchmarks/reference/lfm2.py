"""Plain reference of the LFM2-MoE block (`LiquidAI/LFM2-24B-A2B`
`config.json`, `model_type: lfm2_moe`; the public modeling code is
`transformers/models/lfm2_moe/modeling_lfm2_moe.py`): gated short
convolutions and grouped-query attention layers mixed as `layer_types`
says, two RMSNorms a layer, a sigmoid router over sparse SwiGLU experts
with no shared one, the head tied to the embedding.

Written for reading, not for speed: a full-sequence forward with no
cache and NO STATE — the convolution is a sum over `conv_L_cache`
shifted copies of its whole input, zeros before the sequence; every
expert computed for every token, in blocks of experts, and combined with
a [T, E] weight that is zero where the router did not select; attention
in blocks of queries (`reference/afmoe.py`'s, which this shares with
`common.py`) so that 4096 positions x 32 heads fit.

What `config.json` alone does not show, taken from the public code and
listed in the configuration file's `assumed`: the order of the in
projection's three parts [B | C | z]; no activation on the convolution;
q and k RMS-normed per head; two norms a layer; the 1e-6 beside the
routing weights' sum; the final norm (`embedding_norm` there); the tied
head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C
from .afmoe import EXPERT_BLOCK, attention, swiglu

CONV = 'conv'


def _dims(cfg):
    h = cfg['hidden_size']
    hd = h // cfg['num_attention_heads']
    return h, cfg['num_attention_heads'] * hd, \
        cfg['num_key_value_heads'] * hd, hd


def is_expert_layer(cfg, i):
    return i >= cfg['num_dense_layers']


def param_shapes(cfg):
    h, nq, nkv, hd = _dims(cfg)
    e, f = cfg['num_experts'], cfg['moe_intermediate_size']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones')}
    for i, kind in enumerate(cfg['layer_types']):
        p = f'l{i}.'
        out.update({p + 'op_norm': ((h,), 'ones'),
                    p + 'ffn_norm': ((h,), 'ones')})
        if kind == CONV:
            out.update({
                p + 'in_w': ((h, 3 * h), 'normal'),
                # ones, a moving sum: `make_weights` has one deviation
                # for every normal leaf, and at 0.02 the operator would be
                # a thirtieth of the residual stream — a wrong state
                # would not show (the file's `assumed.initializer`)
                p + 'conv_w': ((h, cfg['conv_L_cache']), 'ones'),
                p + 'out_w': ((h, h), 'normal')})
        else:
            out.update({
                p + 'q_w': ((h, nq), 'normal'),
                p + 'k_w': ((h, nkv), 'normal'),
                p + 'v_w': ((h, nkv), 'normal'),
                p + 'o_w': ((nq, h), 'normal'),
                p + 'q_norm': ((hd,), 'ones'),
                p + 'k_norm': ((hd,), 'ones')})
        if is_expert_layer(cfg, i):
            out.update({
                p + 'router_w': ((h, e), 'normal'),
                # seeded, not zero: selection and weight then differ
                p + 'expert_bias': ((e,), 'normal'),
                p + 'experts_gate': ((e, h, f), 'normal'),
                p + 'experts_up': ((e, h, f), 'normal'),
                p + 'experts_down': ((e, f, h), 'normal')})
        else:
            ff = cfg['intermediate_size']
            out.update({p + 'mlp_gate': ((h, ff), 'normal'),
                        p + 'mlp_up': ((h, ff), 'normal'),
                        p + 'mlp_down': ((ff, h), 'normal')})
    return out


def short_conv(ref, lp, a):
    """a [S, h] -> [S, h]: `(C * conv(B * z)) W_out` with `[B | C | z] =
    a W_in` and `conv(u)_t = sum_j w[:, j] u_{t-L+1+j}`, `u` zero
    before the sequence: L shifted copies of `u`, summed."""
    gate_in, gate_out, z = jnp.split(ref.mm(a, lp['in_w']), 3, axis=-1)
    u = gate_in * z
    w = lp['conv_w'].astype(jnp.float32)
    s, taps = u.shape[0], w.shape[1]
    c = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j             # tap j reads the input `back` ago
        c = c + w[:, j] * jnp.pad(u, ((back, 0), (0, 0)))[:s]
    return ref.mm(gate_out * c, lp['out_w'])


def routing(cfg, lp, m):
    """-> [T, E] float32: the weight of every expert for every token,
    zero where the router did not select it."""
    s = jax.nn.sigmoid(jnp.matmul(m, lp['router_w'].astype(jnp.float32),
                                  precision=C.HIGHEST))
    pick = s + lp['expert_bias'].astype(jnp.float32) \
        if cfg['use_expert_bias'] else s
    _, sel = jax.lax.top_k(pick, cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, sel, axis=-1)     # the bias selects only
    if cfg['norm_topk_prob']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * cfg['routed_scaling_factor']
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, sel].set(w)


def experts(ref, cfg, lp, m):
    """sum_e weight[t, e] * expert_e(m): every expert for every token,
    EXPERT_BLOCK experts at a time; no shared expert."""
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
    return routed


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    _, _, _, hd = _dims(cfg)
    eps, theta = cfg['norm_eps'], cfg['rope_parameters']['rope_theta']
    nh, nk = cfg['num_attention_heads'], cfg['num_key_value_heads']

    def one(seq):
        s = seq.shape[0]
        x = params['embed'].astype(jnp.float32)[seq]
        for i, kind in enumerate(cfg['layer_types']):
            lp = {k[len(f'l{i}.'):]: v for k, v in params.items()
                  if k.startswith(f'l{i}.')}
            a = C.rms_norm(x, lp['op_norm'], eps)
            if kind == CONV:
                x = x + short_conv(ref, lp, a)
            else:
                q = C.rms_norm(ref.mm(a, lp['q_w']).reshape(s, nh, hd),
                               lp['q_norm'], eps)
                k = C.rms_norm(ref.mm(a, lp['k_w']).reshape(s, nk, hd),
                               lp['k_norm'], eps)
                v = ref.mm(a, lp['v_w']).reshape(s, nk, hd)
                q, k = C.rope(q[None], theta)[0], C.rope(k[None], theta)[0]
                x = x + ref.mm(attention(ref, q, k, v, None), lp['o_w'])
            m = C.rms_norm(x, lp['ffn_norm'], eps)
            if is_expert_layer(cfg, i):
                x = x + experts(ref, cfg, lp, m)
            else:
                x = x + swiglu(ref, m, lp['mlp_gate'], lp['mlp_up'],
                               lp['mlp_down'])
        return C.rms_norm(x, params['norm'], eps)
    return jnp.stack([one(seq) for seq in ids])


def logits_of(cfg, params, hidden, mode='f32'):
    """The head is the embedding."""
    return C.Ref(mode).mm(hidden, params['embed'].T)
