"""Plain reference of the Llama-style block (InternLM2's `config.json`:
RMSNorm, rotary positions in the rotate-half convention, grouped KV
heads, SwiGLU, no biases, untied head). Stacked leaves `layers.*` are
[L, ...]; the forward scans over them, upcasting one layer at a time.

Departure from the published checkpoint layout, noted: InternLM2 stores
one fused `wqkv` per layer; the equations are the same with q, k and v
as three matrices, which is how the program under test holds them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C


def param_shapes(cfg):
    h, ff, L = cfg['hidden_size'], cfg['intermediate_size'], cfg['num_hidden_layers']
    hd = cfg.get('head_dim') or h // cfg['num_attention_heads']
    nq, nkv = cfg['num_attention_heads'] * hd, cfg['num_key_value_heads'] * hd
    return {
        'embed': ((cfg['vocab_size'], h), 'normal'),
        'layers.in_norm': ((L, h), 'ones'),
        'layers.q_w': ((L, h, nq), 'normal'),
        'layers.k_w': ((L, h, nkv), 'normal'),
        'layers.v_w': ((L, h, nkv), 'normal'),
        'layers.o_w': ((L, nq, h), 'normal'),
        'layers.post_norm': ((L, h), 'ones'),
        'layers.gate_w': ((L, h, ff), 'normal'),
        'layers.up_w': ((L, h, ff), 'normal'),
        'layers.down_w': ((L, ff, h), 'normal'),
        'norm': ((h,), 'ones'),
        'head': ((h, cfg['vocab_size']), 'normal'),
    }


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    nh, nkv = cfg['num_attention_heads'], cfg['num_key_value_heads']
    eps, theta = cfg['rms_norm_eps'], cfg['rope_theta']
    b, s = ids.shape
    x = params['embed'].astype(jnp.float32)[ids]

    @jax.checkpoint
    def block(x, lp):
        y = C.rms_norm(x, lp['in_norm'], eps)
        q = C.rope(ref.mm(y, lp['q_w']).reshape(b, s, nh, -1), theta)
        k = C.rope(ref.mm(y, lp['k_w']).reshape(b, s, nkv, -1), theta)
        v = ref.mm(y, lp['v_w']).reshape(b, s, nkv, -1)
        x = x + ref.mm(C.causal_attention(ref, q, k, v), lp['o_w'])
        y = C.rms_norm(x, lp['post_norm'], eps)
        y = jax.nn.silu(ref.mm(y, lp['gate_w'])) * ref.mm(y, lp['up_w'])
        return x + ref.mm(y, lp['down_w']), None

    layers = {k[len('layers.'):]: v for k, v in params.items()
              if k.startswith('layers.')}
    x, _ = jax.lax.scan(block, x, layers)
    return C.rms_norm(x, params['norm'], eps)


def logits_of(cfg, params, hidden, mode='f32'):
    return C.Ref(mode).mm(hidden, params['head'])


def loss(cfg, params, ids, mode='f32'):
    hidden = hidden_states(cfg, params, ids, mode)
    return C.lm_loss(lambda h: logits_of(cfg, params, h, mode), hidden, ids)
