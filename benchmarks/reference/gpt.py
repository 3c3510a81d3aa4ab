"""Plain reference of the GPT block as PaddleNLP's GPT-3 configs state
it: pre-LayerNorm, learned positions, fused QKV with biases, GELU (erf),
tied output head. Stacked leaves `layers.*` are [L, ...]; the forward
scans over them, upcasting one layer at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C


def param_shapes(cfg):
    h, ff, L = cfg['hidden_size'], cfg['intermediate_size'], cfg['num_hidden_layers']
    return {
        'wte': ((cfg['vocab_size'], h), 'normal'),
        'wpe': ((cfg['max_position_embeddings'], h), 'normal'),
        'layers.ln1_w': ((L, h), 'ones'), 'layers.ln1_b': ((L, h), 'zeros'),
        'layers.qkv_w': ((L, h, 3 * h), 'normal'),
        'layers.qkv_b': ((L, 3 * h), 'zeros'),
        'layers.out_w': ((L, h, h), 'normal'),
        'layers.out_b': ((L, h), 'zeros'),
        'layers.ln2_w': ((L, h), 'ones'), 'layers.ln2_b': ((L, h), 'zeros'),
        'layers.fc1_w': ((L, h, ff), 'normal'),
        'layers.fc1_b': ((L, ff), 'zeros'),
        'layers.fc2_w': ((L, ff, h), 'normal'),
        'layers.fc2_b': ((L, h), 'zeros'),
        'lnf_w': ((h,), 'ones'), 'lnf_b': ((h,), 'zeros'),
    }


def hidden_states(cfg, params, ids, mode='f32'):
    """ids [B,S] -> final-norm hidden states [B,S,H], float32."""
    ref = C.Ref(mode)
    nh = cfg['num_attention_heads']
    eps = cfg['layer_norm_epsilon']
    b, s = ids.shape
    x = params['wte'].astype(jnp.float32)[ids] \
        + params['wpe'].astype(jnp.float32)[jnp.arange(s)][None]

    @jax.checkpoint
    def block(x, lp):
        f32 = lambda a: a.astype(jnp.float32)
        y = C.layer_norm(x, lp['ln1_w'], lp['ln1_b'], eps)
        qkv = ref.mm(y, lp['qkv_w']) + f32(lp['qkv_b'])
        q, k, v = (t.reshape(b, s, nh, -1) for t in jnp.split(qkv, 3, -1))
        a = C.causal_attention(ref, q, k, v)
        x = x + ref.mm(a, lp['out_w']) + f32(lp['out_b'])
        y = C.layer_norm(x, lp['ln2_w'], lp['ln2_b'], eps)
        y = jax.nn.gelu(ref.mm(y, lp['fc1_w']) + f32(lp['fc1_b']),
                        approximate=False)
        return x + ref.mm(y, lp['fc2_w']) + f32(lp['fc2_b']), None

    layers = {k[len('layers.'):]: v for k, v in params.items()
              if k.startswith('layers.')}
    x, _ = jax.lax.scan(block, x, layers)
    return C.layer_norm(x, params['lnf_w'], params['lnf_b'], eps)


def logits_of(cfg, params, hidden, mode='f32'):
    return C.Ref(mode).mm(hidden, params['wte'].T)


def loss(cfg, params, ids, mode='f32'):
    hidden = hidden_states(cfg, params, ids, mode)
    return C.lm_loss(lambda h: logits_of(cfg, params, h, mode), hidden, ids)
