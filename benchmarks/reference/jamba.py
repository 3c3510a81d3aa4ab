"""Plain reference of the Jamba block (`ai21labs/AI21-Jamba2-3B`
`config.json`, `model_type: jamba`; the public modeling code is
`transformers/models/jamba/modeling_jamba.py`): Mamba-1 selective
state-space layers (arXiv:2312.00752) with Jamba's three inner RMSNorms,
and multi-query attention with no positions on layers `i %
attn_layer_period == attn_layer_offset`; two RMSNorms and a dense SwiGLU
a layer; the head tied to the embedding.

A Mamba layer, `a` the layer's normed input, `Di = mamba_expand x h`, `N
= mamba_d_state`, `R = mamba_dt_rank`:

    [x' | z] = a W_in                                    [2 Di]
    u_t = silu(b_conv + sum_j w_conv[:, j] x'_{t-3+j})   4 taps, zeros before
    [r | B | C]_t = u_t W_x                              [R + N + N]
    r, B, C = rmsnorm(r), rmsnorm(B), rmsnorm(C)         learned weights
    dt_t = softplus(r_t W_dt + b_dt)                     [Di]
    h_t = exp(dt_t[:, None] A) h_{t-1} + (dt_t u_t)[:, None] B_t[None, :]
    y_t = h_t C_t + D u_t                                A = -exp(A_log), [Di, N]
    out_t = (y_t silu(z_t)) W_out

Written for reading, not for speed: a full-sequence forward with NO
cache, NO state handed anywhere and NO chunk; **the recurrence is a
`lax.scan` over the positions**, `h` `[Di, N]` float32 as published (the
program holds it turned: a layout, not a departure); the convolution is
four shifted copies of the whole sequence, summed; attention is one
masked softmax, in blocks of queries (`reference/afmoe.py`'s: 5,120
positions x 20 heads of scores are 2 GB at once).

What `config.json` alone does not show is listed in the configuration
file's `assumed`: which layers attend (offset and period as the public
code reads them); the inner norms' eps = `rms_norm_eps`; `dt` = softplus
AFTER the bias; zeros before a sequence; tap 3 on the token itself;
`head_dim` = hidden / heads; no positions of any kind; no norm on q and
k; the final norm (`final_layernorm` there); the tied head. The control
rounds the operands of every PRODUCT (`common.quant_fp8`); the
recurrence, elementwise, stays float32 in either mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C
from .afmoe import attention, swiglu

FULL = 'full_attention'


def layer_types(cfg):
    """Layer i attends iff `i % attn_layer_period == attn_layer_offset`."""
    return [FULL if i % cfg['attn_layer_period'] == cfg['attn_layer_offset']
            else 'mamba' for i in range(cfg['num_hidden_layers'])]


def _dims(cfg):
    h = cfg['hidden_size']
    return (h, cfg['mamba_expand'] * h, cfg['mamba_d_state'],
            cfg['mamba_dt_rank'], h // cfg['num_attention_heads'])


def param_shapes(cfg):
    h, di, n, r, hd = _dims(cfg)
    nq, nkv = cfg['num_attention_heads'] * hd, cfg['num_key_value_heads'] * hd
    ff = cfg['intermediate_size']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones')}
    for i, kind in enumerate(layer_types(cfg)):
        p = f'l{i}.'
        out.update({p + 'in_norm': ((h,), 'ones'),
                    p + 'ff_norm': ((h,), 'ones'),
                    p + 'mlp_gate': ((h, ff), 'normal'),
                    p + 'mlp_up': ((h, ff), 'normal'),
                    p + 'mlp_down': ((ff, h), 'normal')})
        if kind == FULL:
            out.update({p + 'q_w': ((h, nq), 'normal'),
                        p + 'k_w': ((h, nkv), 'normal'),
                        p + 'v_w': ((h, nkv), 'normal'),
                        p + 'o_w': ((nq, h), 'normal')})
            continue
        out.update({
            p + 'in_w': ((h, 2 * di), 'normal'),
            # ones and zeros (the configuration file's
            # `assumed.initializer`; `make_weights` has one deviation for
            # every normal leaf): taps ONE, a moving sum (lfm2's reason:
            # at 0.02 the convolved input would be a fiftieth of its
            # size and `silu` linear); `A_log` ONE, `b_dt` ZERO: dt =
            # softplus(N(0, 0.25)) is about 0.7 and a channel forgets to
            # e^(-0.7 e) = 0.15 a token; `D` ONE, the skip at full size
            p + 'conv_w': ((di, cfg['mamba_d_conv']), 'ones'),
            p + 'conv_b': ((di,), 'zeros'),
            p + 'x_w': ((di, r + 2 * n), 'normal'),
            p + 'dt_norm': ((r,), 'ones'),
            p + 'b_norm': ((n,), 'ones'),
            p + 'c_norm': ((n,), 'ones'),
            p + 'dt_w': ((r, di), 'normal'),
            p + 'dt_b': ((di,), 'zeros'),
            p + 'a_log': ((di, n), 'ones'),
            p + 'd': ((di,), 'ones'),
            p + 'out_w': ((di, h), 'normal')})
    return out


def causal_conv(x, w, bias):
    """x [S, Di], w [Di, L], bias [Di]: `b + sum_j w[:, j] x_{t-L+1+j}`,
    zeros before the sequence: L shifted copies of `x`, summed."""
    s, taps = x.shape[0], w.shape[1]
    w = w.astype(jnp.float32)
    c = jnp.zeros_like(x) + bias.astype(jnp.float32)
    for j in range(taps):
        back = taps - 1 - j             # tap j reads the input `back` ago
        c = c + w[:, j] * jnp.pad(x, ((back, 0), (0, 0)))[:s]
    return c


def selective_scan(u, dt, b, c, a, d):
    """The recurrence, one position after another from a zero state: u,
    dt [S, Di], b, c [S, N], a [Di, N], d [Di] -> y [S, Di]."""
    def step(h, x):
        u, dt, b, c = x
        h = jnp.exp(dt[:, None] * a) * h + (dt * u)[:, None] * b[None, :]
        return h, jnp.sum(h * c[None, :], axis=-1) + d * u
    return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                        (u, dt, b, c))[1]


def mamba(ref, cfg, lp, a):
    """a [S, h] (normed) -> [S, h]."""
    _, di, n, r, _ = _dims(cfg)
    eps = cfg['rms_norm_eps']
    xz = ref.mm(a, lp['in_w'])
    x, z = xz[:, :di], xz[:, di:]
    u = jax.nn.silu(causal_conv(x, lp['conv_w'], lp['conv_b']))
    rbc = ref.mm(u, lp['x_w'])
    low = C.rms_norm(rbc[:, :r], lp['dt_norm'], eps)
    b = C.rms_norm(rbc[:, r:r + n], lp['b_norm'], eps)
    c = C.rms_norm(rbc[:, r + n:], lp['c_norm'], eps)
    dt = jax.nn.softplus(ref.mm(low, lp['dt_w'])
                         + lp['dt_b'].astype(jnp.float32))
    y = selective_scan(u, dt, b, c, -jnp.exp(lp['a_log'].astype(jnp.float32)),
                       lp['d'].astype(jnp.float32))
    return ref.mm(y * jax.nn.silu(z), lp['out_w'])


def self_attention(ref, cfg, lp, a):
    """a [S, h] (normed) -> [S, h]: every query head on its K,V head,
    no positions, no norm on q and k."""
    s = a.shape[0]
    hd = _dims(cfg)[-1]
    nh, nk = cfg['num_attention_heads'], cfg['num_key_value_heads']
    q = ref.mm(a, lp['q_w']).reshape(s, nh, hd)
    k = ref.mm(a, lp['k_w']).reshape(s, nk, hd)
    v = ref.mm(a, lp['v_w']).reshape(s, nk, hd)
    return ref.mm(attention(ref, q, k, v, None), lp['o_w'])


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    eps = cfg['rms_norm_eps']

    def one(seq):
        x = params['embed'].astype(jnp.float32)[seq]
        for i, kind in enumerate(layer_types(cfg)):
            lp = {k[len(f'l{i}.'):]: v for k, v in params.items()
                  if k.startswith(f'l{i}.')}
            mixer = self_attention if kind == FULL else mamba
            x = x + mixer(ref, cfg, lp, C.rms_norm(x, lp['in_norm'], eps))
            x = x + swiglu(ref, C.rms_norm(x, lp['ff_norm'], eps),
                           lp['mlp_gate'], lp['mlp_up'], lp['mlp_down'])
        return C.rms_norm(x, params['norm'], eps)
    return jnp.stack([one(seq) for seq in ids])


def logits_of(cfg, params, hidden, mode='f32'):
    """The head is the embedding."""
    return C.Ref(mode).mm(hidden, params['embed'].T)
