"""Plain reference of the Ling-3.0 block (`inclusionAI/Ling-3.0-flash`
`config.json`, `model_type: bailing_hybrid`): Kimi-delta linear-attention
layers (KDA, arXiv:2510.26692) and latent attention whose output is gated
head by head, five to one; two RMSNorms a layer; a dense SwiGLU on the
first `first_k_dense_replace` layers, else a sigmoid router that chooses
groups before experts, sparse SwiGLU experts and a shared one; the head
untied.

A KDA layer, per head h of width d, `a` the layer's normed input:

    q = l2norm(silu(conv(a W_q)))   k = l2norm(silu(conv(a W_k)))
    v = silu(conv(a W_v))
    g = lower * sigmoid(exp(A_log_h) * (a W_f + dt_bias))    [d], in (lower, 0)
    beta = sigmoid(a W_b)                                    a scalar
    S_t = (I - beta k k^T) diag(exp(g)) S_{t-1} + beta k v^T   [d, d]
    o_t = S_t^T q / sqrt(d)
    y   = concat_h(RMSNorm_d(o_t) * sigmoid(a W_g)_h) W_o

Written for reading, not for speed: a full-sequence forward with NO
cache and NO state handed anywhere; **KDA is the recurrence token by
token** (`lax.scan` over the positions, elementwise float32), never a
chunked form; the convolution is a sum of shifted copies of the whole
sequence; latent attention makes K and V by head for every position
(`reference/deepseek_v3.py`'s blocks of queries) and gates the heads
after the softmax-weighted sum; every HELD expert is computed for every
token and combined with the routing weight over ALL of the router's
experts, zero where it did not select — a pick that is not held
(`num_experts` experts from `expert_share.first` on are) is simply
absent, as in the program.

The router, from the rule (DeepSeek-V3's `noaux_tc`): `c = sigmoid(m
W_r) + bias`; the experts are `n_group` groups of consecutive experts; a
group's score is the sum of its two largest `c`; the `topk_group` best
groups are kept; the `num_experts_per_tok` largest `c` inside them are
the picks; their weights are the SCORES (no bias) over their sum + 1e-20,
times `routed_scaling_factor`.

What `config.json` alone does not show is listed in the configuration
file's `assumed` (a dagger in ISSUE 43): the convolution has no bias and
its tap L-1 is on the token itself; the l2norm's 1e-6; the safe gate as
above; `W_f` and `W_g` full rank; the output norm's eps is
`rms_norm_eps`; the latent layer's gate is one sigmoid a head of the
layer's input; the latent's RMSNorm is the only norm inside it; the
kept groups are selected among by -inf elsewhere. The multi-token-
prediction layer is left out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C
from .afmoe import swiglu
from .deepseek_v3 import attention, rotary

MLA = 'mla'
EXPERT_BLOCK = 4       # experts computed at once: [4, T, 768] float32


def is_expert_layer(cfg, i):
    return i >= cfg['first_k_dense_replace']


def param_shapes(cfg):
    h, nh, d = cfg['hidden_size'], cfg['num_attention_heads'], cfg['head_dim']
    lat, nope = cfg['kv_lora_rank'], cfg['qk_nope_head_dim']
    rd, vd = cfg['qk_rope_head_dim'], cfg['v_head_dim']
    held, f = cfg['num_experts'], cfg['moe_intermediate_size']
    routed = cfg['expert_share']['routed']
    taps = cfg['short_conv_kernel_size']
    out = {'embed': ((cfg['vocab_size'], h), 'normal'),
           'norm': ((h,), 'ones'),
           'head': ((h, cfg['vocab_size']), 'normal')}
    for i in range(cfg['num_hidden_layers']):
        p = f'l{i}.'
        out.update({p + 'in_norm': ((h,), 'ones'),
                    p + 'post_norm': ((h,), 'ones')})
        if cfg['layer_types'][i] == MLA:
            out.update({
                p + 'q_w': ((h, nh * (nope + rd)), 'normal'),
                p + 'kva_w': ((h, lat + rd), 'normal'),
                p + 'kv_norm': ((lat,), 'ones'),
                p + 'kvb_w': ((lat, nh * (nope + vd)), 'normal'),
                p + 'gate_w': ((h, nh), 'normal'),
                p + 'o_w': ((nh * vd, h), 'normal')})
        else:
            out.update({
                p + 'kq_w': ((h, nh * d), 'normal'),
                p + 'kk_w': ((h, nh * d), 'normal'),
                p + 'kv_w': ((h, nh * d), 'normal'),
                # ones (the configuration file's `assumed.initializer`:
                # at the generator's one deviation a filter of 0.02 would
                # leave the activations linear and the l2norm would hide
                # them), A_log ones and dt_bias zeros: the gate's
                # argument is e x N(0, 1.0): decays that differ by
                # channel and by token
                p + 'q_conv': ((nh * d, taps), 'ones'),
                p + 'k_conv': ((nh * d, taps), 'ones'),
                p + 'v_conv': ((nh * d, taps), 'ones'),
                p + 'f_w': ((h, nh * d), 'normal'),
                p + 'a_log': ((nh,), 'ones'),
                p + 'dt_bias': ((nh * d,), 'zeros'),
                p + 'b_w': ((h, nh), 'normal'),
                p + 'g_w': ((h, nh * d), 'normal'),
                p + 'o_norm': ((d,), 'ones'),
                p + 'ko_w': ((nh * d, h), 'normal')})
        if is_expert_layer(cfg, i):
            shared = f * cfg['num_shared_experts']
            out.update({
                p + 'router_w': ((h, routed), 'normal'),
                # seeded, not zero: selection and weight then differ
                p + 'expert_bias': ((routed,), 'normal'),
                p + 'experts_gate': ((held, h, f), 'normal'),
                p + 'experts_up': ((held, h, f), 'normal'),
                p + 'experts_down': ((held, f, h), 'normal'),
                p + 'shared_gate': ((h, shared), 'normal'),
                p + 'shared_up': ((h, shared), 'normal'),
                p + 'shared_down': ((shared, h), 'normal')})
        else:
            ff = cfg['intermediate_size']
            out.update({p + 'mlp_gate': ((h, ff), 'normal'),
                        p + 'mlp_up': ((h, ff), 'normal'),
                        p + 'mlp_down': ((ff, h), 'normal')})
    return out


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def short_conv(x, w):
    """x [S, C], w [C, L]: `y_t = sum_j w[:, j] x_{t-L+1+j}`, zeros
    before the sequence; then SiLU."""
    s, taps = x.shape[0], w.shape[1]
    past = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return jax.nn.silu(sum(past[j:j + s] * w[:, j] for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """The recurrence, one position after another from a zero state:
    q, k, g [S, H, d], v [S, H, d], beta [S, H] -> o [S, H, d]."""
    d = q.shape[-1]

    def step(state, x):
        q, k, v, g, beta = x
        state = jnp.exp(g)[..., None] * state
        seen = jnp.sum(state * k[..., None], axis=-2)       # S^T k
        state = state + (beta[:, None, None] * k[..., None]
                         * (v - seen)[..., None, :])
        return state, jnp.sum(state * q[..., None], axis=-2) / math.sqrt(d)
    zero = jnp.zeros(q.shape[1:] + (v.shape[-1],), jnp.float32)
    return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]


def kda(ref, cfg, lp, a):
    """a [S, h] (normed) -> [S, h]."""
    s = a.shape[0]
    nh, d = cfg['num_attention_heads'], cfg['head_dim']
    heads = lambda t: t.reshape(s, nh, d)     # noqa: E731
    q = l2norm(heads(short_conv(ref.mm(a, lp['kq_w']), lp['q_conv'])))
    k = l2norm(heads(short_conv(ref.mm(a, lp['kk_w']), lp['k_conv'])))
    v = heads(short_conv(ref.mm(a, lp['kv_w']), lp['v_conv']))
    arg = heads(ref.mm(a, lp['f_w']) + lp['dt_bias'].astype(jnp.float32))
    g = cfg['kda_lower_bound'] * jax.nn.sigmoid(
        jnp.exp(lp['a_log'].astype(jnp.float32))[:, None] * arg)
    beta = jax.nn.sigmoid(ref.mm(a, lp['b_w']))
    o = C.rms_norm(delta_rule(q, k, v, g, beta), lp['o_norm'],
                   cfg['rms_norm_eps'])
    o = o * heads(jax.nn.sigmoid(ref.mm(a, lp['g_w'])))
    return ref.mm(o.reshape(s, nh * d), lp['ko_w'])


def self_attention(ref, cfg, lp, a):
    """a [S, h] (normed) -> [S, h]: latent attention written out, K and
    V by head for every position, each head's output times one sigmoid
    of the layer's input."""
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
    o = attention(ref, q, k, kv[..., nope:]).reshape(s, nh, vd)
    o = o * jax.nn.sigmoid(ref.mm(a, lp['gate_w']))[..., None]
    return ref.mm(o.reshape(s, nh * vd), lp['o_w'])


def routing(cfg, lp, m):
    """-> [T, routed] float32: the weight of every expert the router
    scores for every token, zero where it did not select it."""
    t = m.shape[0]
    s = jax.nn.sigmoid(jnp.matmul(m, lp['router_w'].astype(jnp.float32),
                                  precision=C.HIGHEST))
    groups, kept = cfg['n_group'], cfg['topk_group']
    c = (s + lp['expert_bias'].astype(jnp.float32)).reshape(t, groups, -1)
    group_score = jnp.sum(jnp.sort(c, axis=-1)[..., -2:], axis=-1)
    best = jnp.argsort(-group_score, axis=-1)[:, :kept]      # [T, kept]
    rows = jnp.arange(t)[:, None]
    inside = jnp.zeros((t, groups), bool).at[rows, best].set(True)
    c = jnp.where(inside[..., None], c, -jnp.inf).reshape(t, -1)
    _, sel = jax.lax.top_k(c, cfg['num_experts_per_tok'])
    w = jnp.take_along_axis(s, sel, axis=-1)     # the bias selects only
    if cfg['norm_topk_prob']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(s).at[rows, sel].set(
        w * cfg['routed_scaling_factor'])


def experts(ref, cfg, lp, m):
    """shared(m) + sum over the HELD experts e of weight[t, e] *
    expert_e(m): every held expert for every token, EXPERT_BLOCK at a
    time; the weight is the router's over all it scores, so a token
    whose picks lie elsewhere gets the shared expert alone."""
    held, first = cfg['num_experts'], cfg['expert_share']['first']
    weight = routing(cfg, lp, m)[:, first:first + held]
    blk = min(EXPERT_BLOCK, held)
    assert held % blk == 0

    def some(acc, args):
        gate, up, down, w = args        # [blk, h, f] x2, [blk, f, h], [blk, T]
        a = jax.nn.silu(ref.einsum('th,ehf->etf', m, gate)) \
            * ref.einsum('th,ehf->etf', m, up)
        y = ref.einsum('etf,efh->eth', a, down)
        return acc + jnp.sum(y * w[:, :, None], axis=0), None
    cut = lambda x: x.reshape(held // blk, blk, *x.shape[1:])  # noqa: E731
    routed, _ = jax.lax.scan(
        some, jnp.zeros_like(m),
        (cut(lp['experts_gate']), cut(lp['experts_up']),
         cut(lp['experts_down']), cut(weight.T)))
    return routed + swiglu(ref, m, lp['shared_gate'], lp['shared_up'],
                           lp['shared_down'])


def hidden_states(cfg, params, ids, mode='f32'):
    ref = C.Ref(mode)
    eps = cfg['rms_norm_eps']

    def one(seq):
        x = params['embed'].astype(jnp.float32)[seq]
        for i in range(cfg['num_hidden_layers']):
            lp = {k[len(f'l{i}.'):]: v for k, v in params.items()
                  if k.startswith(f'l{i}.')}
            mixer = self_attention if cfg['layer_types'][i] == MLA else kda
            x = x + mixer(ref, cfg, lp, C.rms_norm(x, lp['in_norm'], eps))
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
