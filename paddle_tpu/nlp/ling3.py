"""Ling-3.0 causal LM (`model_type: bailing_hybrid`; inclusionAI
Ling-3.0-flash, 125B-A5.5B): Kimi-delta linear-attention layers and
head-gated latent attention five to one, and sparse SwiGLU experts
behind a sigmoid router that chooses its groups first.

What it is made of, and where that lives:

- `x0 = E[ids]`; a layer is `x += mixer(N_in(x))`, `x += f(N_post(x))`
  (two RMSNorms a layer); `logits = N_final(x) W_head` (untied). Layer
  `i` is a LATENT-attention layer iff `(i + 1) % layer_group_size == 0`
  (or as `layer_types` says), every other a KDA layer.
- a latent layer's mixer is `nlp/deepseek_v3.py`'s `DeepseekV3Attention`
  with its output gated head-wise (`attention_output_gate`): both its
  paths, its kernel, its LATENT cache entry.
- a KDA layer's mixer (`KimiDeltaAttention`, here; Kimi Delta Attention,
  arXiv:2510.26692), H heads of `d = head_dim`, `a = N_in(x)`:

      q = l2norm(silu(conv(a W_q)))  k = l2norm(silu(conv(a W_k)))
      v = silu(conv(a W_v))                       each [H, d]
      g = lower * sigmoid(exp(A_log_h) * (a W_f + dt_bias))   [H, d]
      alpha = exp(g), beta = sigmoid(a W_b)       [H, d], [H]
      S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t / sqrt(d)
      y = concat_h(RMSNorm_d(o_t) * sigmoid(a W_g)_h) W_o

  `conv` is a depthwise causal convolution of `short_conv_kernel_size`
  taps, no bias; `lower` is `kda_lower_bound` (-5: the SAFE gate, a
  decay per channel in (e^-5, 1)). What a sequence keeps of its past is
  no row: a STATE ENTRY of two leaves, `{'S': [B, H, d, d] float32,
  'conv': [B, taps - 1, 3 H d] float32}` (the matrix; the convolutions'
  last inputs, q, k and v side by side) — 2 MiB and 144 KiB a layer a
  sequence at the published widths, whatever its length.
- `f` of the first `first_k_dense_replace` layers is `nlp/llama.py`'s
  SwiGLU; of the others `nlp/afmoe.py`'s expert layer: `s = sigmoid(m
  W_r)` in float32, `c = s + bias`, a group's score the sum of its two
  largest `c`, the `topk_group` best of `n_group` groups kept, top-k of
  `c` inside them (`afmoe.route`), `w = s[sel] / (sum s[sel] + 1e-20) *
  routed_scaling_factor`, plus the shared expert unweighted. The layer
  may hold a share of the router's experts (`num_routed_experts`,
  `first_expert`).

**One token and many** (`kda_step`, `kda_chunked`). A call of one token
(a decode sub-step) is the recurrence itself, elementwise in float32: a
read and a write of the state — as ONE kernel where `ops.pallas.
kda_step_kernel` takes the call (a TPU, a float32 state of whole-lane
heads: `pallas_kernels.kda_decode_step`, in place), else as XLA fuses
`kda_step` (two reads and a write). A longer call (a prefill) goes CHUNK by
chunk of `KDA_CHUNK` tokens, the chunks one after another and a
chunk's tokens at once: with `G_i = sum_{j<=i} g_j` inside a chunk that
starts at `S_0`, `A_ij = beta_i (k_i * exp(G_i - G_j)) . k_j` (j < i),
`U = (I + A)^-1 diag(beta) (V - (K * exp(G)) S_0)`, `o_i = S_0^T (q_i *
exp(G_i)) + sum_{j<=i} ((q_i * exp(G_i - G_j)) . k_j) u_j`, `S_C =
diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T`. Everything
that does not read `S_0` — `A`, its inverse, the pair products — is
made for all chunks at once, before the scan. `exp(G_i - G_j)` is at
most 1, but as a PRODUCT of a factor of `i` and a factor of `j` (the
only form that is a matmul) one of them grows like `e^(5 n)` over `n`
tokens, and float32 ends at `e^88`: so the pair products are made in
blocks of `KDA_BLOCK` = 16 rows, each against a reference point of its
own (`G` at the block's middle), around which neither factor passes
`e^40`.

A caller that forwards a right-padded prompt says how many of its
tokens may enter the state (`generation.state_scope`); a token past
that is folded as `beta = 0, g = 0`, the identity update, and the
convolutions' inputs are cut there (`nlp/lfm2.py::short_conv`'s way).

Refused by name, because not built: a SwiGLU limit (the published
model's last seven layers), KDA's low-rank gates, an unsafe gate, a
compressed or position-free latent query, grouped KDA heads, nGPT, a
value norm, an up-projection norm, rope scaling. The multi-token-
prediction layer is left out. Activations are float32 and products
three bf16 passes (`afmoe.ACTIVATION_PRECISION`). Served, not trained.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..nn import initializer as I
from ..nn.common_layers import Linear
from ..nn.layer import Layer
from ..nn.norm import RMSNorm
from ..ops import pallas as _pallas
from ..tensor import Tensor, apply_op, to_jax
from .deepseek_v3 import (DeepseekV3Attention, DeepseekV3DecoderLayer,
                          DeepseekV3ForCausalLM, DeepseekV3Model,
                          check_route_groups)
from .generation import folded_tokens, state_layers
from .llama import _col_linear, _row_linear

MLA, KDA = 'mla', 'kda'

# tokens a chunk of a prefill's scan: a `[64, 64]` triangular solve a head
KDA_CHUNK = 64
# rows of a chunk whose pair products share one reference point: over
# 16 tokens the safe gate's decay is at most e^80, which float32 holds
# (from the block's middle e^40 either way, with room for small keys)
KDA_BLOCK = 16


class Ling3Config:
    model_type = 'bailing_hybrid'

    def __init__(self, vocab_size=157184, hidden_size=2560,
                 intermediate_size=6144, moe_intermediate_size=768,
                 moe_shared_expert_intermediate_size=768,
                 num_hidden_layers=42, first_k_dense_replace=2,
                 layer_group_size=6, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=32,
                 head_dim=128, kv_lora_rank=512, q_lora_rank=None,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=6000000.0, rope_interleave=True,
                 rope_scaling=None,
                 gated_attention_proj_granularity_type='head_wise',
                 short_conv_kernel_size=4, kda_safe_gate=True,
                 kda_lower_bound=-5, use_kda_lora=False, use_mla_nope=False,
                 num_kv_heads_for_linear_attn=0, num_experts=512,
                 num_experts_per_tok=8, num_shared_experts=1, n_group=8,
                 topk_group=4, norm_topk_prob=True,
                 routed_scaling_factor=2.5, score_function='sigmoid',
                 moe_router_enable_expert_bias=True,
                 expert_swiglu_limit_list=None,
                 share_expert_swiglu_limit_list=None, use_nGPT=False,
                 value_norm=False, up_proj_norm=False, rms_norm_eps=1e-6,
                 max_position_embeddings=262144, tie_word_embeddings=False,
                 num_routed_experts=None, first_expert=0,
                 pad_token_id=0, bos_token_id=1,
                 eos_token_id=2, tensor_parallel=False, **kwargs):
        for name, limits in (
                ('expert_swiglu_limit_list', expert_swiglu_limit_list),
                ('share_expert_swiglu_limit_list',
                 share_expert_swiglu_limit_list)):
            if limits is not None and (len(limits) != num_hidden_layers
                                       or any(limits)):
                raise ValueError(
                    f'{name}: one entry a layer, every one 0 — a SwiGLU '
                    'clamp (the published model\'s last seven layers) is '
                    'not implemented')
        not_built = dict(
            use_kda_lora=use_kda_lora, use_mla_nope=use_mla_nope,
            use_nGPT=use_nGPT, value_norm=value_norm,
            up_proj_norm=up_proj_norm, tie_word_embeddings=tie_word_embeddings,
            **{'kda_safe_gate false': not kda_safe_gate,
               'q_lora_rank': q_lora_rank is not None,
               'num_kv_heads_for_linear_attn':
                   num_kv_heads_for_linear_attn != 0,
               'rope_scaling': rope_scaling is not None,
               'moe_router_enable_expert_bias false':
                   not moe_router_enable_expert_bias})
        for name, asked in not_built.items():
            if asked:
                raise ValueError(f'{name}: not implemented (the published '
                                 'Ling-3.0-flash does not use it)')
        if score_function != 'sigmoid':
            raise ValueError(f'score_function {score_function!r}: only the '
                             'sigmoid router is implemented')
        if gated_attention_proj_granularity_type != 'head_wise':
            raise ValueError(
                'gated_attention_proj_granularity_type '
                f'{gated_attention_proj_granularity_type!r}: only the '
                'head_wise gate is implemented')
        if num_key_value_heads != num_attention_heads:
            raise ValueError('num_key_value_heads: latent attention makes '
                             'a K and V for every query head')
        if moe_shared_expert_intermediate_size != moe_intermediate_size:
            raise ValueError('moe_shared_expert_intermediate_size: the '
                             'shared expert has a routed expert\'s width')
        if not kda_lower_bound < 0:
            raise ValueError('kda_lower_bound: the safe gate\'s bound is '
                             'negative')
        routed = int(num_routed_experts or num_experts)
        check_route_groups(routed, num_experts_per_tok, n_group, topk_group)
        if layer_types is None:
            layer_types = [MLA if (i + 1) % layer_group_size == 0 else KDA
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers \
                or set(layer_types) - {MLA, KDA}:
            raise ValueError('layer_types must name kda or mla for every '
                             'layer')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.layer_group_size = layer_group_size
        self.layer_types = list(layer_types)
        # one character a layer: a scalar, so it rides the program
        # store's statics (`describe_statics` keeps scalars only)
        self.layer_pattern = ''.join('A' if t == MLA else 'K'
                                     for t in self.layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.kv_lora_rank = kv_lora_rank
        self.q_lora_rank = None
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.rope_theta = rope_theta
        self.rope_interleave = bool(rope_interleave)
        self.rope_scaling = None
        self.softmax_gain = 1.0
        self.softmax_scale = 1.0 / math.sqrt(self.qk_head_dim)
        self.attention_output_gate = 'head_wise'
        self.short_conv_kernel_size = int(short_conv_kernel_size)
        self.kda_lower_bound = float(kda_lower_bound)
        self.num_experts = num_experts
        self.num_routed_experts = routed
        self.first_expert = int(first_expert)
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = int(num_shared_experts or 0)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = False
        self.pad_token_id = pad_token_id
        self.bos_token_id = bos_token_id
        self.eos_token_id = eos_token_id
        self.tensor_parallel = tensor_parallel
        # what the published file says and only one value of is built
        self.moe_shared_expert_intermediate_size = moe_intermediate_size
        self.gated_attention_proj_granularity_type = 'head_wise'
        self.kda_safe_gate = self.moe_router_enable_expert_bias = True
        self.use_kda_lora = self.use_mla_nope = self.use_nGPT = False
        self.value_norm = self.up_proj_norm = False
        self.num_kv_heads_for_linear_attn = 0
        self.score_function = 'sigmoid'
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        # under the names `afmoe.py`'s expert layer reads
        self.route_norm = norm_topk_prob
        self.route_scale = routed_scaling_factor
        for k, v in kwargs.items():
            setattr(self, k, v)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized: a dense KDA layer, then a KDA and a latent layer
        with experts; 4 heads x 8, a latent of 16 with rope 4; a router
        over 16 experts in 4 groups, top-3 inside the best 2, of which
        experts 4-11 (groups 1 and 2 whole) are held, and a shared
        expert."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 32)
        kw.setdefault('intermediate_size', 64)
        kw.setdefault('moe_intermediate_size', 16)
        kw.setdefault('moe_shared_expert_intermediate_size',
                      kw['moe_intermediate_size'])
        kw.setdefault('num_hidden_layers', 3)
        kw.setdefault('first_k_dense_replace', 1)
        kw.setdefault('layer_types', [KDA, KDA, MLA])
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('num_key_value_heads', kw['num_attention_heads'])
        kw.setdefault('head_dim', 8)
        kw.setdefault('kv_lora_rank', 16)
        kw.setdefault('qk_nope_head_dim', 8)
        kw.setdefault('qk_rope_head_dim', 4)
        kw.setdefault('v_head_dim', 8)
        kw.setdefault('rope_theta', 10000.0)
        kw.setdefault('num_experts', 8)
        kw.setdefault('num_routed_experts', 16)
        kw.setdefault('first_expert', 4)
        kw.setdefault('num_experts_per_tok', 3)
        kw.setdefault('n_group', 4)
        kw.setdefault('topk_group', 2)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)

    @classmethod
    def tiny_latent_first(cls, **kw):
        """`tiny()` in another order — dense latent, KDA, KDA — with two
        dense layers and every expert held: nothing may hang on where
        the latent layer stands nor on a share."""
        kw.setdefault('num_hidden_layers', 3)
        kw.setdefault('first_k_dense_replace', 2)
        kw.setdefault('num_experts', 16)
        kw.setdefault('first_expert', 0)
        kw.setdefault('layer_types', [MLA, KDA, KDA])
        return cls.tiny(**kw)


# ---------------------------------------------------------------------------
# the KDA operator, in plain jax: its parts, the recurrence, the chunks
# ---------------------------------------------------------------------------
def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def short_conv_silu(u, w, state, folded, bias=None):
    """`silu(conv(u))`: u [B, S, C], one depthwise causal filter of L
    taps a channel `w` [C, L] (tap L-1 on the token itself) and, where a
    family has one (`nlp/jamba.py`), a `bias` [C] inside the SiLU,
    `state` [B, L-1, C] the last inputs before the call (zeros before a
    sequence). -> (the activations [B, S, C], the state after the first
    `folded` tokens of the call)."""
    s, taps = u.shape[1], w.shape[1]
    past = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    # token t's taps are u_{t-L+1} .. u_t: rows t .. t+L-1 of `past`
    c = sum(past[:, j:j + s] * w[:, j].astype(u.dtype) for j in range(taps))
    if bias is not None:
        c = c + bias.astype(u.dtype)
    with jax.named_scope('state_write'):
        new_state = jax.lax.dynamic_slice_in_dim(
            past, folded, taps - 1, axis=1).astype(state.dtype)
    return jax.nn.silu(c), new_state


def kda_gates(f, b, a_log, dt_bias, lower):
    """The two gates: f [B, S, H*d] and b [B, S, H] as projected ->
    (g [B, S, H, d] in (`lower`, 0), the log of a decay per channel;
    beta [B, S, H] in (0, 1))."""
    heads = a_log.shape[0]
    arg = (f + dt_bias.astype(f.dtype)).reshape(f.shape[:2] + (heads, -1))
    arg = arg * jnp.exp(a_log.astype(f.dtype))[:, None]
    return lower * jax.nn.sigmoid(arg), jax.nn.sigmoid(b)


def kda_step(q, k, v, g, beta, state):
    """The recurrence, one token: q, k, g [B, H, d_k], v [B, H, d_v],
    beta [B, H], state [B, H, d_k, d_v] -> (o [B, H, d_v], the state
    after the token). Elementwise and float32: with one row a sequence
    there is nothing for a matrix unit, and both sums over d_k read the
    decayed state in one pass (`S_t^T q = (alpha S)^T q + (k . q) u`)."""
    q = q * (1.0 / math.sqrt(q.shape[-1]))
    decayed = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(decayed * k[..., None], axis=-2))
    o = jnp.sum(decayed * q[..., None], axis=-2) \
        + jnp.sum(k * q, axis=-1, keepdims=True) * u
    with jax.named_scope('state_write'):
        return o, decayed + k[..., None] * u[..., None, :]


def _pair_products(rows, k, cum, block):
    """`sum_d r[i, d] k[j, d] exp(cum[i, d] - cum[j, d])` for j <= i
    inside a chunk (whatever for j > i: the caller masks), for every `r`
    of the tuple `rows` against the same keys: r, k, cum [..., C, d] ->
    a tuple of [..., C, C]. `cum` is the running sum of the log
    decays, so the exponent is at most 0 where it counts; the product is
    split as `(rows_i exp(cum_i - R)) . (k_j exp(R - cum_j))` with R the
    sum at the MIDDLE of row i's block of `block` rows: inside the block
    both factors stay within `exp(+-block / 2 * 5)`, e^40 — a reference
    at the block's start would leave e^-80 on its last rows, and what is
    under a thousandth of a unit key there would fall under float32's
    smallest number —, a key of an EARLIER block has a factor of at
    most 1 (where it underflows the pair is under e^-47), and keys of
    LATER blocks, whose factor would pass any bound, are zeroed: they
    are above the diagonal."""
    c, d = k.shape[-2:]
    nb = c // block
    lead = k.shape[:-2]
    by_block = cum.reshape(lead + (nb, block, d))
    ref = by_block[..., block // 2, :]                        # [..., nb, d]
    row_f = jnp.exp(by_block - ref[..., :, None, :])
    key_block = jnp.arange(c) // block                        # [C]
    seen = key_block[None, :] <= jnp.arange(nb)[:, None]      # [nb, C]
    key_f = jnp.exp(jnp.where(
        seen[..., None], ref[..., :, None, :] - cum[..., None, :, :],
        -jnp.inf))                                            # [..., nb, C, d]
    keys = k[..., None, :, :] * key_f
    return tuple(jnp.einsum('...aid,...ajd->...aij',
                            r.reshape(lead + (nb, block, d)) * row_f,
                            keys).reshape(lead + (c, c)) for r in rows)


def kda_chunked(q, k, v, g, beta, state, chunk):
    """The recurrence over S tokens, chunk by chunk (the module's
    docstring has the algebra): q, k, g [B, S, H, d_k], v [B, S, H,
    d_v], beta [B, S, H], state [B, H, d_k, d_v] -> (o [B, S, H, d_v],
    the state after the S tokens). S is padded to whole chunks with
    identity updates (`beta = 0, g = 0`). What does not read the state
    is made for every chunk at once; the scan over the chunks is five
    products a chunk."""
    b, s, h, dk = k.shape
    n = -(-s // chunk)
    q = q * (1.0 / math.sqrt(dk))

    def chunks(t):      # [B, S, H, ...] -> [n, B, H, C, ...]
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, n, chunk) + t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 1, 0), 2, 3)
    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-2)                      # G, [n, B, H, C, d_k]
    i = jnp.arange(chunk)
    kk, qk = _pair_products((k, q), k, cum, KDA_BLOCK)
    a = jnp.where(i[:, None] > i[None, :], kk, 0.0) * beta[..., None]
    qk = jnp.where(i[:, None] >= i[None, :], qk, 0.0)
    # (I + A)^-1 diag(beta): A is strictly lower, so forward substitution
    t = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(chunk, dtype=a.dtype),
        jnp.broadcast_to(jnp.eye(chunk, dtype=a.dtype), a.shape),
        lower=True, unit_diagonal=True) * beta[..., None, :]
    from_start = jnp.exp(cum)
    to_end = jnp.exp(cum[..., -1:, :] - cum)
    k_in, q_in, k_out = k * from_start, q * from_start, k * to_end
    end_decay = from_start[..., -1, :]                    # [n, B, H, d_k]

    def one(s0, xs):
        k_in, q_in, k_out, end_decay, t, qk, v = xs
        u = jnp.einsum('bhij,bhjv->bhiv', t,
                       v - jnp.einsum('bhcd,bhdv->bhcv', k_in, s0))
        o = jnp.einsum('bhcd,bhdv->bhcv', q_in, s0) \
            + jnp.einsum('bhij,bhjv->bhiv', qk, u)
        with jax.named_scope('state_write'):
            s1 = end_decay[..., None] * s0 \
                + jnp.einsum('bhcd,bhcv->bhdv', k_out, u)
        return s1, o
    state, o = jax.lax.scan(one, state,
                            (k_in, q_in, k_out, end_decay, t, qk, v))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)     # [B, n, C, H, d_v]
    return o.reshape(b, n * chunk, h, -1)[:, :s], state


def kda_mix(xq, xk, xv, f, b, wq, wk, wv, a_log, dt_bias, state, conv,
            folded, lower, chunk, fold_all):
    """Everything of a KDA layer between its projections: the three
    convolutions, both gates, the recurrence. xq, xk, xv, f [B, S, H*d]
    and b [B, S, H] as projected; the taps [H*d, L] each; `state` [B, H,
    d, d] and `conv` [B, L-1, 3 H d] as the call finds them; of its S
    tokens the first `folded` enter what it returns (all, `fold_all`).
    -> (o [B, S, H, d], state, conv)."""
    bsz, s = xq.shape[:2]
    heads = a_log.shape[0]
    qkv, conv = short_conv_silu(
        jnp.concatenate([xq, xk, xv], axis=-1),
        jnp.concatenate([wq, wk, wv], axis=0), conv, folded)
    q, k, v = (t.reshape(bsz, s, heads, -1) for t in jnp.split(qkv, 3, -1))
    q, k = l2norm(q), l2norm(k)
    g, beta = kda_gates(f, b, a_log, dt_bias, lower)
    if not fold_all:        # a token past `folded`: the identity update
        enters = jnp.arange(s) < folded
        g = jnp.where(enters[None, :, None, None], g, 0.0)
        beta = jnp.where(enters[None, :, None], beta, 0.0)
    if s == 1:
        step = q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state
        kernel = _pallas.kda_step_kernel(state)
        if kernel is None:
            o, state = kda_step(*step)
        else:       # one pass over the state, in place
            with jax.named_scope('state_write'):
                o, state = kernel(*step)
        return o[:, None], state, conv
    o, state = kda_chunked(q, k, v, g, beta, state,
                           min(chunk, -(-s // KDA_BLOCK) * KDA_BLOCK))
    return o, state, conv


class KimiDeltaAttention(Layer):
    def __init__(self, config: Ling3Config):
        super().__init__()
        self.config = config
        h, nh, d = (config.hidden_size, config.num_attention_heads,
                    config.head_dim)
        self.taps = config.short_conv_kernel_size
        self.q_proj = _col_linear(config, h, nh * d)
        self.k_proj = _col_linear(config, h, nh * d)
        self.v_proj = _col_linear(config, h, nh * d)
        taps = I.Normal(0.0, 0.02)
        for name in ('q_conv', 'k_conv', 'v_conv'):     # depthwise
            setattr(self, name, self.create_parameter(
                (nh * d, self.taps), default_initializer=taps))
        # the decay's gate, full rank (`no_kda_lora`), and its scale and
        # bias; the update's strength, a head
        self.f_proj = _col_linear(config, h, nh * d)
        self.A_log = self.create_parameter(
            (nh,), default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            (nh * d,), default_initializer=I.Constant(0.0))
        self.b_proj = Linear(h, nh, bias_attr=False)
        # the output's norm over a head and its gate, full rank
        self.g_proj = _col_linear(config, h, nh * d)
        self.o_norm = RMSNorm(d, epsilon=config.rms_norm_eps)
        self.o_proj = _row_linear(config, nh * d, h)

    def init_state(self, batch_size):
        """The entry of a sequence that has no past: zeros."""
        cfg = self.config
        nh, d = cfg.num_attention_heads, cfg.head_dim
        return {'S': jnp.zeros((batch_size, nh, d, d), jnp.float32),
                'conv': jnp.zeros((batch_size, self.taps - 1, 3 * nh * d),
                                  jnp.float32)}

    def forward(self, hidden, state=None):
        """`state` None: a whole sequence from its start, nothing kept.
        Else -> (output, the entry as `generation.state_scope` says)."""
        cfg = self.config
        nh, d = cfg.num_attention_heads, cfg.head_dim
        bsz, s = hidden.shape[0], hidden.shape[1]
        past = state if state is not None else jax.tree_util.tree_map(
            Tensor, self.init_state(bsz))
        n = folded_tokens(s)
        # an op INPUT, not a closure capture (see `LlamaAttention`'s rope)
        folded = Tensor(jnp.asarray(n, jnp.int32))
        o, new_s, new_conv = apply_op(
            kda_mix, self.q_proj(hidden), self.k_proj(hidden),
            self.v_proj(hidden), self.f_proj(hidden), self.b_proj(hidden),
            self.q_conv, self.k_conv, self.v_conv, self.A_log, self.dt_bias,
            past['S'], past['conv'], folded, _name='kda_mix',
            lower=cfg.kda_lower_bound, chunk=KDA_CHUNK,
            fold_all=isinstance(n, int))
        out = apply_op(
            lambda t, gate: (t * jax.nn.sigmoid(gate).reshape(t.shape)
                             ).reshape(bsz, s, nh * d),
            self.o_norm(o), self.g_proj(hidden), _name='kda_out_gate')
        out = self.o_proj(out)
        if state is None:
            return out
        return out, {'S': new_s, 'conv': new_conv}


class Ling3DecoderLayer(DeepseekV3DecoderLayer):
    """`DeepseekV3DecoderLayer` with the mixer its place in the pattern
    gives: latent attention (that layer's own block) or KDA."""

    def mixer(self, config, layer_idx):
        self.is_attention = config.layer_types[layer_idx] == MLA
        return DeepseekV3Attention(config) if self.is_attention \
            else KimiDeltaAttention(config)

    def attention_block(self, hidden, keep=None, cache=None, **kwargs):
        if self.is_attention:
            return super().attention_block(hidden, cache=cache, **kwargs)
        with jax.named_scope('norm'):
            h = self.input_layernorm(hidden)
        with jax.named_scope('kda'):
            if keep is not None:        # a pad's input is no input
                h = h * keep
            out = self.self_attn(h, state=cache)
        return out if cache is not None else (out, None)

    def forward(self, hidden, keep=None, cache=None, **kwargs):
        out, new_cache = self.attention_block(hidden, keep=keep, cache=cache,
                                              **kwargs)
        h = hidden + out
        h = h + self.mlp_block(h)
        if cache is not None:
            return h, new_cache
        return h


class Ling3Model(DeepseekV3Model):
    """embed -> N decoder layers -> the final RMSNorm; the cache has a
    state entry where the layer is KDA and a latent entry where it
    attends."""

    config_class = Ling3Config
    layer_class = Ling3DecoderLayer

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, cache_offset=None):
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(to_jax(input_ids))
        with jax.named_scope('embed'):
            # float32 from here on, whatever the parameters are stored in
            h = self.embed_tokens(ids).astype('float32')
        mask = attention_mask
        if mask is not None and not isinstance(mask, Tensor):
            mask = Tensor(to_jax(mask))
        keep = None
        if mask is not None and len(mask.shape) == 2:
            # [B, S] padding mask: attention gets it as [B, 1, 1, S]
            # boolean, a KDA layer zeroes the pads' inputs with it
            keep = apply_op(lambda m: (m > 0)[:, :, None].astype(
                jnp.float32), mask, _name='pad_keep')
            mask = apply_op(
                lambda m: (m > 0)[:, None, None, :], mask, _name='pad_mask')
        new_caches = []
        for i, layer in enumerate(self.layers):
            layer_cache = None if cache is None else jax.tree_util.tree_map(
                lambda c: c if isinstance(c, Tensor) else Tensor(c),
                cache[i], is_leaf=lambda c: isinstance(c, Tensor))
            out = layer(h, position_offset=position_offset, attn_mask=mask,
                        keep=keep, cache=layer_cache,
                        cache_offset=cache_offset)
            if layer_cache is not None:
                h, c = out
                new_caches.append(c)
            else:
                h = out
        with jax.named_scope('norm'):
            h = self.norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        """One entry a layer: the latent pair of `max_length` rows on an
        attending layer (`DeepseekV3Model.init_cache`'s); on a KDA layer
        the state entry, float32 whatever `dtype` the rows are kept in,
        and of no length."""
        latent = super().init_cache(batch_size, max_length, dtype)
        return tuple(entry if layer.is_attention
                     else layer.self_attn.init_state(batch_size)
                     for layer, entry in zip(self.layers, latent))


class Ling3ForCausalLM(DeepseekV3ForCausalLM):
    config_class = Ling3Config
    model_class = Ling3Model

    def scan_chunks(self, tokens):
        """Chunks ONE KDA layer scans, one after another, in a call of
        `tokens` tokens (a whole prefill's bucket), under the name the
        serving engine says it by on `serving.prefill`, beside what the
        expert layers say (`afmoe.expert_kernel_layers`)."""
        return {'kda_chunks': -(-tokens // KDA_CHUNK),
                **super().scan_chunks(tokens)}

    def state_kernel_layers(self, cache, slots):
        """How many of `cache`'s state entries a decode sub-step of
        `slots` sequences updates as ONE kernel (`ops.pallas.
        kda_step_kernel`, asked with the call `kda_mix` makes: the leaf
        `S` as held): what the serving engine says on
        `serving.decode_round`."""
        return sum(
            _pallas.kda_step_kernel(jax.ShapeDtypeStruct(
                (slots,) + tuple(cache[i]['S'].shape[1:]),
                cache[i]['S'].dtype)) is not None
            for i in state_layers(cache))

    def generate(self, input_ids, *args, attention_mask=None, **kwargs):
        if attention_mask is not None and \
                not bool(jnp.all(to_jax(attention_mask) > 0)):
            raise ValueError(
                'Ling3ForCausalLM.generate() takes no padded prompts: the '
                'batch path masks a pad out of attention, and a KDA '
                'layer\'s state has nothing to mask — the pad would be '
                'folded in. Generate each length on its own, or serve '
                'through InferenceEngine, which pads on the right and '
                'folds only the real tokens into the state')
        return super().generate(input_ids, *args, **kwargs)

    def speculative_generate(self, *args, **kwargs):
        raise NotImplementedError(
            'speculative decoding rejects a draft by moving the position '
            'back, and a KDA layer\'s state cannot be moved back: it '
            'needs a snapshot of the state per proposed token (ROADMAP)')
