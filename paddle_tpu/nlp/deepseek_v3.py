"""DeepSeek-V3-style causal LM (`model_type: deepseek_v3`; Kakao
Kanana-2-30B-A3B, 30B-A3B; `nlp/xing4.py` builds its layer from this
file's attention): multi-head LATENT attention, whose cache is a row of
`kv_lora_rank + qk_rope_head_dim` numbers a token shared by every head,
and sparse SwiGLU experts behind a sigmoid router plus a shared MLP.

What it is made of, and where that lives:

- `x0 = E[ids]`; a layer is `x += attn(N_in(x))`, `x += f(N_post(x))`
  (two RMSNorms a layer); `logits = N_final(x) W_head` (untied).
- attention (`DeepseekV3Attention`, here), `a = N_in(x)`, H heads:
  `q = a W_q` or, where the configuration gives a `q_lora_rank`, the
  COMPRESSED query `q = RMSNorm_q(a W_qa) W_qb`; a head `[q_nope ;
  q_rope]`; `[c' ; r'] = a W_kva`, ONE of each a token; `c =
  RMSNorm_kv(c')`; `[k_nope_h ; v_h] = c W_kvb`; rotary positions on
  `q_rope` and on `r'` (`llama._rope`, rotate-half; where
  `rope_interleave`, the dims are stored as interleaved pairs and
  de-interleaved first, the same permutation on both, so every `q_rope
  . r` is what a pairwise rotation gives); `k_h = [k_nope_h ; r]`;
  logits over `sqrt(qk_nope_head_dim + qk_rope_head_dim)`, causal.
- positions are plain (`rope_scaling` null) or YaRN's (`rope_scaling.
  type: yarn`, the public DeepSeek-V3 rule; `yarn_inv_freq`): the
  pairs that turn more than `beta_fast` times over the original length
  keep `theta^(-2i/d)`, those that turn less than `beta_slow` times take
  it over `factor`, a linear ramp between; the rotated dims times
  `mscale(factor, mscale) / mscale(factor, mscale_all_dim)` and the
  LOGITS times `mscale(factor, mscale_all_dim)^2`, `mscale(s, a) = 0.1
  a ln s + 1` (`DeepseekV3Config.softmax_scale`: the einsums and the
  kernel alike take the configuration's scale). Any other
  `rope_scaling.type` is refused by name.
- `f` of the first `first_k_dense_replace` layers is `nlp/llama.py`'s
  SwiGLU; of the others `nlp/afmoe.py`'s expert layer: `s = sigmoid(m
  W_r)` in float32, `sel = top_k(s + bias)` (the bias selects only;
  with `n_group` > 1 inside the `topk_group` best groups, `afmoe.route`),
  `w = s[sel] / (sum s[sel] + 1e-20) * routed_scaling_factor`, plus the
  `n_shared_experts` shared experts as ONE unweighted MLP of their
  summed width; no token dropped.
- where the configuration asks (`attention_output_gate: head_wise`;
  `nlp/ling3.py`), each head's output is multiplied by one sigmoid of
  the layer's input before `o_proj`, on every path below.

**The cache is latent** (`init_cache`, `generation.latent_layers`): an
entry is `(c [B, L, kv_lora_rank], r [B, L, qk_rope_head_dim])` — rows
like K and V (position p in row p, hidden above a position by a mask,
shareable up to one, rewindable), and NO head axis: `c` after its norm,
`r` after its rotation. Two leaves and not one of their summed width:
the value product reads `c` alone, and a slice of a wider leaf is a copy
of the leaf where the compiler does not fuse it.

**Two attention paths, chosen by what the call is** (`forward`):
(i) a call that STARTS AN EMPTY SLOT — no cache at all, or a cache with
the literal slot 0 and no mask, which is how a whole prefill arrives
(`serving.engine._prefill_fn`, `GenerationMixin.generate`): every row a
query may see is one the call itself brings, so it attends over its OWN
tokens with `k_h` and `v_h` made from its `c` and `r`, in blocks of
`PREFILL_QUERY_BLOCK` queries one after another, block `i` against the
keys up to its own last row and no further (`[H, block, (i + 1) x
block]` scores, one block's live at once; the half of bucket x bucket
above the diagonal is never computed), and never reads the slot it
writes;
(ii) any call AGAINST ROWS HELD (a decode sub-step, speculation's k+1
rows, a prefill chunk): absorbed. With `W_kvb = [W_UK_h ; W_UV_h]` a
head, `q_nope_h . k_nope_jh = (q_nope_h W_UK_h^T) . c_j`, so `qL_h =
q_nope_h W_UK_h^T`, logits `(qL_h . c_j + q_rope_h . r_j) /
sqrt(nope + rope)`, `oL_h = sum_j p_ij c_j`, `o_h = oL_h W_UV_h`: the
rows are read as they lie and no K or V is ever made from them.
What runs the middle of that — logits, softmax, `oL` — is picked from
the call alone (`ops.pallas.latent_decode_kernel`, no flag and no
name): ONE query a slot with a boolean mask on a TPU, which is a decode
sub-step of either decode block, goes through the Mosaic kernel
`pallas_kernels.mla_decode_attention`, which walks each slot's row
tiles up to the last row the mask shows and reads a tile once for the
scores and the values (XLA's two einsums stream every row of every slot
twice); speculation's k+1 rows, a prefill chunk, a prefix attach, an
additive mask and every other backend are the einsums. The two
products with `W_kvb` stay XLA's either way (scope `latent_absorb`).

Activations are float32 and products three bf16 passes
(`afmoe.ACTIVATION_PRECISION`, and why, at `AfmoeForCausalLM.forward`:
this router is that one, and picks 6 of 128). Served, not trained.
"""
from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.layer import Layer
from ..nn import functional as F
from ..nn.common_layers import Embedding, Linear
from ..nn.norm import RMSNorm
from ..ops import pallas as _pallas
from ..tensor import Tensor, apply_op, to_jax
from .afmoe import ACTIVATION_PRECISION, AfmoeSparseMLP, expert_kernel_layers
from .generation import (GenerationMixin, active_rows as _active_rows,
                         as_offset as _as_offset,
                         attended_rows as _attended_rows,
                         decode_mask as _decode_mask,
                         offset_grid as _offset_grid,
                         update_kv_cache as _update_kv_cache)
from .llama import LlamaMLP, _col_linear, _rope, _row_linear

# queries scored at once by a call that attends over its own tokens
PREFILL_QUERY_BLOCK = 1024

_NEG = float(jnp.finfo(jnp.float32).min)


_TINY_YARN = dict(
    q_lora_rank=12, rope_theta=100.0,
    rope_scaling={'type': 'yarn', 'factor': 8, 'beta_fast': 4,
                  'beta_slow': 1, 'mscale': 1.0, 'mscale_all_dim': 0.5,
                  'original_max_position_embeddings': 16})


class DeepseekV3Config:
    model_type = 'deepseek_v3'

    def __init__(self, vocab_size=128256, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=768,
                 num_hidden_layers=48, first_k_dense_replace=1,
                 moe_layer_freq=1, num_attention_heads=32,
                 num_key_value_heads=None, q_lora_rank=None,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=1000000.0,
                 rope_interleave=True, rope_scaling=None,
                 n_routed_experts=128, n_shared_experts=2,
                 num_experts_per_tok=6, norm_topk_prob=True,
                 routed_scaling_factor=2.448, scoring_func='sigmoid',
                 topk_method='noaux_tc', n_group=1, topk_group=1,
                 attention_output_gate=None,
                 rms_norm_eps=1e-6, attention_bias=False,
                 max_position_embeddings=32768, tie_word_embeddings=False,
                 pad_token_id=0, bos_token_id=1, eos_token_id=2,
                 tensor_parallel=False, **kwargs):
        if q_lora_rank is not None and int(q_lora_rank) < 1:
            raise ValueError(
                f'q_lora_rank {q_lora_rank!r}: null (no query compression) '
                'or the width of the compressed query')
        kind = None if rope_scaling is None else rope_scaling.get(
            'type', rope_scaling.get('rope_type'))
        if rope_scaling is not None and kind != 'yarn':
            raise ValueError(
                f'rope_scaling type {kind!r}: plain rotary positions '
                '(null) and YaRN (yarn) are implemented, nothing else')
        if scoring_func != 'sigmoid' or topk_method != 'noaux_tc':
            raise ValueError(
                f'scoring_func {scoring_func!r} / topk_method '
                f'{topk_method!r}: only the sigmoid router with a '
                'selection bias (noaux_tc) is implemented')
        check_route_groups(n_routed_experts, num_experts_per_tok, n_group,
                           topk_group)
        if attention_output_gate not in (None, 'head_wise'):
            raise ValueError(
                f'attention_output_gate {attention_output_gate!r}: no gate '
                '(null) and one sigmoid a head (head_wise) are implemented')
        if moe_layer_freq != 1:
            raise ValueError('moe_layer_freq: every layer after the dense '
                             'ones is an expert layer (1), nothing else is '
                             'implemented')
        if attention_bias or tie_word_embeddings:
            raise ValueError('attention_bias / tie_word_embeddings: the '
                             'family has neither, and neither is '
                             'implemented')
        if num_key_value_heads not in (None, num_attention_heads):
            raise ValueError('num_key_value_heads: latent attention makes '
                             'a K and V for every query head')
        if qk_rope_head_dim % 2:
            raise ValueError('qk_rope_head_dim: rotary positions need an '
                             'even number of dims')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = None if q_lora_rank is None else int(q_lora_rank)
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.qk_head_dim = qk_nope_head_dim + qk_rope_head_dim
        self.rope_theta = rope_theta
        self.rope_interleave = bool(rope_interleave)
        self.rope_scaling = None if rope_scaling is None \
            else dict(rope_scaling)
        # the logits' scale: 1/sqrt(qk) and, under YaRN, mscale^2 on it
        self.softmax_gain = 1.0 if rope_scaling is None else _yarn_mscale(
            rope_scaling['factor'],
            rope_scaling.get('mscale_all_dim', 0)) ** 2
        self.softmax_scale = self.softmax_gain / math.sqrt(self.qk_head_dim)
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.attention_output_gate = attention_output_gate
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.tie_word_embeddings = False
        self.pad_token_id = pad_token_id
        self.bos_token_id = bos_token_id
        self.eos_token_id = eos_token_id
        self.tensor_parallel = tensor_parallel
        # under the names `afmoe.py`'s expert layer reads
        self.num_experts = n_routed_experts
        self.route_norm = norm_topk_prob
        self.route_scale = 1.0 if routed_scaling_factor is None \
            else routed_scaling_factor
        self.num_shared_experts = int(n_shared_experts or 0)
        for k, v in kwargs.items():
            setattr(self, k, v)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized: one dense layer and two expert layers; 4 heads of
        nope 8 / rope 4 / v 8 over a latent of 16, the rotary dims
        interleaved; 8 experts top-2 + a shared MLP of two experts'
        width, the published routing scale."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 64)
        kw.setdefault('intermediate_size', 96)
        kw.setdefault('moe_intermediate_size', 16)
        kw.setdefault('num_hidden_layers', 3)
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('kv_lora_rank', 16)
        kw.setdefault('qk_nope_head_dim', 8)
        kw.setdefault('qk_rope_head_dim', 4)
        kw.setdefault('v_head_dim', 8)
        kw.setdefault('n_routed_experts', 8)
        kw.setdefault('num_experts_per_tok', 2)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)

    @classmethod
    def tiny_yarn(cls, **kw):
        """`tiny()` with a COMPRESSED query (rank 12) and YaRN positions
        stretched 8 times over 16 original positions: at theta 100 the
        two rotary pairs straddle the ramp, so a test at 64 positions
        reads every part of the rule; mscale and mscale_all_dim differ,
        so both factors show."""
        return cls.tiny(**{**_TINY_YARN, **kw})

    @classmethod
    def tiny_wide_v(cls, **kw):
        """`tiny()` with V WIDER than the un-rotated part of K (12
        against 8), the rotary dims stored as halves, one shared expert
        and two dense layers: neither a width, nor the storage order of
        the rotary dims, nor where the expert layers start may be baked
        in."""
        kw.setdefault('v_head_dim', 12)
        kw.setdefault('rope_interleave', False)
        kw.setdefault('n_shared_experts', 1)
        kw.setdefault('first_k_dense_replace', 2)
        return cls.tiny(**kw)


def check_route_groups(routed, k, n_group, topk_group):
    """A group limit the router can keep (`afmoe.route`): whole groups,
    no more kept than there are, and room for the k picks inside them."""
    if n_group < 1 or routed % n_group or not 1 <= topk_group <= n_group \
            or routed // n_group < 2 or topk_group * (routed // n_group) < k:
        raise ValueError(
            f'n_group {n_group} / topk_group {topk_group}: {routed} experts '
            f'do not divide into groups of two or more of which '
            f'{topk_group} hold {k} picks')


def _yarn_mscale(factor, mscale):
    """YaRN's magnitude correction for a stretch of `factor`."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim, theta, factor, original_max_position_embeddings,
                  beta_fast=32, beta_slow=1, **_):
    """`[dim/2]` float32: the angle a position turns each rotary pair by
    under YaRN (the public DeepSeek-V3 rule). With `f = theta^(-2i/dim)`
    as `llama._rope` makes it, pair i keeps `f` below `low`, takes `f /
    factor` above `high`, and in between `f/factor * ramp + f * (1 -
    ramp)`, `ramp = (i - low) / (high - low)`; `low` / `high` are the
    pairs that turn `beta_fast` / `beta_slow` times over the original
    length, rounded outwards. Written as `f * (1 - ramp * (1 - 1/factor))`
    — the same number — so that `factor` 1 gives `f` bit for bit."""
    def turns(n):       # the (fractional) pair that turns n times
        return dim * math.log(original_max_position_embeddings
                              / (n * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), dim - 1)
    span = (high - low) or 0.001
    f = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    return f * (1.0 - ramp * (1.0 - 1.0 / factor))


def _rotary(t, positions, theta, interleave, scaling=None):
    """Rotary positions on `t` `[B, S, heads, rope]`. Where the dims are
    stored as interleaved pairs (`rope_interleave`) they are brought to
    halves first, `[x0, x2, .., x1, x3, ..]`, and stay so: the same
    permutation on a query and on the shared key leaves every product
    of the two what a rotation of the pairs `(x_2i, x_2i+1)` gives.
    `scaling` (a YaRN `rope_scaling`, or None) gives the angles and the
    factor on the rotated dims."""
    if interleave:
        t = jnp.concatenate([t[..., 0::2], t[..., 1::2]], axis=-1)
    if scaling is None:
        return _rope(t, positions, theta)
    out = _rope(t, positions, theta,
                inv_freq=yarn_inv_freq(t.shape[-1], theta, **scaling))
    factor = scaling['factor']
    gain = _yarn_mscale(factor, scaling.get('mscale', 1)) \
        / _yarn_mscale(factor, scaling.get('mscale_all_dim', 0))
    return out if gain == 1.0 else out * jnp.asarray(gain, out.dtype)


def _starts_empty_slot(cache_offset, attn_mask):
    """Whether a cached call brings every row its queries may see: the
    LITERAL slot 0 (a Python or NumPy integer — a constant of the
    caller's program; a traced value may be anything) and no mask. That
    is how a whole prefill arrives (`generation.cached_forward`'s
    contract). Anything else finds rows held."""
    return (attn_mask is None
            and isinstance(cache_offset, (int, np.integer))
            and int(cache_offset) == 0)


def own_tokens_pairs(tokens):
    """Query-key pairs ONE layer's `_own_tokens_attention` computes for
    a call of `tokens` tokens (a whole prefill's bucket): a block of
    queries against the keys up to its own last row. What the serving
    engine says on `serving.prefill` beside the pairs a causal mask
    lets through."""
    blk = PREFILL_QUERY_BLOCK
    return sum((min(first + blk, tokens) - first) * min(first + blk, tokens)
               for first in range(0, tokens, blk))


def _own_tokens_attention(q, k, v, mask, gain=1.0):
    """Causal attention of a call over its OWN S tokens: q, k `[B, S, H,
    qk]`, v `[B, S, H, v]` -> `[B, S, H, v]`; `mask` None or a caller's
    boolean `[B, 1, 1, S]` of keys that are no padding; logits over
    `sqrt(qk)` times `gain` — `ops.pallas.flash_attention` scales by
    `1/sqrt(qk)` and takes no other, so what a configuration has beside
    that (`softmax_gain`) is put on the queries. More than
    `PREFILL_QUERY_BLOCK` queries go block after block, and a block is
    scored against the keys UP TO ITS OWN LAST ROW and no further (a
    static prefix of k, v and the mask: what lies above the diagonal is
    never computed; `own_tokens_pairs` counts what is). Each block
    waits for the one before it, so that one block's `[B, H, block,
    keys]` scores are all that live."""
    if gain != 1.0:
        q = q * jnp.asarray(gain, q.dtype)
    s, blk = q.shape[1], PREFILL_QUERY_BLOCK
    if s <= blk:
        return _pallas.flash_attention(q, k, v, mask=mask, causal=True)
    outs = []
    for first in range(0, s, blk):
        last = min(first + blk, s)
        qi = q[:, first:last]
        if outs:        # after the block before it, not beside it
            qi, outs[-1] = jax.lax.optimization_barrier((qi, outs[-1]))
        # causal with fewer queries than keys: the last query sees the
        # last key (`_attention_xla`'s alignment)
        outs.append(_pallas.flash_attention(
            qi, k[:, :last], v[:, :last],
            mask=None if mask is None else mask[..., :last], causal=True))
    return jnp.concatenate(outs, axis=1)


def _latent_attention(q_nope, q_rope, c, r, w_kvb, mask, scale):
    """Attention over latent rows, absorbed: q_nope `[B, Sq, H, nope]`,
    q_rope `[B, Sq, H, rope]`, rows c `[B, L, C]` (normed) and r `[B, L,
    rope]` (rotated), `w_kvb` `[C, H, nope + v]`, `mask` (boolean, or
    additive) broadcastable to `[B, 1, Sq, L]` -> `[B, Sq, H, v]`. Every head
    reads the same rows; the softmax is float32.

    Where `ops.pallas.latent_decode_kernel` takes the call (one query a
    slot, a boolean mask, a TPU: its conditions are the whole choice),
    the rows go through ONE kernel that walks a slot's row tiles up to
    the last row the mask shows and reads each once for the scores and
    the values; a slot that is not decoding (`generation.active_rows`)
    is bounded at nothing — its output is discarded — and walks one
    tile. Everything else is XLA's two einsums over every row the mask
    has a column for."""
    nope = q_nope.shape[-1]
    kernel = _pallas.latent_decode_kernel(q_nope, c, mask)
    with jax.named_scope('latent_absorb'):
        q_lat = jnp.einsum('bqhn,chn->bqhc', q_nope,
                           w_kvb[..., :nope].astype(q_nope.dtype))
    if kernel is not None:
        seen = jnp.broadcast_to(mask[:, 0, 0], (c.shape[0], mask.shape[-1]))
        active = _active_rows()
        if active is not None:
            seen = seen & active[:, None]
        o_lat = kernel(q_lat[:, 0], q_rope[:, 0], c, r, seen,
                       scale)[:, None].astype(q_nope.dtype)
    else:
        logits = (jnp.einsum('bqhc,bkc->bhqk', q_lat, c,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum('bqhr,bkr->bhqk', q_rope, r,
                               preferred_element_type=jnp.float32)) * scale
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, _NEG)
        else:
            logits = logits + mask.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1).astype(q_nope.dtype)
        o_lat = jnp.einsum('bhqk,bkc->bqhc', probs, c)
    with jax.named_scope('latent_absorb'):
        return jnp.einsum('bqhc,chv->bqhv', o_lat,
                          w_kvb[..., nope:].astype(o_lat.dtype))


class DeepseekV3Attention(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        h, nh = config.hidden_size, config.num_attention_heads
        if config.q_lora_rank is None:
            self.q_proj = _col_linear(config, h, nh * config.qk_head_dim)
        else:       # the compressed query: down, norm, up by head
            self.q_a_proj = Linear(h, config.q_lora_rank, bias_attr=False)
            self.q_a_layernorm = RMSNorm(config.q_lora_rank,
                                         epsilon=config.rms_norm_eps)
            self.q_b_proj = _col_linear(config, config.q_lora_rank,
                                        nh * config.qk_head_dim)
        self.kv_a_proj_with_mqa = Linear(
            h, config.kv_lora_rank + config.qk_rope_head_dim,
            bias_attr=False)
        self.kv_a_layernorm = RMSNorm(config.kv_lora_rank,
                                      epsilon=config.rms_norm_eps)
        self.kv_b_proj = _col_linear(
            config, config.kv_lora_rank,
            nh * (config.qk_nope_head_dim + config.v_head_dim))
        self.o_proj = _row_linear(config, nh * config.v_head_dim, h)
        # Gated Attention (arXiv:2505.06708), head-wise: one sigmoid a
        # head of the layer's input on the softmax-weighted sum, before
        # `o_proj`. Absent unless the configuration asks
        self.gate_proj = Linear(h, nh, bias_attr=False) if getattr(
            config, 'attention_output_gate', None) else None

    def _gated(self, out, hidden):
        """`out` [B, S, H, v] times `sigmoid(hidden W_gate)` [B, S, H]."""
        if self.gate_proj is None:
            return out
        return apply_op(lambda o, g: o * jax.nn.sigmoid(g)[..., None]
                        .astype(o.dtype), out, self.gate_proj(hidden),
                        _name='mla_head_gate')

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        cfg = self.config
        offset = _as_offset(position_offset)
        # cache_offset = SLOT in the static cache, position_offset = the
        # LOGICAL position (rotary); see LlamaAttention
        slot = _as_offset(cache_offset) if cache_offset is not None \
            else offset
        nh, lat = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rd, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
        theta, interleave = cfg.rope_theta, cfg.rope_interleave
        scaling, scale = cfg.rope_scaling, cfg.softmax_scale

        def rope(t, off):
            return _rotary(t, _offset_grid(off, t.shape[1]), theta,
                           interleave, scaling)

        def split_q(qv, off):
            qv = qv.reshape(qv.shape[0], qv.shape[1], nh, nope + rd)
            return qv[..., :nope], rope(qv[..., nope:], off)

        def split_kv(kv, off):
            return kv[..., :lat], rope(kv[..., None, lat:], off)[:, :, 0]
        off_t = offset if isinstance(offset, Tensor) else Tensor(offset)
        q = self.q_proj(hidden) if cfg.q_lora_rank is None \
            else self.q_b_proj(self.q_a_layernorm(self.q_a_proj(hidden)))
        q_nope, q_rope = apply_op(split_q, q, off_t, _name='mla_split_q')
        c, r = apply_op(split_kv, self.kv_a_proj_with_mqa(hidden), off_t,
                        _name='mla_split_kv')
        c = self.kv_a_layernorm(c)

        fresh = cache is None or _starts_empty_slot(cache_offset, attn_mask)
        if cache is not None:
            with jax.named_scope('kv_write'):
                c_cache, r_cache = _update_kv_cache(cache[0], cache[1],
                                                    c, r, slot)
        if fresh:
            # path (i): over the call's own tokens, K and V made from
            # its c and r; the slot it writes is never read
            def own(qn, qr, cv, rv, kv, *m):
                kv = kv.reshape(kv.shape[0], kv.shape[1], nh, nope + vd)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(rv[:, :, None], rv.shape[:2]
                                      + (nh, rd))], axis=-1)
                return _own_tokens_attention(
                    jnp.concatenate([qn, qr], axis=-1), k, kv[..., nope:],
                    m[0] if m else None, cfg.softmax_gain)
            out = apply_op(own, q_nope, q_rope, c, r, self.kv_b_proj(c),
                           *(() if attn_mask is None else (attn_mask,)),
                           _name='mla_own_tokens')
        else:
            # path (ii): absorbed, over the rows held. The kernel, where
            # it takes the call, is handed the leaves whole (it bounds
            # its own reading, and a slice of a leaf is a copy to it);
            # XLA's einsums the rows the mask has columns for
            mask = attn_mask if attn_mask is not None \
                else _decode_mask(q_nope, c_cache, slot)
            c_rows, r_rows = (c_cache, r_cache) \
                if _pallas.latent_decode_kernel(q_nope, c_cache, mask) \
                else _attended_rows(c_cache, r_cache, mask)

            def held(qn, qr, cv, rv, w, m):
                return _latent_attention(
                    qn, qr, cv, rv, w.reshape(lat, nh, nope + vd), m, scale)
            out = apply_op(held, q_nope, q_rope, c_rows, r_rows,
                           self.kv_b_proj.weight, mask, _name='mla_latent')
        out = apply_op(
            lambda t: t.reshape(t.shape[0], t.shape[1], nh * vd),
            self._gated(out, hidden), _name='merge_heads')
        out = self.o_proj(out)
        if cache is not None:
            return out, (c_cache, r_cache)
        return out


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, config: DeepseekV3Config, layer_idx: int):
        super().__init__()
        eps = config.rms_norm_eps
        self.self_attn = self.mixer(config, layer_idx)
        self.moe_enabled = layer_idx >= config.first_k_dense_replace
        self.mlp = AfmoeSparseMLP(config) if self.moe_enabled \
            else LlamaMLP(types.SimpleNamespace(
                hidden_size=config.hidden_size,
                intermediate_size=config.intermediate_size,
                tensor_parallel=config.tensor_parallel))
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=eps)

    def mixer(self, config, layer_idx):
        """What stands between the layer's first norm and the residual
        path: latent attention; a family that mixes kinds of layer
        (`nlp/ling3.py`) gives its own by `layer_idx`."""
        return DeepseekV3Attention(config)

    def attention_block(self, hidden, position_offset=None, attn_mask=None,
                        cache=None, cache_offset=None):
        """`attn(N_in(hidden))` -> (what it adds to the residual path,
        the layer's new cache entry or None). The two blocks are what a
        layer with another residual path (`nlp/xing4.py`) shares."""
        with jax.named_scope('norm'):
            h = self.input_layernorm(hidden)
        with jax.named_scope('attention'):
            out = self.self_attn(
                h, position_offset=position_offset, attn_mask=attn_mask,
                cache=cache, cache_offset=cache_offset)
        return out if cache is not None else (out, None)

    def mlp_block(self, hidden):
        """`f(N_post(hidden))`: the dense MLP or the expert layer."""
        with jax.named_scope('norm'):
            normed = self.post_attention_layernorm(hidden)
        if self.moe_enabled:        # its own scopes: moe/router, ...
            return self.mlp(normed)
        with jax.named_scope('mlp'):
            return self.mlp(normed)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        out, new_cache = self.attention_block(
            hidden, position_offset=position_offset, attn_mask=attn_mask,
            cache=cache, cache_offset=cache_offset)
        h = hidden + out
        h = h + self.mlp_block(h)
        if cache is not None:
            return h, new_cache
        return h


def _tensor(c):
    return c if isinstance(c, Tensor) else Tensor(c)


class DeepseekV3PretrainedModel(Layer):
    config_class = DeepseekV3Config
    base_model_prefix = 'model'


class DeepseekV3Model(DeepseekV3PretrainedModel):
    """embed -> N decoder layers -> the final RMSNorm. What the layers
    hand from one to the next is the hidden state `[B, S, h]`; a family
    whose residual path is wider (`nlp/xing4.py`: `[B, S, n, h]`) gives
    its own `layer_class` and the two ends of the path."""

    layer_class = DeepseekV3DecoderLayer

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = [self.layer_class(config, i)
                       for i in range(config.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f'layers.{i}', l)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, cache_offset=None):
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(to_jax(input_ids))
        with jax.named_scope('embed'):
            # float32 from here on, whatever the parameters are stored in
            h = self.embed_tokens(ids).astype('float32')
        h = self.residual_in(h)
        mask = attention_mask
        if mask is not None and not isinstance(mask, Tensor):
            mask = Tensor(to_jax(mask))
        if mask is not None and len(mask.shape) == 2:
            # [B, S] padding mask -> [B, 1, 1, S] boolean
            mask = apply_op(
                lambda m: (m > 0)[:, None, None, :], mask, _name='pad_mask')
        new_caches = []
        for i, layer in enumerate(self.layers):
            layer_cache = None
            if cache is not None:
                layer_cache = tuple(map(_tensor, cache[i]))
            out = layer(h, position_offset=position_offset, attn_mask=mask,
                        cache=layer_cache, cache_offset=cache_offset)
            if layer_cache is not None:
                h, c = out
                new_caches.append(c)
            else:
                h = out
        h = self.residual_out(h)
        with jax.named_scope('norm'):
            h = self.norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def residual_in(self, embedded):
        """The embedding as the first layer takes it."""
        return embedded

    def residual_out(self, hidden):
        """What the last layer hands on, as the final norm takes it."""
        return hidden

    def init_cache(self, batch_size, max_length, dtype=None):
        """One latent entry a layer: `(c [B, max_length, kv_lora_rank],
        r [B, max_length, qk_rope_head_dim])`, row = position, no head
        axis (`generation.latent_layers`)."""
        cfg = self.config
        dt = dtype or 'float32'
        lead = (batch_size, int(max_length))
        return tuple((jnp.zeros(lead + (cfg.kv_lora_rank,), dt),
                      jnp.zeros(lead + (cfg.qk_rope_head_dim,), dt))
                     for _ in self.layers)


class DeepseekV3ForCausalLM(DeepseekV3PretrainedModel, GenerationMixin):
    model_class = DeepseekV3Model

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.model = self.model_class(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, labels=None,
                cache_offset=None):
        with jax.default_matmul_precision(ACTIVATION_PRECISION):
            out = self.model(input_ids, position_offset=position_offset,
                             attention_mask=attention_mask, cache=cache,
                             use_cache=use_cache, cache_offset=cache_offset)
            h, new_cache = out if use_cache else (out, None)
            with jax.named_scope('lm_head'):
                logits = self.lm_head(h)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                (labels if isinstance(labels, Tensor)
                 else Tensor(to_jax(labels))).reshape([-1]))
            return (loss, logits, new_cache) if use_cache else (loss, logits)
        if use_cache:
            return logits, new_cache
        return logits

    def init_cache(self, batch_size, max_length, dtype=None):
        return self.model.init_cache(batch_size, max_length, dtype)

    # what a whole prefill's attention computes a layer, for the serving
    # engine to say on `serving.prefill`
    own_tokens_pairs = staticmethod(own_tokens_pairs)

    # the expert layers whose routed experts a whole prefill's program
    # runs as the grouped kernel, for the serving engine to say on
    # `serving.prefill` (`model.scan_chunks(bucket)`)
    scan_chunks = expert_kernel_layers
