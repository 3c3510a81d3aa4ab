"""Llama model family (RMSNorm + SwiGLU + RoPE + GQA).

Upstream analogue: PaddleNLP `paddlenlp/transformers/llama/modeling.py`
(LlamaModel / LlamaForCausalLM). TPU-native design notes:
- attention lowers to `F.scaled_dot_product_attention` (pallas flash
  kernel on TPU, fused XLA softmax chain elsewhere); GQA is expressed by
  keeping K/V at `num_key_value_heads` and letting the attention core
  broadcast groups — no materialised `repeat` in the model code.
- decode uses a static-shape KV cache `[B, L_total, H_kv, D]` updated
  with `lax.dynamic_update_slice` so generation never recompiles.
- everything routes through `apply_op`, so the same forward works on the
  eager tape (training/backward) and traced under `jax.jit`.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.common_layers import Linear
from ..nn.layer import Layer
from ..nn.norm import RMSNorm
from ..nn.common_layers import Embedding
from ..tensor import Tensor, apply_op, to_jax
from .generation import (GenerationMixin, as_offset as _as_offset,
                         attended_rows as _attended_rows,
                         decode_mask as _decode_mask,
                         offset_grid as _offset_grid,
                         update_kv_cache as _update_kv_cache)


class LlamaConfig:
    model_type = 'llama'

    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, tie_word_embeddings=False,
                 pad_token_id=0, bos_token_id=1, eos_token_id=2,
                 use_recompute=False, tensor_parallel=False,
                 sequence_parallel=False, **kwargs):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.pad_token_id = pad_token_id
        self.bos_token_id = bos_token_id
        self.eos_token_id = eos_token_id
        self.use_recompute = use_recompute
        self.tensor_parallel = tensor_parallel
        self.sequence_parallel = sequence_parallel
        for k, v in kwargs.items():
            setattr(self, k, v)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def llama2_7b(cls, **kw):
        return cls(vocab_size=32000, hidden_size=4096,
                   intermediate_size=11008, num_hidden_layers=32,
                   num_attention_heads=32, num_key_value_heads=32,
                   max_position_embeddings=4096, **kw)

    @classmethod
    def llama2_13b(cls, **kw):
        return cls(vocab_size=32000, hidden_size=5120,
                   intermediate_size=13824, num_hidden_layers=40,
                   num_attention_heads=40, num_key_value_heads=40, **kw)

    @classmethod
    def llama2_70b(cls, **kw):
        return cls(vocab_size=32000, hidden_size=8192,
                   intermediate_size=28672, num_hidden_layers=80,
                   num_attention_heads=64, num_key_value_heads=8, **kw)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized config (also used by CI smoke tests)."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 64)
        kw.setdefault('intermediate_size', 128)
        kw.setdefault('num_hidden_layers', 2)
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('num_key_value_heads', 2)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)


def _rope(x, positions, theta, rotary_dim=None, inv_freq=None):
    """Rotary embedding, rotate-half convention. x: [B, S, H, D] raw array,
    positions: [S] or [B, S] raw int array. `rotary_dim` (None: all of
    D) is how many LEADING dims of a head rotate, rotate-half within
    them; the others pass as they are (partial rotary). `inv_freq`
    (`[D/2]` float32; None: `theta ** (-2i/D)`, the plain table) is the
    angle a position turns each pair by, for a caller whose positions
    are scaled (`deepseek_v3.yarn_inv_freq`); `theta` is then unread."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [_rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)) \
        if inv_freq is None else inv_freq
    pos = positions.astype(jnp.float32)
    freqs = pos[..., None] * inv                      # [..., S, D/2]
    while freqs.ndim < 3:
        freqs = freqs[None]                           # [B(1), S, D/2]
    cos = jnp.cos(freqs)[:, :, None, :]               # [B, S, 1, D/2]
    sin = jnp.sin(freqs)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _col_linear(config, in_f, out_f):
    """Plain Linear, or mp-column-sharded when config.tensor_parallel
    (upstream: tensor_parallel_degree>1 swaps in fleet's parallel layers)."""
    if config.tensor_parallel:
        from ..distributed.parallel_layers import ColumnParallelLinear
        return ColumnParallelLinear(in_f, out_f, has_bias=False,
                                    gather_output=False)
    return Linear(in_f, out_f, bias_attr=False)


def _row_linear(config, in_f, out_f):
    if config.tensor_parallel:
        from ..distributed.parallel_layers import RowParallelLinear
        return RowParallelLinear(in_f, out_f, has_bias=False,
                                 input_is_parallel=True)
    return Linear(in_f, out_f, bias_attr=False)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_key_value_heads = config.num_key_value_heads
        self.head_dim = hd
        self.q_proj = _col_linear(config, h, self.num_heads * hd)
        self.k_proj = _col_linear(config, h,
                                  self.num_key_value_heads * hd)
        self.v_proj = _col_linear(config, h,
                                  self.num_key_value_heads * hd)
        self.o_proj = _row_linear(config, self.num_heads * hd, h)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        cfg = self.config
        offset = _as_offset(position_offset)
        # cache_offset = SLOT index in the static cache (scalar, or [B]
        # per-row slots for the serving engine's slot pool);
        # position_offset = LOGICAL position for RoPE (scalar or [B] for
        # left-padded prompts). They coincide for unpadded prompts.
        slot = _as_offset(cache_offset) if cache_offset is not None \
            else offset
        nh, nkv, hd = self.num_heads, self.num_key_value_heads, self.head_dim
        theta = cfg.rope_theta

        q = apply_op(
            lambda v: v.reshape(v.shape[0], v.shape[1], nh, hd),
            self.q_proj(hidden), _name='split_heads')
        k = apply_op(
            lambda v: v.reshape(v.shape[0], v.shape[1], nkv, hd),
            self.k_proj(hidden), _name='split_heads')
        v = apply_op(
            lambda v_: v_.reshape(v_.shape[0], v_.shape[1], nkv, hd),
            self.v_proj(hidden), _name='split_heads')

        # offset rides as an op INPUT (int tensor), not a closure capture:
        # a captured jax scalar would make every rope call uncacheable in
        # the eager dispatch cache
        def rope_q(qv, off):
            pos = _offset_grid(off, qv.shape[1])
            return _rope(qv, pos, theta)
        off_t = offset if isinstance(offset, Tensor) else Tensor(offset)
        q = apply_op(rope_q, q, off_t, _name='rope')
        k = apply_op(rope_q, k, off_t, _name='rope')

        if cache is None:
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=True)
        else:
            with jax.named_scope('kv_write'):
                k_cache, v_cache = _update_kv_cache(cache[0], cache[1],
                                                    k, v, slot)
            # a caller-built mask (padded-prompt decode) wins over the
            # default slot-causal one
            mask = attn_mask if attn_mask is not None \
                else _decode_mask(q, k_cache, slot)
            out = F.scaled_dot_product_attention(
                q, *_attended_rows(k_cache, v_cache, mask), attn_mask=mask)
        out = apply_op(
            lambda t: t.reshape(t.shape[0], t.shape[1], nh * hd),
            out, _name='merge_heads')
        out = self.o_proj(out)
        if cache is not None:
            return out, (k_cache, v_cache)
        return out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = _col_linear(config, h, i)
        self.up_proj = _col_linear(config, h, i)
        self.down_proj = _row_linear(config, i, h)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        # the named scopes are the device trace's vocabulary
        # (programs.scopes.SCOPES): every op's `op_name` carries them
        residual = hidden
        with jax.named_scope('norm'):
            h = self.input_layernorm(hidden)
        with jax.named_scope('attention'):
            attn_out = self.self_attn(
                h, position_offset=position_offset, attn_mask=attn_mask,
                cache=cache, cache_offset=cache_offset)
        new_cache = None
        if cache is not None:
            attn_out, new_cache = attn_out
        h = residual + attn_out
        with jax.named_scope('norm'):
            normed = self.post_attention_layernorm(h)
        with jax.named_scope('mlp'):
            h = h + self.mlp(normed)
        if cache is not None:
            return h, new_cache
        return h


class LlamaPretrainedModel(Layer):
    config_class = LlamaConfig
    base_model_prefix = 'llama'


class LlamaModel(LlamaPretrainedModel):
    """Reference parity: paddlenlp LlamaModel (embed → N decoder layers →
    final RMSNorm)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel:
            from ..distributed.parallel_layers import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                       config.hidden_size)
        else:
            self.embed_tokens = Embedding(config.vocab_size,
                                          config.hidden_size)
        self.layers = [LlamaDecoderLayer(config)
                       for _ in range(config.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f'layers.{i}', l)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, blocks_fn=None,
                cache_offset=None):
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(to_jax(input_ids))
        with jax.named_scope('embed'):
            h = self.embed_tokens(ids)
        if blocks_fn is not None:
            # pipeline-parallel path (fleet.DistTrainStep pp): the decoder
            # stack is replaced by a scheduled collective program; embed
            # and final norm stay outside the pipelined region.
            if attention_mask is not None or cache is not None \
                    or position_offset is not None:
                raise ValueError('blocks_fn (pipeline) path supports only '
                                 'full-length causal batches from position '
                                 '0 (mask/cache/offset unsupported)')
            h = apply_op(blocks_fn, h, _name='pp_blocks')
            with jax.named_scope('norm'):
                return self.norm(h)
        sp_pin = None
        if self.config.sequence_parallel:
            # keep activations sequence-sharded over 'sp' between blocks;
            # GSPMD gathers seq only where attention truly needs it
            from ..distributed.parallel_layers import _constraint
            from jax.sharding import PartitionSpec as P
            sp_pin = _constraint(P('dp', 'sp', None))
            h = sp_pin(h)
        mask = attention_mask
        if mask is not None and not isinstance(mask, Tensor):
            mask = Tensor(to_jax(mask))
        if mask is not None and len(mask.shape) == 2:
            # [B, S] padding mask -> [B, 1, 1, S] boolean
            mask = apply_op(
                lambda m: (m > 0)[:, None, None, :], mask, _name='pad_mask')
        from .. import autograd as _ag
        remat = (self.config.use_recompute and cache is None
                 and _ag._state.functional)
        new_caches = []
        for i, layer in enumerate(self.layers):
            layer_cache = None
            if cache is not None:
                kc, vc = cache[i]
                layer_cache = (
                    kc if isinstance(kc, Tensor) else Tensor(kc),
                    vc if isinstance(vc, Tensor) else Tensor(vc))
            if remat:
                # trade FLOPs for HBM: rematerialize the block in backward
                # (upstream: recompute_configs; here jax.checkpoint —
                # closed-over traced params are lifted and differentiated).
                # use_recompute='dots' keeps matmul outputs and recomputes
                # only elementwise chains; 'dots_no_batch' keeps only
                # weight-matmul outputs (batched attention dots at
                # b*h*s^2 would blow HBM) — the middle trade: backward
                # re-runs just attention + elementwise, so the remat
                # overhead drops from ~1/3 of model flops to a few %.
                policy = {
                    'dots': jax.checkpoint_policies.dots_saveable,
                    'dots_no_batch':
                        jax.checkpoint_policies
                        .dots_with_no_batch_dims_saveable,
                }.get(self.config.use_recompute)
                out = Tensor(jax.checkpoint(
                    lambda hv, l=layer: l(
                        Tensor(hv), position_offset=position_offset,
                        attn_mask=mask).value, policy=policy)(h.value))
            else:
                out = layer(h, position_offset=position_offset,
                            attn_mask=mask, cache=layer_cache,
                            cache_offset=cache_offset)
            if layer_cache is not None:
                h, c = out
                new_caches.append(c)
            else:
                h = out
            if sp_pin is not None:
                h = sp_pin(h)
        with jax.named_scope('norm'):
            h = self.norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        cfg = self.config
        dt = dtype or 'float32'
        shape = (batch_size, int(max_length), cfg.num_key_value_heads,
                 cfg.head_dim)
        return tuple(
            (jnp.zeros(shape, dt), jnp.zeros(shape, dt))
            for _ in range(cfg.num_hidden_layers))


class LlamaForCausalLM(LlamaPretrainedModel, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def _logits(self, h):
        with jax.named_scope('lm_head'):
            if self.lm_head is not None:
                return self.lm_head(h)
            w = self.llama.embed_tokens.weight
            return apply_op(lambda hv, wv: hv @ wv.T, h, w,
                            _name='tied_lm_head')

    def pp_blocks(self):
        """Pipeline-parallel protocol (consumed by fleet.DistTrainStep):
        (param-name prefix of the uniform decoder blocks, the block list).
        """
        return 'llama.layers', self.llama.layers

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, labels=None, blocks_fn=None,
                cache_offset=None):
        out = self.llama(input_ids, position_offset=position_offset,
                         attention_mask=attention_mask, cache=cache,
                         use_cache=use_cache, blocks_fn=blocks_fn,
                         cache_offset=cache_offset)
        if use_cache:
            h, new_cache = out
        else:
            h, new_cache = out, None
        logits = self._logits(h)
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
        return self.llama.init_cache(batch_size, max_length, dtype)
