"""MiMo-V2 causal LM (`model_type: mimo_v2`, Xiaomi MiMo-V2.5 /
MiMo-V2-Flash, 309B-A15B): window and full attention mixed five to one
with a cache geometry of its own per kind, sparse SwiGLU experts behind
a sigmoid router, of which a chip may hold a share.

Text path only: the multi-token-prediction layers and the vision and
audio towers of the published model are not in this file. What it is
made of, and where that lives:

- `x0 = E[ids]`; a layer is `x += attn(N_in(x))`, `x += f(N_post(x))`
  (two RMSNorms a layer, no QK norm); `logits = N_final(x) W_head`
  (untied).
- attention, both kinds (`MiMoV2Attention`, here): 64 query heads of
  192; K of 192 and V of **128** a KV head; 4 KV heads on a full layer,
  8 on a window layer; `v` scaled by `attention_value_scale` (the
  softmax is linear in V, so scaling V scales the output); rotary
  positions on the FIRST `int(head_dim * partial_rotary_factor)` dims of
  q and k (`llama._rope` with `rotary_dim`), theta `rope_theta` on a
  full layer and `swa_rope_theta` on a window layer; logits over
  `sqrt(head_dim)`. A full layer sees every earlier key; a window layer
  the `sliding_window` newest, the query's own among them. Where
  `add_swa_attention_sink_bias` / `add_full_attention_sink_bias` say so
  the layer has a SINK: one learned logit a query head, a column of the
  softmax that takes mass and gives no value
  (`ops.pallas._attention_xla`).
- `f` of a layer whose `moe_layer_freq` is 0 is `nlp/llama.py`'s SwiGLU;
  of the others `nlp/afmoe.py`'s expert layer with no shared expert:
  `s = sigmoid(m W_r)` in float32, `sel = top_k(s + bias)` (the bias
  selects only), `w = s[sel] / (sum s[sel] + 1e-20)`, no token dropped.
  The layer holds `n_routed_experts` experts from `first_expert` on, of
  the `num_routed_experts` its router scores (`AfmoeSparseMLP`).

**The cache has a geometry per layer** (`init_cache`): a full layer
keeps `(K [B, max_length, 4, 192], V [B, max_length, 4, 128])`, row =
position; a window layer keeps `(K [B, W, 8, 192], V [B, W, 8, 128])`
with `W = min(sliding_window, max_length)` rows, a RING: position p
lives in row `p mod W`, rotary already applied
(`generation.update_ring_cache`, `ring_mask`). A ring's rows cannot be
hidden by a mask of positions — a row written past the live position
has replaced one the window still needs — so a caller that forwards a
right-padded prompt says how many of its tokens may enter the ring
(`generation.state_scope`), as for a recurrent state. A window layer
takes no mask of positions from its caller: it derives what it sees
from the slot it is given.

Activations are float32 and products three bf16 passes
(`afmoe.ACTIVATION_PRECISION`, and why, at `AfmoeForCausalLM.forward`:
this router is that one, and picks 8 of 256). Served, not trained.

A FULL layer's decode sub-step on a TPU is `nlp/afmoe.py`'s kernel
bounded per slot (`generation.bounded_decode_attention`: K 192 wide
beside V 128, sixteen query heads a KV head) unless the layer has a
sink, which the kernel does not know; a ring is 128 rows read whole and
keeps `_attention_xla`, whatever it has.
"""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.common_layers import Embedding, Linear
from ..nn.norm import RMSNorm
from ..ops import pallas as _pallas
from ..tensor import Tensor, apply_op, to_jax
from .afmoe import (ACTIVATION_PRECISION, AfmoeSparseMLP, _narrow,
                    _window_mask, expert_kernel_layers)
from .generation import (GenerationMixin, as_offset as _as_offset,
                         attended_rows as _attended_rows,
                         bounded_decode_attention, bounded_decode_tile,
                         decode_mask as _decode_mask,
                         offset_grid as _offset_grid, ring_mask,
                         update_kv_cache as _update_kv_cache,
                         update_ring_cache)
from .llama import LlamaMLP, _col_linear, _rope, _row_linear

FULL, WINDOW = 0, 1


class MiMoV2Config:
    model_type = 'mimo_v2'

    def __init__(self, vocab_size=152576, hidden_size=4096,
                 intermediate_size=16384, moe_intermediate_size=2048,
                 num_hidden_layers=48, hybrid_layer_pattern=None,
                 moe_layer_freq=None, num_attention_heads=64,
                 num_key_value_heads=4, swa_num_key_value_heads=8,
                 head_dim=192, v_head_dim=128, partial_rotary_factor=0.334,
                 rope_theta=10000000.0, swa_rope_theta=10000.0,
                 sliding_window=128, attention_value_scale=0.707,
                 add_swa_attention_sink_bias=True,
                 add_full_attention_sink_bias=False,
                 n_routed_experts=256, num_routed_experts=None,
                 first_expert=0, num_experts_per_tok=8,
                 norm_topk_prob=True, routed_scaling_factor=None,
                 n_shared_experts=None, scoring_func='sigmoid',
                 n_group=1, topk_group=1, layernorm_epsilon=1e-5,
                 max_position_embeddings=1048576,
                 tie_word_embeddings=False, pad_token_id=0, bos_token_id=1,
                 eos_token_id=2, tensor_parallel=False, **kwargs):
        if scoring_func != 'sigmoid':
            raise ValueError(f'scoring_func {scoring_func!r}: only the '
                             'sigmoid router is implemented')
        if n_group != 1 or topk_group != 1:
            raise ValueError('n_group / topk_group: the published model '
                             'limits no group, and no limit is implemented')
        if n_shared_experts:
            raise ValueError('n_shared_experts: the published model has '
                             'none and none is implemented')
        if tie_word_embeddings:
            raise ValueError('mimo_v2 has an untied head')
        if hybrid_layer_pattern is None:
            # the published pattern: a full layer, then periods of five
            # window layers and a full one (layer 5 is full as well)
            hybrid_layer_pattern = [
                FULL if i == 0 or i % 6 == 5 else WINDOW
                for i in range(num_hidden_layers)]
        if moe_layer_freq is None:
            moe_layer_freq = [int(i > 0) for i in range(num_hidden_layers)]
        for name, pat in (('hybrid_layer_pattern', hybrid_layer_pattern),
                          ('moe_layer_freq', moe_layer_freq)):
            if len(pat) != num_hidden_layers or set(pat) - {0, 1}:
                raise ValueError(f'{name} must give 0 or 1 for every layer')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.hybrid_layer_pattern = [int(v) for v in hybrid_layer_pattern]
        self.moe_layer_freq = [int(v) for v in moe_layer_freq]
        # one character a layer: scalars, so they ride the program
        # store's statics (`describe_statics` keeps scalars only)
        self.layer_pattern = ''.join(
            'FW'[v] for v in self.hybrid_layer_pattern)
        self.moe_pattern = ''.join('DE'[v] for v in self.moe_layer_freq)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.head_dim = head_dim
        self.v_head_dim = v_head_dim
        self.partial_rotary_factor = partial_rotary_factor
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        if self.rotary_dim % 2:
            raise ValueError(f'int(head_dim * partial_rotary_factor) = '
                             f'{self.rotary_dim}: rotate-half needs an '
                             'even number of dims')
        self.rope_theta = rope_theta
        self.swa_rope_theta = swa_rope_theta
        self.sliding_window = int(sliding_window)
        self.attention_value_scale = attention_value_scale
        self.add_swa_attention_sink_bias = bool(add_swa_attention_sink_bias)
        self.add_full_attention_sink_bias = \
            bool(add_full_attention_sink_bias)
        # the experts HELD here, and of how many the router chooses
        self.n_routed_experts = n_routed_experts
        self.num_routed_experts = int(num_routed_experts
                                      or n_routed_experts)
        self.first_expert = int(first_expert)
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.layernorm_epsilon = layernorm_epsilon
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
        self.num_shared_experts = 0
        for k, v in kwargs.items():
            setattr(self, k, v)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized: the published order cut to `[full (dense), window,
        window, full]`; window 4; 4 q heads, 1 KV head on a full layer
        and 2 on a window layer; qk 12 / v 8 with 4 rotated; experts 4-7
        of a router over 16, top-2; sinks on the window layers."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 32)
        kw.setdefault('intermediate_size', 64)
        kw.setdefault('moe_intermediate_size', 16)
        kw.setdefault('num_hidden_layers', 4)
        kw.setdefault('hybrid_layer_pattern', [0, 1, 1, 0])
        kw.setdefault('moe_layer_freq', [0, 1, 1, 1])
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('num_key_value_heads', 1)
        kw.setdefault('swa_num_key_value_heads', 2)
        kw.setdefault('head_dim', 12)
        kw.setdefault('v_head_dim', 8)
        kw.setdefault('partial_rotary_factor', 0.334)
        kw.setdefault('sliding_window', 4)
        kw.setdefault('n_routed_experts', 4)
        kw.setdefault('num_routed_experts', 16)
        kw.setdefault('first_expert', 4)
        kw.setdefault('num_experts_per_tok', 2)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)

    @classmethod
    def tiny_window_first(cls, **kw):
        """Another order, `[window (dense), full, window]`, the sink on
        the FULL layers instead, every expert held: neither the order,
        nor which kind has the sink, nor the share may be baked in."""
        kw.setdefault('num_hidden_layers', 3)
        kw.setdefault('hybrid_layer_pattern', [1, 0, 1])
        kw.setdefault('moe_layer_freq', [0, 1, 1])
        kw.setdefault('add_swa_attention_sink_bias', False)
        kw.setdefault('add_full_attention_sink_bias', True)
        kw.setdefault('n_routed_experts', 16)
        kw.setdefault('num_routed_experts', 16)
        kw.setdefault('first_expert', 0)
        return cls.tiny(**kw)


def _attend(q, k, v, mask, sink, causal=False):
    """`ops.pallas.flash_attention` over Tensors; `mask` and `sink` ([H])
    may be None."""
    def f(qv, kv, vv, mv, sv):
        return _pallas.flash_attention(qv, kv, vv, mask=mv, causal=causal,
                                       sink=sv)
    return apply_op(f, q, k, v, mask, sink, _name='mimo_attention')


class MiMoV2Attention(Layer):
    def __init__(self, config: MiMoV2Config, layer_idx: int):
        super().__init__()
        self.config = config
        h, hd, vd = config.hidden_size, config.head_dim, config.v_head_dim
        window = config.hybrid_layer_pattern[layer_idx] == WINDOW
        self.num_heads = config.num_attention_heads
        self.num_key_value_heads = config.swa_num_key_value_heads \
            if window else config.num_key_value_heads
        # what a window layer has and a full one has not
        self.window = config.sliding_window if window else None
        self.theta = config.swa_rope_theta if window else config.rope_theta
        self.q_proj = _col_linear(config, h, self.num_heads * hd)
        self.k_proj = _col_linear(config, h, self.num_key_value_heads * hd)
        self.v_proj = _col_linear(config, h, self.num_key_value_heads * vd)
        self.o_proj = _row_linear(config, self.num_heads * vd, h)
        sink = config.add_swa_attention_sink_bias if window \
            else config.add_full_attention_sink_bias
        self.sink = self.create_parameter(
            (self.num_heads,), default_initializer=I.Constant(0.0)) \
            if sink else None

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        cfg = self.config
        offset = _as_offset(position_offset)
        # cache_offset = SLOT in the static cache, position_offset = the
        # LOGICAL position (rotary); see LlamaAttention
        slot = _as_offset(cache_offset) if cache_offset is not None \
            else offset
        nh, nkv = self.num_heads, self.num_key_value_heads
        hd, vd = cfg.head_dim, cfg.v_head_dim
        theta, rd, window = self.theta, cfg.rotary_dim, self.window
        scale = float(cfg.attention_value_scale)

        def heads(t, n, d):
            return apply_op(
                lambda v: v.reshape(v.shape[0], v.shape[1], n, d), t,
                _name='split_heads')

        def rope(t, off):
            return _rope(t, _offset_grid(off, t.shape[1]), theta, rd)
        off_t = offset if isinstance(offset, Tensor) else Tensor(offset)
        q = apply_op(rope, heads(self.q_proj(hidden), nh, hd), off_t,
                     _name='rope')
        k = apply_op(rope, heads(self.k_proj(hidden), nkv, hd), off_t,
                     _name='rope')
        v = heads(self.v_proj(hidden), nkv, vd) * scale
        b, s = q.shape[0], q.shape[1]

        if cache is None:
            # a whole sequence from its start: causal, a window layer
            # banded, a caller's [B, 1, 1, S] padding mask narrowing it
            mask = attn_mask
            if window is not None:
                def local(qv, *m):
                    win = _window_mask(jnp.int32(0), s, s, window)
                    return _narrow(m[0], win) if m else win
                mask = apply_op(local, q, *(
                    () if attn_mask is None else (attn_mask,)),
                    _name='window_mask')
            out = _attend(q, k, v, mask, self.sink, causal=True)
        elif window is None:
            with jax.named_scope('kv_write'):
                k_cache, v_cache = _update_kv_cache(cache[0], cache[1],
                                                    k, v, slot)
            mask = attn_mask if attn_mask is not None \
                else _decode_mask(q, k_cache, slot)
            # a decode sub-step of a layer without a sink on a TPU is
            # ONE kernel over the leaves whole, bounded per slot by the
            # mask (`afmoe.AfmoeAttention`); every other call XLA's
            out = bounded_decode_attention(q, k_cache, v_cache, mask,
                                           self.sink)
            if out is None:
                out = _attend(q, *_attended_rows(k_cache, v_cache, mask),
                              mask, self.sink)
        else:
            # the ring: what is visible follows from the slot alone, so
            # the caller's mask of positions is not read (its rows are
            # not this leaf's rows)
            rows = cache[0].shape[1]
            slot_t = slot if isinstance(slot, Tensor) else Tensor(slot)
            mask = apply_op(
                lambda sl: ring_mask(sl, b, s, rows, window), slot_t,
                _name='ring_mask')
            with jax.named_scope('kv_write'):
                k_cache, v_cache = update_ring_cache(cache[0], cache[1],
                                                     k, v, slot)
            if s == 1:      # written first: the ring holds the query too
                out = _attend(q, k_cache, v_cache, mask, self.sink)
            else:           # the ring as it was found, then the call
                def beside(c, new):
                    return jnp.concatenate([c, new.astype(c.dtype)], axis=1)
                out = _attend(
                    q, apply_op(beside, cache[0], k, _name='ring_and_call'),
                    apply_op(beside, cache[1], v, _name='ring_and_call'),
                    mask, self.sink)
        out = apply_op(
            lambda t: t.reshape(t.shape[0], t.shape[1], nh * vd),
            out, _name='merge_heads')
        out = self.o_proj(out)
        if cache is not None:
            return out, (k_cache, v_cache)
        return out


class MiMoV2DecoderLayer(Layer):
    def __init__(self, config: MiMoV2Config, layer_idx: int):
        super().__init__()
        eps = config.layernorm_epsilon
        self.self_attn = MiMoV2Attention(config, layer_idx)
        self.moe_enabled = bool(config.moe_layer_freq[layer_idx])
        self.mlp = AfmoeSparseMLP(config) if self.moe_enabled \
            else LlamaMLP(types.SimpleNamespace(
                hidden_size=config.hidden_size,
                intermediate_size=config.intermediate_size,
                tensor_parallel=config.tensor_parallel))
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=eps)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        with jax.named_scope('norm'):
            h = self.input_layernorm(hidden)
        with jax.named_scope('attention'):
            out = self.self_attn(
                h, position_offset=position_offset, attn_mask=attn_mask,
                cache=cache, cache_offset=cache_offset)
        new_cache = None
        if cache is not None:
            out, new_cache = out
        h = hidden + out
        with jax.named_scope('norm'):
            normed = self.post_attention_layernorm(h)
        if self.moe_enabled:        # its own scopes: moe/router, ...
            h = h + self.mlp(normed)
        else:
            with jax.named_scope('mlp'):
                h = h + self.mlp(normed)
        if cache is not None:
            return h, new_cache
        return h


def _tensor(c):
    return c if isinstance(c, Tensor) else Tensor(c)


class MiMoV2PretrainedModel(Layer):
    config_class = MiMoV2Config
    base_model_prefix = 'model'


class MiMoV2Model(MiMoV2PretrainedModel):
    """embed -> N decoder layers -> the final RMSNorm."""

    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = [MiMoV2DecoderLayer(config, i)
                       for i in range(config.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f'layers.{i}', l)
        self.norm = RMSNorm(config.hidden_size,
                            epsilon=config.layernorm_epsilon)

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
        with jax.named_scope('norm'):
            h = self.norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        """One (K, V) a layer, each kind at its own geometry: a full
        layer `max_length` rows of its 4 KV heads, a window layer a RING
        of `min(sliding_window, max_length)` rows of its 8; K as wide as
        `head_dim`, V as `v_head_dim`."""
        cfg = self.config
        dt = dtype or 'float32'
        out = []
        for layer in self.layers:
            attn = layer.self_attn
            rows = int(max_length) if attn.window is None \
                else min(attn.window, int(max_length))
            lead = (batch_size, rows, attn.num_key_value_heads)
            out.append((jnp.zeros(lead + (cfg.head_dim,), dt),
                        jnp.zeros(lead + (cfg.v_head_dim,), dt)))
        return tuple(out)


class MiMoV2ForCausalLM(MiMoV2PretrainedModel, GenerationMixin):
    def __init__(self, config: MiMoV2Config):
        super().__init__()
        self.config = config
        self.model = MiMoV2Model(config)
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

    def attention_windows(self):
        """Per layer, the rows a query can see at most: the window of a
        window layer, None for a full one. The serving engine counts
        the cache rows a round NEEDS from it."""
        return tuple(l.self_attn.window for l in self.model.layers)

    def decode_tiles(self, cache, slots, rows):
        """`AfmoeForCausalLM.decode_tiles`: a full layer without a
        sink; a ring is read whole."""
        return tuple(
            None if l.self_attn.window is not None else bounded_decode_tile(
                l.self_attn.num_heads, entry, slots, rows, l.self_attn.sink)
            for l, entry in zip(self.model.layers, cache))

    def generate(self, input_ids, *args, attention_mask=None, **kwargs):
        if attention_mask is not None and \
                not bool(jnp.all(to_jax(attention_mask) > 0)):
            raise ValueError(
                'MiMoV2ForCausalLM.generate() takes no padded prompts: the '
                'batch path hides a pad by a mask over cache rows, and a '
                'window layer\'s ring has no row a pad could be hidden in '
                '— the pad would replace a position the window still '
                'needs. Generate each length on its own, or serve through '
                'InferenceEngine, which pads on the right and writes only '
                'the real tokens into the ring')
        return super().generate(input_ids, *args, **kwargs)

    def speculative_generate(self, *args, **kwargs):
        raise NotImplementedError(
            'speculative decoding rejects a draft by moving the position '
            'back, and a ring cannot be moved back: the rejected tokens '
            'have already replaced the rows a window back (ROADMAP)')

    # the expert layers whose routed experts a whole prefill's program
    # runs as the grouped kernel, for the serving engine to say on
    # `serving.prefill` (`model.scan_chunks(bucket)`)
    scan_chunks = expert_kernel_layers
