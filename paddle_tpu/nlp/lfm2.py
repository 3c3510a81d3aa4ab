"""LFM2-MoE causal LM (`model_type: lfm2_moe`, Liquid LFM2-24B-A2B):
gated short convolutions and grouped-query attention mixed three to one,
sparse SwiGLU experts behind a sigmoid router.

Upstream analogue: `transformers/models/lfm2_moe/modeling_lfm2_moe.py`.
What it is made of, and where that lives:

- `x0 = E[ids]`; a layer is `x += op(N_op(x))`, `x += f(N_ffn(x))` (two
  RMSNorms a layer); `logits = N_final(x) E^T` (the head is the
  embedding).
- a `conv` layer's operator (`Lfm2ShortConv`, here): `[B | C | z] = a
  W_in` (h -> 3h), `u = B * z`, `c_t = sum_j w[:, j] u_{t-L+1+j}` — a
  depthwise causal convolution over the last `conv_L_cache` inputs, no
  bias, no activation — and `op(a)_t = (C_t * c_t) W_out`. What a
  sequence keeps of its past is not rows of K and V but ONE leaf
  `[B, conv_L_cache, h]`: the last inputs `u`, the newest last. (The
  newest `L - 1` would do; the public code keeps `L`, and so does this.)
- a `full_attention` layer: `nlp/afmoe.py`'s attention with rotary
  positions on and its output gate off — q and k RMS-normed per head,
  causal softmax over grouped KV heads.
- `f` of the first `num_dense_layers` layers is `nlp/llama.py`'s SwiGLU;
  of the others `nlp/afmoe.py`'s expert layer with no shared expert:
  `s = sigmoid(m W_r)` in float32, `sel = top_k(s + expert_bias)` (the
  bias selects only), `w = s[sel] / (sum s[sel] + 1e-6) *
  routed_scaling_factor`, no token dropped.

`init_cache` gives one entry a layer: `(K, V)` on an attention layer,
the state leaf (float32) on a conv layer. A caller that forwards a
right-padded prompt says how many of its tokens may enter the state
(`generation.state_scope`); a cache of K and V needs no such word, since
a row past the live position is masked, and a state cannot be masked.

Activations are float32 and products three bf16 passes
(`afmoe.ACTIVATION_PRECISION`, and why, at `AfmoeForCausalLM.forward`:
this router is that one). Served, not trained: neither the expert loop
nor the decode kernel has a reverse mode (ROADMAP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.common_layers import Embedding
from ..nn.norm import RMSNorm
from ..tensor import Tensor, apply_op, to_jax
from .afmoe import (ACTIVATION_PRECISION, FULL, AfmoeAttention,
                    AfmoeSparseMLP, expert_kernel_layers)
from .generation import (GenerationMixin, bounded_decode_tile,
                         folded_tokens)
from .llama import LlamaMLP, _col_linear, _row_linear

CONV = 'conv'


class Lfm2MoeConfig:
    model_type = 'lfm2_moe'

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_hidden_layers=40, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=8,
                 num_experts=64, num_experts_per_tok=4,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 use_expert_bias=True, conv_L_cache=3, conv_bias=False,
                 layer_types=None, max_position_embeddings=128000,
                 norm_eps=1e-5, rope_theta=1000000.0,
                 tie_word_embeddings=True, pad_token_id=0, bos_token_id=1,
                 eos_token_id=2, tensor_parallel=False, **kwargs):
        if conv_bias:
            raise ValueError('conv_bias: the published model has none and '
                             'none is implemented')
        if not tie_word_embeddings:
            raise ValueError('lfm2_moe ties its head to the embedding')
        if not use_expert_bias:
            raise ValueError('use_expert_bias false: the published model '
                             'selects with the bias, and only that is '
                             'implemented')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.use_expert_bias = True
        self.conv_L_cache = conv_L_cache
        self.conv_bias = False
        if layer_types is None:
            # the published pattern: two conv layers, then periods of
            # one attention layer and three conv layers
            layer_types = [FULL if i >= 2 and (i - 2) % 4 == 0 else CONV
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {CONV, FULL}:
            raise ValueError('layer_types must name conv or full_attention '
                             'for every layer')
        self.layer_types = list(layer_types)
        # one character a layer: a scalar, so it rides the program
        # store's statics (`describe_statics` keeps scalars only)
        self.layer_pattern = ''.join('C' if t == CONV else 'A'
                                     for t in self.layer_types)
        self.max_position_embeddings = max_position_embeddings
        self.norm_eps = norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = True
        self.pad_token_id = pad_token_id
        self.bos_token_id = bos_token_id
        self.eos_token_id = eos_token_id
        self.tensor_parallel = tensor_parallel
        # under the names `afmoe.py`'s attention and expert layers read
        self.rms_norm_eps = norm_eps
        self.route_norm = norm_topk_prob
        self.route_scale = routed_scaling_factor
        self.num_shared_experts = 0
        for k, v in kwargs.items():
            setattr(self, k, v)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized: one dense conv layer, then one whole period
        (attention, conv, conv, conv) of expert layers; 8 experts top-2,
        4 q / 2 KV heads x 8."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 32)
        kw.setdefault('intermediate_size', 64)
        kw.setdefault('moe_intermediate_size', 16)
        kw.setdefault('num_hidden_layers', 5)
        kw.setdefault('num_dense_layers', 1)
        kw.setdefault('layer_types', [CONV, FULL, CONV, CONV, CONV])
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('num_key_value_heads', 2)
        kw.setdefault('num_experts', 8)
        kw.setdefault('num_experts_per_tok', 2)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)

    @classmethod
    def tiny_conv_first(cls, **kw):
        """`tiny()` in another order — dense conv, conv, attention,
        conv: nothing may hang on where the attention layer stands."""
        kw.setdefault('num_hidden_layers', 4)
        kw.setdefault('layer_types', [CONV, CONV, FULL, CONV])
        return cls.tiny(**kw)


def short_conv(bcz, w, state, folded):
    """The gated short convolution between its two projections: `bcz`
    [B, S, 3h] = [B | C | z], `w` [h, L], `state` [B, L, h] (the last L
    inputs `u = B * z` before this call, the newest last; zeros before
    a sequence). -> (`C * conv(u)` [B, S, h], the state after the first
    `folded` tokens of the call [B, L, h], float32)."""
    gate_in, gate_out, z = jnp.split(bcz, 3, axis=-1)
    u = gate_in * z
    s, taps = u.shape[1], w.shape[1]
    past = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    # token t's taps are u_{t-L+1} .. u_t: rows t+1 .. t+L of `past`
    c = sum(past[:, j + 1:j + 1 + s] * w[:, j].astype(u.dtype)
            for j in range(taps))
    with jax.named_scope('state_write'):
        new_state = jax.lax.dynamic_slice_in_dim(
            past, folded, taps, axis=1).astype(state.dtype)
    return gate_out * c, new_state


class Lfm2ShortConv(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h = config.hidden_size
        self.taps = int(config.conv_L_cache)
        self.in_proj = _col_linear(config, h, 3 * h)
        self.out_proj = _row_linear(config, h, h)
        # depthwise: one filter of `taps` weights a channel
        self.conv_weight = self.create_parameter(
            (h, self.taps), default_initializer=I.Normal(0.0, 0.02))

    def forward(self, hidden, state=None):
        """`state` None: a whole sequence from its start, nothing kept.
        Else -> (output, the state as `generation.state_scope` says)."""
        bcz = self.in_proj(hidden)
        b, s = bcz.shape[0], bcz.shape[1]
        past = state if state is not None else Tensor(jnp.zeros(
            (b, self.taps, bcz.shape[2] // 3), jnp.float32))
        # an op INPUT, not a closure capture (see `LlamaAttention`'s rope)
        folded = Tensor(jnp.asarray(folded_tokens(s), jnp.int32))
        out, new_state = apply_op(short_conv, bcz, self.conv_weight, past,
                                  folded, _name='short_conv')
        out = self.out_proj(out)
        return out if state is None else (out, new_state)


class Lfm2SparseMLP(AfmoeSparseMLP):
    """`afmoe.py`'s expert layer as this family has it: no shared
    expert (the configuration counts none), and 1e-6 beside the sum the
    weights are normalised by."""

    route_norm_eps = 1e-6


class Lfm2DecoderLayer(Layer):
    def __init__(self, config: Lfm2MoeConfig, layer_idx: int):
        super().__init__()
        eps = config.norm_eps
        self.is_attention = config.layer_types[layer_idx] == FULL
        if self.is_attention:
            # every attention layer rotary, none gated
            self.self_attn = AfmoeAttention(config, layer_idx, rotary=True,
                                            gated=False)
        else:
            self.conv = Lfm2ShortConv(config)
        self.moe_enabled = layer_idx >= config.num_dense_layers
        self.feed_forward = Lfm2SparseMLP(config) if self.moe_enabled \
            else LlamaMLP(config)
        self.operator_norm = RMSNorm(config.hidden_size, epsilon=eps)
        self.ffn_norm = RMSNorm(config.hidden_size, epsilon=eps)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                keep=None, cache=None, cache_offset=None):
        with jax.named_scope('norm'):
            h = self.operator_norm(hidden)
        if self.is_attention:
            with jax.named_scope('attention'):
                out = self.self_attn(
                    h, position_offset=position_offset, attn_mask=attn_mask,
                    cache=cache, cache_offset=cache_offset)
        else:
            with jax.named_scope('conv'):
                if keep is not None:     # a pad's input is no input
                    h = h * keep
                out = self.conv(h, state=cache)
        new_cache = None
        if cache is not None:
            out, new_cache = out
        h = hidden + out
        with jax.named_scope('norm'):
            normed = self.ffn_norm(h)
        if self.moe_enabled:        # its own scopes: moe/router, ...
            h = h + self.feed_forward(normed)
        else:
            with jax.named_scope('mlp'):
                h = h + self.feed_forward(normed)
        if cache is not None:
            return h, new_cache
        return h


def _tensor(c):
    return c if isinstance(c, Tensor) else Tensor(c)


class Lfm2MoePretrainedModel(Layer):
    config_class = Lfm2MoeConfig
    base_model_prefix = 'model'


class Lfm2MoeModel(Lfm2MoePretrainedModel):
    """embed -> N decoder layers -> the final RMSNorm (`embedding_norm`
    in the public code)."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = [Lfm2DecoderLayer(config, i)
                       for i in range(config.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f'layers.{i}', l)
        self.embedding_norm = RMSNorm(config.hidden_size,
                                      epsilon=config.norm_eps)

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
            # boolean, a conv layer zeroes the pads' inputs with it
            keep = apply_op(lambda m: (m > 0)[:, :, None].astype(
                jnp.float32), mask, _name='pad_keep')
            mask = apply_op(
                lambda m: (m > 0)[:, None, None, :], mask, _name='pad_mask')
        new_caches = []
        for i, layer in enumerate(self.layers):
            layer_cache = None
            if cache is not None:       # (K, V), or the state leaf
                layer_cache = tuple(map(_tensor, cache[i])) \
                    if layer.is_attention else _tensor(cache[i])
            out = layer(h, position_offset=position_offset, attn_mask=mask,
                        keep=keep, cache=layer_cache,
                        cache_offset=cache_offset)
            if layer_cache is not None:
                h, c = out
                new_caches.append(c)
            else:
                h = out
        with jax.named_scope('norm'):
            h = self.embedding_norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        """One entry a layer: (K, V) of `max_length` rows on an attention
        layer; on a conv layer the state leaf, float32 whatever `dtype`
        K and V are kept in, and of no length."""
        cfg = self.config
        kv = (batch_size, int(max_length), cfg.num_key_value_heads,
              cfg.head_dim)
        state = (batch_size, int(cfg.conv_L_cache), cfg.hidden_size)
        dt = dtype or 'float32'
        return tuple(
            (jnp.zeros(kv, dt), jnp.zeros(kv, dt)) if t == FULL
            else jnp.zeros(state, jnp.float32) for t in cfg.layer_types)


class Lfm2MoeForCausalLM(Lfm2MoePretrainedModel, GenerationMixin):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, labels=None,
                cache_offset=None):
        with jax.default_matmul_precision(ACTIVATION_PRECISION):
            out = self.model(input_ids, position_offset=position_offset,
                             attention_mask=attention_mask, cache=cache,
                             use_cache=use_cache, cache_offset=cache_offset)
            h, new_cache = out if use_cache else (out, None)
            with jax.named_scope('lm_head'):
                logits = apply_op(lambda hv, wv: hv @ wv.T, h,
                                  self.model.embed_tokens.weight,
                                  _name='tied_lm_head')
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

    def decode_tiles(self, cache, slots, rows):
        """`AfmoeForCausalLM.decode_tiles`; a conv layer attends over
        nothing."""
        return tuple(bounded_decode_tile(l.self_attn.num_heads, entry,
                                         slots, rows)
                     if l.is_attention else None
                     for l, entry in zip(self.model.layers, cache))

    def generate(self, input_ids, *args, attention_mask=None, **kwargs):
        if attention_mask is not None and \
                not bool(jnp.all(to_jax(attention_mask) > 0)):
            raise ValueError(
                'Lfm2MoeForCausalLM.generate() takes no padded prompts: '
                'the batch path masks a pad out of attention, and a conv '
                'layer\'s state has nothing to mask — the pad would be '
                'convolved in. Generate each length on its own, or serve '
                'through InferenceEngine, which pads on the right and '
                'folds only the real tokens into the state')
        return super().generate(input_ids, *args, **kwargs)

    def speculative_generate(self, *args, **kwargs):
        raise NotImplementedError(
            'speculative decoding rejects a draft by moving the position '
            'back, and a conv layer\'s state cannot be moved back: it '
            'needs a snapshot of the state per proposed token (ROADMAP)')

    # the expert layers whose routed experts a whole prefill's program
    # runs as the grouped kernel, for the serving engine to say on
    # `serving.prefill` (`model.scan_chunks(bucket)`)
    scan_chunks = expert_kernel_layers
