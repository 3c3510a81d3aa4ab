"""GPT model family (upstream analogue: PaddleNLP
`paddlenlp/transformers/gpt/modeling.py` — GPTModel / GPTForCausalLM,
GPT-3 1.3B headline config).

TPU-native: pre-LN transformer with learned position embeddings; causal
attention lowers to the shared `F.scaled_dot_product_attention`
choke-point (pallas flash kernel on TPU); decode shares the static-shape
KV-cache scheme with the Llama family (see llama.py docstring).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn.common_layers import Dropout, Embedding, Linear
from ..nn.layer import Layer
from ..nn.norm import LayerNorm
from ..tensor import Tensor, apply_op, to_jax
from .generation import (GenerationMixin, as_offset as _as_offset,
                         attended_rows as _attended_rows,
                         decode_mask as _decode_mask,
                         offset_grid as _offset_grid,
                         update_kv_cache as _update_kv_cache)


class GPTConfig:
    model_type = 'gpt'

    def __init__(self, vocab_size=50304, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=None, hidden_act='gelu',
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 max_position_embeddings=1024, initializer_range=0.02,
                 layer_norm_epsilon=1e-5, pad_token_id=0, eos_token_id=50256,
                 bos_token_id=50256, tie_word_embeddings=True, **kwargs):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.layer_norm_epsilon = layer_norm_epsilon
        self.pad_token_id = pad_token_id
        self.eos_token_id = eos_token_id
        self.bos_token_id = bos_token_id
        self.tie_word_embeddings = tie_word_embeddings
        for k, v in kwargs.items():
            setattr(self, k, v)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def gpt3_1p3b(cls, **kw):
        """GPT-3 XL (1.3B): 24 layers, d_model 2048, 16 heads x 128."""
        return cls(vocab_size=50304, hidden_size=2048, num_hidden_layers=24,
                   num_attention_heads=16, max_position_embeddings=2048, **kw)

    @classmethod
    def gpt2_small(cls, **kw):
        return cls(vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                   num_attention_heads=12, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 64)
        kw.setdefault('num_hidden_layers', 2)
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('max_position_embeddings', 128)
        kw.setdefault('hidden_dropout_prob', 0.0)
        kw.setdefault('attention_probs_dropout_prob', 0.0)
        return cls(**kw)


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h, nh = config.hidden_size, config.num_attention_heads
        self.num_heads = nh
        self.head_dim = config.head_dim
        self.qkv_proj = Linear(h, 3 * h)
        self.out_proj = Linear(h, h)
        self.dropout_p = config.attention_probs_dropout_prob

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        nh, hd = self.num_heads, self.head_dim
        offset = _as_offset(position_offset)
        slot = _as_offset(cache_offset) if cache_offset is not None \
            else offset
        qkv = self.qkv_proj(hidden)
        q, k, v = (apply_op(
            lambda t, i=i: t[..., i * nh * hd:(i + 1) * nh * hd].reshape(
                t.shape[0], t.shape[1], nh, hd),
            qkv, _name='split_qkv') for i in range(3))
        if cache is None:
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=True,
                dropout_p=self.dropout_p, training=self.training)
        else:
            with jax.named_scope('kv_write'):
                k_cache, v_cache = _update_kv_cache(cache[0], cache[1],
                                                    k, v, slot)
            mask = attn_mask if attn_mask is not None \
                else _decode_mask(q, k_cache, slot)
            out = F.scaled_dot_product_attention(
                q, *_attended_rows(k_cache, v_cache, mask), attn_mask=mask)
        out = apply_op(lambda t: t.reshape(t.shape[0], t.shape[1], nh * hd),
                       out, _name='merge_heads')
        out = self.out_proj(out)
        if cache is not None:
            return out, (k_cache, v_cache)
        return out


class GPTDecoderLayer(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.norm1 = LayerNorm(config.hidden_size,
                               epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.norm2 = LayerNorm(config.hidden_size,
                               epsilon=config.layer_norm_epsilon)
        self.linear1 = Linear(config.hidden_size, config.intermediate_size)
        self.linear2 = Linear(config.intermediate_size, config.hidden_size)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.act = {'gelu': F.gelu, 'relu': F.relu}[config.hidden_act]

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        # the named scopes are the device trace's vocabulary
        # (programs.scopes.SCOPES): every op's `op_name` carries them
        residual = hidden
        with jax.named_scope('norm'):
            normed = self.norm1(hidden)
        with jax.named_scope('attention'):
            out = self.attn(normed, position_offset=position_offset,
                            attn_mask=attn_mask, cache=cache,
                            cache_offset=cache_offset)
        new_cache = None
        if cache is not None:
            out, new_cache = out
        h = residual + self.dropout(out)
        with jax.named_scope('norm'):
            normed = self.norm2(h)
        with jax.named_scope('mlp'):
            h = h + self.dropout(self.linear2(self.act(self.linear1(
                normed))))
        if cache is not None:
            return h, new_cache
        return h


class GPTModel(Layer):
    config_class = GPTConfig
    base_model_prefix = 'gpt'

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.word_embeddings = Embedding(config.vocab_size,
                                         config.hidden_size)
        self.position_embeddings = Embedding(config.max_position_embeddings,
                                             config.hidden_size)
        self.embed_dropout = Dropout(config.hidden_dropout_prob)
        self.layers = [GPTDecoderLayer(config)
                       for _ in range(config.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f'layers.{i}', l)
        self.final_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, blocks_fn=None,
                cache_offset=None):
        ids = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(to_jax(input_ids))
        offset = _as_offset(position_offset)
        pos = apply_op(
            lambda iv: jnp.clip(_offset_grid(offset, iv.shape[1]), 0, None),
            ids, _name='positions')
        with jax.named_scope('embed'):
            h = self.word_embeddings(ids) + self.position_embeddings(pos)
            h = self.embed_dropout(h)
        if blocks_fn is not None:
            # pipeline-parallel path — see LlamaModel.forward
            if attention_mask is not None or cache is not None:
                raise ValueError('blocks_fn (pipeline) path supports only '
                                 'full-length causal batches')
            h = apply_op(blocks_fn, h, _name='pp_blocks')
            with jax.named_scope('norm'):
                return self.final_norm(h)
        mask = attention_mask
        if mask is not None and not isinstance(mask, Tensor):
            mask = Tensor(to_jax(mask))
        if mask is not None and len(mask.shape) == 2:
            mask = apply_op(lambda m: (m > 0)[:, None, None, :], mask,
                            _name='pad_mask')
        new_caches = []
        for i, layer in enumerate(self.layers):
            layer_cache = None
            if cache is not None:
                kc, vc = cache[i]
                layer_cache = (
                    kc if isinstance(kc, Tensor) else Tensor(kc),
                    vc if isinstance(vc, Tensor) else Tensor(vc))
            out = layer(h, position_offset=position_offset, attn_mask=mask,
                        cache=layer_cache, cache_offset=cache_offset)
            if layer_cache is not None:
                h, c = out
                new_caches.append(c)
            else:
                h = out
        with jax.named_scope('norm'):
            h = self.final_norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        cfg = self.config
        shape = (batch_size, int(max_length), cfg.num_attention_heads,
                 cfg.head_dim)
        return tuple(
            (jnp.zeros(shape, dtype or 'float32'),
             jnp.zeros(shape, dtype or 'float32'))
            for _ in range(cfg.num_hidden_layers))


class GPTForCausalLM(Layer, GenerationMixin):
    config_class = GPTConfig

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def _logits(self, h):
        with jax.named_scope('lm_head'):
            if self.lm_head is not None:
                return self.lm_head(h)
            w = self.gpt.word_embeddings.weight
            return apply_op(lambda hv, wv: hv @ wv.T, h, w,
                            _name='tied_lm_head')

    def pp_blocks(self):
        """Pipeline-parallel protocol — see LlamaForCausalLM.pp_blocks."""
        return 'gpt.layers', self.gpt.layers

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, labels=None, blocks_fn=None,
                cache_offset=None):
        out = self.gpt(input_ids, position_offset=position_offset,
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
        return self.gpt.init_cache(batch_size, max_length, dtype)
