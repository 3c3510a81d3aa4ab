"""AFMoE causal LM (`model_type: afmoe`, Arcee Trinity): sparse experts
behind a sigmoid router plus a shared expert, sliding-window and full
attention mixed, gated grouped-query attention.

Upstream analogue: `transformers/models/afmoe/modeling_afmoe.py`. What
differs from `nlp/llama.py`, whose pieces this file imports:

- `x0 = E[ids] * sqrt(h)` (`mup_enabled`).
- attention: q and k are RMS-normed per head (learned weight); a
  `sliding_attention` layer applies rotary positions and sees keys
  `0 <= i - j < sliding_window`, a `full_attention` layer has NO
  positional encoding and sees every earlier key; the attention output is
  multiplied by `sigmoid(hidden @ W_gate)` before `o_proj`. Each layer
  derives its OWN visibility from the cache slots it is given: the
  serving engine hands every layer one slot-causal mask and the window
  layers narrow it here — in whole prefill, in decode and in chunked
  prefill alike.
- four norms a layer: `x += N_post_attn(attn(N_in(x)))`,
  `x += N_post_mlp(f(N_pre_mlp(x)))`.
- `f` of the first `num_dense_layers` layers is a SwiGLU MLP; of the
  others `shared(m) + sum_{e in sel} w_e expert_e(m)` with
  `s = sigmoid(m W_r)` in float32, `sel = top_k(s + expert_bias)` (the
  bias selects only), `w = s[sel] / (sum s[sel] + 1e-20) * route_scale`.
  No token is dropped and there is no capacity (`grouped_experts`).

Activations are float32 and products three bf16 passes whatever the
parameters are stored in (`ACTIVATION_PRECISION`, and why, at
`AfmoeForCausalLM.forward`).

Attention against a cache SERVES on one of two schedules of the same
sum, picked from the call alone (`ops.pallas.kv_decode_kernel` through
`generation.bounded_decode_attention`; no flag, no model's name): one
float32 query a slot under a boolean mask on a TPU — a decode sub-step
— is ONE pallas kernel a layer over the leaves whole, which walks each
slot's row tiles from the first row the mask shows (a window layer's
window starts there) to the last and reads each once for the scores and
the values, in the arithmetic `ACTIVATION_PRECISION` gives XLA; every
other call (a prefill, a chunk, speculation's k+1 rows, every other
backend) is `_attention_xla` over the rows the mask has columns for.
`nlp/lfm2.py`'s attention layers are this class.

`expert_bias` is a buffer upstream; here it is a frozen parameter (it is
state a checkpoint fills, and `named_parameters()` is how weights reach
a model in this repo). The expert layer SERVES, on one of three schedules
of the same sum, picked by backend and shape (`ops.pallas.expert_kernel`):
on a TPU a call one block wide — a decode sub-step — is ONE pallas kernel
that streams the touched experts' weights and a wider one — a prefill —
ONE grouped matmul over the sorted picks; every other call is the loop
`grouped_experts`. None has a reverse mode — training one is ROADMAP's.
"""
from __future__ import annotations

import math
import types

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, ParamAttr
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.common_layers import Embedding, Linear
from ..nn.norm import RMSNorm
from ..ops.pallas import expert_kernel
from ..tensor import Tensor, apply_op, to_jax
from .generation import (GenerationMixin, as_offset as _as_offset,
                         attended_rows as _attended_rows,
                         bounded_decode_attention, bounded_decode_tile,
                         decode_mask as _decode_mask, note_routing,
                         offset_grid as _offset_grid,
                         update_kv_cache as _update_kv_cache)
from .llama import LlamaMLP, _col_linear, _rope, _row_linear

SLIDING, FULL = 'sliding_attention', 'full_attention'

# rows of one expert's block in `grouped_experts` when an expert may get
# more than that (prefill); a batch of fewer tokens is one block wide
BLOCK_ROWS = 256

# float32 activations' products: bf16 passes, 3 (2 against a bf16 weight)
ACTIVATION_PRECISION = 'high'


class AfmoeConfig:
    model_type = 'afmoe'

    def __init__(self, vocab_size=200192, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=1024,
                 num_hidden_layers=32, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=4,
                 head_dim=128, num_experts=128, num_experts_per_tok=8,
                 num_shared_experts=1, route_norm=True, route_scale=2.826,
                 score_func='sigmoid', sliding_window=2048,
                 global_attn_every_n_layers=4, layer_types=None,
                 max_position_embeddings=131072, rms_norm_eps=1e-5,
                 rope_theta=10000.0, mup_enabled=True,
                 tie_word_embeddings=False, pad_token_id=0,
                 bos_token_id=1, eos_token_id=2, tensor_parallel=False,
                 **kwargs):
        if score_func != 'sigmoid':
            raise ValueError(f'score_func {score_func!r}: only the sigmoid '
                             'router is implemented')
        if tie_word_embeddings:
            raise ValueError('afmoe has an untied head')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.score_func = score_func
        self.sliding_window = sliding_window
        self.global_attn_every_n_layers = global_attn_every_n_layers
        if layer_types is None:
            n = global_attn_every_n_layers
            layer_types = [FULL if (i + 1) % n == 0 else SLIDING
                           for i in range(num_hidden_layers)]
        if len(layer_types) != num_hidden_layers or \
                set(layer_types) - {SLIDING, FULL}:
            raise ValueError('layer_types must name sliding_attention or '
                             'full_attention for every layer')
        self.layer_types = list(layer_types)
        # one character a layer: a scalar, so it rides the program
        # store's statics (`describe_statics` keeps scalars only)
        self.layer_pattern = ''.join('S' if t == SLIDING else 'F'
                                     for t in self.layer_types)
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.mup_enabled = mup_enabled
        self.tie_word_embeddings = False
        self.pad_token_id = pad_token_id
        self.bos_token_id = bos_token_id
        self.eos_token_id = eos_token_id
        self.tensor_parallel = tensor_parallel
        for k, v in kwargs.items():
            setattr(self, k, v)

    @classmethod
    def trinity_mini(cls, **kw):
        """`arcee-ai/Trinity-Mini` `config.json` (26B-A3B): the defaults."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized: one dense sliding layer, then one whole period
        (sliding, sliding, sliding, full) of expert layers; 8 experts
        top-2 + a shared one, window 8, 4 q / 2 KV heads (`rep` 2)."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 32)
        kw.setdefault('intermediate_size', 64)
        kw.setdefault('moe_intermediate_size', 16)
        kw.setdefault('num_hidden_layers', 5)
        kw.setdefault('num_dense_layers', 1)
        kw.setdefault('layer_types', [SLIDING] * 4 + [FULL])
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('num_key_value_heads', 2)
        kw.setdefault('head_dim', 8)
        kw.setdefault('num_experts', 8)
        kw.setdefault('num_experts_per_tok', 2)
        kw.setdefault('sliding_window', 8)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)

    @classmethod
    def tiny_rep4(cls, **kw):
        """`tiny()` with 8 q / 2 KV heads: four query heads a KV head."""
        kw.setdefault('num_attention_heads', 8)
        return cls.tiny(**kw)


def _window_mask(slot, sq, cache_len, window):
    """[1 or B, 1, Sq, L] bool: key slot j is inside the window of the
    query at slot i (`i - j < window`; causality is the caller's mask).
    `slot` is the first query's cache slot, a scalar or [B]."""
    q_slot = _offset_grid(slot, sq)                       # [Sq] | [B, Sq]
    k_slot = jnp.arange(cache_len, dtype=jnp.int32)
    m = k_slot > q_slot[..., None] - window               # [.., Sq, L]
    return m[None, None] if m.ndim == 2 else m[:, None]


def _narrow(mask, win):
    """A caller's mask AND the window (boolean), or plus it (additive)."""
    if mask.dtype == jnp.bool_:
        return mask & win
    return mask + jnp.where(win, 0.0, jnp.finfo(jnp.float32).min)


class AfmoeAttention(Layer):
    """`rotary` / `gated` / `qk_norm`: what another family's attention
    layer of this shape has or lacks (`nlp/lfm2.py`: rotary on a full
    layer, no gate; `nlp/jamba.py`: no positions, no gate, no norm on q
    and k); the defaults are this family's."""

    def __init__(self, config: AfmoeConfig, layer_idx: int,
                 rotary=None, gated=True, qk_norm=True):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_key_value_heads = config.num_key_value_heads
        self.head_dim = hd
        sliding = config.layer_types[layer_idx] == SLIDING
        # what a sliding layer has and a full one has not
        self.window = int(config.sliding_window) if sliding else None
        self.rotary = sliding if rotary is None else rotary
        self.q_proj = _col_linear(config, h, self.num_heads * hd)
        self.k_proj = _col_linear(config, h, self.num_key_value_heads * hd)
        self.v_proj = _col_linear(config, h, self.num_key_value_heads * hd)
        self.gate_proj = _col_linear(config, h, self.num_heads * hd) \
            if gated else None
        self.o_proj = _row_linear(config, self.num_heads * hd, h)
        self.q_norm = RMSNorm(hd, epsilon=config.rms_norm_eps) \
            if qk_norm else None
        self.k_norm = RMSNorm(hd, epsilon=config.rms_norm_eps) \
            if qk_norm else None

    def _gated(self, out, hidden):
        if self.gate_proj is None:
            return out
        return out * F.sigmoid(self.gate_proj(hidden))

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        offset = _as_offset(position_offset)
        # cache_offset = SLOT in the static cache, position_offset = the
        # LOGICAL position (rotary); see LlamaAttention
        slot = _as_offset(cache_offset) if cache_offset is not None \
            else offset
        nh, nkv, hd = self.num_heads, self.num_key_value_heads, self.head_dim
        theta, window = self.config.rope_theta, self.window

        def heads(t, n):
            return apply_op(
                lambda v: v.reshape(v.shape[0], v.shape[1], n, hd), t,
                _name='split_heads')
        def normed(norm, t):
            return t if norm is None else norm(t)
        q = normed(self.q_norm, heads(self.q_proj(hidden), nh))
        k = normed(self.k_norm, heads(self.k_proj(hidden), nkv))
        v = heads(self.v_proj(hidden), nkv)

        if self.rotary:
            def rope(t, off):
                return _rope(t, _offset_grid(off, t.shape[1]), theta)
            off_t = offset if isinstance(offset, Tensor) else Tensor(offset)
            q = apply_op(rope, q, off_t, _name='rope')
            k = apply_op(rope, k, off_t, _name='rope')

        if cache is None:
            if window is None:
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask, is_causal=True)
            else:
                def local(qv, *m):
                    s = qv.shape[1]
                    win = _window_mask(jnp.int32(0), s, s, window)
                    return _narrow(m[0], win) if m else win
                mask = apply_op(local, q, *(
                    () if attn_mask is None else (attn_mask,)),
                    _name='window_mask')
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=mask, is_causal=True)
        else:
            with jax.named_scope('kv_write'):
                k_cache, v_cache = _update_kv_cache(cache[0], cache[1],
                                                    k, v, slot)
            mask = attn_mask if attn_mask is not None \
                else _decode_mask(q, k_cache, slot)
            if window is not None:
                slot_t = slot if isinstance(slot, Tensor) else Tensor(slot)
                # against the mask's own length: a caller's shorter mask
                # is the rows attention reads (`attended_rows`)
                mask = apply_op(
                    lambda m, qv, sl: _narrow(m, _window_mask(
                        sl, qv.shape[1], m.shape[-1], window)),
                    mask, q, slot_t, _name='window_mask')
            # a decode sub-step on a TPU is ONE kernel over the leaves
            # whole, bounded per slot by the mask (the window's first
            # row too); every other call XLA's chain over the rows the
            # mask has columns for
            out = bounded_decode_attention(q, k_cache, v_cache, mask)
            if out is None:
                out = F.scaled_dot_product_attention(
                    q, *_attended_rows(k_cache, v_cache, mask),
                    attn_mask=mask)
        out = apply_op(
            lambda t: t.reshape(t.shape[0], t.shape[1], nh * hd),
            out, _name='merge_heads')
        out = self.o_proj(self._gated(out, hidden))
        if cache is not None:
            return out, (k_cache, v_cache)
        return out


def route(scores, bias, k, route_norm, route_scale, eps, n_group=1,
          topk_group=1):
    """The router's choice: `scores` [..., E] float32 (sigmoid), `bias`
    [E]. The bias SELECTS and is not in the weight; `eps` stands beside
    the sum the weights are normalised by (1e-20 in AFMoE, 1e-6 in
    LFM2). With `n_group` > 1 the choice is GROUP-LIMITED (DeepSeek-V3's
    `noaux_tc`): the E experts are `n_group` groups of consecutive
    experts, a group's score is the sum of its two largest `scores +
    bias`, and the k are taken inside the `topk_group` best groups —
    with a group a chip, a token's picks land on at most that many
    chips. One group is no limit, and the program it ever was.
    -> (selected [..., k] int32, weights [..., k] float32)."""
    choice = scores + bias.astype(jnp.float32)
    if n_group > 1:
        by_group = choice.reshape(choice.shape[:-1] + (n_group, -1))
        best_two, _ = jax.lax.top_k(by_group, 2)
        _, groups = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
        kept = jnp.any(groups[..., None] == jnp.arange(n_group), axis=-2)
        choice = jnp.where(kept[..., None], by_group,
                           -jnp.inf).reshape(choice.shape)
    _, sel = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w * route_scale


def grouped_experts(x, sel, w, gate_w, up_w, down_w):
    """`sum_k w[t, k] * SwiGLU_{sel[t, k]}(x[t])` for every token, none
    dropped: x [T, h], sel / w [T, k], stacked expert leaves [E, h, f],
    [E, h, f], [E, f, h] -> [T, h].

    The T x k picks are sorted by expert and walked in blocks of `bm`
    rows that belong to ONE expert, by a loop that ends after the last
    real block: an expert nobody picked is never read. The shapes
    decide the blocks: a prefill of thousands of tokens runs blocks of
    `BLOCK_ROWS` rows through the MXU; a batch of fewer rows has `bm` =
    the batch, one block per DISTINCT expert, and moves that expert's
    weights once — which bytes, the router decided. Nothing of size
    T x E x anything is built. A block's rows past its expert's last
    pick belong to the next experts and are overwritten by their own
    blocks, which come later.

    A pick may name NO expert of these leaves: `sel == E`, one past the
    last (a layer that holds a share of its router's experts gives the
    picks it does not hold so; never a negative index, which would
    wrap). Such a pick sorts behind every real one, is counted for no
    expert and walked by no block; what its row of `ys` holds is some
    block's overhang, so its weight must be zero, and it adds nothing.

    This is the path of every backend but a TPU and of leaves that are
    not bf16, and the parity ground truth of the two kernels that take
    a TPU's calls (`ops.pallas.expert_kernel`: one for a call one block
    wide, PR 31, one for a wider, PR 49): there
    an iteration starts its three products' weight streams cold, nothing
    fetches the next expert meanwhile, `g` and `u` go through HBM, and
    every expert's last block is walked whole (PERF.md section 6)."""
    t, h = x.shape
    k, e = sel.shape[1], gate_w.shape[0]
    n = t * k
    bm = min(BLOCK_ROWS, -(-t // 8) * 8)
    flat = sel.reshape(n)
    order = jnp.argsort(flat, stable=True)       # sorted row -> pick
    counts = jnp.zeros(e, jnp.int32).at[flat].add(1)
    first_row = jnp.cumsum(counts) - counts      # an expert's first row
    blocks = (counts + bm - 1) // bm
    block_end = jnp.cumsum(blocks)
    # every block's expert and first row, before the loop (a search
    # inside it would be a loop of its own); at most this many blocks
    j = jnp.arange(n // bm + min(e, n), dtype=jnp.int32)
    block_ex = jnp.minimum(
        jnp.sum(block_end[None, :] <= j[:, None], axis=1), e - 1)
    block_row = first_row[block_ex] + (
        j - (block_end - blocks)[block_ex]) * bm
    xs = jnp.pad(x[order // k], ((0, bm), (0, 0)))
    ys = jnp.zeros((n + bm, h), x.dtype)

    def body(carry):
        j, ys = carry
        ex, row = block_ex[j], block_row[j]
        xi = jax.lax.dynamic_slice(xs, (row, 0), (bm, h))
        g = xi @ jax.lax.dynamic_index_in_dim(gate_w, ex, keepdims=False)
        u = xi @ jax.lax.dynamic_index_in_dim(up_w, ex, keepdims=False)
        yi = (jax.nn.silu(g) * u) @ jax.lax.dynamic_index_in_dim(
            down_w, ex, keepdims=False)
        return j + 1, jax.lax.dynamic_update_slice(ys, yi, (row, 0))

    _, ys = jax.lax.while_loop(lambda c: c[0] < block_end[-1], body,
                               (jnp.int32(0), ys))
    where = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))          # pick -> sorted row
    picked = ys[where].reshape(t, k, h).astype(jnp.float32)
    return jnp.sum(picked * w[..., None], axis=1).astype(x.dtype)


class AfmoeSparseMLP(Layer):
    """Routed experts (stacked leaves [E, h, f]) plus the shared expert
    (none where the configuration counts no shared expert).

    The layer may hold a SHARE of the experts its router chooses among —
    one chip's, of a layer divided over several (expert parallel): the
    configuration's `num_routed_experts` is then the router's width and
    `first_expert` the first of the `num_experts` held here. The router
    scores, selects and normalises over all of them as published; a
    pick that is not held adds nothing HERE, and the partial sum is the
    layer's result — what the other chips would add, and the exchange
    that would bring it, are not stood in for. A configuration without
    those keys holds every expert, and its program is the one it was."""

    route_norm_eps = 1e-20

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        h, f, e = (config.hidden_size, config.moe_intermediate_size,
                   config.num_experts)
        routed = int(getattr(config, 'num_routed_experts', None) or e)
        self.first_expert = int(getattr(config, 'first_expert', 0))
        if not 0 <= self.first_expert <= routed - e:
            raise ValueError(
                f'experts {self.first_expert}..{self.first_expert + e - 1} '
                f'are not among the router\'s {routed}')
        self.holds_share = e < routed
        self.router = Linear(h, routed, bias_attr=False)
        self.expert_bias = self.create_parameter(
            (routed,), attr=ParamAttr(trainable=False),
            default_initializer=I.Constant(0.0))
        std = I.Normal(0.0, 0.02)
        self.gate_w = self.create_parameter((e, h, f),
                                            default_initializer=std)
        self.up_w = self.create_parameter((e, h, f),
                                          default_initializer=std)
        self.down_w = self.create_parameter((e, f, h),
                                            default_initializer=std)
        self.shared_experts = LlamaMLP(types.SimpleNamespace(
            hidden_size=h,
            intermediate_size=f * config.num_shared_experts,
            tensor_parallel=config.tensor_parallel)) \
            if config.num_shared_experts else None

    def forward(self, x):
        cfg = self.config
        k, norm, scale, eps = (cfg.num_experts_per_tok, cfg.route_norm,
                               float(cfg.route_scale), self.route_norm_eps)
        # a configuration that names no groups has one: no limit, and
        # `route` is called as it always was
        n_group = int(getattr(cfg, 'n_group', 1) or 1)
        groups = (n_group, int(cfg.topk_group)) if n_group > 1 else ()

        def router(xv, wr, bias):
            # float32 and exact: which experts a token gets must not
            # hang on a bf16 pass of the MXU. -> [B, S, k] both
            scores = jax.nn.sigmoid(jnp.matmul(
                xv.astype(jnp.float32), wr.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            return route(scores, bias, k, norm, scale, eps, *groups)

        # by backend, leaf dtype and shape, nothing else: on a TPU one of
        # two kernels by the call's width, else the loop over blocks
        kernel = expert_kernel(math.prod(x.shape[:-1]), BLOCK_ROWS,
                               to_jax(self.gate_w).dtype)
        routed_experts = kernel or grouped_experts
        first, held = self.first_expert, cfg.num_experts

        def own(sel, w):
            # the picks in this layer's own numbering: one that is not
            # held becomes `held`, one past the last, with no weight
            local = sel - first
            mine = (local >= 0) & (local < held)
            return jnp.where(mine, local, held), jnp.where(mine, w, 0.0)

        def experts(xv, sel, w, gw, uw, dw):
            out = routed_experts(xv.reshape(-1, xv.shape[-1]),
                                 sel.reshape(-1, k), w.reshape(-1, k),
                                 gw, uw, dw)
            return out.reshape(xv.shape)

        with jax.named_scope('moe/router'):
            sel, w = apply_op(router, x, self.router.weight,
                              self.expert_bias, _name='moe_router')
            if self.holds_share:
                sel, w = apply_op(own, sel, w, _name='moe_own_picks')
        note_routing(sel, held, kernel is not None, self.holds_share)
        with jax.named_scope('moe/experts'):
            routed = apply_op(experts, x, sel, w, self.gate_w, self.up_w,
                              self.down_w, _name='moe_experts')
        if self.shared_experts is None:
            return routed
        with jax.named_scope('moe/shared'):
            return self.shared_experts(x) + routed


class AfmoeDecoderLayer(Layer):
    def __init__(self, config: AfmoeConfig, layer_idx: int):
        super().__init__()
        eps = config.rms_norm_eps
        self.self_attn = AfmoeAttention(config, layer_idx)
        self.moe_enabled = layer_idx >= config.num_dense_layers
        self.mlp = AfmoeSparseMLP(config) if self.moe_enabled \
            else LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=eps)
        self.pre_mlp_layernorm = RMSNorm(config.hidden_size, epsilon=eps)
        self.post_mlp_layernorm = RMSNorm(config.hidden_size, epsilon=eps)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        with jax.named_scope('norm'):
            h = self.input_layernorm(hidden)
        with jax.named_scope('attention'):
            attn_out = self.self_attn(
                h, position_offset=position_offset, attn_mask=attn_mask,
                cache=cache, cache_offset=cache_offset)
        new_cache = None
        if cache is not None:
            attn_out, new_cache = attn_out
        with jax.named_scope('norm'):
            h = hidden + self.post_attention_layernorm(attn_out)
            normed = self.pre_mlp_layernorm(h)
        if self.moe_enabled:        # its own scopes: moe/router, ...
            out = self.mlp(normed)
        else:
            with jax.named_scope('mlp'):
                out = self.mlp(normed)
        with jax.named_scope('norm'):
            h = h + self.post_mlp_layernorm(out)
        if cache is not None:
            return h, new_cache
        return h


class AfmoePretrainedModel(Layer):
    config_class = AfmoeConfig
    base_model_prefix = 'model'


class AfmoeModel(AfmoePretrainedModel):
    """embed * sqrt(h) -> N decoder layers -> final RMSNorm."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = [AfmoeDecoderLayer(config, i)
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
            if self.config.mup_enabled:
                h = h * math.sqrt(self.config.hidden_size)
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
            h = self.norm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        """Every layer holds `max_length` rows, window layers too (a row
        budget per layer type is ROADMAP's)."""
        cfg = self.config
        shape = (batch_size, int(max_length), cfg.num_key_value_heads,
                 cfg.head_dim)
        dt = dtype or 'float32'
        return tuple((jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                     for _ in range(cfg.num_hidden_layers))


class AfmoeForCausalLM(AfmoePretrainedModel, GenerationMixin):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, labels=None,
                cache_offset=None):
        # Activations are float32 and every product bf16 passes on the
        # MXU (`ACTIVATION_PRECISION`): THREE where both sides are
        # float32 (scores, a state's products), TWO against a weight
        # stored in bf16, which has no low part (PERF.md, PR 47). Why:
        # which 8 of 128 experts a token gets hangs on the 8th and 9th
        # score, 0.009 apart on average, and in single-pass bf16 one
        # token-layer in thirty picks another expert than the float32
        # reference does, and more flips in every layer after it. The
        # passes are NOT free where a product is a few rows against a
        # large weight: XLA pushes every weight tile through the MXU once
        # a pass, and the expert loop was bound by that, not its bytes
        # (PR 31). The expert kernel stacks the activations' bf16 parts
        # by rows and pushes each tile once; the other passes remain.
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
        sliding layer, None for a full one. The serving engine counts
        the cache rows a round NEEDS from it."""
        return tuple(l.self_attn.window for l in self.model.layers)

    def decode_tiles(self, cache, slots, rows):
        """Per layer, the row tile by which a decode sub-step's
        attention over `cache`'s entry is bounded per slot under a mask
        of `rows` columns, None where it reads every row
        (`generation.bounded_decode_tile`: float32 queries, no sink).
        The serving engine counts the cache rows a round READS by it."""
        return tuple(bounded_decode_tile(l.self_attn.num_heads, entry,
                                         slots, rows)
                     for l, entry in zip(self.model.layers, cache))

    def scan_chunks(self, tokens):
        """`expert_kernel_layers`, for the serving engine to say on
        `serving.prefill` of a bucket of `tokens` tokens."""
        return expert_kernel_layers(self, tokens)


def expert_kernel_layers(model, tokens):
    """`{'expert_kernel_layers': n}`: the expert layers of `model` whose
    routed experts a call of `tokens` tokens — a whole prefill's bucket
    — is dispatched to the grouped kernel (`ops.pallas.expert_kernel`
    asked as each layer's `forward` asks it, and what it answers read):
    all of them on a TPU over bf16 leaves where the bucket is more than
    one block wide, none where the loop or the one-block kernel runs.
    The DISPATCH's answer, not a count of the program's kernels: a
    prefill's program holds one fewer, because nothing a prefill returns
    is fed by the last layer's MLP and the compiler drops it, loop or
    kernel. What a family's `scan_chunks` says, and the serving engine
    on `serving.prefill` after it."""
    def grouped(layer):
        kernel = expert_kernel(tokens, BLOCK_ROWS,
                               to_jax(layer.gate_w).dtype)
        return kernel is not None \
            and kernel.func.__name__ == 'moe_grouped_experts'
    return {'expert_kernel_layers': sum(
        grouped(layer) for layer in model.sublayers()
        if isinstance(layer, AfmoeSparseMLP))}
