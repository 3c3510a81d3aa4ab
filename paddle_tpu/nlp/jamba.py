"""Jamba causal LM (`model_type: jamba`; AI21 Jamba2-3B / Jamba
Reasoning 3B): Mamba-1 selective state-space layers and multi-query
attention thirteen to one, a dense SwiGLU on every layer, the head tied
to the embedding.

What it is made of, and where that lives:

- `x0 = E[ids]`; a layer is `x += mixer(N_in(x))`, `x += f(N_ff(x))`
  (two RMSNorms a layer: `input_layernorm`, `pre_ff_layernorm`); `logits
  = N_final(x) E^T`. Layer `i` ATTENDS iff `i % attn_layer_period ==
  attn_layer_offset` (layers 7 and 21 of the published 28), every other
  is a Mamba layer; `f` is `nlp/llama.py`'s SwiGLU on every layer
  (`num_experts` 1).
- an attention layer's mixer is `nlp/afmoe.py`'s attention with no
  positions, no gate and NO norm on q and k: `num_attention_heads` query
  heads on `num_key_value_heads` K,V heads (20 on ONE, of 128), causal
  softmax over `sqrt(head_dim)`; both its paths and its decode kernel.
- a Mamba layer's mixer (`JambaMambaMixer`, here; Mamba, arXiv:
  2312.00752, with Jamba's three inner norms), `a = N_in(x)`, `d_inner =
  mamba_expand * hidden`, `N = mamba_d_state`, `R = mamba_dt_rank`:

      [x' | z] = a W_in                                  [2 d_inner]
      u_t = silu(b_conv + sum_j w_conv[:, j] x'_{t-L+1+j})   depthwise, L taps
      [r | B | C]_t = u_t W_x                            [R + N + N]
      r, B, C = RMSNorm(r), RMSNorm(B), RMSNorm(C)
      dt_t = softplus(r_t W_dt + b_dt)                   [d_inner]
      h_t = exp(dt_t A) * h_{t-1} + (dt_t u_t) B_t^T     A = -exp(A_log)
      y_t = h_t C_t + D * u_t
      out_t = (y_t * silu(z_t)) W_out

  The decay differs by (channel, state, token): `h` is `[d_inner, N]`
  numbers a sequence, DIAGONAL in its recurrence. What a sequence keeps
  of its past is no row: a STATE ENTRY of two leaves, `{'h': [B, N,
  d_inner] float32, 'conv': [B, L - 1, d_inner] float32}` (the state;
  the convolution's last inputs `x'`) — 320 KiB and 60 KiB a layer a
  sequence at the published widths, whatever its length. **`h` is held
  with `d_inner` minor**: 5120 is 40 whole groups of 128 lanes, where
  the published `[d_inner, 16]` would pad its 16 to 128 on a TPU, eight
  times the bytes in memory and on every read. `A_log` keeps its
  published shape and is turned once a call.

**One token and many** (`mamba_step`; `prefill_scan`). A call of one
token (a decode sub-step) is the recurrence itself, elementwise in
float32: a read and a write of the state. A longer call (a prefill) is
the same sum by one of TWO schedules, picked by `ops.pallas.
ssm_scan_kernel` from the call alone (backend, the state's dtype and
shape; no flag). On a TPU, for a float32 state of whole lanes and
sublanes: ONE Mosaic kernel a layer (`ssm_prefill_scan`), a block of
channels' `h` in VMEM, the tokens walked in order — `mamba_step`'s own
products; u, dt, B, C read and y written, nothing else. Everywhere else
`mamba_scan`: chunks of `SSM_CHUNK` tokens one after another in a
`lax.scan` carrying `h`, a chunk's tokens an associative scan of the
pairs `(a, b) -> (a2 a1, a2 b1 + b2)`, every `a` in (0, 1] — the tier-1
path, the parity ground truth, and the one a gradient can take.

A caller that forwards a right-padded prompt says how many of its
tokens may enter the state (`generation.state_scope`); a token past
that, like a pad of a left-padded batch, is folded as `dt = 0` — decay
one, input nothing: the state passes it bit for bit, either schedule —
and the convolution's inputs are cut there (`ling3.short_conv_silu`).

Refused by name, because not built: experts, a sliding window, a Mamba
projection's bias, an untied head. Float32 activations and state; two
bf16 passes against a bf16 weight, three float32 by float32 (`afmoe`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn import initializer as I
from ..nn.common_layers import Embedding, Linear
from ..nn.layer import Layer
from ..nn.norm import RMSNorm
from ..ops import pallas as _pallas
from ..tensor import Tensor, apply_op, to_jax
from .afmoe import ACTIVATION_PRECISION, FULL, AfmoeAttention
from .generation import GenerationMixin, bounded_decode_tile, folded_tokens
from .ling3 import short_conv_silu
from .llama import LlamaMLP, _col_linear, _row_linear

MAMBA = 'mamba'

# tokens a chunk of a prefill's scan: `[64, N, d_inner]` float32 pairs,
# 20 MiB each at the published widths
SSM_CHUNK = 64


class JambaConfig:
    model_type = 'jamba'

    def __init__(self, vocab_size=65536, hidden_size=2560,
                 intermediate_size=8192, num_hidden_layers=28,
                 num_attention_heads=20, num_key_value_heads=1,
                 attn_layer_offset=7, attn_layer_period=14,
                 expert_layer_offset=1, expert_layer_period=2,
                 num_experts=1, num_experts_per_tok=1, hidden_act='silu',
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=160, mamba_conv_bias=True,
                 mamba_proj_bias=False, use_mamba_kernels=True,
                 sliding_window=None, num_logits_to_keep=1,
                 max_position_embeddings=262144, rms_norm_eps=1e-6,
                 tie_word_embeddings=True,
                 pad_token_id=0, bos_token_id=1, eos_token_id=2,
                 tensor_parallel=False, **kwargs):
        if num_experts != 1 or num_experts_per_tok != 1:
            raise ValueError(
                f'num_experts {num_experts} (top {num_experts_per_tok}): '
                'every layer\'s feed-forward is ONE dense SwiGLU here; a '
                'state-space mixer beside an expert layer is not '
                'implemented (ROADMAP)')
        if sliding_window is not None:
            raise ValueError('sliding_window: not implemented (the '
                             'published Jamba2-3B has none)')
        if mamba_proj_bias:
            raise ValueError('mamba_proj_bias: not implemented (the '
                             'published Jamba2-3B has none)')
        if not mamba_conv_bias:
            raise ValueError('mamba_conv_bias false: the convolution has '
                             'its bias here, as published')
        if not tie_word_embeddings:
            raise ValueError('tie_word_embeddings false: the head is the '
                             'embedding here, as published')
        if hidden_act != 'silu':
            raise ValueError(f'hidden_act {hidden_act!r}: only silu is '
                             'implemented')
        if hidden_size % num_attention_heads \
                or num_attention_heads % num_key_value_heads:
            raise ValueError('num_attention_heads must divide hidden_size '
                             'and num_key_value_heads the heads')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.attn_layer_offset = attn_layer_offset
        self.attn_layer_period = attn_layer_period
        # the layer order is offset and period alone, as published
        self.layer_types = [
            FULL if i % attn_layer_period == attn_layer_offset else MAMBA
            for i in range(num_hidden_layers)]
        # one character a layer: a scalar, so it rides the program
        # store's statics (`describe_statics` keeps scalars only)
        self.layer_pattern = ''.join('A' if t == FULL else 'M'
                                     for t in self.layer_types)
        self.mamba_d_state = int(mamba_d_state)
        self.mamba_d_conv = int(mamba_d_conv)
        self.mamba_expand = int(mamba_expand)
        self.mamba_dt_rank = int(mamba_dt_rank)
        self.mamba_d_inner = self.mamba_expand * hidden_size
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.pad_token_id = pad_token_id
        self.bos_token_id = bos_token_id
        self.eos_token_id = eos_token_id
        self.tensor_parallel = tensor_parallel
        # what the published file says and only one value of is built
        self.num_experts = self.num_experts_per_tok = 1
        self.expert_layer_offset = expert_layer_offset
        self.expert_layer_period = expert_layer_period
        self.hidden_act = 'silu'
        self.mamba_conv_bias, self.mamba_proj_bias = True, False
        self.use_mamba_kernels = use_mamba_kernels
        self.num_logits_to_keep = num_logits_to_keep
        self.tie_word_embeddings = True
        # under the names `afmoe.py`'s attention reads: no window, and
        # no positions of any kind
        self.sliding_window = None
        self.rope_theta = None
        for k, v in kwargs.items():
            setattr(self, k, v)

    @classmethod
    def tiny(cls, **kw):
        """Test-sized: a period of four with the attention layer second
        (mamba, attention, mamba, mamba), then one layer of the next
        period; 4 query heads on ONE K,V head of 8; d_inner 64, 8
        states, dt rank 6."""
        kw.setdefault('vocab_size', 128)
        kw.setdefault('hidden_size', 32)
        kw.setdefault('intermediate_size', 64)
        kw.setdefault('num_hidden_layers', 5)
        kw.setdefault('attn_layer_offset', 1)
        kw.setdefault('attn_layer_period', 4)
        kw.setdefault('num_attention_heads', 4)
        kw.setdefault('num_key_value_heads', 1)
        kw.setdefault('mamba_d_state', 8)
        kw.setdefault('mamba_dt_rank', 6)
        kw.setdefault('max_position_embeddings', 256)
        return cls(**kw)

    @classmethod
    def tiny_attention_last(cls, **kw):
        """`tiny()` in another order — mamba, mamba, attention — with
        two K,V heads: nothing may hang on where the attention layer
        stands nor on there being one K,V head."""
        kw.setdefault('num_hidden_layers', 3)
        kw.setdefault('attn_layer_offset', 2)
        kw.setdefault('attn_layer_period', 3)
        kw.setdefault('num_key_value_heads', 2)
        return cls.tiny(**kw)


# ---------------------------------------------------------------------------
# the Mamba operator, in plain jax: the recurrence and the scan (the
# convolution is `ling3.short_conv_silu`, with this family's bias)
# ---------------------------------------------------------------------------
def mamba_step(u, dt, b, c, a, d, h):
    """The recurrence, one token: u, dt [B, Di], b, c [B, N], `a` [Di,
    N] (negative: `-exp(A_log)`), `d` [Di], h [B, N, Di] -> (y [B, Di],
    the state after the token). Elementwise and float32: with one row a
    sequence there is nothing for a matrix unit; the sum over the N
    states is N rows of the state as it is held."""
    decay = jnp.exp(dt[:, None, :] * a.T)
    with jax.named_scope('state_write'):
        h = decay * h + (dt * u)[:, None, :] * b[:, :, None]
    return jnp.sum(h * c[:, :, None], axis=1) + d * u, h


def _compose(first, then):
    """Two affine updates of the state, one after the other, as one:
    `h -> a2 (a1 h + b1) + b2`."""
    (a1, b1), (a2, b2) = first, then
    return a2 * a1, a2 * b1 + b2


def mamba_scan(u, dt, b, c, a, d, h0, folded, chunk):
    """The recurrence over S tokens, chunk by chunk: u, dt [B, S, Di],
    b, c [B, S, N], `a` [Di, N], `d` [Di], h0 [B, N, Di] -> (y [B, S,
    Di], the state after the first `folded` tokens; `folded` None: after
    all S). A token past `folded`, and the tokens that pad S to whole
    chunks, are `dt = 0`: decay exactly one, input exactly nothing — the
    state passes them bit for bit (their `y` is of that state, and
    nobody's). Inside a chunk the tokens' updates `h -> a_t h + b_t` are
    composed by an associative scan, `h_t = A_t h_0 + B_t`, and `y` is
    made there; the chunks go one after another, carrying `h`."""
    bsz, s, di = u.shape
    n = -(-s // chunk)
    if folded is not None:
        dt = jnp.where((jnp.arange(s) < folded)[None, :, None], dt, 0.0)

    def chunks(t):      # [B, S, X] -> [n, B, C, X]
        t = jnp.pad(t, ((0, 0), (0, n * chunk - s), (0, 0)))
        return jnp.moveaxis(t.reshape(bsz, n, chunk, t.shape[-1]), 1, 0)
    at = a.T                                              # [N, Di]

    def one(h, xs):
        u, dt, b, c = xs
        decay = jnp.exp(dt[:, :, None, :] * at)           # [B, C, N, Di]
        fed = (dt * u)[:, :, None, :] * b[..., None]
        run_a, run_b = jax.lax.associative_scan(_compose, (decay, fed),
                                                axis=1)
        hs = run_a * h[:, None] + run_b
        y = jnp.sum(hs * c[..., None], axis=2) + d * u
        with jax.named_scope('state_write'):
            h = hs[:, -1]
        return h, y
    h, y = jax.lax.scan(one, h0, tuple(map(chunks, (u, dt, b, c))))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, n * chunk, di)[:, :s], h


def mamba_mix(u, z, dt, b, c, a_log, d, h, folded, *keep, chunk, fold_all):
    """A Mamba layer between its projections, after the convolution: u
    [B, S, Di] the convolved input, `z` [B, S, Di] the gate's, `dt` [B,
    S, Di] as projected WITH its bias (softplus is here), b, c [B, S, N]
    normed, `a_log` [Di, N], `d` [Di], `h` [B, N, Di] as the call finds
    it; of its S tokens the first `folded` enter what it returns (all,
    `fold_all`), and none where `keep` [B, S, 1] is zero (a left-padded
    batch's pads). -> (`y * silu(z)` [B, S, Di], h)."""
    dt = jax.nn.softplus(dt)
    if keep:
        dt = dt * keep[0]
    a = -jnp.exp(a_log.astype(jnp.float32))
    d = d.astype(jnp.float32)
    if u.shape[1] == 1 and fold_all:
        y, h = mamba_step(u[:, 0], dt[:, 0], b[:, 0], c[:, 0], a, d, h)
        y = y[:, None]
    else:
        y, h = prefill_scan(u, dt, b, c, a, d, h,
                            None if fold_all else folded,
                            min(chunk, u.shape[1]))
    return y * jax.nn.silu(z), h


class JambaMambaMixer(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        h, di = config.hidden_size, config.mamba_d_inner
        n, r = config.mamba_d_state, config.mamba_dt_rank
        self.taps = config.mamba_d_conv
        self.in_proj = _col_linear(config, h, 2 * di)
        # depthwise: one filter of `taps` weights and a bias a channel
        self.conv_weight = self.create_parameter(
            (di, self.taps), default_initializer=I.Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter(
            (di,), is_bias=True, default_initializer=I.Constant(0.0))
        self.x_proj = Linear(di, r + 2 * n, bias_attr=False)
        self.dt_layernorm = RMSNorm(r, epsilon=config.rms_norm_eps)
        self.b_layernorm = RMSNorm(n, epsilon=config.rms_norm_eps)
        self.c_layernorm = RMSNorm(n, epsilon=config.rms_norm_eps)
        self.dt_proj = Linear(r, di)                        # with its bias
        # the published start: state j of every channel decays as j + 1
        self.A_log = self.create_parameter(
            (di, n), default_initializer=I.Assign(jnp.broadcast_to(
                jnp.log(jnp.arange(1.0, n + 1.0)), (di, n))))
        self.D = self.create_parameter(
            (di,), default_initializer=I.Constant(1.0))
        self.out_proj = _row_linear(config, di, h)

    def init_state(self, batch_size):
        """The entry of a sequence that has no past: zeros."""
        cfg = self.config
        return {'h': jnp.zeros((batch_size, cfg.mamba_d_state,
                                cfg.mamba_d_inner), jnp.float32),
                'conv': jnp.zeros((batch_size, self.taps - 1,
                                   cfg.mamba_d_inner), jnp.float32)}

    def forward(self, hidden, state=None, keep=None):
        """`state` None: a whole sequence from its start, nothing kept.
        Else -> (output, the entry as `generation.state_scope` says).
        `keep` [B, S, 1]: zero where a token is a pad."""
        cfg = self.config
        di, n, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        bsz, s = hidden.shape[0], hidden.shape[1]
        past = state if state is not None else jax.tree_util.tree_map(
            Tensor, self.init_state(bsz))
        count = folded_tokens(s)
        # an op INPUT, not a closure capture (see `LlamaAttention`'s rope)
        folded = Tensor(jnp.asarray(count, jnp.int32))
        x, z = apply_op(lambda t: (t[..., :di], t[..., di:]),
                        self.in_proj(hidden), _name='mamba_split')
        u, new_conv = apply_op(short_conv_silu, x, self.conv_weight,
                               past['conv'], folded, self.conv_bias,
                               _name='mamba_conv')
        low, b, c = apply_op(
            lambda t: (t[..., :r], t[..., r:r + n], t[..., r + n:]),
            self.x_proj(u), _name='mamba_split')
        y, new_h = apply_op(
            mamba_mix, u, z, self.dt_proj(self.dt_layernorm(low)),
            self.b_layernorm(b), self.c_layernorm(c), self.A_log, self.D,
            past['h'], folded, *(() if keep is None else (keep,)),
            _name='mamba_mix', chunk=SSM_CHUNK,
            fold_all=isinstance(count, int))
        out = self.out_proj(y)
        if state is None:
            return out
        return out, {'h': new_h, 'conv': new_conv}


class JambaDecoderLayer(Layer):
    def __init__(self, config: JambaConfig, layer_idx: int):
        super().__init__()
        eps = config.rms_norm_eps
        self.is_attention = config.layer_types[layer_idx] == FULL
        if self.is_attention:
            # no positions, no gate, no norm on q and k
            self.self_attn = AfmoeAttention(config, layer_idx, rotary=False,
                                            gated=False, qk_norm=False)
        else:
            self.mamba = JambaMambaMixer(config)
        self.feed_forward = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size, epsilon=eps)
        self.pre_ff_layernorm = RMSNorm(config.hidden_size, epsilon=eps)

    def forward(self, hidden, position_offset=None, attn_mask=None,
                keep=None, cache=None, cache_offset=None):
        with jax.named_scope('norm'):
            h = self.input_layernorm(hidden)
        if self.is_attention:
            with jax.named_scope('attention'):
                out = self.self_attn(
                    h, position_offset=position_offset, attn_mask=attn_mask,
                    cache=cache, cache_offset=cache_offset)
        else:
            with jax.named_scope('ssm'):
                if keep is not None:     # a pad's input is no input
                    h = h * keep
                out = self.mamba(h, state=cache, keep=keep)
        new_cache = None
        if cache is not None:
            out, new_cache = out
        h = hidden + out
        with jax.named_scope('norm'):
            normed = self.pre_ff_layernorm(h)
        with jax.named_scope('mlp'):
            h = h + self.feed_forward(normed)
        if cache is not None:
            return h, new_cache
        return h


class JambaPretrainedModel(Layer):
    config_class = JambaConfig
    base_model_prefix = 'model'


class JambaModel(JambaPretrainedModel):
    """embed -> N decoder layers -> the final RMSNorm; the cache has a
    state entry where the layer is Mamba and a (K, V) pair where it
    attends."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = [JambaDecoderLayer(config, i)
                       for i in range(config.num_hidden_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f'layers.{i}', l)
        self.final_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)

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
            # boolean; a Mamba layer zeroes the pads' inputs and their
            # steps with it
            keep = apply_op(lambda m: (m > 0)[:, :, None].astype(
                jnp.float32), mask, _name='pad_keep')
            mask = apply_op(
                lambda m: (m > 0)[:, None, None, :], mask, _name='pad_mask')
        new_caches = []
        for i, layer in enumerate(self.layers):
            # (K, V), or the state entry: every leaf a Tensor
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
            h = self.final_layernorm(h)
        if use_cache:
            return h, tuple(new_caches)
        return h

    def init_cache(self, batch_size, max_length, dtype=None):
        """One entry a layer: (K, V) of `max_length` rows on an
        attention layer; on a Mamba layer the state entry, float32
        whatever `dtype` K and V are kept in, and of no length."""
        cfg = self.config
        kv = (batch_size, int(max_length), cfg.num_key_value_heads,
              cfg.head_dim)
        dt = dtype or 'float32'
        return tuple(
            (jnp.zeros(kv, dt), jnp.zeros(kv, dt)) if layer.is_attention
            else layer.mamba.init_state(batch_size) for layer in self.layers)


class JambaForCausalLM(JambaPretrainedModel, GenerationMixin):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.model = JambaModel(config)

    def forward(self, input_ids, position_offset=None, attention_mask=None,
                cache=None, use_cache=False, cache_offset=None):
        with jax.default_matmul_precision(ACTIVATION_PRECISION):
            out = self.model(input_ids, position_offset=position_offset,
                             attention_mask=attention_mask, cache=cache,
                             use_cache=use_cache, cache_offset=cache_offset)
            h, new_cache = out if use_cache else (out, None)
            with jax.named_scope('lm_head'):
                logits = apply_op(lambda hv, wv: hv @ wv.T, h,
                                  self.model.embed_tokens.weight,
                                  _name='tied_lm_head')
        if use_cache:
            return logits, new_cache
        return logits

    def init_cache(self, batch_size, max_length, dtype=None):
        return self.model.init_cache(batch_size, max_length, dtype)

    def decode_tiles(self, cache, slots, rows):
        """`AfmoeForCausalLM.decode_tiles`; a Mamba layer attends over
        nothing."""
        return tuple(bounded_decode_tile(l.self_attn.num_heads, entry,
                                         slots, rows)
                     if l.is_attention else None
                     for l, entry in zip(self.model.layers, cache))

    def scan_chunks(self, tokens):
        """What the serving engine says on `serving.prefill` of a call
        of `tokens` tokens (a whole prefill's bucket): `ssm_kernel_layers`,
        the Mamba layers whose recurrence the program runs as ONE kernel
        (`ops.pallas.ssm_scan_kernel`, asked as `prefill_scan` asks it),
        and `ssm_chunks`, the chunks `mamba_scan` walks one after
        another in each of the others (none where there are no others)."""
        cfg = self.config
        state = jax.ShapeDtypeStruct(
            (1, cfg.mamba_d_state, cfg.mamba_d_inner), jnp.float32)
        mamba = sum(not layer.is_attention for layer in self.model.layers)
        by_kernel = _pallas.ssm_scan_kernel(state, tokens) is not None
        return {'ssm_chunks': 0 if by_kernel else -(-tokens // SSM_CHUNK),
                'ssm_kernel_layers': mamba if by_kernel else 0}

    def generate(self, input_ids, *args, attention_mask=None, **kwargs):
        if attention_mask is not None and \
                not bool(jnp.all(to_jax(attention_mask) > 0)):
            raise ValueError(
                'JambaForCausalLM.generate() takes no padded prompts: the '
                'batch path masks a pad out of attention, and a Mamba '
                'layer\'s state has nothing to mask — the pad would be '
                'folded in. Generate each length on its own, or serve '
                'through InferenceEngine, which pads on the right and '
                'folds only the real tokens into the state')
        return super().generate(input_ids, *args, **kwargs)

    def speculative_generate(self, *args, **kwargs):
        raise NotImplementedError(
            'speculative decoding rejects a draft by moving the position '
            'back, and a Mamba layer\'s state cannot be moved back: it '
            'needs a snapshot of the state per proposed token (ROADMAP)')


# below the classes: the lines above them are in the digests of the
# decode programs, whose kernels' calls carry this file's line numbers
def prefill_scan(u, dt, b, c, a, d, h0, folded, chunk):
    """`mamba_scan`'s arguments and results, by the schedule the call
    admits: ONE kernel where `ops.pallas.ssm_scan_kernel` gives one (a
    TPU, a float32 state of whole lanes and sublanes, more than one
    token) — `folded` applied to `dt` here, the identity update —, else
    `mamba_scan`. The same recurrence either way; the kernel makes
    `mamba_step`'s products in its order and is forward only (nothing
    differentiates through a served model; `jax.grad` through it
    raises, and off a TPU meets `mamba_scan`)."""
    kernel = _pallas.ssm_scan_kernel(h0, u.shape[1])
    if kernel is None:
        return mamba_scan(u, dt, b, c, a, d, h0, folded, chunk)
    if folded is not None:
        dt = jnp.where((jnp.arange(u.shape[1]) < folded)[None, :, None],
                       dt, 0.0)
    return kernel(u, dt, b, c, a, d, h0)
