"""Xing4.0 causal LM (`model_type: xing4_0`; XingChen-AGI
Xing4.0-29B-A4B): `nlp/deepseek_v3.py`'s latent attention (with a
compressed query and YaRN positions) and expert layer, around each of
which the residual path is `hc_mult` STREAMS mixed by manifold-constrained
hyper-connections (mHC, arXiv:2512.24880).

What a layer hands the next is `x [B, S, n, C]` float32, n = `hc_mult`
streams of the hidden size. Around a block `F` (attention, or the MLP /
expert layer, each with its own RMSNorm in front: `DeepseekV3DecoderLayer.
attention_block` / `mlp_block`, shared, not copied), one
`HyperConnection`:

    u       = vec(x) / rms(vec(x))                 [nC], no weight
    [p|q|R] = u Phi,   Phi [nC, n + n + n*n]       one product a sublayer
    H_pre   = sigmoid(a_pre  * p + b_pre)          [n]
    H_post  = 2 * sigmoid(a_post * q + b_post)     [n]
    H_res   = SK(exp(clip(a_res * R + b_res, lo, hi)))   [n, n]
    y       = F(H_pre . x)                         the block sees ONE stream
    x'      = H_res x + H_post (outer) y

`SK` is `hc_sinkhorn_iters` rounds of "divide every column by its sum +
`hc_eps`, then every row", which leaves `H_res` (nearly) doubly
stochastic: the streams are mixed, never amplified. `a_*` are three
learned scalars a sublayer, `b_*` biases. The first layer takes the
embedding copied to the n streams, the final norm their sum. The maps
and both mixes are float32 whatever the parameters are stored in, under
the scope `mhc` (`mhc/maps`, `mhc/mix`). With `hc_mult` 1, `a_*` 0,
`b_pre` and `b_res` large and `b_post` 0 the layer IS
`DeepseekV3DecoderLayer` (tests/test_xing4.py holds that).

Nothing below `forward` knows the streams: the cache entry is
`deepseek_v3.py`'s latent one, and the serving engine reads
`residual_streams` only to say it on a decode round's span. The
multi-token-prediction layer of the published model is not built
(dropped at serving, as DeepSeek-V3's). Served, not trained.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..nn import initializer as I
from ..nn.layer import Layer
from ..tensor import apply_op
from .deepseek_v3 import (_TINY_YARN, DeepseekV3Config,
                          DeepseekV3DecoderLayer, DeepseekV3ForCausalLM,
                          DeepseekV3Model)


class Xing4Config(DeepseekV3Config):
    model_type = 'xing4_0'

    def __init__(self, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, **kwargs):
        published = dict(
            vocab_size=131072, hidden_size=3584, intermediate_size=9216,
            moe_intermediate_size=1024, num_hidden_layers=40,
            first_k_dense_replace=2, q_lora_rank=768, rope_theta=10000.0,
            rope_scaling={'type': 'yarn', 'factor': 64, 'beta_fast': 32,
                          'beta_slow': 1, 'mscale': 1, 'mscale_all_dim': 1,
                          'original_max_position_embeddings': 4096},
            n_routed_experts=64, n_shared_experts=1, num_experts_per_tok=4,
            routed_scaling_factor=2, max_position_embeddings=262144)
        super().__init__(**{**published, **kwargs})
        if int(hc_mult) < 1 or int(hc_sinkhorn_iters) < 0:
            raise ValueError('hc_mult / hc_sinkhorn_iters: at least one '
                             'stream and no fewer than no rounds')
        self.hc_mult = int(hc_mult)
        self.hc_sinkhorn_iters = int(hc_sinkhorn_iters)
        self.hc_eps = float(hc_eps)
        self.mhc_h_res_clamp_min = float(mhc_h_res_clamp_min)
        self.mhc_h_res_clamp_max = float(mhc_h_res_clamp_max)

    @classmethod
    def tiny(cls, **kw):
        """`DeepseekV3Config.tiny_yarn()` (one dense and two expert
        layers, a compressed query, YaRN over 16 original positions)
        under four streams of 64."""
        return super().tiny(**{'first_k_dense_replace': 1, **_TINY_YARN,
                               **kw})


def sinkhorn(m, iters, eps):
    """`iters` rounds over `m [..., n, n]` (positive): every column over
    its sum + `eps`, then every row. A loop and not `iters` copies of
    the round: on the chip the two cost the same in a decode sub-step
    and the loop a third less over a prefill's tokens, and it compiles
    in a twentieth of the time (PERF.md 7q)."""
    def one_round(_, m):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, one_round, m)


def connection_maps(x, phi, a_pre, a_post, a_res, b_pre, b_post, b_res, *,
                    iters, eps, norm_eps, lo, hi):
    """The three maps of one sublayer from the streams `x [..., n, C]`:
    -> `H_pre [..., n]`, `H_post [..., n]`, `H_res [..., n, n]`, float32.
    `vec(x)` is normed by its own rms with no weight; dividing AFTER the
    product is the same number and reads `x` once."""
    n = x.shape[-2]
    f32 = lambda t: t.astype(jnp.float32)     # noqa: E731
    flat = f32(x).reshape(x.shape[:-2] + (-1,))
    z = jnp.matmul(flat, f32(phi)) * jax.lax.rsqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + norm_eps)
    h_pre = jax.nn.sigmoid(f32(a_pre) * z[..., :n] + f32(b_pre))
    h_post = 2.0 * jax.nn.sigmoid(f32(a_post) * z[..., n:2 * n]
                                  + f32(b_post))
    r = z[..., 2 * n:].reshape(z.shape[:-1] + (n, n))
    h_res = sinkhorn(jnp.exp(jnp.clip(f32(a_res) * r + f32(b_res), lo, hi)),
                     iters, eps)
    return h_pre, h_post, h_res


def mix_in(x, h_pre):
    """`H_pre . x`: the one stream a block sees, `[..., C]`."""
    return sum(h_pre[..., i, None] * x[..., i, :]
               for i in range(x.shape[-2]))


def mix_out(x, y, h_post, h_res):
    """`H_res x + H_post (outer) y`: the streams after a block that
    gave `y [..., C]`. Written stream by stream: n is a handful, and a
    contraction over it is no work for a matrix unit."""
    n = x.shape[-2]
    return jnp.stack(
        [sum(h_res[..., i, j, None] * x[..., j, :] for j in range(n))
         + h_post[..., i, None] * y for i in range(n)], axis=-2)


class HyperConnection(Layer):
    """The residual path around ONE block: `enter` makes the maps from
    the streams and gives the block its input, `leave` takes the block's
    output back into the streams."""

    def __init__(self, config: Xing4Config):
        super().__init__()
        n, c = config.hc_mult, config.hidden_size
        self._maps = dict(iters=config.hc_sinkhorn_iters, eps=config.hc_eps,
                          norm_eps=config.rms_norm_eps,
                          lo=config.mhc_h_res_clamp_min,
                          hi=config.mhc_h_res_clamp_max)
        self.phi = self.create_parameter(
            (n * c, 2 * n + n * n), default_initializer=I.Normal(0.0, 0.02))
        # a small gate and no bias: every map starts constant
        for name in ('a_pre', 'a_post', 'a_res'):
            setattr(self, name, self.create_parameter(
                (1,), default_initializer=I.Constant(0.01)))
        for name, shape in (('b_pre', (n,)), ('b_post', (n,)),
                            ('b_res', (n, n))):
            setattr(self, name, self.create_parameter(
                shape, default_initializer=I.Constant(0.0)))

    def enter(self, streams):
        """-> (`H_pre . x`, the two maps `leave` needs)."""
        with jax.named_scope('mhc'):
            with jax.named_scope('maps'):
                h_pre, h_post, h_res = apply_op(
                    connection_maps, streams, self.phi, self.a_pre,
                    self.a_post, self.a_res, self.b_pre, self.b_post,
                    self.b_res, _name='mhc_maps', **self._maps)
            with jax.named_scope('mix'):
                hidden = apply_op(mix_in, streams, h_pre, _name='mhc_mix_in')
        return hidden, (h_post, h_res)

    def leave(self, streams, out, maps):
        with jax.named_scope('mhc'), jax.named_scope('mix'):
            return apply_op(mix_out, streams, out, *maps,
                            _name='mhc_mix_out')


class Xing4DecoderLayer(DeepseekV3DecoderLayer):
    """`DeepseekV3DecoderLayer`'s two blocks, each inside a
    `HyperConnection` over the streams `[B, S, n, C]`."""

    def __init__(self, config: Xing4Config, layer_idx: int):
        super().__init__(config, layer_idx)
        self.hc_attn = HyperConnection(config)
        self.hc_mlp = HyperConnection(config)

    def forward(self, streams, position_offset=None, attn_mask=None,
                cache=None, cache_offset=None):
        hidden, maps = self.hc_attn.enter(streams)
        out, new_cache = self.attention_block(
            hidden, position_offset=position_offset, attn_mask=attn_mask,
            cache=cache, cache_offset=cache_offset)
        streams = self.hc_attn.leave(streams, out, maps)
        hidden, maps = self.hc_mlp.enter(streams)
        streams = self.hc_mlp.leave(streams, self.mlp_block(hidden), maps)
        if cache is not None:
            return streams, new_cache
        return streams


class Xing4Model(DeepseekV3Model):
    """embed, copied to the streams -> N layers -> the streams' sum ->
    the final RMSNorm."""

    config_class = Xing4Config
    layer_class = Xing4DecoderLayer

    def residual_in(self, embedded):
        n = self.config.hc_mult
        with jax.named_scope('mhc'), jax.named_scope('mix'):
            return apply_op(
                lambda e: jnp.broadcast_to(
                    e[..., None, :], e.shape[:-1] + (n, e.shape[-1])),
                embedded, _name='mhc_streams')

    def residual_out(self, streams):
        with jax.named_scope('mhc'), jax.named_scope('mix'):
            return apply_op(lambda x: jnp.sum(x, axis=-2), streams,
                            _name='mhc_sum')


class Xing4ForCausalLM(DeepseekV3ForCausalLM):
    config_class = Xing4Config
    model_class = Xing4Model

    @property
    def residual_streams(self):
        """How many streams of the hidden size a layer hands the next
        (the serving engine says it on `serving.decode_round`)."""
        return self.config.hc_mult
