"""What the plain references share: seeded weights, a matmul whose
precision can be stepped down (the control), attention, cross-entropy
and AdamW written out in `jax.numpy` and float32.

Nothing of the program is imported, and nothing the program made is
read: weights come from the seed through `make_weights`, which the
harness also uses to fill the program's model — the one thing both sides
share is this generator.

Precision modes of `Ref.mm`:
  'f32'  float32 operands, `precision=HIGHEST` — the reference.
  'fp8'  both operands rounded to float8_e4m3fn with a per-tensor scale
         before a float32 matmul: the nearest precision below bfloat16,
         the step a later PR would be tempted by — the control.
  'int8' the same with symmetric per-tensor int8.
The rounding is straight-through for gradients, so a control training
step is a step with quantized forward operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def make_weights(shapes, seed, dtype='bfloat16', std=0.02):
    """One jitted call: every leaf on the device, from the seed, in the
    dtype it is served or trained in. `shapes` maps a canonical name to
    (shape, kind), kind one of 'normal', 'ones', 'zeros'."""
    names = sorted(shapes)

    def gen(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind = shapes[name]
            if kind == 'normal':
                v = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif kind == 'ones':
                v = jnp.ones(shape, jnp.float32)
            else:
                v = jnp.zeros(shape, jnp.float32)
            out[name] = v.astype(dtype)
        return out
    # the seed may exceed 32 signed bits: fold its halves in
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return jax.jit(gen)(key)


def _ste(x, q):
    return x + jax.lax.stop_gradient(q - x)


def quant_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return _ste(x, q)


def quant_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return _ste(x, jnp.clip(jnp.rint(x / s), -127, 127) * s)


class Ref:
    """Matmul and friends at one precision mode."""

    def __init__(self, mode='f32'):
        if mode not in ('f32', 'fp8', 'int8'):
            raise ValueError(f'unknown precision mode {mode!r}')
        self.mode = mode

    def q(self, x):
        x = x.astype(jnp.float32)
        if self.mode == 'fp8':
            return quant_fp8(x)
        if self.mode == 'int8':
            return quant_int8(x)
        return x

    def mm(self, x, w):
        return jnp.matmul(self.q(x), self.q(w), precision=HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b), precision=HIGHEST)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(jnp.float32)


def rope(x, theta):
    """Rotate-half rotary embedding over positions 0..S-1; x [B,S,H,D]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(f)[None, :, None, :], jnp.sin(f)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(ref, q, k, v):
    """q [B,S,H,D], k/v [B,S,Hkv,D] -> [B,S,H*D]; grouped KV heads are
    shared by H/Hkv query heads each."""
    b, s, h, d = q.shape
    g = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], g, d)
    sc = ref.einsum('bqkgd,bskd->bkgqs', qg, k) / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    o = ref.einsum('bkgqs,bskd->bqkgd', p, v)
    return o.reshape(b, s, h * d)


def lm_loss(head, hidden, ids):
    """Mean cross-entropy of predicting token t+1 from positions <= t.
    `head(h)` gives the logits of hidden states [S, H]; rows go through
    one at a time and are rematerialized in the backward pass, so only
    one row's [S, V] float32 logits are alive at once."""
    @jax.checkpoint
    def row(h, t):
        lp = jax.nn.log_softmax(head(h[:-1]).astype(jnp.float32), -1)
        return -jnp.sum(jnp.take_along_axis(lp, t[1:, None], -1))
    total = jnp.sum(jax.lax.map(lambda a: row(*a), (hidden, ids)))
    return total / (ids.shape[0] * (ids.shape[1] - 1))


def adamw_leaf(p, g, m, v, step, hp):
    """One AdamW update of one leaf as the configuration states it:
    float32 arithmetic on the stored parameter, moments stored in
    `moment_dtype`, decoupled decay, the result stored in the
    parameter's dtype."""
    b1, b2, eps, lr, wd = (hp['beta1'], hp['beta2'], hp['epsilon'],
                           hp['learning_rate'], hp['weight_decay'])
    p32, g32 = p.astype(jnp.float32), g.astype(jnp.float32)
    m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
    v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
    t = jnp.asarray(step, jnp.float32)
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new = p32 - lr_t * m32 / (jnp.sqrt(v32) + eps) - lr * wd * p32
    return new.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)


def train_reference(loss_fn, weights, batches, hp, steps):
    """Follow `steps` AdamW steps from `weights` over `batches` (a list
    of [B,S] id arrays). Returns the per-step losses, the first
    gradient's sum of squares and its sketch per leaf and layer
    (`sq_per_layer`, `sketch_per_layer`), and the parameters after the
    last step. `weights` is donated."""
    mdt = hp.get('moment_dtype', 'float32')
    grad_step = jax.jit(jax.value_and_grad(loss_fn))

    def update(p, g, m, v, step):
        out = {k: adamw_leaf(p[k], g[k], m[k], v[k], step, hp) for k in p}
        return tuple({k: o[i] for k, o in out.items()} for i in range(3))
    update = jax.jit(update, donate_argnums=(0, 1, 2, 3))

    p = weights
    m = {k: jnp.zeros(x.shape, mdt) for k, x in p.items()}
    v = {k: jnp.zeros(x.shape, mdt) for k, x in p.items()}
    losses, g1 = [], None
    for i in range(steps):
        loss, g = grad_step(p, batches[i])
        losses.append(float(loss))
        if i == 0:
            g1 = jax.jit(lambda t: (sq_per_layer(t), sketch_per_layer(t)))(g)
        p, m, v = update(p, g, m, v, jnp.float32(i + 1))
    return losses, g1[0], g1[1], p


def sq_per_layer(tree):
    """Sum of squares per leaf; a leaf whose name starts with 'layers.'
    is stacked [L, ...] and gives one number per layer."""
    out = {}
    for k, v in tree.items():
        x = jnp.square(v.astype(jnp.float32))
        out[k] = x.reshape(x.shape[0], -1).sum(-1) if k.startswith(
            'layers.') else x.sum()
    return out


def signs(n):
    """A fixed +-1 pattern over a flat index (the low bit of a 32-bit
    mixing hash): the direction a leaf's gradient is projected on, the
    same on both sides of a comparison."""
    x = jnp.arange(n, dtype=jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    return 1.0 - 2.0 * ((x ^ (x >> 16)) & 1).astype(jnp.float32)


def sketch(v):
    """A leaf's projection on `signs`: linear in the leaf, so a rounding
    error moves it in first order (a norm moves only in second)."""
    return jnp.sum(v.astype(jnp.float32).reshape(-1) * signs(v.size))


def sketch_per_layer(tree):
    """`sketch` per leaf; one number per layer of a stacked leaf."""
    out = {}
    for k, v in tree.items():
        if k.startswith('layers.'):
            x = v.astype(jnp.float32).reshape(v.shape[0], -1)
            out[k] = jnp.sum(x * signs(x.shape[1]), -1)
        else:
            out[k] = sketch(v)
    return out


def sq_delta_per_layer(a, b):
    return sq_per_layer({k: a[k].astype(jnp.float32)
                         - b[k].astype(jnp.float32) for k in a})
