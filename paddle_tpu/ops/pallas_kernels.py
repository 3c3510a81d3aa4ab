"""Hand-written pallas TPU kernels (upstream analogue: the reference's
fused CUDA kernels under paddle/phi/kernels/fusion/gpu/ and its
flash-attn integration).

Written for the jax that is installed (0.9.0: `pltpu.CompilerParams`,
scalars in SMEM via scalar prefetch); no shim for any other.

Contents:
- `flash_attention(q, k, v, causal=...)` — differentiable flash attention
  used by the SDPA dispatch on TPU. Forward+backward are the jax pallas
  TPU library kernels (public `jax.experimental.pallas.ops.tpu
  .flash_attention`), layout-adapted from paddle's [B, S, H, D].
- `flash_attention_fwd(...)` — this repo's own blockwise online-softmax
  pallas kernel (forward only; used on no-grad paths, parity-tested in
  interpret mode on CPU against the XLA reference).
- `rms_norm(x, weight, eps)` — fused RMSNorm pallas kernel with an
  analytic custom VJP.
- `paged_attention`, `adapter_matmul` — scalar-prefetch kernels of the
  serving engine (a page table; a packed adapter bank).
- `moe_decode_experts`, `moe_grouped_experts(x, sel, w, gate_w, up_w,
  down_w)` — an expert layer's routed experts: a decode batch's, and a
  prefill's sorted picks by tile (dispatch: `ops.pallas.expert_kernel`).
- `mla_decode_attention(q_lat, q_rope, c, r, seen, scale)` — decode
  attention over latent rows bounded per slot (`latent_decode_kernel`).
- `kv_decode_attention(q, k, v, seen, scale)` — its sibling for float32
  queries over K and V by head (`kv_decode_kernel`; `decode_walk`).
- `kda_decode_step`, `ssm_prefill_scan` — a KDA layer's one-token update
  in place; a Mamba layer's prompt, `h` in VMEM (`kda_step_kernel`, ...).
(fp32 accumulators; add no line ABOVE a kernel: its lines are in digests.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)


# ---------------------------------------------------------------------------
# library-kernel dispatch (differentiable train path)
# ---------------------------------------------------------------------------

def _fa_block_sizes(sq, sk):
    """Block sizes bq=1024/bk=512, chosen in round 4 from a sweep on a
    v5e under the jax of that time (fwd+bwd 6.33 -> 4.16 ms at
    [4,16,2048,128] vs 512/512 then; not re-measured on the current
    runtime — PERF.md). They pass jax 0.9.0's Mosaic at the smoke's
    shapes (chip_smoke.py). Library defaults when seq doesn't divide."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    bq = min(1024, sq)
    bk = min(512, sk)
    if sq % bq or sk % bk:
        return None
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
        block_q_dq=bq)


def flash_attention(q, k, v, causal=False):
    """[B, S, H, D] flash attention via the jax pallas TPU kernel.

    GQA is handled by repeating KV heads (the kernel wants equal heads).
    The repeat is NOT free: XLA materialises it, `rep` copies of K and of
    V (measured on the v5e in the XLA decode path: 0.59 ms per 192 MiB
    copy, PERF.md section 5; `ops.pallas._attention_xla` therefore
    contracts over KV groups in place). Here it is a copy of the
    [B, S, H_kv, D] training activations, once per call.
    PADDLE_TPU_OWN_FLASH=1 switches to this repo's own fwd+bwd kernels
    (flash_attention_own) instead of the jax library's.
    """
    import os
    if os.environ.get('PADDLE_TPU_OWN_FLASH', '').lower() in ('1', 'true'):
        return flash_attention_own(q, k, v, causal)
    b, sq, h, d = q.shape
    kv_heads = k.shape[2]
    if kv_heads != h:
        rep = h // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _fa)
    # library layout is [B, H, S, D]
    out = _fa(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
              v.transpose(0, 2, 1, 3), causal=causal,
              sm_scale=1.0 / math.sqrt(d),
              block_sizes=_fa_block_sizes(sq, k.shape[1]))
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# our own forward kernel: blockwise online softmax
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
                      block_q, block_k, n_k, with_lse=False):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref = None
        m_ref, l_ref, acc_ref = rest
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [Bq, D]
        kk = k_ref[0, 0].astype(jnp.float32)         # [Bk, D]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [Bq, Bk]
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, _NEG_INF)
        m_prev = m_ref[:]                             # [Bq, 128]
        m_cur = jnp.max(s, axis=-1, keepdims=True)    # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])     # [Bq, 1]
        p = jnp.exp(s - m_new[:, :1])                     # [Bq, Bk]
        l_ref[:] = l_ref[:] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new

    if causal:
        # whole KV block above the diagonal contributes nothing — skip
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            # m/l scratch keep identical copies across all 128 lanes, so
            # the [block_q, 128] lse tile is their elementwise combination
            # (TPU tiling wants the last dim 128-wide; layout matches the
            # jax library kernel's (B, H, Sq, MIN_BLOCK_SIZE) residuals)
            lse_ref[0, 0] = m_ref[:] + jnp.log(l_ref[:])


def _check_blocks(sq, sk, block_q, block_k):
    """The grid pads the last block with pl.cdiv, and padded key rows
    would contribute exp-mass to the online-softmax denominator — fail
    loud instead of returning silently wrong results."""
    if sq % block_q or sk % block_k:
        raise ValueError(
            f'flash kernel needs seq lengths divisible by block sizes: '
            f'sq={sq} %% block_q={block_q} or sk={sk} %% block_k={block_k} '
            f'!= 0; pad the sequence or pick smaller blocks')


def flash_attention_fwd(q, k, v, causal=False, block_q=128, block_k=128,
                        interpret=False, return_lse=False):
    """Forward flash attention, [B, S, H, D] (this repo's kernel).

    With return_lse=True also returns the per-row logsumexp as a
    [B, H, Sq, 128] fp32 array (value replicated over the 128-lane dim —
    the TPU tiling layout the backward kernels consume; take [..., 0]
    for the logical [B, H, Sq] values).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    if kv_heads != h:
        rep = h // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = q.transpose(0, 2, 1, 3)      # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    _check_blocks(sq, sk, block_q, block_k)
    n_q, n_k = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, n_k=n_k, with_lse=return_lse)
    out_specs = [pl.BlockSpec((1, 1, block_q, d),
                              lambda b_, h_, iq, ik: (b_, h_, iq, 0))]
    out_shape = [jax.ShapeDtypeStruct(qt.shape, q.dtype)]
    if return_lse:
        # the lse residual is only materialized when the caller (the
        # backward pass) actually needs it — forward-only calls skip the
        # [B, H, Sq, 128] fp32 write entirely
        out_specs.append(pl.BlockSpec((1, 1, block_q, 128),
                                      lambda b_, h_, iq, ik: (b_, h_, iq, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sq, 128), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        name='flash_fwd',
    )(qt, kt, vt)
    if return_lse:
        out, lse = res
        return out.transpose(0, 2, 1, 3), lse
    return res[0].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# our own backward kernels: dq and dk/dv sweeps (FlashAttention-2 scheme)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                         n_k):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [Bq, D]
        kk = k_ref[0, 0].astype(jnp.float32)           # [Bk, D]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, _NEG_INF)
        lse = lse_ref[0, 0][:, :1]                     # [Bq, 1]
        p = jnp.exp(s - lse)                           # [Bq, Bk]
        do = do_ref[0, 0].astype(jnp.float32)          # [Bq, D]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bq, Bk]
        delta = delta_ref[0, 0][:, :1]                 # [Bq, 1]
        ds = p * (dp - delta) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds, kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          block_q, block_k, n_q):
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [Bq, D]
        kk = k_ref[0, 0].astype(jnp.float32)           # [Bk, D]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, _NEG_INF)
        lse = lse_ref[0, 0][:, :1]                     # [Bq, 1]
        p = jnp.exp(s - lse)                           # [Bq, Bk]
        do = do_ref[0, 0].astype(jnp.float32)          # [Bq, D]
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bk, D]
        dp = jax.lax.dot_general(
            do, v_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bq, Bk]
        delta = delta_ref[0, 0][:, :1]                 # [Bq, 1]
        ds = p * (dp - delta) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Bk, D]

    if causal:
        # q blocks strictly above the diagonal see none of this k block
        @pl.when(iq * block_q + block_q - 1 >= ik * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, g, causal=False, block_q=128,
                        block_k=128, interpret=False):
    """dq/dk/dv via two pallas sweeps. All arrays [B, H, S, D] (already
    transposed); lse [B, H, Sq, 128] fp32 (lane-replicated, from
    flash_attention_fwd); returns grads in the same layout."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    _check_blocks(sq, sk, block_q, block_k)
    n_q, n_k = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    scale = 1.0 / math.sqrt(d)
    # delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term,
    # lane-replicated to the same [B, H, Sq, 128] tiling as lse
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True), (b, h, sq, 128))

    qspec = pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j: (b_, h_, j, 0))
    rowq = pl.BlockSpec((1, 1, block_q, 128),
                        lambda b_, h_, i, j: (b_, h_, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k),
        grid=(b, h, n_q, n_k),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name='flash_bwd_dq',
    )(q, k, v, g, lse, delta)

    # dkv sweep: grid iterates k blocks in dim 2, q blocks in dim 3
    qspec2 = pl.BlockSpec((1, 1, block_q, d),
                          lambda b_, h_, j, i: (b_, h_, i, 0))
    kspec2 = pl.BlockSpec((1, 1, block_k, d),
                          lambda b_, h_, j, i: (b_, h_, j, 0))
    rowq2 = pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h_, j, i: (b_, h_, i, 0))
    kout = pl.BlockSpec((1, 1, block_k, d),
                        lambda b_, h_, j, i: (b_, h_, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q),
        grid=(b, h, n_k, n_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2],
        out_specs=[kout, kout],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name='flash_bwd_dkv',
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_own(q, k, v, causal=False, block_q=128, block_k=128,
                        interpret=False):
    """This repo's fully-owned differentiable flash attention,
    [B, S, H, D] layout (fwd online-softmax + FA-2 style bwd sweeps).
    Selected over the jax library kernel by PADDLE_TPU_OWN_FLASH=1."""
    # undifferentiated (inference) path: skip the [B,H,Sq,128] fp32 LSE
    # write — only the custom_vjp fwd rule below needs it as a residual
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret,
                               return_lse=False)


def _flash_own_fwd(q, k, v, causal, block_q, block_k, interpret):
    h, kvh = q.shape[2], k.shape[2]
    out, lse = flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k, interpret=interpret,
                                   return_lse=True)
    return out, (q, k, v, out, lse)


def _flash_own_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    h, kvh = q.shape[2], k.shape[2]
    kf, vf = k, v
    if kvh != h:
        rep = h // kvh
        kf = jnp.repeat(k, rep, axis=2)
        vf = jnp.repeat(v, rep, axis=2)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    dq, dk, dv = flash_attention_bwd(
        tr(q), tr(kf), tr(vf), tr(out), lse, tr(g), causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret)
    dq, dk, dv = tr(dq), tr(dk), tr(dv)
    if kvh != h:
        rep = h // kvh
        b, sk_, _, d = dk.shape
        # repeat interleaves groups per kv head: fold [H] -> [HKV, rep]
        dk = dk.reshape(b, sk_, kvh, rep, d).sum(3).astype(k.dtype)
        dv = dv.reshape(b, sk_, kvh, rep, d).sum(3).astype(v.dtype)
    return dq, dk, dv


flash_attention_own.defvjp(_flash_own_fwd, _flash_own_bwd)


# ---------------------------------------------------------------------------
# fused RMSNorm with analytic custom VJP
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_pallas(x2d, w, eps, block_rows, interpret):
    rows, width = x2d.shape
    return pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
            pl.BlockSpec((width,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name='rms_norm_fwd',
    )(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm(x, weight, eps=1e-6, interpret=False):
    """Fused y = x * rsqrt(mean(x^2) + eps) * weight over the last dim."""
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    rows = x2d.shape[0]
    block = rows if rows <= 256 else 256
    out = _rms_pallas(x2d, weight, eps, block, interpret)
    return out.reshape(shape)


def _rms_fwd(x, weight, eps, interpret):
    return rms_norm(x, weight, eps, interpret), (x, weight)


def _rms_bwd(eps, interpret, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    h = x.shape[-1]
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    gw = gf * wf
    dx = inv * gw - xf * (inv ** 3 / h) * jnp.sum(gw * xf, axis=-1,
                                                  keepdims=True)
    dw = jnp.sum((xf * inv) * gf, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# fused softmax cross-entropy over the vocab dim (VERDICT r4 #5;
# upstream analogue: paddle/phi/kernels/gpu/cross_entropy_kernel.cu)
# ---------------------------------------------------------------------------

def _ce_fwd_kernel(lab_ref, x_ref, loss_ref, lse_ref, m_s, s_s, t_s, *,
                   n_vblocks, block_v, vocab):
    """Single-pass online-softmax CE forward: grid (rows, vocab-seq).
    Scratch carries running (max, expsum, target-logit) per row; the
    logits tile is read from HBM exactly ONCE (the XLA path reads it
    for the max pass and again for the exp-sum pass)."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        s_s[:] = jnp.zeros_like(s_s)
        t_s[:] = jnp.zeros_like(t_s)

    xf = x_ref[:].astype(jnp.float32)  # [rows, block_v]
    rows = xf.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1) + j * block_v
    inb = cols < vocab
    xf = jnp.where(inb, xf, _NEG_INF)
    m_old = m_s[:, 0]
    m_new = jnp.maximum(m_old, jnp.max(xf, axis=1))
    scale = jnp.exp(m_old - m_new)
    s_s[:, 0] = s_s[:, 0] * scale + jnp.sum(
        jnp.exp(xf - m_new[:, None]), axis=1)
    m_s[:, 0] = m_new
    lab = lab_ref[:, 0]  # [rows] int32 (column-vector view, see fwd)
    hit = (cols == lab[:, None]) & inb
    t_s[:, 0] = t_s[:, 0] + jnp.sum(
        jnp.where(hit, x_ref[:].astype(jnp.float32), 0.0), axis=1)

    @pl.when(j == n_vblocks - 1)
    def _fin():
        lse = m_s[:, 0] + jnp.log(s_s[:, 0])
        lse_ref[:, 0] = lse
        loss_ref[:, 0] = lse - t_s[:, 0]


def _ce_bwd_kernel(lab_ref, g_ref, x_ref, lse_ref, dx_ref, *, block_v,
                   vocab):
    """dx = (softmax(x) - onehot(lab)) * g, tile-local (no scan state):
    grid (rows, vocab)."""
    j = pl.program_id(1)
    xf = x_ref[:].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1) + j * block_v
    p = jnp.exp(xf - lse_ref[:])
    onehot = (cols == lab_ref[:]).astype(jnp.float32)
    dx = (p - onehot) * g_ref[:]
    inb = cols < vocab
    dx_ref[:] = jnp.where(inb, dx, 0.0).astype(dx_ref.dtype)


def _ce_pad(n, b):
    return -(-n // b) * b


def softmax_cross_entropy_fwd(logits, labels, block_rows=256,
                              block_v=2048, interpret=False):
    """(per-row nll [N] f32, lse [N] f32) for logits [N, V], labels [N]
    int32. Single HBM pass over the logits."""
    n, v = logits.shape
    np_, vp = _ce_pad(n, block_rows), _ce_pad(v, block_v)
    if np_ != n:
        logits = jnp.pad(logits, ((0, np_ - n), (0, 0)))
        labels = jnp.pad(labels, (0, np_ - n))
    if vp != v:
        logits = jnp.pad(logits, ((0, 0), (0, vp - v)))
    n_vblocks = vp // block_v
    # rank-1 operands are carried as [np_, 1] column vectors: a rank-1
    # block would have to match XLA's rank-1 tiling ({0:T(1024)}), which
    # conflicts with a 256-row block; a (block_rows, 1) 2-D block is
    # layout-legal on both sides
    col = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    loss, lse = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, n_vblocks=n_vblocks,
                          block_v=block_v, vocab=v),
        grid=(np_ // block_rows, n_vblocks),
        in_specs=[
            col,
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        ],
        out_specs=[col, col],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary')),
        interpret=interpret,
        name='ce_fwd',
    )(labels.astype(jnp.int32).reshape(np_, 1), logits)
    return loss.reshape(np_)[:n], lse.reshape(np_)[:n]


def softmax_cross_entropy_bwd(logits, labels, lse, g, block_rows=256,
                              block_v=2048, interpret=False):
    """dlogits for the fused CE (one fused HBM pass, bf16 out)."""
    n, v = logits.shape
    np_, vp = _ce_pad(n, block_rows), _ce_pad(v, block_v)
    if np_ != n:
        logits = jnp.pad(logits, ((0, np_ - n), (0, 0)))
        labels = jnp.pad(labels, (0, np_ - n))
        lse = jnp.pad(lse, (0, np_ - n))
        g = jnp.pad(g, (0, np_ - n))
    if vp != v:
        logits = jnp.pad(logits, ((0, 0), (0, vp - v)))
    col = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    dx = pl.pallas_call(
        functools.partial(_ce_bwd_kernel, block_v=block_v, vocab=v),
        grid=(np_ // block_rows, vp // block_v),
        in_specs=[
            col,
            col,
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
            col,
        ],
        out_specs=pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, vp), logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel')),
        interpret=interpret,
        name='ce_bwd',
    )(labels.astype(jnp.int32).reshape(np_, 1), g.reshape(np_, 1),
      logits, lse.reshape(np_, 1))
    return dx[:n, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_cross_entropy(logits, labels, interpret=False):
    """Differentiable fused CE: per-row nll [N] for [N, V] logits.
    Residuals are (bf16 logits, f32 lse) — no fp32 [N, V] buffer ever
    exists; backward recomputes softmax tile-by-tile."""
    return _sce_fwd(logits, labels, interpret)[0]


def _sce_fwd(logits, labels, interpret):
    loss, lse = softmax_cross_entropy_fwd(logits, labels,
                                          interpret=interpret)
    return loss, (logits, labels, lse)


def _sce_bwd(interpret, res, g):
    logits, labels, lse = res
    dx = softmax_cross_entropy_bwd(logits, labels, lse, g,
                                   interpret=interpret)
    return dx, None


softmax_cross_entropy.defvjp(_sce_fwd, _sce_bwd)


# ---------------------------------------------------------------------------
# fused paged-attention decode kernel (ISSUE 16; upstream analogue:
# vLLM's paged_attention_v1 CUDA kernel, SOSP'23). One query token per
# slot attends over its page-table-scattered KV: the kernel gathers
# pages, dequantizes int8 KV against per-(page, head) scales, and runs
# the online-softmax attend in one pass — the KV never materializes
# contiguously in HBM.
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_pages, v_pages, table, lengths, *,
                              k_scales=None, v_scales=None, sm_scale=None):
    """Pure-lax paged attention: gather pages → dequant → masked attend.

    The CPU/backward-compat fallback for `paged_attention` (and the
    parity ground truth for the pallas kernel, which is run against it
    in interpret mode).

    q           [N, H, D]      one decode query per slot
    k/v_pages   [num_pages, page_size, HKV, D]  paged KV (float or int8)
    table       [N, P] int32   per-slot page table (page 0 = null page)
    lengths     [N] int32      valid KV rows per slot (pos < length)
    k/v_scales  [num_pages, HKV] f32 int8 dequant scales, or None

    GQA folds query heads as [HKV, G] groups (G = H // HKV), matching
    `jnp.repeat(k, G, axis=2)` head order everywhere else in the repo.
    Slots with length == 0 yield a finite but meaningless row (uniform
    average of their gathered pages) — callers mask inactive slots, per
    the serving engine's active-mask convention.
    """
    n, h, d = q.shape
    ps, hkv = k_pages.shape[1], k_pages.shape[2]
    p = table.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    k = k_pages[table].astype(jnp.float32)     # [N, P, ps, HKV, D]
    v = v_pages[table].astype(jnp.float32)
    if k_scales is not None:
        k = k * k_scales[table][:, :, None, :, None]
    if v_scales is not None:
        v = v * v_scales[table][:, :, None, :, None]
    s_len = p * ps
    k = k.reshape(n, s_len, hkv, d)
    v = v.reshape(n, s_len, hkv, d)
    g = h // hkv
    qf = q.astype(jnp.float32).reshape(n, hkv, g, d) * sm_scale
    s = jnp.einsum('nkgd,nskd->nkgs', qf, k)   # [N, HKV, G, S]
    kpos = jnp.arange(s_len, dtype=jnp.int32)
    live = kpos[None, :] < lengths[:, None]
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum('nkgs,nskd->nkgd', w, v)
    return o.reshape(n, h, d).astype(q.dtype)


def _paged_attn_kernel(table_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                       page_size, n_pages, sm_scale, quant):
    """Grid (N, HKV, P); pages arrive via scalar-prefetch page-table
    lookup in the k/v BlockSpec index maps, so each step's DMA lands the
    right page while the previous one computes."""
    if quant:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
    n = pl.program_id(0)
    ip = pl.program_id(2)

    @pl.when(ip == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # pages entirely past the slot's length contribute nothing — skip
    # their FLOPs (their DMA was to the null page already if unreserved);
    # page 0 always computes so fully-idle slots still finalize finite
    @pl.when((ip == 0) | (ip * page_size < len_ref[n]))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale     # [G, D]
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # [ps, D]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        if quant:
            k = k * ks_ref[0, 0]
            v = v * vs_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [G, ps]
        kpos = ip * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < len_ref[n], s, _NEG_INF)
        m_prev = m_s[:]                                    # [G, 128]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        pexp = jnp.exp(s - m_new[:, :1])
        l_s[:] = l_s[:] * alpha + jnp.broadcast_to(
            jnp.sum(pexp, axis=-1, keepdims=True), l_s.shape)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = m_new

    @pl.when(ip == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[:] / l_s[:, :1]).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, table, lengths, k_scales,
                            v_scales, sm_scale, interpret):
    n, h, d = q.shape
    ps, hkv = k_pages.shape[1], k_pages.shape[2]
    p = table.shape[1]
    g = h // hkv
    quant = k_scales is not None
    q4 = q.reshape(n, hkv, g, d)
    qspec = pl.BlockSpec((1, 1, g, d),
                         lambda n_, h_, p_, tr, lr: (n_, h_, 0, 0))
    kspec = pl.BlockSpec((1, ps, 1, d),
                         lambda n_, h_, p_, tr, lr: (tr[n_, p_], 0, h_, 0))
    in_specs = [qspec, kspec, kspec]
    args = (table.astype(jnp.int32), lengths.astype(jnp.int32),
            q4, k_pages, v_pages)
    if quant:
        sspec = pl.BlockSpec((1, 1),
                             lambda n_, h_, p_, tr, lr: (tr[n_, p_], h_))
        in_specs += [sspec, sspec]
        args += (k_scales, v_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, hkv, p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda n_, h_, p_, tr, lr: (n_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),   # running max
            pltpu.VMEM((g, 128), jnp.float32),   # running denom
            pltpu.VMEM((g, d), jnp.float32),     # output accumulator
        ])
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=ps, n_pages=p,
                          sm_scale=sm_scale, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hkv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary')),
        interpret=interpret,
        name='paged_attention',
    )(*args)
    return out.reshape(n, h, d)


def paged_attention(q, k_pages, v_pages, table, lengths, *, k_scales=None,
                    v_scales=None, sm_scale=None, interpret=False):
    """Fused paged-attention decode step over a page-table KV pool.

    Dispatch: the pallas kernel under `pltpu` on TPU (or anywhere with
    interpret=True); the pure-lax gather reference on every other
    backend so CPU tier-1 runs unchanged. Shapes as in
    `paged_attention_reference`; pass k/v_scales for int8 pages.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret or jax.default_backend() == 'tpu':
        return _paged_attention_pallas(q, k_pages, v_pages, table, lengths,
                                       k_scales, v_scales, sm_scale,
                                       interpret)
    return paged_attention_reference(q, k_pages, v_pages, table, lengths,
                                     k_scales=k_scales, v_scales=v_scales,
                                     sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# fused segmented adapter matmul (ISSUE 19; upstream analogues: Punica's
# SGMV / S-LoRA's unified multi-adapter batched kernels). Each batch row
# carries its own LoRA adapter slot in a packed bank; the kernel gathers
# that row's [H, R] / [R, O] factors straight out of the bank via
# scalar-prefetched indices and computes x @ A @ B * scale without ever
# materializing per-request adapter copies — so ONE compiled decode
# program serves any heterogeneous adapter mix.
# ---------------------------------------------------------------------------

def adapter_matmul_reference(x, a_bank, b_bank, rows, scale):
    """Pure-lax segmented LoRA delta: gather-over-the-bank + einsum.

    The CPU fallback for `adapter_matmul` (and the parity ground truth
    for the pallas kernel, run against it in interpret mode).

    x       [B, T, H]    per-row activations (decode: B=num_slots, T=1)
    a_bank  [C, H, R]    packed down-projection factors, C bank slots
    b_bank  [C, R, O]    packed up-projection factors
    rows    [B] int32    per-row bank slot (slot 0 = zero base adapter)
    scale   [C] f32      per-slot alpha/rank scaling (scale[0] == 0)

    Returns the [B, T, O] delta in x.dtype. Rows pointing at slot 0 get
    an exactly-zero delta (0-factors x 0-scale), so adapter-less rows
    decode bit-identically to a bank-less engine.
    """
    xf = x.astype(jnp.float32)
    a = a_bank[rows].astype(jnp.float32)        # [B, H, R]
    b = b_bank[rows].astype(jnp.float32)        # [B, R, O]
    s = scale[rows].astype(jnp.float32)         # [B]
    h1 = jnp.einsum('bth,bhr->btr', xf, a)
    out = jnp.einsum('btr,bro->bto', h1, b)
    return (out * s[:, None, None]).astype(x.dtype)


def _adapter_matmul_kernel(rows_ref, scale_ref, x_ref, a_ref, b_ref, o_ref):
    """Grid (B,); the row's bank slot arrives via scalar-prefetch in the
    a/b BlockSpec index maps, so each step's DMA lands that row's
    factors while the previous row computes. The per-slot scale is a
    scalar, so it rides the scalar prefetch too (SMEM): a (1, 1) VMEM
    block over a [C, 1] array is a slice Mosaic's tiling refuses."""
    x = x_ref[0].astype(jnp.float32)                       # [T, H]
    a = a_ref[0].astype(jnp.float32)                       # [H, R]
    b = b_ref[0].astype(jnp.float32)                       # [R, O]
    h1 = jnp.dot(x, a, preferred_element_type=jnp.float32)
    out = jnp.dot(h1, b, preferred_element_type=jnp.float32)
    scale = scale_ref[rows_ref[pl.program_id(0)]]
    o_ref[0] = (out * scale).astype(o_ref.dtype)


def _adapter_matmul_pallas(x, a_bank, b_bank, rows, scale, interpret):
    bsz, t, h = x.shape
    r = a_bank.shape[2]
    o = b_bank.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz,),
        in_specs=[
            pl.BlockSpec((1, t, h), lambda i, rr, ss: (i, 0, 0)),
            pl.BlockSpec((1, h, r), lambda i, rr, ss: (rr[i], 0, 0)),
            pl.BlockSpec((1, r, o), lambda i, rr, ss: (rr[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, o), lambda i, rr, ss: (i, 0, 0)),
    )
    return pl.pallas_call(
        _adapter_matmul_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, t, o), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',)),
        interpret=interpret,
        name='adapter_matmul',
    )(rows.astype(jnp.int32), scale.astype(jnp.float32), x, a_bank, b_bank)


def adapter_matmul(x, a_bank, b_bank, rows, scale, *, interpret=False):
    """Fused gather+matmul LoRA delta over a packed adapter bank.

    Dispatch: the pallas kernel under `pltpu` on TPU (or anywhere with
    interpret=True); the pure-lax gather reference on every other
    backend so CPU tier-1 runs unchanged. Shapes as in
    `adapter_matmul_reference`.
    """
    if interpret or jax.default_backend() == 'tpu':
        return _adapter_matmul_pallas(x, a_bank, b_bank, rows, scale,
                                      interpret)
    return adapter_matmul_reference(x, a_bank, b_bank, rows, scale)


# ---------------------------------------------------------------------------
# routed experts of one decode batch (ISSUE 31; upstream analogues: the
# grouped-GEMM decode kernels of vLLM's fused_moe and MegaBlocks). The
# batch is one block wide, so every touched expert multiplies every row;
# the grid walks the DISTINCT experts the batch picked and the weight
# BlockSpecs read the scalar-prefetched id, so Pallas's double buffering
# has expert N+1's tiles in flight while expert N multiplies.
# ---------------------------------------------------------------------------

# rows are padded to a whole packed bf16 tile, so the three parts of the
# activations stack at tile boundaries
_EXPERT_ROW_TILE = 16


def _expert_f_tile(f):
    """How much of an expert's `f` a grid step takes: 512 where it
    divides (trinity-mini's 1024, lfm2's 1536). On the chip 512, 768 and
    a whole expert stream alike, 733-739 GB/s, and 256 a tenth slower
    (CHANGES.md, PR 31); the smallest of the equals holds the least
    VMEM and exposes the shortest first fetch."""
    return next((n for n in (512, 384, 256, 128) if f % n == 0), f)


def _split3(a):
    """[T, n] float32 -> [3T, n] bf16: the three bf16 parts whose sum is
    `a` to float32's last bit (hi, mid, lo), stacked by rows so that ONE
    product against a bf16 weight tile pushes the tile through the MXU
    once for all three passes."""
    hi = a.astype(jnp.bfloat16)
    r = a - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _dot3(parts, w):
    """`parts` [3T, n] (`_split3`) times a bf16 tile [n, m] -> [T, m]
    float32: the three passes summed, the small ones first. The
    precision is spelled out: the models trace this under
    `default_matmul_precision('high')`, which is what the parts ARE,
    and which Mosaic refuses on a dot."""
    t = parts.shape[0] // 3
    y = jnp.dot(parts, w, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.DEFAULT)
    return (y[2 * t:] + y[t:2 * t]) + y[:t]


def _moe_decode_kernel(ids_ref, cnt_ref, x_ref, wd_ref, g_ref, u_ref,
                       d_ref, o_ref, xs_ref):
    """Grid (distinct experts, tiles of f), both `arbitrary`: step
    (i, j) adds `wd[:, ids[i]] * (silu(x G_j) * (x U_j)) D_j` to the
    resident float32 output. Steps past the count do nothing (and their
    block indices repeat the last real step's, so nothing is fetched).
    An expert's column of the dense routing weight `wd` [T, E] is picked
    with a lane mask: a (T, 1) block over it is a slice Mosaic's tiling
    refuses (`_adapter_matmul_kernel`)."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        xs_ref[...] = _split3(x_ref[...])

    @pl.when(i < cnt_ref[0])
    def _():
        xs = xs_ref[...]
        g, u = _dot3(xs, g_ref[0]), _dot3(xs, u_ref[0])
        y = _dot3(_split3(jax.nn.silu(g) * u), d_ref[0])
        wd = wd_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, wd.shape, 1)
        col = jnp.sum(jnp.where(lane == ids_ref[i], wd, 0.0), axis=1,
                      keepdims=True)
        o_ref[...] += y * col


def moe_decode_experts(x, sel, w, gate_w, up_w, down_w, *, f_tile=None,
                       interpret=False):
    """`sum_k w[t, k] * SwiGLU_{sel[t, k]}(x[t])` for a batch one block
    wide, as ONE kernel that streams the touched experts' weights: x
    [T, h] float32, sel / w [T, k], bf16 expert leaves [E, h, f],
    [E, h, f], [E, f, h] -> [T, h] float32.

    Every row is multiplied by every expert the batch touched and
    weighted by a dense [T, E] routing weight that is zero where the row
    did not pick it; an expert nobody picked is never read. Products are
    the activations' three bf16 parts against the bf16 tile, summed in
    float32 (no less than `precision='high'` gives: that splits in two).
    `f_tile` (a divisor of f, a multiple of 128 or f itself) is how much
    of an expert a grid step takes; None is `_expert_f_tile(f)`."""
    t, h = x.shape
    k, (e, _, f) = sel.shape[1], gate_w.shape
    if x.dtype != jnp.float32:
        raise ValueError(f'moe_decode_experts: float32 activations, not '
                         f'{x.dtype}')
    if not (gate_w.dtype == up_w.dtype == down_w.dtype == jnp.bfloat16):
        raise ValueError(
            'moe_decode_experts: bf16 expert weights (the three-part '
            f'product is exact only against them), not {gate_w.dtype}')
    if up_w.shape != (e, h, f) or down_w.shape != (e, f, h) \
            or sel.shape != (t, k) or w.shape != (t, k):
        raise ValueError(
            f'moe_decode_experts: x {x.shape}, sel {sel.shape}, w '
            f'{w.shape} against leaves {gate_w.shape}, {up_w.shape}, '
            f'{down_w.shape}')
    if f_tile is None:
        f_tile = _expert_f_tile(f)
    if f % f_tile or (f_tile != f and f_tile % 128):
        raise ValueError(f'moe_decode_experts: f_tile {f_tile} must '
                         f'divide f {f} in multiples of 128')
    nf = f // f_tile
    tp = -(-t // _EXPERT_ROW_TILE) * _EXPERT_ROW_TILE
    bound = min(e, t * k)
    # tiny, in XLA: the dense routing weight, and the distinct experts in
    # order, padded with the last real one (a repeated block index is
    # not fetched again)
    experts = jnp.arange(e, dtype=jnp.int32)
    hit = sel[:, :, None] == experts                          # [T, k, E]
    wd = jnp.sum(jnp.where(hit, w.astype(jnp.float32)[:, :, None], 0.0),
                 axis=1)
    touched = jnp.any(hit, axis=(0, 1))
    cnt = jnp.sum(touched, dtype=jnp.int32)
    ids = jnp.argsort(~touched, stable=True)[:bound].astype(jnp.int32)
    ids = jnp.where(jnp.arange(bound) < cnt, ids, ids[cnt - 1])

    def resident(i, j, ids_ref, cnt_ref):
        return 0, 0

    def tile(i, j, cnt_ref):    # past the count: the last real step's
        return jnp.where(i < cnt_ref[0], j, nf - 1)

    def columns(i, j, ids_ref, cnt_ref):        # of gate_w, up_w
        return ids_ref[i], 0, tile(i, j, cnt_ref)

    def rows(i, j, ids_ref, cnt_ref):           # of down_w
        return ids_ref[i], tile(i, j, cnt_ref), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bound, nf),
        in_specs=[
            pl.BlockSpec((tp, h), resident),
            pl.BlockSpec((tp, e), resident),
            pl.BlockSpec((1, h, f_tile), columns),
            pl.BlockSpec((1, h, f_tile), columns),
            pl.BlockSpec((1, f_tile, h), rows),
        ],
        out_specs=pl.BlockSpec((tp, h), resident),
        scratch_shapes=[pltpu.VMEM((3 * tp, h), jnp.bfloat16)])
    # two buffers of each weight tile, the resident rows, and the
    # products' float32 intermediates; under the chip's 128 MiB
    tile_bytes = 3 * h * f_tile * 2
    vmem = 2 * tile_bytes + 3 * tp * (4 * h + 3 * f_tile) * 4 + (8 << 20)
    pad = ((0, tp - t), (0, 0))
    out = pl.pallas_call(
        _moe_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tp, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name='moe_decode_experts',
    )(ids, cnt[None], jnp.pad(x, pad), jnp.pad(wd, pad), gate_w, up_w,
      down_w)
    return out[:t]


# ---------------------------------------------------------------------------
# decode attention over latent rows (ISSUE 38; upstream analogues:
# FlashMLA's and vLLM's absorbed MLA decode kernels). One query a slot,
# every head reading the same rows `c` (and `r` for the rotary half of
# a logit): the grid walks the row tiles the slots HOLD, slot after slot
# up to each slot's length, and a tile serves the scores AND the values
# while it sits in VMEM — XLA's two einsums stream `c` twice, over every
# row of every slot.
# ---------------------------------------------------------------------------

def _mla_row_tile(rows):
    """How many rows a grid step takes: 512 where it divides the rows
    attended (CHANGES.md, PR 38, has the chip's numbers at 256, 512 and
    1024), else the largest whole number of lanes that does; None where
    there is none."""
    return next((n for n in (512, 256, 128) if rows % n == 0), None)


def _split2(a):
    """float32 [n, k] -> [2n, k] bf16, `[hi ; lo]` stacked by rows, hi +
    lo = a to 16 bits of mantissa: the two parts `precision='high'`
    splits an operand into. Rows that ARE bf16 have no `lo`: they come
    back as they are."""
    if a.dtype == jnp.bfloat16:
        return a
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, lo], axis=0)


def _dot_high(a2, n, b2, m, dims):
    """`a . b` as `precision='high'` gives it — hi.lo, lo.hi, hi.hi in
    float32, the small ones first — from the operands' stacked parts
    (`_split2`; `n`, `m` the rows of `a`, `b`; a `b2` of `m` rows is an
    operand that is bf16 and has no `lo`). Both parts of `a` meet a
    tile of `b_hi` in ONE product, so the tile is pushed through the MXU
    once for the two."""
    def dot(a, b):
        return jax.lax.dot_general(a, b, (dims, ((), ())),
                                   preferred_element_type=jnp.float32,
                                   precision=jax.lax.Precision.DEFAULT)
    y = dot(a2, b2[:m])
    y = y[n:] + y[:n]
    return y if b2.shape[0] == m else dot(a2[:n], b2[m:]) + y


def _mla_decode_kernel(slot_ref, tile_ref, last_ref, ql_ref, qr_ref, c_ref,
                       r_ref, seen_ref, o_ref, ql_s, qr_s, m_s, l_s, acc_s,
                       *, tile, scale):
    """ONE flat grid over the row tiles the slots hold, slot after slot
    (`slot_ref`, `tile_ref`: step -> the slot, and which of its tiles):
    a step folds its tile into the slot's online softmax (running max
    `m_s`, sum `l_s`, `[H, C]` accumulator `acc_s`, float32); a slot's
    first tile starts them, its last (`last_ref`) writes the output. No
    step is spent on a tile past a slot's bound, and the next slot's
    first tile is in flight while this slot's last multiplies."""
    i = pl.program_id(0)
    h = ql_ref.shape[1]

    @pl.when(tile_ref[i] == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        # the scaled query's parts, once a slot
        ql_s[...] = _split2(ql_ref[0].astype(jnp.float32) * scale)
        qr_s[...] = _split2(qr_ref[0].astype(jnp.float32) * scale)

    c, r = _split2(c_ref[0]), _split2(r_ref[0])         # once, for both
    nt = ((1,), (1,))               # contract the minor dim of both
    s = _dot_high(ql_s[...], h, c, tile, nt) \
        + _dot_high(qr_s[...], h, r, tile, nt)                 # [H, T]
    s = jnp.where(seen_ref[0] != 0, s, _NEG_INF)
    m_prev = m_s[...]                                      # [H, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p = jnp.exp(s - m_new[:, :1])
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + _dot_high(
        _split2(p), h, c, tile, ((1,), (0,)))
    m_s[...] = m_new

    @pl.when(last_ref[i] != 0)
    def _():
        o_ref[0] = (acc_s[...] / l_s[:, :1]).astype(o_ref.dtype)


def mla_decode_attention(q_lat, q_rope, c, r, seen, scale, *, tile=None,
                         interpret=False):
    """Absorbed latent attention of ONE query a slot: q_lat `[B, H, C]`
    and q_rope `[B, H, R]` float32 against the rows c `[B, L, C]` and r
    `[B, L, R]` (float32 or bf16, as the pool holds them), `seen` `[B,
    rows]` boolean, `rows <= L`: `softmax_k((q_lat . c_k + q_rope . r_k)
    * scale) . c` over the rows `seen` shows -> `[B, H, C]` float32.

    A slot's bound is its last seen row + 1: the row tiles under it are
    walked, each read once for the scores and the values, and a row
    `seen` hides under the bound stays hidden. A slot that sees no row
    walks one tile and gives a finite, meaningless average of it (the
    caller discards an inactive slot's output). The grid is as long as
    the tiles to walk (a dynamic bound: what a batch costs goes by the
    rows it holds, not by `B x L`). Products are what `precision='high'`
    gives XLA (`_dot_high`), the softmax float32. `tile` (dividing
    `rows` and `L`; on a TPU whole lanes) is the rows a grid step takes;
    None is `_mla_row_tile` of both."""
    bsz, h, lat = q_lat.shape
    length, rope, rows = c.shape[1], r.shape[2], seen.shape[1]
    if q_rope.shape != (bsz, h, rope) or c.shape != (bsz, length, lat) \
            or r.shape[:2] != (bsz, length) or seen.shape[0] != bsz \
            or rows > length or c.dtype != r.dtype:
        raise ValueError(
            f'mla_decode_attention: q {q_lat.shape} / {q_rope.shape} '
            f'against rows {c.shape} {c.dtype} / {r.shape} {r.dtype}, '
            f'seen {seen.shape}')
    if seen.dtype != jnp.bool_:
        raise ValueError(f'mla_decode_attention: a boolean mask, not '
                         f'{seen.dtype}')
    if tile is None:
        tile = _mla_row_tile(math.gcd(rows, length))
    if not tile or rows % tile or length % tile:
        raise ValueError(f'mla_decode_attention: tile {tile} must divide '
                         f'the rows attended {rows} and held {length}')
    parts = 1 if c.dtype == jnp.bfloat16 else 2
    # tiny, in XLA: a slot's bound and tiles, the table step -> (slot,
    # its tile, whether its last) — one entry more than the most steps,
    # every entry past the walk the last real step's — and the mask as
    # the kernel reads it
    k = jnp.arange(1, rows + 1, dtype=jnp.int32)
    bound = jnp.max(jnp.where(seen, k, 0), axis=1)
    tiles = (jnp.maximum(bound, 1) + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    step = jnp.minimum(jnp.arange(bsz * (rows // tile) + 1, dtype=jnp.int32),
                       ends[-1] - 1)
    slot = jnp.sum(ends[None, :] <= step[:, None], axis=1, dtype=jnp.int32)
    at = step - (ends - tiles)[slot]
    last = (at == tiles[slot] - 1).astype(jnp.int32)

    def of_slot(i, slot_ref, tile_ref, last_ref):
        return slot_ref[i], 0, 0

    def of_tile(i, slot_ref, tile_ref, last_ref):
        return slot_ref[i], tile_ref[i], 0

    def seen_tile(i, slot_ref, tile_ref, last_ref):
        return slot_ref[i], 0, tile_ref[i]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ends[-1],),
        in_specs=[
            pl.BlockSpec((1, h, lat), of_slot),
            pl.BlockSpec((1, h, rope), of_slot),
            pl.BlockSpec((1, tile, lat), of_tile),
            pl.BlockSpec((1, tile, rope), of_tile),
            pl.BlockSpec((1, 1, tile), seen_tile),
        ],
        out_specs=pl.BlockSpec((1, h, lat), of_slot),
        scratch_shapes=[
            pltpu.VMEM((2 * h, lat), jnp.bfloat16),     # [hi ; lo] q_lat
            pltpu.VMEM((2 * h, rope), jnp.bfloat16),    # [hi ; lo] q_rope
            pltpu.VMEM((h, 128), jnp.float32),          # running max
            pltpu.VMEM((h, 128), jnp.float32),          # running sum
            pltpu.VMEM((h, lat), jnp.float32),          # accumulator
        ])
    # two buffers of each row tile (`r` padded to whole lanes), their
    # parts, the products' float32 results; far under the chip's 128 MiB
    lanes = lat + -(-rope // 128) * 128
    vmem = tile * lanes * (2 * c.dtype.itemsize + 2 * parts) \
        + 16 * h * (tile + lat) * 4 + (8 << 20)
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, tile=tile, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, lat), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',), vmem_limit_bytes=vmem),
        interpret=interpret,
        name='mla_decode_attention',
    )(slot, at, last, q_lat, q_rope, c, r,
      seen.astype(jnp.int32)[:, None, :])


# ---------------------------------------------------------------------------
# decode attention over K and V held by head (ISSUE 40; upstream
# analogues: vLLM's and jax's ragged paged attention kernels, whose
# strided loads of a head's rows `by_stride` has). The sibling of the
# latent kernel above for leaves `[slot, row, H_kv, D]`: the same flat
# grid over the row tiles the slots hold, bounded per slot at BOTH ends (a
# window layer's walk starts at its first seen row), a K tile and a V
# tile each used once while they sit in VMEM. A tile is taken as the
# leaf holds it, a row's KV heads side by side — its LINES `[tile x
# H_kv, D]` — because every other arrangement is a copy of the leaf
# (`serving/kv_pool.py` on layouts; CHANGES.md, PR 40, has the chip's
# numbers for the forms tried).
# ---------------------------------------------------------------------------

def decode_walk(first, bound, tile):
    """The row tiles a slot's decode attention walks -> (the tile it
    starts at, how many): from the tile of the `first` row it sees to
    the tile of the last (`bound` = that row + 1), ONE tile — tile 0 —
    of a slot that sees nothing (`first` = `bound` = 0). Plain
    arithmetic on whole numbers, numpy's or jax's: the kernel's table
    and the serving engine's `read_rows` both come from here."""
    start = first // tile
    return start, (bound + (bound == 0) + tile - 1) // tile - start


def _kv_lines(ref):
    """A leaf's tile as its LINES `[tile * H_kv, D]`, line `j` row `j //
    H_kv` of KV head `j % H_kv`: what a block of the leaf's view `[B, L
    * H_kv, D]` is, and what a block `[1, tile, H_kv, D]` of the leaf
    itself (a leaf too wide for that view) is reshaped to."""
    return ref[0] if len(ref.shape) == 3 else ref[0].reshape(
        -1, ref.shape[-1])


def _kv_fold(s, v2, lines, n, m_ref, l_ref, acc_ref):
    """Fold one tile's masked scores `s` `[n, lines]` and its values'
    stacked parts `v2` into an online softmax's running max, sum and
    accumulator (float32)."""
    m_prev = m_ref[...]                                    # [n, 128]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p = jnp.exp(s - m_new[:, :1])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + _dot_high(
        _split2(p), n, v2, lines, ((1,), (0,)))
    m_ref[...] = m_new


def _kv_decode_kernel(slot_ref, tile_ref, edge_ref, q_ref, k_ref, v_ref,
                      seen_ref, o_ref, m_s, l_s, acc_s, *, tile, hkv, scale,
                      by_stride):
    """`_mla_decode_kernel`'s grid (`slot_ref`, `tile_ref`: step -> the
    slot, and which tile of its leaf; `edge_ref`: 1 on a slot's first
    step, 2 on its last, 3 on both) with a V operand and KV heads. A
    tile comes as its lines (`_kv_lines`), a row's heads side by side.
    `by_stride`: a KV head at a time, its rows every `H_kv`-th line (a
    strided load, which Mosaic has for float32 lines of at most 128
    lanes), its `rep` queries folded into that head's online softmax:
    the kernel at its DMA pace. Else every query head meets every line
    — `H_kv` times the products on the MXU, the least loaded unit, and
    of the softmax's element-wise work: an eighth slower a tile on the
    v5e — and `seen_ref` names each line's KV head, -1 where the mask
    hides its row: query head `h` sees the lines of KV head `h // rep`."""
    i = pl.program_id(0)
    h = q_ref.shape[1]
    rep = h // hkv

    @pl.when(edge_ref[i] % 2 == 1)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32) * scale
    nt = ((1,), (1,))               # contract the minor dim of both
    if by_stride:
        seen = seen_ref[0] != 0                                # [1, T]
        for g in range(hkv):
            rows, mine = pl.ds(g, tile, stride=hkv), pl.ds(g * rep, rep)
            s = _dot_high(_split2(q[g * rep:(g + 1) * rep]), rep,
                          _split2(k_ref[0, rows, :]), tile, nt)
            _kv_fold(jnp.where(seen, s, _NEG_INF), _split2(v_ref[0, rows, :]),
                     tile, rep, m_s.at[mine], l_s.at[mine], acc_s.at[mine])
    else:
        lines = tile * hkv
        s = _dot_high(_split2(q), h, _split2(_kv_lines(k_ref)), lines, nt)
        mine = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0) // rep
        _kv_fold(jnp.where(seen_ref[0] == mine, s, _NEG_INF),
                 _split2(_kv_lines(v_ref)), lines, h, m_s, l_s, acc_s)

    @pl.when(edge_ref[i] >= 2)
    def _():
        o_ref[0] = (acc_s[...] / l_s[:, :1]).astype(o_ref.dtype)


def kv_decode_attention(q, k, v, seen, scale, *, tile=None, interpret=False):
    """Attention of ONE query a slot over K and V held by head: q `[B,
    H, D]` float32 against k `[B, L, H_kv, D]` and v `[B, L, H_kv, Dv]`
    (float32 or bf16, as the pool holds them; query head `j * rep + r`
    reads KV head `j`, `rep = H // H_kv`), `seen` `[B, rows]` boolean,
    `rows <= L`: `softmax_k(q . k_k * scale) . v` over the rows `seen`
    shows -> `[B, H, Dv]` float32.

    A slot's walk runs from the tile of the first row it sees to the
    tile of the last (`decode_walk`): those tiles are read, each once
    for the scores and the values, and a row `seen` hides between the
    two stays hidden. A slot that sees no row walks one tile and gives
    a finite, meaningless average of it (the caller discards an
    inactive slot's output). The grid is as long as the tiles to walk.
    Products are what `precision='high'` gives XLA (`_dot_high`), the
    softmax float32.

    A leaf of at most 128 lanes a head is taken as its lines `[B, L *
    H_kv, D]`, a bitcast of a row-major leaf; a wider one (mimo's K,
    192) as it is, and its tile is reshaped to lines in the kernel:
    either way nothing of a leaf the serving pool holds is copied on
    the way in (`serving/kv_pool.py` on layouts). Float32 leaves both
    taken as lines are read a KV head at a time, by stride; any other
    pair all heads at once (`_kv_decode_kernel`). `tile` (dividing
    `rows` and `L`; on a TPU whole lanes) is the rows a grid step
    takes; None is `_mla_row_tile` of both."""
    bsz, h, d = q.shape
    length, hkv, dv, rows = k.shape[1], k.shape[2], v.shape[3], seen.shape[1]
    if k.shape != (bsz, length, hkv, d) or v.shape[:3] != k.shape[:3] \
            or h % hkv or seen.shape[0] != bsz or rows > length \
            or k.dtype != v.dtype:
        raise ValueError(
            f'kv_decode_attention: q {q.shape} against k {k.shape} '
            f'{k.dtype} / v {v.shape} {v.dtype}, seen {seen.shape}')
    if seen.dtype != jnp.bool_:
        raise ValueError(f'kv_decode_attention: a boolean mask, not '
                         f'{seen.dtype}')
    if tile is None:
        tile = _mla_row_tile(math.gcd(rows, length))
    if not tile or rows % tile or length % tile:
        raise ValueError(f'kv_decode_attention: tile {tile} must divide '
                         f'the rows attended {rows} and held {length}')
    parts = 1 if k.dtype == jnp.bfloat16 else 2
    by_stride = max(d, dv) <= 128 and k.dtype == jnp.float32
    # tiny, in XLA: a slot's first and last seen row, its walk, the
    # table step -> (slot, its tile, whether its first or its last) —
    # one entry more than the most steps, every entry past the walk the
    # last real step's — and the mask as the kernel reads it
    row = jnp.arange(rows, dtype=jnp.int32)
    bound = jnp.max(jnp.where(seen, row + 1, 0), axis=1)
    first = jnp.minimum(jnp.min(jnp.where(seen, row, rows), axis=1), bound)
    start, tiles = decode_walk(first, bound, tile)
    ends = jnp.cumsum(tiles)
    step = jnp.minimum(jnp.arange(bsz * (rows // tile) + 1, dtype=jnp.int32),
                       ends[-1] - 1)
    slot = jnp.sum(ends[None, :] <= step[:, None], axis=1, dtype=jnp.int32)
    at = step - (ends - tiles)[slot]
    edge = (at == 0) + 2 * (at == tiles[slot] - 1)
    if by_stride:
        seen, per_row = seen.astype(jnp.int32), 1
    else:           # by line: its KV head, -1 where its row is hidden
        seen = jnp.where(seen[:, :, None], jnp.arange(hkv, dtype=jnp.int32),
                         -1).reshape(bsz, rows * hkv)
        per_row = hkv

    def of_slot(i, slot_ref, tile_ref, edge_ref):
        return slot_ref[i], 0, 0

    def of_tile(i, slot_ref, tile_ref, edge_ref):
        return slot_ref[i], tile_ref[i], 0

    def of_tile4(i, slot_ref, tile_ref, edge_ref):
        return slot_ref[i], tile_ref[i], 0, 0

    def seen_tile(i, slot_ref, tile_ref, edge_ref):
        return slot_ref[i], 0, tile_ref[i]

    def leaf(c):
        width = c.shape[-1]
        if width <= 128:
            return (c.reshape(bsz, length * hkv, width),
                    pl.BlockSpec((1, tile * hkv, width), of_tile))
        return c, pl.BlockSpec((1, tile, hkv, width), of_tile4)
    (k, k_block), (v, v_block) = leaf(k), leaf(v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ends[-1],),
        in_specs=[
            pl.BlockSpec((1, h, d), of_slot), k_block, v_block,
            pl.BlockSpec((1, 1, tile * per_row), seen_tile),
        ],
        out_specs=pl.BlockSpec((1, h, dv), of_slot),
        scratch_shapes=[
            pltpu.VMEM((h, 128), jnp.float32),          # running max
            pltpu.VMEM((h, 128), jnp.float32),          # running sum
            pltpu.VMEM((h, dv), jnp.float32),           # accumulator
        ])
    # two buffers of each tile (its lines padded to whole lanes), their
    # parts, the products' float32 results; far under the chip's 128 MiB
    lanes = -(-d // 128) * 128 + -(-dv // 128) * 128
    vmem = tile * hkv * lanes * (2 * k.dtype.itemsize + 2 * parts) \
        + 16 * h * (tile * per_row + dv) * 4 + (8 << 20)
    return pl.pallas_call(
        functools.partial(_kv_decode_kernel, tile=tile, hkv=hkv, scale=scale,
                          by_stride=by_stride),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',), vmem_limit_bytes=vmem),
        interpret=interpret,
        name='kv_decode_attention',
    )(slot, at + start[slot], edge.astype(jnp.int32), q, k, v,
      seen[:, None, :])


# ---------------------------------------------------------------------------
# a KDA layer's recurrence of one token (ISSUE 44; `nlp/ling3.py::
# kda_step` is the plain form and the parity ground truth). XLA makes
# two fusions of it — both sums over d_k read the decayed state, then
# the update reads it again — because `u` needs the first sum before
# the update can start: two reads and a write of `[B, H, d_k, d_v]`
# float32 a layer a sub-step. Here a block of heads' tiles sits in VMEM
# between the sums and the update: one read, one write, in place.
# ---------------------------------------------------------------------------
_KDA_TILE_BYTES = 2 << 20       # a grid step's state block, each way
_KDA_COLUMNS = 128              # q, k and the decay of a block's heads


def _kda_head_block(heads, dk, dv):
    """The heads a grid step of `kda_decode_step` takes, None where
    none will do: the most that divide `heads`, keep the block of their
    `[d_k, d_v]` float32 tiles within `_KDA_TILE_BYTES` and their q, k
    and decay rows within `_KDA_COLUMNS` — whole sublanes (8) of them,
    or every head. (On the v5e blocks of 8, 16 and 32 heads of 64 KiB
    ran alike, each at the pace of a kernel that only copies the tiles:
    PERF.md, PR 44.)"""
    most = min(heads, _KDA_COLUMNS // 3,
               max(_KDA_TILE_BYTES // (4 * dk * dv), 1))
    for n in range(most, 0, -1):
        if heads % n == 0 and (n % 8 == 0 or n == heads):
            return n
    return None


def _kda_step_kernel(q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref, o_ref,
                     out_ref, *, scale):
    """One block of heads of one slot. q, k and g come by ROWS (a
    head's vector along lanes) and the state wants them along d_k, its
    sublane axis: the block's rows are stacked `[q ; k ; exp(g) ; 0]`
    and turned ONCE (a 2-D float32 transpose), so head `j`'s vectors
    are columns `j`, `n + j`, `2 n + j`. Then a head at a time,
    elementwise float32 as `kda_step` spells it: the decayed tile, both
    sums over d_k from it, `u`, `o`, and the tile written back."""
    n = q_ref.shape[1]
    q = q_ref[0].astype(jnp.float32) * scale                   # [n, d_k]
    k = k_ref[0].astype(jnp.float32)
    kq = jnp.sum(k * q, axis=-1, keepdims=True)                # [n, 1]
    rows = jnp.concatenate(
        [q, k, jnp.exp(g_ref[0].astype(jnp.float32)),
         jnp.zeros((_KDA_COLUMNS - 3 * n, q.shape[1]), jnp.float32)], axis=0)
    cols = rows.T                                           # [d_k, 128]
    for j in range(n):
        qc, kc, decay = (cols[:, i * n + j:i * n + j + 1] for i in range(3))
        decayed = s_ref[0, j] * decay                       # [d_k, d_v]
        u = beta_ref[0, j:j + 1] * (v_ref[0, j:j + 1].astype(jnp.float32)
                                    - jnp.sum(decayed * kc, axis=0,
                                              keepdims=True))  # [1, d_v]
        o_ref[0, j:j + 1] = jnp.sum(decayed * qc, axis=0, keepdims=True) \
            + kq[j:j + 1] * u
        out_ref[0, j] = decayed + kc * u


def kda_decode_step(q, k, v, g, beta, state, *, heads=None, interpret=False):
    """`nlp/ling3.py::kda_step` as ONE kernel: q, k, g `[B, H, d_k]`, v
    `[B, H, d_v]`, beta `[B, H]`, state `[B, H, d_k, d_v]` float32 ->
    (o `[B, H, d_v]` float32, the state after the token). A grid over
    (slot, block of `heads` heads): a step brings its block of `[d_k,
    d_v]` tiles into VMEM once and writes it back once, and the state
    is ALIASED input to output — donated to a caller that carries it
    (the decode scan), the update is in place and no second buffer of
    the state's size exists. The state, both sums and the update are
    float32 multiply-adds on the vector unit; nothing of the state
    meets the matrix unit. `beta = 0, g = 0` leaves a tile bit for bit
    (the identity update a folded-out token relies on). `heads`
    (dividing H; on a TPU whole sublanes or all of them) is the heads a
    grid step takes; None is `_kda_head_block`'s."""
    bsz, h, dk = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or g.shape != q.shape or v.shape != (bsz, h, dv) \
            or beta.shape != (bsz, h) or state.shape != (bsz, h, dk, dv):
        raise ValueError(
            f'kda_decode_step: q {q.shape}, k {k.shape}, g {g.shape}, '
            f'v {v.shape}, beta {beta.shape} against state {state.shape}')
    if state.dtype != jnp.float32:
        raise ValueError(f'kda_decode_step: a float32 state, not '
                         f'{state.dtype}')
    if heads is None:
        heads = _kda_head_block(h, dk, dv)
    if not heads or h % heads or 3 * heads > _KDA_COLUMNS:
        raise ValueError(f'kda_decode_step: a block of {heads} heads must '
                         f'divide {h} and be at most {_KDA_COLUMNS // 3}')

    def rows(width):
        return pl.BlockSpec((1, heads, width), lambda b, i: (b, i, 0))
    tiles = pl.BlockSpec((1, heads, dk, dv), lambda b, i: (b, i, 0, 0))
    # two buffers of the block each way, a head's temporaries, the rows
    vmem = 4 * heads * dk * dv * 4 + (16 << 20)
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, scale=1.0 / math.sqrt(dk)),
        grid=(bsz, h // heads),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dv), tiles],
        out_specs=[rows(dv), tiles],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name='kda_decode_step',
    )(q, k, g, v,
      jnp.broadcast_to(beta.astype(jnp.float32)[..., None], (bsz, h, dv)),
      state)


# ---------------------------------------------------------------------------
# a Mamba layer's recurrence over a prompt (ISSUE 47; `nlp/jamba.py::
# mamba_step` token by token is the plain form and the parity ground
# truth). XLA's schedule (`mamba_scan`) composes a chunk's affine
# updates by an associative scan: every level of it a pass over `[C, N,
# Di]` float32 pairs through HBM. The decay is one number a channel a
# STATE, so a chunk has no matrix form; but the state of a block of
# channels is a few vregs, so here it never leaves the core: the tokens
# are walked in order and only `u`, `dt`, `b`, `c` come and `y` goes.
# ---------------------------------------------------------------------------
_SSM_TOKENS = 128               # tokens a grid step walks
_SSM_LANES = 8192               # channels a grid step holds, at most
_SSM_VREGS = 8                  # of the state, carried through a walk


def _ssm_blocks(n, di):
    """(channels a grid step holds, channels a walk carries in vregs):
    the most whole lanes that divide `di` up to `_SSM_LANES`; of those,
    the most whose `[n, lanes]` float32 state is `_SSM_VREGS` vregs."""
    lanes = max(m for m in range(128, min(di, _SSM_LANES) + 1, 128)
                if di % m == 0)
    walk = max(m for m in range(128, lanes + 1, 128) if lanes % m == 0
               and (m == 128 or n * m <= _SSM_VREGS * 1024))
    return lanes, walk


def _ssm_scan_kernel(u_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, h0_ref,
                     y_ref, h_ref, *, walk):
    """One block of tokens of one block of channels of one sequence.
    `h_ref` is the output's block and stays in VMEM over the token axis:
    the state as the tokens before left it. A walk takes `walk` channels
    — their `[N, walk]` state in vregs — through the block's tokens, the
    recurrence as `mamba_step` spells it, elementwise float32; `b` and
    `c` come with their N values down the sublanes of 128 equal lanes."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = h0_ref[...]
    tokens, lanes = u_ref.shape[1], u_ref.shape[2]
    for at in range(0, lanes, walk):
        cols = pl.ds(at, walk)
        a_t, skip = at_ref[:, cols], d_ref[:, cols]        # [N, w], [1, w]

        def token(t, h):
            row = pl.ds(t, 1)
            u, dt = u_ref[0, row, cols], dt_ref[0, row, cols]   # [1, w]
            b = jnp.tile(b_ref[0, t], (1, walk // 128))         # [N, w]
            c = jnp.tile(c_ref[0, t], (1, walk // 128))
            h = jnp.exp(dt * a_t) * h + (dt * u) * b
            y_ref[0, row, cols] = jnp.sum(h * c, axis=0, keepdims=True) \
                + skip * u
            return h

        def eight(i, h):        # Mosaic unrolls all of a loop or none
            for j in range(8):
                h = token(8 * i + j, h)
            return h
        h_ref[0, :, cols] = jax.lax.fori_loop(0, tokens // 8, eight,
                                              h_ref[0, :, cols])


@functools.partial(jax.jit, static_argnames='interpret')
def ssm_prefill_scan(u, dt, b, c, a, d, h0, *, interpret=False):
    """`nlp/jamba.py::mamba_scan` as ONE kernel, `folded` already
    applied to `dt`: u, dt `[B, S, Di]`, b, c `[B, S, N]`, `a` `[Di, N]`
    (negative), `d` `[Di]`, h0 `[B, N, Di]` float32 -> (y `[B, S, Di]`
    float32, the state after the S tokens). A grid over (sequence, block
    of channels, block of `_SSM_TOKENS` tokens, in order): the block's
    state `[N, lanes]` lives in VMEM across the token axis (the output's
    own block, aliased to `h0`), and a token is `decay = exp(dt_t A^T)`,
    `h = decay h + (dt_t u_t) b_t`, `y_t = sum_n h c_t + d u_t` on the
    vector unit, in `mamba_step`'s order: the products of decays an
    associative scan rounds are not made. `b` and `c` reach the sublanes
    broadcast over 128 lanes by XLA (8 KB a token each). A token with
    `dt = 0` — past `folded`, a pad, the tail that fills the last block
    — is decay one and input nothing: the state passes it bit for bit.
    Forward only: nothing differentiates through a served model's scan,
    and `jax.grad` through this call raises (`mamba_scan` is the path a
    gradient takes). Jitted, so that a program's 26 layers trace and
    lower the walk's unrolled body ONCE (0.8 s of host time a call
    otherwise: 39 s of serve-ssm-reason's set-up, PERF.md, PR 47)."""
    bsz, s, di = u.shape
    n = b.shape[-1]
    if dt.shape != u.shape or b.shape != (bsz, s, n) or c.shape != b.shape \
            or a.shape != (di, n) or d.shape != (di,) \
            or h0.shape != (bsz, n, di):
        raise ValueError(
            f'ssm_prefill_scan: u {u.shape}, dt {dt.shape}, b {b.shape}, c '
            f'{c.shape}, a {a.shape}, d {d.shape} against state {h0.shape}')
    if h0.dtype != jnp.float32 or n % 8 or di % 128:
        raise ValueError(f'ssm_prefill_scan: a float32 state of whole '
                         f'sublanes and lanes, not {h0.dtype} {h0.shape}')
    lanes, walk = _ssm_blocks(n, di)
    tokens = min(_SSM_TOKENS, -(-s // 8) * 8)
    pad = -s % tokens

    def rows(t, wide=False):        # dt = 0 over the tail: the identity
        t = jnp.pad(t.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        return jnp.broadcast_to(t[..., None], t.shape + (128,)) if wide \
            else t
    by_token = pl.BlockSpec((1, tokens, lanes), lambda i, j, k: (i, k, j))
    by_state = pl.BlockSpec((1, tokens, n, 128), lambda i, j, k: (i, k, 0, 0))
    state = pl.BlockSpec((1, n, lanes), lambda i, j, k: (i, 0, j))

    def of_channels(r):
        return pl.BlockSpec((r, lanes), lambda i, j, k: (0, j))
    # two buffers of every block, the state's twice over, and room
    vmem = 8 * tokens * (3 * lanes + 2 * n * 128) + 16 * n * lanes \
        + (16 << 20)
    y, h = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, walk=walk),
        grid=(bsz, di // lanes, (s + pad) // tokens),
        in_specs=[by_token, by_token, by_state, by_state, of_channels(n),
                  of_channels(1), state],
        out_specs=[by_token, state],
        out_shape=[jax.ShapeDtypeStruct((bsz, s + pad, di), jnp.float32),
                   jax.ShapeDtypeStruct(h0.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name='ssm_prefill_scan',
    )(rows(u), rows(dt), rows(b, True), rows(c, True),
      a.T.astype(jnp.float32), d.astype(jnp.float32)[None], h0)
    return y[:, :s], h


# ---------------------------------------------------------------------------
# routed experts of a call MORE than one block wide (ISSUE 49; upstream
# analogue: megablox's grouped matmul, `jax.experimental.pallas.ops.tpu.
# megablox`). The picks sorted by expert are cut into aligned tiles of
# rows; a tile is visited once for every expert that has rows in it,
# one after another, and a visit multiplies the whole tile by that
# expert's weights and keeps the rows that are the expert's. The grid
# walks (visit, tile of f): the weights' BlockSpecs read the visit's
# expert from a prefetched table, so Pallas' double buffering has the
# next expert in flight while this one multiplies, and the gate, up,
# SwiGLU and down of a tile never leave VMEM. XLA's `while` over blocks
# (`nlp/afmoe.py::grouped_experts`) starts every block's three weight
# streams cold and sends `g` and `u` through HBM.
# ---------------------------------------------------------------------------

# an expert's three weight tiles, both buffers of each: what `f` is cut by
_GROUPED_WEIGHT_VMEM = 56 << 20


# rows of a tile (whole packed bf16 tiles of 16 rows). A tile that
# straddles two experts is multiplied once for each, so a layer walks
# a tile an expert more than its picks: on the chip
# 128 and 64 level with each other and a sixth to a half faster than 256
# at all six cells' shapes, from 24 rows an expert (mimo) to 640 (xing4)
# — 64 walks fewer rows and feeds the MXU half-empty (CHANGES.md, PR 49)
_GROUPED_ROW_TILE = 128


def _grouped_f_tile(h, f):
    """How much of an expert's `f` a grid step takes: all of it where the
    three weight tiles fit VMEM twice over (then a tile of rows that
    follows one of the same expert fetches nothing), else the most whole
    lanes that divide it and do (mimo's expert is 50 MB)."""
    fits = [n for n in range(128, f, 128)
            if f % n == 0 and 12 * h * n <= _GROUPED_WEIGHT_VMEM]
    return f if 12 * h * f <= _GROUPED_WEIGHT_VMEM or not fits else max(fits)


def _moe_grouped_kernel(ex_ref, tile_ref, lo_ref, hi_ref, x_ref, g_ref,
                        u_ref, d_ref, o_ref, xs_ref):
    """Grid (visits, tiles of f), both `arbitrary`: visit `i` is tile
    `tile[i]` of the sorted rows against expert `ex[i]`, whose rows are
    `lo[i] <= row < hi[i]`. The output's tile stays in VMEM over a
    tile's visits (they follow one another): the first zeroes it and
    splits the rows, each adds `(silu(x G_j) * (x U_j)) D_j` on the
    expert's own rows."""
    i, j = pl.program_id(0), pl.program_id(1)
    tm = o_ref.shape[0]

    @pl.when((j == 0) & ((i == 0)
                        | (tile_ref[i] != tile_ref[jnp.maximum(i - 1, 0)])))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)
        xs_ref[...] = _split2(x_ref[...])

    def dot(parts, w):      # a bf16 tile has no low part: two products
        return _dot_high(parts, tm, w, w.shape[0], ((1,), (0,)))
    xs = xs_ref[...]
    g, u = dot(xs, g_ref[0]), dot(xs, u_ref[0])
    y = dot(_split2(jax.nn.silu(g) * u), d_ref[0])
    row = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    o_ref[...] += jnp.where((row >= lo_ref[i]) & (row < hi_ref[i]), y, 0.0)


@functools.partial(jax.jit, static_argnames=('tile', 'f_tile', 'interpret'))
def _moe_grouped(x, sel, w, gate_w, up_w, down_w, *, tile, f_tile,
                 interpret):
    t, h = x.shape
    k, (e, _, f) = sel.shape[1], gate_w.shape
    n, nf = t * k, f // f_tile
    tiles = -(-n // tile)
    # the sort is `grouped_experts`': a pick of no expert held here
    # (`sel == e`) sorts behind every real one and is counted for none
    flat = sel.reshape(n)
    order = jnp.argsort(flat, stable=True)        # sorted row -> pick
    counts = jnp.zeros(e, jnp.int32).at[flat].add(1)
    last = jnp.cumsum(counts)
    first = last - counts
    # tiny, in XLA: the tiles an expert's rows lie in, and the table
    # visit -> (expert, tile, the expert's rows); at most this many
    visits = jnp.where(counts > 0, (last - 1) // tile - first // tile + 1, 0)
    ends = jnp.cumsum(visits)
    i = jnp.arange(tiles + min(e, n), dtype=jnp.int32)
    ex = jnp.minimum(jnp.sum(ends[None, :] <= i[:, None], axis=1,
                             dtype=jnp.int32), e - 1)
    at = jnp.clip(first[ex] // tile + i - (ends - visits)[ex], 0, tiles - 1)
    xs = jnp.pad(x[order // k], ((0, tiles * tile - n), (0, 0)))

    def rows(i, j, ex_ref, tile_ref, lo_ref, hi_ref):
        return tile_ref[i], 0

    def columns(i, j, ex_ref, tile_ref, lo_ref, hi_ref):  # of gate_w, up_w
        return ex_ref[i], 0, j

    def down(i, j, ex_ref, tile_ref, lo_ref, hi_ref):
        return ex_ref[i], j, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # a layer none of whose experts was picked walks one visit that
        # keeps no row
        grid=(jnp.maximum(ends[-1], 1), nf),
        in_specs=[
            pl.BlockSpec((tile, h), rows),
            pl.BlockSpec((1, h, f_tile), columns),
            pl.BlockSpec((1, h, f_tile), columns),
            pl.BlockSpec((1, f_tile, h), down),
        ],
        out_specs=pl.BlockSpec((tile, h), rows),
        scratch_shapes=[pltpu.VMEM((2 * tile, h), jnp.bfloat16)])
    # two buffers of each weight tile and of the rows in and out, the
    # rows' parts, the products' float32 results; under the chip's 128 MiB
    vmem = 12 * h * f_tile + tile * (44 * h + 28 * f_tile) + (8 << 20)
    ys = pl.pallas_call(
        _moe_grouped_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles * tile, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name='moe_grouped_experts',
    )(ex, at, first[ex], last[ex], xs, gate_w, up_w, down_w)
    where = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))           # pick -> sorted row
    # a tile no expert has rows in is never written: what it holds is
    # not zero, and no pick held here lies in it
    picked = jnp.where((sel < e)[..., None], ys[where].reshape(t, k, h), 0.0)
    return jnp.sum(picked * w.astype(jnp.float32)[..., None], axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _moe_grouped_taped(x, sel, w, gate_w, up_w, down_w, tile, f_tile,
                       interpret):
    return _moe_grouped(x, sel, w, gate_w, up_w, down_w, tile=tile,
                        f_tile=f_tile, interpret=interpret)


def _moe_grouped_fwd(x, sel, w, gate_w, up_w, down_w, tile, f_tile,
                     interpret):
    return _moe_grouped(x, sel, w, gate_w, up_w, down_w, tile=tile,
                        f_tile=f_tile, interpret=interpret), None


def _moe_grouped_bwd(tile, f_tile, interpret, saved, ct):
    # as the loop it stands in for (a `lax.while_loop` has none either)
    raise NotImplementedError(
        'moe_grouped_experts has no reverse mode: an expert layer\'s '
        'routed experts serve, on every schedule (training one is '
        'ROADMAP\'s)')


_moe_grouped_taped.defvjp(_moe_grouped_fwd, _moe_grouped_bwd)


def moe_grouped_experts(x, sel, w, gate_w, up_w, down_w, *,
                        interpret=False):
    """`sum_k w[t, k] * SwiGLU_{sel[t, k]}(x[t])` for a call MORE than
    one block wide, as ONE grouped matmul over the picks sorted by
    expert: x [T, h] float32, sel / w [T, k], bf16 expert leaves [E, h,
    f], [E, h, f], [E, f, h] -> [T, h] float32; `sel == E` is a pick of
    no expert held here, and adds nothing.

    The sort, the gather of the sorted rows and the weighted sum over
    `k` are XLA's and `grouped_experts`' own; between them the T x k
    sorted rows are cut into tiles of `_GROUPED_ROW_TILE` rows, and a
    tile is multiplied once for every expert with rows in it: no pick is
    dropped, an expert nobody picked is never read, a tile behind the
    last held pick is never visited. Products are the activations' two
    bf16 parts against the bf16 tile, summed in float32 (what
    `precision='high'` gives XLA there); `silu(g) * u` is float32 and
    split again for `down`. A grid step takes `_grouped_f_tile` of an
    expert's `f`. Under a tape the forward is this kernel and the
    pullback refuses by name, as the loop's does (`grouped_experts` is a
    `while`: training an expert layer is ROADMAP's). Jitted, so that a
    program's expert layers lower the body once."""
    t, h = x.shape
    k, (e, _, f) = sel.shape[1], gate_w.shape
    if x.dtype != jnp.float32:
        raise ValueError(f'moe_grouped_experts: float32 activations, not '
                         f'{x.dtype}')
    if not (gate_w.dtype == up_w.dtype == down_w.dtype == jnp.bfloat16):
        raise ValueError(
            'moe_grouped_experts: bf16 expert weights (the two-part '
            f'product is exact only against them), not {gate_w.dtype}')
    if up_w.shape != (e, h, f) or down_w.shape != (e, f, h) \
            or sel.shape != (t, k) or w.shape != (t, k):
        raise ValueError(
            f'moe_grouped_experts: x {x.shape}, sel {sel.shape}, w '
            f'{w.shape} against leaves {gate_w.shape}, {up_w.shape}, '
            f'{down_w.shape}')
    return _moe_grouped_taped(x, sel, w, gate_w, up_w, down_w,
                              _GROUPED_ROW_TILE, _grouped_f_tile(h, f),
                              interpret)
