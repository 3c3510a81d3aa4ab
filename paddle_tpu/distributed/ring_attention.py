"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Upstream analogue: PaddleNLP's sep (sequence-parallel) attention and the
reference's NCCL send/recv ring (RingFlashAttention); papers: Ring
Attention (Liu et al. 2023), DeepSpeed-Ulysses.

TPU-native design: activations are sequence-sharded over the 'sp' mesh
axis. Inside `shard_map`, each chip holds q/k/v blocks [B, S/sp, H, D];
K/V blocks rotate around the ring with `lax.ppermute` (one ICI hop per
step, overlapped by XLA with the block matmuls) while softmax statistics
(running max + log-sum-exp) accumulate blockwise in fp32 — numerically
identical to full attention. Causality is enforced per (q-block, k-block)
pair from global block indices, so late blocks are fully masked rather
than skipped (SPMD programs are static; XLA still elides all-masked
matmuls poorly, but the ring is load-balanced by construction for the
zig-zag layout used by callers that shard with `zigzag=True`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import env

_NEG_INF = float(np.finfo(np.float32).min)


def _block_attn(q, k, v, mask):
    """One blockwise attention step in fp32 stats.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; mask: [Sq, Sk] bool (True=keep).
    Returns (numerator [B,Sq,H,D] fp32, row max m [B,H,Sq], row sum l).
    """
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    logits = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                       # [B,H,Sq]
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(mask[None, None], p, 0.0)            # kill exp(NEG-NEG)=1
    l = jnp.sum(p, axis=-1)                            # [B,H,Sq]
    num = jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))
    return num, m, l


def _ring_body(q, k, v, sp_axis: str, n_sp: int, causal: bool):
    """Runs on one chip inside shard_map; q/k/v local blocks."""
    b, s_loc, h, dd = q.shape
    if k.shape[2] != h:                                 # GQA broadcast
        rep = h // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    my = lax.axis_index(sp_axis)

    def step(carry, i):
        kb, vb, num, m, l = carry
        src_block = (my - i) % n_sp                     # whose K/V we hold
        if causal:
            qpos = my * s_loc + jnp.arange(s_loc)
            kpos = src_block * s_loc + jnp.arange(s_loc)
            mask = qpos[:, None] >= kpos[None, :]
        else:
            mask = jnp.ones((s_loc, s_loc), bool)
        bn, bm, bl = _block_attn(q, kb, vb, mask)
        new_m = jnp.maximum(m, bm)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(bm - new_m)
        # [B,H,S] -> [B,S,H,1] to scale the [B,S,H,D] numerator
        num = num * alpha.transpose(0, 2, 1)[..., None] \
            + bn * beta.transpose(0, 2, 1)[..., None]
        l = l * alpha + bl * beta
        # rotate K/V to the next chip (skip the final useless hop is not
        # possible in a static program; XLA overlaps it with the epilogue)
        perm = [(j, (j + 1) % n_sp) for j in range(n_sp)]
        kb = lax.ppermute(kb, sp_axis, perm)
        vb = lax.ppermute(vb, sp_axis, perm)
        return (kb, vb, num, new_m, l), None

    num0 = jnp.zeros((b, s_loc, h, dd), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    (kb, vb, num, m, l), _ = lax.scan(
        step, (k, v, num0, m0, l0), jnp.arange(n_sp))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (num / denom).astype(q.dtype)


def ring_attention(q, k, v, causal: bool = True, axis: str = 'sp',
                   mesh: Optional[Mesh] = None):
    """Exact attention over sequence-sharded q/k/v ([B, S, H, D], S sharded
    over `axis`). Call inside jit; works on raw arrays."""
    mesh = mesh or env.get_mesh()
    n_sp = mesh.shape[axis]
    if n_sp == 1:
        from ..ops.pallas import _attention_xla
        return _attention_xla(q, k, v, causal=causal)
    spec = P(None, axis, None, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def run(ql, kl, vl):
        return _ring_body(ql, kl, vl, axis, n_sp, causal)
    return run(q, k, v)


def ulysses_attention(q, k, v, causal: bool = True, axis: str = 'sp',
                      mesh: Optional[Mesh] = None, attn_fn=None):
    """DeepSpeed-Ulysses: all-to-all re-shards sequence→heads, full-length
    attention runs locally (head-sharded), all-to-all back. Cheaper than a
    ring when heads % sp == 0 and sequence fits per-chip memory."""
    mesh = mesh or env.get_mesh()
    n_sp = mesh.shape[axis]
    from ..ops.pallas import _attention_xla
    attn_fn = attn_fn or (lambda a, b, c: _attention_xla(a, b, c,
                                                         causal=causal))
    if n_sp == 1:
        return attn_fn(q, k, v)
    if q.shape[2] % n_sp or k.shape[2] % n_sp:
        return ring_attention(q, k, v, causal=causal, axis=axis, mesh=mesh)
    spec = P(None, axis, None, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    def run(ql, kl, vl):
        # [B, S/sp, H, D] -> [B, S, H/sp, D]
        def to_heads(x):
            return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

        def to_seq(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)
        o = attn_fn(to_heads(ql), to_heads(kl), to_heads(vl))
        return to_seq(o)
    return run(q, k, v)
