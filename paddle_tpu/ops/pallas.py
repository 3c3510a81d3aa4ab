"""Hot fused ops: TPU pallas kernels, or XLA where a kernel does not apply.

Upstream analogue: the reference's hand-fused CUDA kernels
(paddle/phi/kernels/fusion/gpu/*, flash-attn integration). Here the
default path is plain jax — XLA already fuses normalization chains into
adjacent matmuls — and the pallas kernels (ops/pallas_kernels.py) take
over on TPU backends for six inner loops where a hand-written schedule
beats the XLA-generated one: attention over a call's own tokens
(`flash_attention`), an expert layer's routed experts (`expert_kernel`:
one weight stream, the next expert in flight, where XLA's `while`
fetches each cold), decode attention over latent rows (`latent_decode_kernel`:
a slot's row tiles read once where XLA's einsums stream every row
twice), its sibling for float32 queries over K and V by head
(`kv_decode_kernel`), a KDA layer's recurrence of one token
(`kda_step_kernel`: the state read once, written in place), a Mamba
layer's over a prompt (`ssm_scan_kernel`: the state stays in VMEM).

Which path runs is decided by explicit conditions on the backend and
the shapes, never by a caught exception: on a TPU a kernel that fails
to lower, compile or run is an error the caller sees (a silent XLA
stand-in made every green run ambiguous — chip_smoke.py counts the
Mosaic custom calls in the compiled step for the same reason).

All functions in this module operate on raw jax arrays (they are called
from inside apply_op bodies / jitted train steps).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@functools.lru_cache(None)
def _pallas_enabled() -> bool:
    if os.environ.get('PADDLE_TPU_DISABLE_PALLAS'):
        return False
    return jax.default_backend() == 'tpu'


def on_mesh(kernel, args, specs):
    """Call a Mosaic `kernel(*args)` so that it also works under an
    active fleet mesh. GSPMD cannot partition a `pallas_call` (an opaque
    custom call): left bare inside `fleet.DistTrainStep`'s one GSPMD jit
    it is refused or fed gathered operands. So when a multi-device mesh
    is active the call is made per shard through `shard_map`;
    `specs(mesh) -> (in_specs, out_specs)` names the mesh axes that shard
    the batch and head dims. With no mesh, one device, or inside someone
    else's `shard_map` (the pipeline schedule — operands are per-shard
    already) the kernel is called as is."""
    from ..distributed import env
    if not env.has_mesh():
        return kernel(*args)
    mesh = env.get_mesh(auto_init=False)
    if mesh.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(*args)
    in_specs, out_specs = specs(mesh)
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def mesh_axis(mesh, axis, n):
    """`axis` if the mesh has it and it divides a dim of size `n`, else
    None (the dim stays whole on every shard)."""
    size = mesh.shape.get(axis)
    return axis if size and n % size == 0 else None


@functools.lru_cache(None)
def pallas_ce_enabled() -> bool:
    """Gate for the fused cross-entropy kernel (separable from the flash
    gate so either can be disabled in isolation while benchmarking)."""
    if os.environ.get('PADDLE_TPU_DISABLE_PALLAS_CE'):
        return False
    return _pallas_enabled()


def rms_norm(v, epsilon=1e-6, axis=-1):
    """x / sqrt(mean(x^2) + eps). XLA fuses this; kept as the single
    choke-point so a pallas kernel can slot in for very wide rows."""
    ms = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=axis, keepdims=True)
    return (v.astype(jnp.float32) * jax.lax.rsqrt(ms + epsilon)).astype(v.dtype)


def _attention_xla(q, k, v, mask=None, causal=False, dropout_p=0.0,
                   dropout_key=None, sink=None):
    """Reference attention in [B, S, H, D] layout (paddle SDPA convention).
    Grouped KV heads (`H_kv < H`) are contracted in place: the query
    heads are viewed as [H_kv, rep] groups and each group reads its one
    K/V head — K and V are never repeated (a `jnp.repeat` of a decode
    cache is a copy of the whole cache, `rep` times its size, per layer
    and sub-step). `rep == 1` takes the ungrouped contraction. V may be
    narrower or wider than Q and K: the output has V's head size.

    `sink` ([H] float, one learned logit a query head) is a column of
    the softmax that takes mass and gives no value: `p_ij = exp(l_ij -
    m_i) / (exp(s_h - m_i) + sum_j' exp(l_ij' - m_i))`, `m_i = max(s_h,
    max_j l_ij)`. Without one the traced program is the one it was."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    rep = h // kv_heads
    scale = 1.0 / np.sqrt(d)
    if rep == 1:
        # [B, H, Sq, Sk]
        logits = jnp.einsum('bqhd,bkhd->bhqk', q, k,
                            preferred_element_type=jnp.float32) * scale
    else:
        # [B, H_kv, rep, Sq, Sk]: query head j * rep + r reads KV head j
        logits = jnp.einsum('bqhrd,bkhd->bhrqk',
                            q.reshape(b, sq, kv_heads, rep, d), k,
                            preferred_element_type=jnp.float32) * scale
    if causal:
        idx_q = jnp.arange(sq)[:, None] + (sk - sq)
        idx_k = jnp.arange(sk)[None, :]
        neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
        logits = jnp.where(idx_k <= idx_q, logits, neg)
    if mask is not None:
        if rep != 1:
            # [B, 1, Sq, Sk] broadcasts over both head axes; a per-head
            # [B, H, Sq, Sk] mask is viewed in the same groups
            mask = mask[:, :, None] if mask.shape[1] == 1 else \
                mask.reshape(mask.shape[0], kv_heads, rep, *mask.shape[2:])
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits,
                               jnp.asarray(jnp.finfo(jnp.float32).min))
        else:
            logits = logits + mask.astype(jnp.float32)
    if sink is None:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        s_h = sink.astype(jnp.float32).reshape(
            (1, h, 1, 1) if rep == 1 else (1, kv_heads, rep, 1, 1))
        top = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), s_h)
        e = jnp.exp(logits - top)
        probs = e / (jnp.sum(e, axis=-1, keepdims=True)
                     + jnp.exp(s_h - top))
    if dropout_p and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    if rep == 1:
        return jnp.einsum('bhqk,bkhd->bqhd', probs.astype(q.dtype), v)
    out = jnp.einsum('bhrqk,bkhd->bqhrd', probs.astype(q.dtype), v)
    return out.reshape(b, sq, h, v.shape[-1])


def flash_attention(q, k, v, mask=None, causal=False, dropout_p=0.0,
                    dropout_key=None, sink=None):
    """Dispatch: pallas flash kernel on TPU (no mask/dropout path), XLA
    softmax-attention otherwise. The pallas path never materializes the
    [B, H, Sq, Sk] logits — the difference between fitting seq 2048
    training on one chip and OOMing. The conditions below are the whole
    selection: a kernel error on the pallas side propagates. The kernel
    knows neither a `sink` nor a V of another head size than Q's: such
    a call is XLA's."""
    h, kvh = q.shape[2], k.shape[2]
    # causal requires sq == sk: the pallas kernel's causal mask is
    # top-left aligned while _attention_xla's is bottom-right aligned —
    # they only agree on square attention
    if (_pallas_enabled() and mask is None and dropout_p == 0.0
            and sink is None and v.shape[-1] == q.shape[-1]
            and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
            and (not causal or q.shape[1] == k.shape[1])
            and h % kvh == 0 and q.shape[-1] >= 64):
        from . import pallas_kernels

        def specs(mesh):
            # batch over dp, heads over mp (kvh divides h, so an axis
            # that splits the kv heads splits the q heads too)
            qkv = P(mesh_axis(mesh, 'dp', q.shape[0]), None,
                    mesh_axis(mesh, 'mp', kvh), None)
            return (qkv, qkv, qkv), qkv

        return on_mesh(
            functools.partial(pallas_kernels.flash_attention, causal=causal),
            (q, k, v), specs)
    return _attention_xla(q, k, v, mask=mask, causal=causal,
                         dropout_p=dropout_p, dropout_key=dropout_key,
                         sink=sink)


def expert_kernel(tokens, block_rows, weight_dtype, interpret=False):
    """Dispatch for an expert layer's routed experts: one of two pallas
    kernels (same arguments as the caller's loop over blocks, `nlp/
    afmoe.py::grouped_experts`) where one applies, None where the loop
    runs. Both want a TPU (or interpret=True anywhere) and bf16 leaves
    (their products of the activations' bf16 parts are exact against
    them alone); the call's width picks between them, as the loop's
    block does:

    - ONE block wide, `tokens <= block_rows` — a decode sub-step,
      speculation's k+1 rows, where every touched expert multiplies
      every row either way: `pallas_kernels.moe_decode_experts`, one
      weight stream over the distinct experts the batch picked.
    - wider — a whole prefill, a chunk or a prefix attach of more than a
      block: `pallas_kernels.moe_grouped_experts`, one grouped matmul
      over the picks sorted by expert in tiles of 128 rows, the next
      tile's expert in flight, `g` and `u` never in HBM.

    Every other backend and every other leaf dtype keep the loop: there
    it is the tier-1 path and the parity ground truth. The conditions
    are the whole selection: a kernel error on a TPU propagates."""
    if not ((interpret or _pallas_enabled())
            and weight_dtype == jnp.bfloat16):
        return None
    from . import pallas_kernels
    if tokens <= block_rows:
        return functools.partial(pallas_kernels.moe_decode_experts,
                                 interpret=interpret)
    return functools.partial(pallas_kernels.moe_grouped_experts,
                             interpret=interpret)


def _one_query_a_slot(q, mask):
    """What both decode attention kernels take: ONE query a slot (`q`
    `[B, 1, ...]`) and hidden rows named by a boolean mask `[B or 1, 1,
    1, n]` shared by the heads."""
    return (q.shape[1] == 1 and mask.dtype == jnp.bool_
            and len(mask.shape) == 4 and mask.shape[1] == 1
            and mask.shape[2] == 1 and mask.shape[0] in (1, q.shape[0]))


def latent_decode_kernel(q, rows, mask, interpret=False):
    """Dispatch for absorbed attention over latent rows
    (`nlp/deepseek_v3.py::_latent_attention`): the pallas kernel
    `pallas_kernels.mla_decode_attention`, its row tile bound (`.keywords
    ['tile']`: what the serving engine counts a round's `read_rows`
    by), where it applies, None where XLA's einsums run. Read from the
    call alone — `q` `[B, Sq, H, ...]`, the leaf `rows` `[B, L, C]` as
    it is held, `mask` `[B or 1, 1, Sq, n]` (anything with a `.shape`
    and a `.dtype`): the kernel takes ONE query a slot (a decode
    sub-step), hidden rows named by a boolean mask shared by the heads,
    on a TPU (or anywhere with interpret=True), rows float32 or bf16 of
    a whole number of lanes, and `n` and `L` a whole number of tiles.
    Speculation's k+1 rows, a prefill chunk, a prefix attach, an
    additive mask and every other backend keep the einsums: there they
    are the tier-1 path and the parity ground truth. The conditions are
    the whole selection: a kernel error on a TPU propagates."""
    from . import pallas_kernels
    n = mask.shape[-1]
    tile = pallas_kernels._mla_row_tile(math.gcd(n, rows.shape[1]))
    if ((interpret or _pallas_enabled()) and _one_query_a_slot(q, mask)
            and rows.dtype in (jnp.float32, jnp.bfloat16)
            and rows.shape[-1] % 128 == 0 and tile is not None):
        return functools.partial(pallas_kernels.mla_decode_attention,
                                 tile=tile, interpret=interpret)
    return None


def kv_decode_kernel(q, k, v, mask, sink=None, interpret=False):
    """Dispatch for decode attention over K and V held by head (the
    cached branch of `nlp/afmoe.py`, `nlp/lfm2.py` through it, and
    `nlp/mimo_v2.py`'s full layers; `nlp/generation.py::
    bounded_decode_attention` makes the call): the pallas kernel
    `pallas_kernels.kv_decode_attention`, its row tile bound (`.keywords
    ['tile']`: what the serving engine counts a round's `read_rows`
    by), where it applies, None where `_attention_xla` runs. Read from
    the call alone — `q` `[B, Sq, H, D]`, the leaves `k` `[B, L, H_kv,
    D]` and `v` `[B, L, H_kv, Dv]` as they are held, `mask` `[B or 1, 1,
    Sq, n]`, `sink` (anything with a `.shape` and a `.dtype`): the
    kernel takes ONE float32 query a slot (a decode sub-step), hidden
    rows named by a boolean mask shared by the heads, no sink, on a TPU
    (or anywhere with interpret=True), leaves float32 or bf16, and `n`
    and `L` a whole number of tiles. Speculation's k+1 rows, a prefill
    or a chunk, an additive or per-head mask, a layer with a sink and
    every other backend keep `_attention_xla`: there it is the tier-1
    path and the parity ground truth.

    **The query's dtype is what keeps `nlp/gpt.py` and `nlp/llama.py`
    out**, and it is an honest condition: their decode attention is a
    single-pass bf16 product with bf16 probabilities, another
    arithmetic than the three-pass float32 one the kernel spells out —
    serving them from it would change what they compute. A sibling for
    bf16 queries waits for the `benchmark` issue that re-sweeps
    serve-docs' backlog (ROADMAP, "the gate"). The conditions are the
    whole selection: a kernel error on a TPU propagates."""
    from . import pallas_kernels
    n = mask.shape[-1]
    tile = pallas_kernels._mla_row_tile(math.gcd(n, k.shape[1]))
    if ((interpret or _pallas_enabled()) and _one_query_a_slot(q, mask)
            and q.dtype == jnp.float32 and sink is None
            and len(k.shape) == 4 and k.dtype == v.dtype
            and k.dtype in (jnp.float32, jnp.bfloat16)
            and q.shape[2] % k.shape[2] == 0 and tile is not None):
        return functools.partial(pallas_kernels.kv_decode_attention,
                                 tile=tile, interpret=interpret)
    return None


def kda_step_kernel(state, interpret=False):
    """Dispatch for a KDA layer's recurrence of ONE token (`nlp/ling3.py
    ::kda_mix`, a call one token long: a decode sub-step): the pallas
    kernel `pallas_kernels.kda_decode_step` (same arguments as `nlp/
    ling3.py::kda_step`; `.keywords['heads']`: the heads a grid step
    takes) where it applies, None where `kda_step` runs. Read from the
    call alone — the leaf `state` `[B, H, d_k, d_v]` as it is held
    (anything with a `.shape` and a `.dtype`): the kernel takes a
    float32 state whose d_k and d_v are whole lanes (a head's tile
    then stands in VMEM as the leaf holds it) and whose heads divide
    into blocks (`_kda_head_block`), on a TPU (or anywhere with
    interpret=True). A head of another size, a state in fewer bits and
    every other backend keep `kda_step`'s two passes: there it is the
    tier-1 path and the parity ground truth. The conditions are the
    whole selection: a kernel error on a TPU propagates."""
    from . import pallas_kernels
    if ((interpret or _pallas_enabled()) and len(state.shape) == 4
            and state.dtype == jnp.float32
            and state.shape[2] % 128 == 0 and state.shape[3] % 128 == 0):
        heads = pallas_kernels._kda_head_block(*state.shape[1:])
        if heads:
            return functools.partial(pallas_kernels.kda_decode_step,
                                     heads=heads, interpret=interpret)
    return None


def ssm_scan_kernel(h, tokens, interpret=False):
    """Dispatch for a Mamba layer's recurrence over MORE than one token
    (`nlp/jamba.py::mamba_mix`, a prefill, a chunk of one, a batch's
    prompts): the pallas kernel `pallas_kernels.ssm_prefill_scan` (the
    arguments of `nlp/jamba.py::mamba_scan` after `folded` has been
    applied to `dt`) where it applies, None where `mamba_scan` runs.
    Read from the call alone — the leaf `h` `[B, N, Di]` as it is held
    (anything with a `.shape` and a `.dtype`) and the call's `tokens`:
    the kernel takes a float32 state whose `Di` is whole lanes and `N`
    whole sublanes (a block of channels' state then stands in vregs as
    the leaf holds it) and more than one token, on a TPU (or anywhere
    with interpret=True). ONE token keeps `mamba_step`, whatever this
    says; a state of another shape or in fewer bits and every other
    backend keep `mamba_scan`'s associative scan: there it is the
    tier-1 path, the parity ground truth and what a gradient meets.
    The conditions are the whole selection: a kernel error on a TPU
    propagates."""
    if ((interpret or _pallas_enabled()) and tokens > 1
            and len(h.shape) == 3 and h.dtype == jnp.float32
            and h.shape[1] % 8 == 0 and h.shape[2] % 128 == 0):
        from . import pallas_kernels
        return functools.partial(pallas_kernels.ssm_prefill_scan,
                                 interpret=interpret)
    return None
