"""Collective communication (upstream: paddle/fluid/distributed/collective/
ProcessGroupNCCL.cc + python/paddle/distributed/communication/*).

TPU-native semantics
--------------------
NCCL collectives are *multi-process*: every rank holds its own tensor and
the collective mixes them. Single-controller JAX holds the whole world in
one process, so the per-rank tensors are modelled as ONE array whose
leading dimension is the group axis ("rank-stacked convention"): a paddle
rank-r tensor of shape [s...] is `stacked[r]` of shape [nranks, s...],
sharded over the group's mesh axis. Every collective here is implemented
as a `shard_map` over that axis emitting the real XLA collective
(`psum` / `all_gather` / `psum_scatter` / `ppermute` / `all_to_all`), so
the same code path is what GSPMD runs over ICI inside a jitted step.

Two API layers:
- eager Tensor API (`all_reduce`, `all_gather`, ...) — paddle-compatible
  signatures operating on rank-stacked Tensors (in-place where upstream is).
- in-jit primitives (`psum`, `ppermute`, ...) — raw-array wrappers for use
  inside `shard_map` bodies (pipeline schedules, ring attention, MoE).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import observability as _obs
from ..tensor import Tensor
from . import env


def _note_collective(op: str, axis: str, v):
    """Count one eager collective into the shared registry: per-(op,
    axis) call and payload-byte counters (the host-side comm ledger a
    fleet debug session reads next to the device trace). No-op while
    observability is disabled."""
    if not _obs.enabled():
        return
    try:
        nbytes = int(np.prod(np.shape(v))) * np.dtype(v.dtype).itemsize
    except Exception:  # paddle-lint: disable=swallowed-exception -- payload-size probe on an abstract value; bytes=0 is the honest answer
        nbytes = 0
    reg = _obs.get_registry()
    labels = dict(op=op, axis=axis)
    reg.counter('paddle_collective_calls_total',
                'eager collective invocations',
                ('op', 'axis')).labels(**labels).inc()
    reg.counter('paddle_collective_bytes_total',
                'eager collective payload bytes',
                ('op', 'axis')).labels(**labels).inc(nbytes)


class ReduceOp:
    SUM = 'sum'
    MAX = 'max'
    MIN = 'min'
    PROD = 'prod'
    AVG = 'avg'


def _pprod(x, axis_name):
    """Product over an axis via log-magnitudes + sign parity (psum has no
    product form; handles negatives and zeros — log(0) = -inf → exp → 0)."""
    x32 = x.astype(jnp.float32)
    n_neg = lax.psum((x32 < 0).astype(jnp.float32), axis_name)
    mag = jnp.exp(lax.psum(jnp.log(jnp.abs(x32)), axis_name))
    sign = jnp.where(jnp.mod(n_neg, 2.0) > 0.5, -1.0, 1.0)
    return (mag * sign).astype(x.dtype)


_REDUCERS = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
    ReduceOp.PROD: _pprod,
    ReduceOp.AVG: lax.pmean,
}


# ---------------------------------------------------------------------------
# in-jit primitives (raw arrays, inside shard_map)
# ---------------------------------------------------------------------------
psum = lax.psum
pmean = lax.pmean
pmax = lax.pmax
pmin = lax.pmin
ppermute = lax.ppermute
axis_index = lax.axis_index


def all_gather_injit(x, axis_name, tiled=False):
    return lax.all_gather(x, axis_name, tiled=tiled)


def reduce_scatter_injit(x, axis_name, scatter_dimension=0, tiled=True):
    return lax.psum_scatter(x, axis_name,
                            scatter_dimension=scatter_dimension, tiled=tiled)


def all_to_all_injit(x, axis_name, split_axis, concat_axis, tiled=True):
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def ring_permute(x, axis_name, shift=1):
    """Send each shard to (index + shift) mod n along `axis_name`."""
    n = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


# ---------------------------------------------------------------------------
# eager Tensor API (rank-stacked)
# ---------------------------------------------------------------------------
def _axis_of(group) -> str:
    g = env.get_group(group) if not isinstance(group, env.ProcessGroup) \
        else group
    if len(g.axis) != 1:
        # whole-mesh group: use the first axis spanning everything only if 1D
        if g.mesh.size == g.mesh.shape[g.mesh.axis_names[0]]:
            return g.mesh.axis_names[0]
        raise ValueError(
            'eager collectives need a single-axis group; pass group="dp" '
            'etc. (multi-axis collectives happen inside jitted steps '
            'via GSPMD)')
    return g.axis[0]


def _val(t):
    return t.value if isinstance(t, Tensor) else jnp.asarray(t)


def _stacked_shard(v, axis_name):
    """Ensure the rank-stacked array is sharded over the group axis."""
    mesh = env.get_mesh()
    n = mesh.shape[axis_name]
    if v.shape[0] != n:
        raise ValueError(
            f'rank-stacked collective input needs leading dim == group size '
            f'({n}); got shape {tuple(v.shape)}. In single-controller SPMD '
            f'each "rank tensor" is a slice of one stacked array.')
    spec = P(axis_name, *([None] * (v.ndim - 1)))
    return jax.device_put(v, NamedSharding(mesh, spec)), mesh, spec


@functools.lru_cache(maxsize=None)
def _all_reduce_fn(axis_name, op, ndim, mesh=None):
    mesh = mesh or env.get_mesh()
    spec = P(axis_name, *([None] * (ndim - 1)))

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                       out_specs=spec)
    def f(x):
        return _REDUCERS[op](x, axis_name)
    return f


@functools.lru_cache(maxsize=None)
def _coll_fn(kind, axis_name, ndim, mesh, extra=None):
    """Cached jitted shard_map program per (collective, axis, rank, mesh)
    — eager collectives in a loop must not retrace every call."""
    spec = P(axis_name, *([None] * (ndim - 1)))
    if kind == 'reduce_scatter':
        def body(x):
            return lax.psum_scatter(x, axis_name, scatter_dimension=1,
                                    tiled=True)
    elif kind == 'broadcast':
        src = extra

        def body(x):
            # one-to-all as a masked all-reduce: O(1) per-device memory
            # (an all_gather+slice would be O(world) — wrong at pod scale)
            r = lax.axis_index(axis_name)
            return lax.psum(jnp.where(r == src, x, jnp.zeros_like(x)),
                            axis_name)
    elif kind == 'alltoall':
        def body(x):
            # received chunks line up on the same dim => grid transpose
            return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=1,
                                  tiled=True)
    elif kind == 'ppermute':
        perm = list(extra)

        def body(x):
            return lax.ppermute(x, axis_name, perm)
    else:
        raise ValueError(kind)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Sum (etc.) over ranks: out[r] = reduce_r' in[r']. In-place."""
    axis = _axis_of(group)
    v, mesh, spec = _stacked_shard(_val(tensor), axis)
    _note_collective('all_reduce', axis, v)
    out = _all_reduce_fn(axis, op, v.ndim, mesh)(v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        tensor._node = None
        return tensor
    return Tensor(out)


def all_gather(tensor_list, tensor=None, group=None, sync_op=True, axis=0):
    """Gather each rank's slice; result replicated. Paddle form fills
    `tensor_list`; also returns the stacked Tensor."""
    if tensor is None:  # called as all_gather(tensor, ...) functional form
        tensor, tensor_list = tensor_list, None
    ax = _axis_of(group)
    v, mesh, spec = _stacked_shard(_val(tensor), ax)
    _note_collective('all_gather', ax, v)
    out = jax.device_put(v, NamedSharding(mesh, P()))  # all-gather = replicate
    if tensor_list is not None:
        tensor_list.clear()
        tensor_list.extend(Tensor(out[i]) for i in range(out.shape[0]))
    return Tensor(out)


def reduce_scatter(output=None, input=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """out[r] = (sum_r' in[r'])[r-th chunk]; input stacked [n, n*c, ...] or
    [n, ...] with dim-1 divisible by n."""
    if input is None:
        input, output = output, None
    ax = _axis_of(group)
    v, mesh, spec = _stacked_shard(_val(input), ax)
    _note_collective('reduce_scatter', ax, v)
    out = _coll_fn('reduce_scatter', ax, v.ndim, mesh)(v)
    if output is not None and isinstance(output, Tensor):
        output._data = out
        output._node = None
        return output
    return Tensor(out)


def broadcast(tensor, src=0, group=None, sync_op=True):
    """out[r] = in[src] for all r. In-place."""
    ax = _axis_of(group)
    v, mesh, spec = _stacked_shard(_val(tensor), ax)
    _note_collective('broadcast', ax, v)
    out = _coll_fn('broadcast', ax, v.ndim, mesh, extra=src)(v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        tensor._node = None
        return tensor
    return Tensor(out)


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """out[dst] = reduce_r in[r]; other ranks keep their input (upstream
    leaves non-dst buffers unspecified; we keep them unchanged)."""
    ax = _axis_of(group)
    v, mesh, spec = _stacked_shard(_val(tensor), ax)
    _note_collective('reduce', ax, v)
    reduced = _all_reduce_fn(ax, op, v.ndim, mesh)(v)
    idx = jnp.arange(v.shape[0]).reshape((-1,) + (1,) * (v.ndim - 1))
    out = jnp.where(idx == dst, reduced, v)
    if isinstance(tensor, Tensor):
        tensor._data = out
        tensor._node = None
        return tensor
    return Tensor(out)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """out[r] = in_list[r] on src. With the stacked convention the list is
    already the stacked array — scatter is a (re)shard of src's data."""
    ax = _axis_of(group)
    if tensor_list is not None:
        stacked = jnp.stack([_val(t) for t in tensor_list])
    else:
        stacked = _val(tensor)
    mesh = env.get_mesh()
    spec = P(ax, *([None] * (stacked.ndim - 1)))
    _note_collective('scatter', ax, stacked)
    out = jax.device_put(stacked, NamedSharding(mesh, spec))
    if isinstance(tensor, Tensor):
        tensor._data = out if tensor_list is None else out
        tensor._node = None
        return tensor
    return Tensor(out)


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """out[r][s] = in[s][r]: transpose the (rank, chunk) grid.

    Accepts the stacked form [n, n, ...] (dim0 = rank, dim1 = chunk) or a
    list of per-rank stacks.
    """
    ax = _axis_of(group)
    if isinstance(in_tensor_list, (list, tuple)):
        v = jnp.stack([_val(t) for t in in_tensor_list])
    else:
        v = _val(in_tensor_list)
    v, mesh, spec = _stacked_shard(v, ax)
    _note_collective('alltoall', ax, v)
    out = _coll_fn('alltoall', ax, v.ndim, mesh)(v)
    if isinstance(out_tensor_list, list):
        out_tensor_list.clear()
        out_tensor_list.extend(Tensor(out[i]) for i in range(out.shape[0]))
    return Tensor(out)


def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    ax = _axis_of(group)
    v = _val(in_tensor)
    n = env.get_mesh().shape[ax]
    for sizes in (in_split_sizes, out_split_sizes):
        if sizes is not None and len(set(sizes)) > 1:
            raise NotImplementedError(
                'alltoall_single with uneven split sizes is not supported '
                'on the static-shape SPMD path; pad to equal chunks')
    stacked = v.reshape((n, n, -1) + v.shape[2:]) if v.shape[0] == n \
        else v.reshape((n, n) + v.shape[1:])
    out = alltoall(Tensor(stacked), group=group)
    if out_tensor is not None and isinstance(out_tensor, Tensor):
        out_tensor._data = out.value.reshape(v.shape)
        out_tensor._node = None
        return out_tensor
    return Tensor(out.value.reshape(v.shape))


# -- point-to-point ---------------------------------------------------------
# Upstream send/recv (paddle/fluid/distributed/collective p2p) is
# multi-process; in SPMD the native form is a collective-permute. send/recv
# calls are therefore *paired* here: send registers the route, recv executes
# one ppermute moving slice src->dst in the rank-stacked array.
_pending_sends: List = []


def send(tensor, dst=0, group=None, sync_op=True):
    _pending_sends.append((tensor, dst, group))
    return tensor


def _match_send(tensor):
    """Find the pending send for this recv: same Tensor object first (the
    rank-stacked array is shared). A shape-based fallback is accepted ONLY
    when it is unambiguous — two in-flight sends of the same shape raise
    rather than silently mispair."""
    for i, (t, dst, g) in enumerate(_pending_sends):
        if t is tensor:
            return i
    shape = tuple(np.shape(_val(tensor)))
    hits = [i for i, (t, dst, g) in enumerate(_pending_sends)
            if tuple(np.shape(_val(t))) == shape]
    if len(hits) > 1:
        raise RuntimeError(
            f'recv() matches {len(hits)} pending send()s of shape {shape}; '
            'pairing by shape would be ambiguous — recv on the same stacked '
            'Tensor object that was sent, or drain sends in order')
    return hits[0] if hits else None


def recv(tensor, src=0, group=None, sync_op=True):
    i = _match_send(tensor)
    if i is None:
        raise RuntimeError(
            'recv() without a matching send() on the same stacked tensor; '
            'in SPMD, pair send/recv in the same program or use '
            'distributed.collective.ppermute inside shard_map')
    t, dst, g = _pending_sends.pop(i)
    ax = _axis_of(g if g is not None else group)
    v, mesh, spec = _stacked_shard(_val(t), ax)
    _note_collective('send_recv', ax, v)
    out = _coll_fn('ppermute', ax, v.ndim, mesh, extra=((src, dst),))(v)
    if isinstance(tensor, Tensor):
        # only dst's slice is defined; others zero (ppermute semantics)
        tensor._data = out
        tensor._node = None
        return tensor
    return Tensor(out)


isend = send
irecv = recv


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    """Execute a batch of P2P ops as one collective-permute.

    SPMD interpretation: the op list is the *same program on every rank*
    (upstream callers compute peers relative to their own rank; the
    single controller sees rank 0's values). A send to peer `d` therefore
    means the uniform ring shift by `d` — perm[(j, (j+d)%n)] — which is
    exactly the pipeline-stage handoff pattern these batches exist for.
    """
    sends = [o for o in p2p_op_list if o.op in (send, isend)]
    recvs = [o for o in p2p_op_list if o.op in (recv, irecv)]
    if not sends:
        return []
    group = p2p_op_list[0].group
    ax = _axis_of(group)
    mesh = env.get_mesh()
    n = mesh.shape[ax]
    shifts = {o.peer % n for o in sends}
    if len(shifts) != 1:
        raise ValueError(
            'batch_isend_irecv with mixed send peers is ambiguous in '
            'single-controller SPMD; batch one uniform shift at a time '
            'or use collective.ppermute inside shard_map')
    shift = shifts.pop()
    perm = tuple((j, (j + shift) % n) for j in range(n))
    outs = []
    for o in sends:
        v, mesh, spec = _stacked_shard(_val(o.tensor), ax)
        _note_collective('batch_p2p', ax, v)
        outs.append(_coll_fn('ppermute', ax, v.ndim, mesh, extra=perm)(v))
    for o, out in zip(recvs, outs):
        if isinstance(o.tensor, Tensor):
            o.tensor._data = out
            o.tensor._node = None
    return []


def barrier(group=None):
    """Device-synchronizing barrier (single-controller: drain the queue)."""
    mesh = env.get_mesh()
    token = jnp.zeros((mesh.size,), jnp.int32)
    ax = mesh.axis_names[0] if len(mesh.axis_names) == 1 else None
    if ax is not None:
        _note_collective('barrier', ax, token)
        token = _all_reduce_fn(ax, ReduceOp.SUM, 1, mesh)(
            jax.device_put(token, NamedSharding(mesh, P(ax))))
    jax.block_until_ready(token)


def wait(tensor, group=None, use_calc_stream=True):
    jax.block_until_ready(_val(tensor))
    return tensor


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Gather all ranks' slices to dst (upstream communication/gather.py).
    Single-controller semantics: the stacked result is materialized and
    `gather_list` (meaningful on dst) is filled with the per-rank
    slices."""
    ax = _axis_of(group)
    v, mesh, spec = _stacked_shard(_val(tensor), ax)
    _note_collective('gather', ax, v)
    out = jax.device_put(v, NamedSharding(mesh, P()))
    if gather_list is not None:
        gather_list.clear()
        gather_list.extend(Tensor(out[i]) for i in range(out.shape[0]))
    return Tensor(out)


def all_gather_object(object_list, obj, group=None):
    """Gather python objects (upstream: pickle over NCCL). In the
    single-controller SPMD model every rank executes this call with its
    own `obj`; here there is one process, so the gathered list is the
    world-size replication of the local object."""
    n = env.get_world_size(group)
    object_list.clear()
    object_list.extend(obj for _ in range(n))
    return object_list
