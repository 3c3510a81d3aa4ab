"""fleet — hybrid-parallel orchestration (upstream:
python/paddle/distributed/fleet/: fleet.init, DistributedStrategy,
HybridCommunicateGroup, distributed_model/distributed_optimizer).

TPU-native design: `fleet.init(strategy)` builds ONE
`jax.sharding.Mesh(devices.reshape(pp, dp, sp, mp), ('pp','dp','sp','mp'))`
— the topology object upstream derives from NCCL subgroups is just the
mesh's named axes. `distributed_model` places parameters per their
PartitionSpec (TP layers pre-mark theirs; everything else replicates).
`distributed_optimizer` + `DistTrainStep` shard optimizer state over 'dp'
(ZeRO-1/2/3 per `strategy.sharding_configs['stage']`) and jit the whole
step so GSPMD emits grad all-reduces / reduce-scatters (dp) and weight
all-gathers (mp) over ICI. When `pp_degree > 1` the step routes the
model's uniform decoder blocks (the `pp_blocks()` protocol) through the
`pipeline.gpipe` collective schedule — microbatched ppermute handoff on
the 'pp' axis — with embed/head outside the pipelined region.
`strategy.recompute / amp / gradient_merge` are honored inside the step
(jax.checkpoint, auto_cast policy, microbatch grad accumulation).
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import framework
from .. import observability as _obs
from ..jit import TrainStep, functional_call, functional_state
from ..nn.layer import Layer
from ..tensor import Tensor
from . import env
from .parallel_layers import (ColumnParallelLinear, ParallelCrossEntropy,
                              RowParallelLinear, VocabParallelEmbedding,
                              get_sharding, shard_batch)
from .fleet_utils import recompute_degrees
from .pipeline import gpipe

_tree = jax.tree_util

# every elastic mesh rebuild appends here; surfaced as the `/summary`
# resize history and debug.observability_summary()'s elastic section
_resize_history: List[Dict[str, Any]] = []


def resize_history() -> List[Dict[str, Any]]:
    """Chronological record of elastic mesh rebuilds (shrink/grow):
    [{'time', 'reason', 'kind', 'from', 'to', 'from_devices',
    'to_devices'}, ...]."""
    return list(_resize_history)


class DistributedStrategy:
    """Upstream: fleet.DistributedStrategy (a protobuf); here a plain
    config object with the same knob names."""

    def __init__(self):
        self.hybrid_configs: Dict[str, Any] = {
            'dp_degree': 1, 'mp_degree': 1, 'pp_degree': 1,
            'sharding_degree': 1, 'sep_degree': 1,
        }
        self.sharding = False                 # ZeRO: shard opt state on dp
        self.sharding_configs: Dict[str, Any] = {'stage': 1}
        self.recompute = False
        self.recompute_configs: Dict[str, Any] = {}
        self.amp = False
        self.amp_configs: Dict[str, Any] = {'level': 'O1',
                                            'dtype': 'bfloat16'}
        self.gradient_merge = False
        self.gradient_merge_configs: Dict[str, Any] = {'k_steps': 1}
        self.pipeline = False
        self.pipeline_configs: Dict[str, Any] = {'accumulate_steps': 1,
                                                 'schedule_mode': '1F1B'}
        self.find_unused_parameters = False


class HybridCommunicateGroup:
    """Topology facade over the mesh (upstream: fleet/base/topology.py)."""

    def __init__(self, mesh: Mesh):
        self._mesh = mesh

    def _size(self, ax):
        return self._mesh.shape.get(ax, 1)

    def get_data_parallel_world_size(self):
        return self._size('dp')

    def get_model_parallel_world_size(self):
        return self._size('mp')

    def get_pipe_parallel_world_size(self):
        return self._size('pp')

    def get_sep_parallel_world_size(self):
        return self._size('sp')

    # single-controller: per-chip ranks live inside shard_map only
    def get_data_parallel_rank(self):
        return 0

    def get_model_parallel_rank(self):
        return 0

    def get_stage_id(self):
        return 0

    def get_model_parallel_group(self):
        return env.get_group('mp')

    def get_data_parallel_group(self):
        return env.get_group('dp')

    def get_pipe_parallel_group(self):
        return env.get_group('pp')

    def topology(self):
        return dict(self._mesh.shape)


class _Fleet:
    def __init__(self):
        self.strategy: Optional[DistributedStrategy] = None
        self._hcg: Optional[HybridCommunicateGroup] = None
        self.initialized = False

    def init(self, role_maker=None, is_collective=True, strategy=None):
        self.strategy = strategy or DistributedStrategy()
        hc = self.strategy.hybrid_configs
        devs = list(jax.devices())
        n = len(devs)
        pp = int(hc.get('pp_degree', 1))
        dp = int(hc.get('dp_degree', 1))
        mp = int(hc.get('mp_degree', 1))
        sp = int(hc.get('sep_degree', hc.get('sp_degree', 1)))
        want = pp * dp * mp * sp
        if want != n:
            if dp == 1 and n % (pp * mp * sp) == 0:
                dp = n // (pp * mp * sp)   # absorb leftover into dp
                hc['dp_degree'] = dp
            else:
                raise ValueError(
                    f'hybrid degrees pp*dp*sp*mp={want} != device count {n}')
        mesh = Mesh(np.asarray(devs).reshape(pp, dp, sp, mp),
                    ('pp', 'dp', 'sp', 'mp'))
        env.set_mesh(mesh)
        self._hcg = HybridCommunicateGroup(mesh)
        self.initialized = True
        if _obs.enabled():
            # record the topology so a registry snapshot identifies the
            # mesh this host is driving (and tags it with process_index)
            reg = _obs.get_registry()
            for ax, size in mesh.shape.items():
                reg.gauge('paddle_fleet_mesh_axis_size',
                          'hybrid mesh axis sizes',
                          ('axis',)).labels(axis=ax).set(size)
            reg.gauge('paddle_fleet_process_count',
                      'participating host processes').set(
                          jax.process_count())
            _obs.emit('fleet_init', mesh=dict(mesh.shape))
        return self

    def rebuild_mesh(self, devices=None, reason='device_change',
                     record=True):
        """Tear down and rebuild the hybrid mesh over `devices` after a
        topology change (host loss / capacity return).

        The elastic re-mesh: mp/pp/sp stay fixed (checkpoint-structural),
        dp is recomputed to absorb the new device count
        (`fleet_utils.recompute_degrees`). Swaps the env mesh + HCG,
        updates the topology gauges, appends to the resize history shown
        on `/summary`, and emits a `topology_change` event. Live arrays
        still sharded over the OLD mesh are untouched — callers restore
        state from a host-canonical checkpoint onto the new mesh
        (resilience.elastic owns that flow).
        """
        if not self.initialized:
            raise RuntimeError('fleet.init must run before rebuild_mesh')
        devs = list(devices) if devices is not None else list(jax.devices())
        old_mesh = env.get_mesh(auto_init=False) if env.has_mesh() else None
        old_shape = dict(old_mesh.shape) if old_mesh is not None else {}
        old_n = int(old_mesh.size) if old_mesh is not None else 0
        hc = recompute_degrees(len(devs), self.strategy.hybrid_configs)
        self.strategy.hybrid_configs.update(hc)
        mesh = Mesh(
            np.asarray(devs).reshape(
                hc['pp_degree'], hc['dp_degree'],
                hc.get('sep_degree', hc.get('sp_degree', 1)),
                hc['mp_degree']),
            ('pp', 'dp', 'sp', 'mp'))
        env.set_mesh(mesh)
        self._hcg = HybridCommunicateGroup(mesh)
        if not record:
            # startup alignment to the probed device view (a relaunched
            # process discovering its world) — not an elastic transition
            return mesh
        kind = ('shrink' if len(devs) < old_n
                else 'grow' if len(devs) > old_n else 'remap')
        entry = {'time': time.time(), 'reason': reason, 'kind': kind,
                 'from': old_shape, 'to': dict(mesh.shape),
                 'from_devices': old_n, 'to_devices': len(devs)}
        _resize_history.append(entry)
        if _obs.enabled():
            reg = _obs.get_registry()
            for ax, size in mesh.shape.items():
                reg.gauge('paddle_fleet_mesh_axis_size',
                          'hybrid mesh axis sizes',
                          ('axis',)).labels(axis=ax).set(size)
            reg.counter('paddle_elastic_resizes_total',
                        'elastic mesh rebuilds by kind',
                        ('kind',)).labels(kind=kind).inc()
        _obs.emit('topology_change', **{k: v for k, v in entry.items()
                                        if k != 'time'})
        return mesh

    def get_hybrid_communicate_group(self):
        return self._hcg

    @property
    def worker_num(self):
        return env.get_world_size()

    def worker_index(self):
        return env.get_rank()

    def barrier_worker(self):
        from . import collective
        collective.barrier()


_fleet = _Fleet()


def init(role_maker=None, is_collective=True, strategy=None):
    return _fleet.init(role_maker, is_collective, strategy)


def get_hybrid_communicate_group():
    return _fleet.get_hybrid_communicate_group()


def rebuild_mesh(devices=None, reason='device_change', record=True):
    return _fleet.rebuild_mesh(devices=devices, reason=reason,
                               record=record)


from . import fleet_utils as utils  # noqa: E402  (fleet.utils.recompute)
_fleet.utils = utils

fleet = _fleet  # upstream spells it fleet.fleet sometimes


def param_spec(param) -> P:
    """The placement of a parameter: marked TP spec, else replicated."""
    return get_sharding(param) or P()


def distributed_model(layer: Layer):
    """Place every parameter/buffer on the mesh per its spec.

    Upstream wraps the layer in PipelineParallel/TensorParallel classes;
    here placement IS the wrapping — forward code is unchanged and GSPMD
    derives the communication.
    """
    mesh = env.get_mesh()
    for _, p in layer.named_parameters():
        spec = param_spec(p)
        # drop axes that don't divide the dim (e.g. tiny test configs)
        fixed = []
        for i, a in enumerate(spec):
            if a is not None and p._data.shape[i] % mesh.shape.get(a, 1):
                fixed.append(None)
            else:
                fixed.append(a)
        p._data = jax.device_put(p._data, NamedSharding(mesh, P(*fixed)))
    for _, b in layer.named_buffers():
        b._data = jax.device_put(b._data, NamedSharding(mesh, P()))
    return layer


def _zero_spec(shape, base: P, dp_size: int, axis='dp') -> P:
    """ZeRO: extend a param's spec by sharding one more dim over dp."""
    if dp_size <= 1 or not shape:
        return base
    used = set()
    for a in base:
        used.update(a if isinstance(a, tuple) else (a,))
    if axis in used:  # already dp-sharded (e.g. a stage-3 param spec)
        return base
    spec = list(base) + [None] * (len(shape) - len(base))
    for i, s in enumerate(shape):
        if spec[i] is None and s % dp_size == 0:
            spec[i] = axis
            return P(*spec)
    return base


def shard_optimizer_state(opt_state, param_specs: Dict[str, P], mesh: Mesh,
                          stage: int = 1):
    """Assign dp-sharded placements to optimizer moments (ZeRO-1).

    Upstream: fleet sharding stage1 (DygraphShardingOptimizer) splits the
    moment buffers across dp ranks; here each moment leaf gets 'dp' added
    to its PartitionSpec and XLA reduce-scatters into it.

    `stage=0` skips the dp extension and places each moment by its
    param's own TP spec — the elastic restore path uses this to reshard
    a host-canonical optimizer state onto a rebuilt (non-ZeRO) mesh.
    """
    dp = mesh.shape.get('dp', 1)

    def place(path, leaf):
        if not hasattr(leaf, 'shape'):
            return leaf
        if getattr(leaf, 'ndim', 0) == 0:
            # scalars (the step counter) go replicated ON THE MESH: left
            # as an uncommitted single-device array, the first step hands
            # back a mesh-committed one and the second call recompiles
            # the whole program (4 min at 1.4B on four chips, PR 21)
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        name = None
        for entry in reversed(path):
            k = getattr(entry, 'key', None)
            if isinstance(k, str) and k in param_specs:
                name = k
                break
        base = param_specs.get(name, P()) if name is not None else P()
        if len(base) > len(leaf.shape):
            base = P()
        spec = base if stage == 0 else _zero_spec(leaf.shape, base, dp)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return _tree.tree_map_with_path(place, opt_state)


class DistributedOptimizer:
    """Thin wrapper marking the optimizer for ZeRO placement; the actual
    sharding happens when DistTrainStep initializes state on-mesh."""

    def __init__(self, inner, strategy: DistributedStrategy):
        self._inner = inner
        self._strategy = strategy

    def __getattr__(self, name):
        return getattr(self._inner, name)


def distributed_optimizer(optimizer, strategy=None):
    return DistributedOptimizer(optimizer,
                                strategy or _fleet.strategy
                                or DistributedStrategy())


def _split_block_params(d: Dict[str, Any], prefix: str, n_blocks: int):
    """Split a flat {name: leaf} dict into (outer, per-block list of
    {suffix: leaf}) around `prefix.<i>.suffix` names."""
    pre = prefix + '.'
    outer: Dict[str, Any] = {}
    blocks = [dict() for _ in range(n_blocks)]
    for name, v in d.items():
        if name.startswith(pre):
            idx, suffix = name[len(pre):].split('.', 1)
            blocks[int(idx)][suffix] = v
        else:
            outer[name] = v
    return outer, blocks


class DistTrainStep:
    """The hybrid-parallel jitted train step (upstream analogue: the
    HybridParallelOptimizer step inside a to_static program; for
    pp_degree>1 it subsumes meta_parallel/pipeline_parallel.py's
    micro-batched 1F1B schedule via `pipeline.gpipe`).

    params live sharded per TP specs (dp-extended under ZeRO-3); opt
    state per ZeRO specs; the batch arrives dp-sharded on dim 0. One
    jax.jit with donation — GSPMD inserts all collectives.
    """

    @_obs.telemetry.constructing('train.step_init')
    def __init__(self, layer: Layer, loss_fn, optimizer,
                 strategy: Optional[DistributedStrategy] = None,
                 retry_policy=None):
        self.layer = layer
        self.loss_fn = loss_fn
        self.optimizer = optimizer._inner \
            if isinstance(optimizer, DistributedOptimizer) else optimizer
        self.strategy = strategy or _fleet.strategy or DistributedStrategy()
        self.mesh = env.get_mesh()
        self._opt_state = None
        self._n_calls = 0
        # transient PjRt/collective failures (link flaps, neighbour HBM
        # pressure) are retried with backoff rather than killing the run;
        # None = fail fast (the pre-resilience behavior)
        self.retry_policy = retry_policy
        st = self.strategy
        dp = self.mesh.shape.get('dp', 1)
        self._dp = dp

        # ---- ZeRO stage (sharding knob) --------------------------------
        self._zero_stage = 0
        if st.sharding or st.hybrid_configs.get('sharding_degree', 1) > 1:
            self._zero_stage = int(st.sharding_configs.get('stage', 1))
            if self._zero_stage not in (1, 2, 3):
                raise ValueError(
                    f'sharding_configs["stage"] must be 1/2/3, got '
                    f'{self._zero_stage}')

        pmap = dict(layer.named_parameters())
        self._param_specs = {}
        for n, p in pmap.items():
            if p.stop_gradient:
                continue
            spec = param_spec(p)
            if self._zero_stage >= 3:
                # ZeRO-3: params stored dp-sharded; GSPMD all-gathers on
                # use and reduce-scatters the grads back.
                spec = _zero_spec(p._data.shape, spec, dp)
                p._data = jax.device_put(
                    p._data, NamedSharding(self.mesh, spec))
            self._param_specs[n] = spec
        self._grad_specs = {
            n: _zero_spec(pmap[n]._data.shape, s, dp)
            for n, s in self._param_specs.items()} \
            if self._zero_stage >= 2 else {}

        # ---- pipeline parallel (pp knob) -------------------------------
        pp_degree = int(st.hybrid_configs.get('pp_degree', 1))
        self._use_pp = pp_degree > 1 or st.pipeline
        if self._use_pp:
            if not hasattr(layer, 'pp_blocks'):
                raise ValueError(
                    'pipeline parallelism needs the model to expose '
                    'pp_blocks() (uniform decoder blocks); '
                    f'{type(layer).__name__} does not')
            self._pp_prefix, blocks = layer.pp_blocks()
            self._pp_template = blocks[0]
            self._pp_L = len(blocks)
            n_stage = max(pp_degree, 1)
            if self._pp_L % n_stage:
                raise ValueError(
                    f'{self._pp_L} blocks not divisible by pp_degree '
                    f'{n_stage}')
            self._pp_nstage = n_stage
            self._pp_per = self._pp_L // n_stage
            self._pp_nmicro = max(
                int(st.pipeline_configs.get('accumulate_steps', 1)), 1)
            # interleaved virtual stages (upstream: hybrid_configs
            # pp_configs/virtual_pp_degree, Megatron-style)
            self._pp_vpp = max(int(st.hybrid_configs.get(
                'virtual_pp_degree',
                st.pipeline_configs.get('virtual_pp_degree', 1))), 1)
            if self._pp_vpp > 1 and self._pp_per % self._pp_vpp:
                raise ValueError(
                    f'{self._pp_per} blocks/stage not divisible by '
                    f'virtual_pp_degree {self._pp_vpp}')
            if self._pp_vpp > 1:
                mode = st.pipeline_configs.get('schedule_mode')
                if mode not in (None, '1F1B'):
                    raise ValueError(
                        f'virtual_pp_degree>1 uses the interleaved '
                        f'schedule; schedule_mode={mode!r} is not '
                        f'compatible')
            pre = self._pp_prefix + '.'
            if any(n.startswith(pre) for n, _ in layer.named_buffers()):
                raise ValueError('pipelined blocks must be buffer-free '
                                 '(stateful layers like BatchNorm cannot '
                                 'ride the pp scan)')

        # ---- recompute knob --------------------------------------------
        self._recompute_whole = False
        if st.recompute:
            gran = st.recompute_configs.get('granularity', 'full')
            cfg = getattr(layer, 'config', None)
            if cfg is not None and hasattr(cfg, 'use_recompute'):
                cfg.use_recompute = gran if gran in (
                    'dots', 'dots_no_batch') else True
            else:
                self._recompute_whole = True  # jax.checkpoint whole fwd

        # ---- amp knob ---------------------------------------------------
        self._amp_cfg = None
        if st.amp:
            self._amp_cfg = (st.amp_configs.get('level', 'O1'),
                             st.amp_configs.get('dtype', 'bfloat16'))

        # ---- gradient merge knob ----------------------------------------
        self._gm_k = int(st.gradient_merge_configs.get('k_steps', 1)) \
            if st.gradient_merge else 1

        def loss_of(pv, batch, frozen, buffers, key):
            import contextlib
            from .. import autograd
            inputs, labels = batch
            args = inputs if isinstance(inputs, tuple) else (inputs,)
            amp_ctx = contextlib.nullcontext()
            if self._amp_cfg is not None:
                from .. import amp as amp_mod
                amp_ctx = amp_mod.auto_cast(True, level=self._amp_cfg[0],
                                            dtype=self._amp_cfg[1])
            with amp_ctx:
                if self._use_pp:
                    out, new_bufs = self._pp_forward(
                        pv, frozen, buffers, args, key)
                else:
                    call = functools.partial(
                        functional_call, self.layer, frozen=frozen,
                        buffers=buffers, args=args, kwargs={}, rng_key=key)
                    if self._recompute_whole:
                        out, new_bufs = jax.checkpoint(
                            lambda p: call(p))(pv)
                    else:
                        out, new_bufs = call(pv)
                with autograd.functional_scope():
                    wrapped_out = _tree.tree_map(Tensor, out)
                    wrapped_lab = _tree.tree_map(
                        lambda v: Tensor(v) if not isinstance(v, Tensor)
                        else v, labels)
                    loss_t = self.loss_fn(wrapped_out, wrapped_lab)
            loss_v = loss_t.value if isinstance(loss_t, Tensor) else loss_t
            return loss_v.astype(jnp.float32), new_bufs

        def step_fn(params, opt_state, buffers, frozen, key, lr, batch):
            k = self._gm_k
            if k > 1:
                # gradient merge: scan k microbatches, average the grads,
                # apply ONE optimizer update (== a k-times-larger batch
                # for mean losses; upstream: GradientMergeOptimizer).
                def resh(v):
                    if v.shape[0] % k:
                        raise ValueError(
                            f'batch dim {v.shape[0]} not divisible by '
                            f'gradient_merge k_steps={k}')
                    return v.reshape((k, v.shape[0] // k) + v.shape[1:])
                mb_batch = _tree.tree_map(resh, batch)

                def body(carry, mb):
                    loss_acc, grad_acc, i, bufs_c = carry
                    mb_key = jax.random.fold_in(key, i)
                    # thread buffers through the carry so running stats
                    # (e.g. BatchNorm) advance per microbatch, matching
                    # the sequential accumulation this knob emulates
                    (l, bufs_c), g = jax.value_and_grad(
                        loss_of, has_aux=True)(
                            params, mb, frozen, bufs_c, mb_key)
                    grad_acc = _tree.tree_map(jnp.add, grad_acc, g)
                    return (loss_acc + l, grad_acc, i + 1, bufs_c), None

                zero_g = _tree.tree_map(jnp.zeros_like, params)
                (loss_sum, grads, _, new_bufs), _ = jax.lax.scan(
                    body, (jnp.float32(0.0), zero_g, jnp.int32(0), buffers),
                    mb_batch)
                loss = loss_sum / k
                grads = _tree.tree_map(lambda g: g / k, grads)
            else:
                (loss, new_bufs), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params, batch, frozen, buffers,
                                           key)
            if self._zero_stage >= 2:
                # ZeRO-2: reduce-scatter grads into their dp shard before
                # the optimizer touches them (moments are already dp-
                # sharded by stage 1's placement).
                grads = {
                    n: jax.lax.with_sharding_constraint(
                        g, NamedSharding(self.mesh, self._grad_specs[n]))
                    for n, g in grads.items()}
            new_params, new_opt = self.optimizer.apply_gradients(
                grads, params, opt_state, lr)
            # pin updated params back to their TP (stage-3: dp-extended)
            # placement
            new_params = {
                n: jax.lax.with_sharding_constraint(
                    v, NamedSharding(self.mesh, self._param_specs[n]))
                for n, v in new_params.items()}
            return loss, new_params, new_opt, new_bufs

        self._jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    def _pp_forward(self, pv, frozen, buffers, args, key):
        """Forward with the decoder stack routed through the gpipe
        collective schedule (upstream: PipelineParallel._forward_step
        micro-batch loop + P2P send/recv; here ONE differentiable scan
        whose reverse-mode replay is the 1F1B backward)."""
        from jax import lax
        prefix, L = self._pp_prefix, self._pp_L
        n_stage, per = self._pp_nstage, self._pp_per
        n_micro = self._pp_nmicro

        outer_p, blocks_p = _split_block_params(pv, prefix, L)
        f_outer, f_blocks = _split_block_params(frozen, prefix, L)

        def stack(blocks):
            if not blocks or not blocks[0]:
                return {}
            return _tree.tree_map(
                lambda *xs: jnp.stack(xs).reshape(
                    (n_stage, per) + xs[0].shape), *blocks)

        stacked = stack(blocks_p)
        f_stacked = stack(f_blocks)
        keys = jax.random.split(key, L).reshape((n_stage, per) + key.shape)
        template = self._pp_template

        def blocks_fn(h):
            B = h.shape[0]
            if B % n_micro:
                raise ValueError(
                    f'batch {B} not divisible by pipeline '
                    f'accumulate_steps={n_micro}')
            if (B // n_micro) % self._dp:
                raise ValueError(
                    f'microbatch {B // n_micro} (batch {B} / '
                    f'accumulate_steps {n_micro}) not divisible by '
                    f'dp_degree {self._dp}')
            mbs = h.reshape((n_micro, B // n_micro) + h.shape[1:])

            def stage_fn(sp_tree, x):
                ks, ps, fps = sp_tree

                def body(hh, xs):
                    kj, lp, flp = xs
                    out, _ = functional_call(
                        template, lp, flp, {}, (hh,), {}, rng_key=kj)
                    return out, None

                hh, _ = lax.scan(body, x, (ks, ps, fps))
                return hh

            if self._pp_vpp > 1:
                # re-split each [pp, per] stage stack into v chunks of
                # per//v blocks and arrange DEVICE-major round-robin
                # ([pp, v, per//v, ...]) for the interleaved schedule
                from .pipeline import (interleaved_pipeline,
                                       stack_interleaved_params)
                v = self._pp_vpp
                cper = per // v
                full = (keys, stacked, f_stacked)
                chunk_trees = [
                    _tree.tree_map(
                        lambda p, c=c: p.reshape(
                            (n_stage * per,) + p.shape[2:])
                        [c * cper:(c + 1) * cper], full)
                    for c in range(n_stage * v)]
                inter = stack_interleaved_params(chunk_trees, n_stage)
                y = interleaved_pipeline(
                    stage_fn, inter, mbs, v, mesh=self.mesh,
                    batch_axis='dp' if self._dp > 1 else None,
                    remat=True)
            else:
                y = gpipe(stage_fn, (keys, stacked, f_stacked), mbs,
                          mesh=self.mesh,
                          batch_axis='dp' if self._dp > 1 else None,
                          schedule=self.strategy.pipeline_configs.get(
                              'schedule_mode', '1F1B'),
                          remat=True)
            return y.reshape((B,) + y.shape[2:])

        return functional_call(self.layer, outer_p, f_outer, buffers,
                               args, {'blocks_fn': blocks_fn}, rng_key=key)

    def _init_opt_state(self, params):
        # every leaf placed on the mesh up front (stage 0 = each moment
        # by its param's own TP spec), so the state the step returns has
        # the shardings of the state it was given
        return shard_optimizer_state(
            self.optimizer.init_state(params), self._param_specs,
            self.mesh, stage=self._zero_stage)

    def _step_args(self, inputs, labels, n_call):
        """The jitted step's arguments at this call: live state, the
        per-call key, and the batch placed dp-sharded on the mesh."""
        params, frozen, buffers = functional_state(self.layer)
        if self._opt_state is None:
            self._opt_state = self._init_opt_state(params)
        key = jax.random.fold_in(framework.default_generator.root_key,
                                 n_call)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        if self.retry_policy is not None:
            from ..resilience.retry import call_with_retry
            batch = call_with_retry(
                lambda: (shard_batch(inputs, mesh=self.mesh),
                         shard_batch(labels, mesh=self.mesh)),
                policy=self.retry_policy, site='device_transfer')
        else:
            batch = (shard_batch(inputs, mesh=self.mesh),
                     shard_batch(labels, mesh=self.mesh))
        return params, self._opt_state, buffers, frozen, key, lr, batch

    def lower(self, inputs, labels):
        """The GSPMD step lowered at these batch shapes
        (`jax.stages.Lowered`; see `jit.TrainStep.lower`)."""
        return self._jitted.lower(*self._step_args(inputs, labels, 0))

    def __call__(self, inputs, labels):
        args = self._step_args(inputs, labels, self._n_calls)
        self._n_calls += 1
        batch = args[-1]
        if _obs.enabled():
            # per-step comm ledger: inside the jitted step GSPMD owns the
            # collectives, so the host-side view counts the dp-sharded
            # batch bytes entering the mesh each step
            batch_bytes = sum(
                int(np.prod(np.shape(v))) * np.dtype(v.dtype).itemsize
                for v in _tree.tree_leaves(batch))
            reg = _obs.get_registry()
            reg.counter('paddle_fleet_steps_total',
                        'DistTrainStep invocations').inc()
            reg.counter('paddle_fleet_batch_bytes_total',
                        'bytes of batch data sharded onto the mesh').inc(
                            batch_bytes)
        with _obs.span('fleet.dist_train_step', step=self._n_calls - 1):
            with _obs.span('train.dispatch'):
                if self.retry_policy is not None:
                    from ..resilience.retry import call_with_retry
                    loss, new_params, self._opt_state, new_bufs = \
                        call_with_retry(self._jitted, *args,
                                        policy=self.retry_policy,
                                        site='dist_step')
                else:
                    loss, new_params, self._opt_state, new_bufs = \
                        self._jitted(*args)
        with _obs.span('train.writeback'):
            pmap = dict(self.layer.named_parameters())
            for n, v in new_params.items():
                pmap[n]._data = v
                pmap[n]._node = None
            bmap = dict(self.layer.named_buffers())
            for n, v in new_bufs.items():
                bmap[n]._data = v
        return Tensor(loss)


# re-export the TP layers under fleet.meta_parallel's names
meta_parallel = type('meta_parallel', (), {
    'ColumnParallelLinear': ColumnParallelLinear,
    'RowParallelLinear': RowParallelLinear,
    'VocabParallelEmbedding': VocabParallelEmbedding,
    'ParallelCrossEntropy': ParallelCrossEntropy,
})
