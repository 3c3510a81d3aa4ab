"""Host-offloaded optimizer state (upstream:
python/paddle/distributed/fleet/meta_parallel/sharding/
group_sharded_optimizer_stage2.py `offload=True`, which pins FP32 master
weights + moments in CPU memory and updates parameters there).

TPU-native design: optimizer slots (Adam moments, fp32 masters) live in
the chip's `pinned_host` memory space instead of HBM. Each step streams
ONE parameter leaf's slots into HBM, runs a donated per-shape update
kernel, and streams the new slots back; jax's async dispatch overlaps
leaf i+1's PCIe transfer with leaf i's update compute. HBM then never
holds more than params + grads + one leaf's slots — for the Llama-2 7B
geometry that is the difference between 8 and 16+ layers training on a
single 16 GB chip (see bench.py `_7b_configs`). The eager streaming
path compiles one tiny kernel per (shape, dtype, decay-coeff). XLA's
in-jit host offload (`device_put` under jit), which would put the whole
update in one program, has not been tried on the current runtime.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from .. import observability as _obs

_tree = jax.tree_util


def _slot_bytes(slots: Dict[str, Any]) -> int:
    return sum(int(v.size) * v.dtype.itemsize for v in slots.values())


def _note_transfer(direction: str, nbytes: int):
    """H2D/D2H ledger for the streamed optimizer slots — the number that
    tells you whether offload's PCIe traffic is hiding under compute or
    dominating the step. No-op while observability is disabled."""
    if not _obs.enabled() or not nbytes:
        return
    _obs.get_registry().counter(
        f'paddle_offload_{direction}_bytes_total',
        f'optimizer-slot {direction.upper()} transfer bytes').inc(nbytes)


def _host_sharding(device=None):
    device = device or jax.devices()[0]
    # TPU devices address host RAM as 'pinned_host'; the CPU backend
    # exposes it as 'unpinned_host' — take whichever this device has
    try:
        kinds = {m.kind for m in device.addressable_memories()}
    except Exception:  # paddle-lint: disable=swallowed-exception -- memory-kind probe; unpinned_host fallback is the documented CPU behavior
        kinds = ()
    kind = 'pinned_host' if 'pinned_host' in kinds else 'unpinned_host'
    return SingleDeviceSharding(device, memory_kind=kind)


def _device_sharding(device=None):
    device = device or jax.devices()[0]
    # 'device' (HBM) on accelerators; the CPU backend has no separate
    # device memory — use its default kind so offload degrades to a
    # correct (if pointless) host<->host stream there
    try:
        kind = device.default_memory().kind
    except Exception:  # paddle-lint: disable=swallowed-exception -- default_memory probe; device kind fallback documented for CPU
        kind = 'device'
    return SingleDeviceSharding(device, memory_kind=kind)


class OffloadEngine:
    """Streams an Optimizer's per-leaf updates through HBM while the
    slot state persists in pinned host memory."""

    def __init__(self, optimizer, device=None):
        self.opt = optimizer
        self.device = device or jax.devices()[0]
        self._host = _host_sharding(self.device)
        self._dev = _device_sharding(self.device)
        self._kernels: Dict[Any, Any] = {}

    # -- state --------------------------------------------------------------
    def init_state(self, params):
        def leaf(p):
            slots = self.opt._leaf_init(p)  # device zeros, one leaf at a
            return {k: jax.device_put(v, self._host)  # time -> no HBM spike
                    for k, v in slots.items()}
        slots = _tree.tree_map(leaf, params)
        return {'step': jnp.zeros((), jnp.int32), 'slots': slots}

    # -- kernels ------------------------------------------------------------
    def _kernel(self, g, p, slots, nm):
        # nm is part of the key: the compiled closure bakes the leaf
        # name in, and optimizers may branch on it beyond _coeff_for
        key = (nm, p.shape, str(p.dtype), str(g.dtype),
               tuple(sorted(slots.keys())))
        if key not in self._kernels:
            opt = self.opt

            def fn(gv, pv, sv, lr, step):
                return opt._leaf_apply(gv, pv, sv, lr, step, name=nm)
            # donate g, p, slots: the update is in-place in HBM
            self._kernels[key] = jax.jit(fn, donate_argnums=(0, 1, 2))
        return self._kernels[key]

    # -- apply --------------------------------------------------------------
    def apply(self, grads, params, state, lr_value):
        """(grads, params, host-state, lr) -> (new_params, new_state).
        Eager python loop; every kernel launch and transfer is async, so
        the H2D fetch of leaf i+1 rides under leaf i's compute."""
        if self.opt._grad_clip is not None:
            grads = self.opt._grad_clip.apply_pytree(grads)
        step = state['step'] + 1
        from . import _flatten_for_update
        treedef, names, flat_p, flat_g, flat_s = _flatten_for_update(
            params, grads, state['slots'])
        n = len(flat_p)
        lr = jnp.asarray(lr_value, jnp.float32)

        staged: list = [None] * n

        def fetch(i):
            if flat_g[i] is not None:
                staged[i] = {k: jax.device_put(v, self._dev)
                             for k, v in flat_s[i].items()}
                _note_transfer('h2d', _slot_bytes(flat_s[i]))
        if n:
            fetch(0)
        new_p, new_s = [], []
        for i in range(n):
            if i + 1 < n:
                fetch(i + 1)  # prefetch: H2D overlaps this leaf's update
            g, p, s, nm = flat_g[i], flat_p[i], flat_s[i], names[i]
            if g is None:
                new_p.append(p)
                new_s.append(s)
                continue
            np_, ns_ = self._kernel(g, p, staged[i], nm)(
                g, p, staged[i], lr, step)
            staged[i] = None
            new_p.append(np_)
            new_s.append({k: jax.device_put(v, self._host)
                          for k, v in ns_.items()})
            _note_transfer('d2h', _slot_bytes(new_s[-1]))
        return (_tree.tree_unflatten(treedef, new_p),
                {'step': step,
                 'slots': _tree.tree_unflatten(treedef, new_s)})
