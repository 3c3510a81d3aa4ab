"""Global framework state: places/devices, default dtype, RNG.

TPU-native analogue of the reference's place/device machinery
(upstream: paddle/phi/backends/, python/paddle/device/). Devices are jax
devices; the "place" API is a thin veneer so reference-style code runs
unchanged. RNG is stateless threefry underneath (reproducible, trace-safe)
with a stateful facade for eager mode.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from . import dtype as _dtype_mod

# --------------------------------------------------------------------------
# Places
# --------------------------------------------------------------------------


class Place:
    device_type = 'unknown'

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f'Place({self.device_type}:{self.device_id})'

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f'{self!r} requested but jax sees no {self.device_type} '
                f'device (platforms present: '
                f'{sorted({d.platform for d in jax.devices()})})')
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = 'cpu'

    def jax_device(self):
        return jax.devices('cpu')[self.device_id % len(jax.devices('cpu'))]


class TPUPlace(Place):
    device_type = 'tpu'


# Alias for reference-style code; there is no CUDA here, it maps to the
# accelerator place (upstream: paddle/phi/common/place.h CUDAPlace).
XLAPlace = TPUPlace
CUDAPlace = TPUPlace


class _State(threading.local):
    def __init__(self):
        self.default_dtype = _dtype_mod.float32
        self.place = None  # lazily resolved


_state = _State()


def _default_place() -> Place:
    if _state.place is None:
        kinds = {d.platform for d in jax.devices()}
        _state.place = TPUPlace(0) if 'tpu' in kinds else CPUPlace(0)
    return _state.place


def set_device(device: str):
    """set_device('tpu') / 'tpu:0' / 'cpu' (upstream: paddle.device.set_device)."""
    name, _, idx = device.partition(':')
    idx = int(idx) if idx else 0
    name = {'gpu': 'tpu', 'xla': 'tpu', 'xpu': 'tpu'}.get(name, name)
    if name == 'tpu':
        _state.place = TPUPlace(idx)
    elif name == 'cpu':
        _state.place = CPUPlace(idx)
    else:
        raise ValueError(f'unknown device {device!r}')
    return _state.place


def get_device() -> str:
    p = _default_place()
    return f'{p.device_type}:{p.device_id}'


def get_place() -> Place:
    return _default_place()


@contextlib.contextmanager
def device_guard(device: str):
    old = _default_place()
    set_device(device)
    try:
        yield
    finally:
        _state.place = old


def synchronize():
    """Block until all dispatched device work is complete."""
    (jnp.zeros(()) + 0).block_until_ready()


def is_compiled_with_cuda() -> bool:  # reference-compat shim
    return False


def is_compiled_with_xla() -> bool:
    return True


# --------------------------------------------------------------------------
# Device memory introspection (upstream: python/paddle/device/cuda/
# max_memory_allocated / memory_allocated / memory_reserved family —
# here backed by PjRt per-device memory_stats()).
# --------------------------------------------------------------------------


def _memory_stats(device_id: Optional[int] = None) -> dict:
    devs = jax.devices()
    dev = devs[device_id or 0] if device_id is not None else devs[0]
    stats = dev.memory_stats()
    return dict(stats) if stats else {}


def memory_allocated(device_id: Optional[int] = None) -> int:
    """Bytes currently allocated on the device (0 when the backend does
    not expose stats, e.g. the CPU test mesh)."""
    return int(_memory_stats(device_id).get('bytes_in_use', 0))


def max_memory_allocated(device_id: Optional[int] = None) -> int:
    """High-water mark of device bytes allocated since process start."""
    s = _memory_stats(device_id)
    return int(s.get('peak_bytes_in_use', s.get('bytes_in_use', 0)))


def memory_reserved(device_id: Optional[int] = None) -> int:
    """Bytes reserved by the allocator pool (>= allocated)."""
    s = _memory_stats(device_id)
    return int(s.get('bytes_reserved',
                     s.get('bytes_reservable_limit', 0)) or
               s.get('bytes_in_use', 0))


def max_memory_reserved(device_id: Optional[int] = None) -> int:
    s = _memory_stats(device_id)
    return int(s.get('peak_bytes_reserved', 0) or max_memory_allocated(
        device_id))


def device_memory_limit(device_id: Optional[int] = None) -> int:
    """Total usable device memory (HBM) in bytes, when known."""
    return int(_memory_stats(device_id).get('bytes_limit', 0))


# --------------------------------------------------------------------------
# Default dtype
# --------------------------------------------------------------------------


def set_default_dtype(d):
    _state.default_dtype = _dtype_mod.convert_dtype(d)


def get_default_dtype():
    return _state.default_dtype


# --------------------------------------------------------------------------
# RNG: stateless threefry core, stateful eager facade, trace-safe capture
# --------------------------------------------------------------------------


class Generator:
    """Counter-based PRNG stream.

    Eager mode: every draw folds a fresh counter into the root key.
    Trace (jit) mode: `trace_scope(key)` installs a per-step key; draws fold
    a trace-local counter so each op site gets a distinct, deterministic
    subkey that varies with the per-step key input (no baked-in constants).
    """

    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._counter = 0
        self._trace_key = None
        self._trace_counter = 0

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._counter = 0
        return self

    @property
    def root_key(self):
        # legacy raw uint32[2] key, NOT jax.random.key(): the bits and
        # every downstream jax.random.* op are identical, but the typed
        # key<fry> aval cannot ride through jax.export serialization —
        # and these keys are inputs to every persisted train/to_static
        # program in the program store
        return jax.random.PRNGKey(self._seed)

    def next_key(self):
        if self._trace_key is not None:
            k = jax.random.fold_in(self._trace_key, self._trace_counter)
            self._trace_counter += 1
            return k
        k = jax.random.fold_in(self.root_key, self._counter)
        self._counter += 1
        return k

    @contextlib.contextmanager
    def trace_scope(self, key):
        old_key, old_ctr = self._trace_key, self._trace_counter
        self._trace_key, self._trace_counter = key, 0
        try:
            yield
        finally:
            self._trace_key, self._trace_counter = old_key, old_ctr

    def state(self):
        return {'seed': self._seed, 'counter': self._counter}

    def set_state(self, st):
        self._seed = int(st['seed'])
        self._counter = int(st['counter'])


default_generator = Generator(0)


def seed(s: int):
    """Global seed (upstream: paddle.seed)."""
    default_generator.manual_seed(s)
    return default_generator


def next_rng_key():
    return default_generator.next_key()


def get_cuda_rng_state():
    """CUDA-API shim (upstream python/paddle/framework/random.py): the
    stateless threefry (seed, counter) pair is the only RNG state on
    TPU — returned as a one-element list to mirror the per-device list
    upstream returns."""
    return [default_generator.state()]


def set_cuda_rng_state(state):
    if state:
        default_generator.set_state(state[0])
