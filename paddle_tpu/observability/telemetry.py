"""Runtime instrumentation: jit-compile listeners, dispatch collector,
device-memory watermark, and per-step training telemetry.

Wires the passive sources into the registry:
- `jax.monitoring` duration listeners turn every backend compile into
  `paddle_jit_compiles_total` / `paddle_jit_compile_seconds_total` —
  the host-side view of "where did my step go" that xprof's device
  traces assume the framework provides (upstream analogue: the
  to_static program-cache hit logs).
- a registry collector mirrors the eager dispatch cache's raw counters
  (paddle_tpu._dispatch) into `paddle_dispatch_*` metrics at snapshot
  time — zero per-op cost, `debug.dispatch_stats()` stays the raw view.
- `StepTelemetry` tracks steps/sec, tokens/sec, last loss, and the
  device-memory watermark (`memory_stats()` when the backend reports
  it, live-array bytes fallback on CPU); hapi's MetricsLoggerCallback
  and examples/train_gpt.py drive it per train step.
"""
from __future__ import annotations

import collections
import gc
import time
from typing import Optional

from . import metrics as _metrics

_installed = [False]


def _synthetic_span(name: str, secs: float):
    """Feed a completed host region straight into the goodput ledger.
    The duration listener fires at region END on the emitting thread,
    so begin = now - secs lands the interval on the span clock AND
    keeps the child-before-parent ordering the ledger's nested-span
    subtraction relies on (a compile inside a train step is credited
    before the step span ends). Direct call, NOT an event-log append —
    a busy dispatch cache compiles thousands of entries per session and
    would flush the bounded event ring."""
    from . import events as _events
    from .goodput import get_ledger
    get_ledger().note_span(name, _events._now() - secs, secs)


def _on_jax_duration(name: str, secs: float, **kw):
    if not _metrics.enabled():
        return
    reg = _metrics.get_registry()
    if name.endswith('backend_compile_duration'):
        reg.counter('paddle_jit_compiles_total',
                    'XLA backend compiles').inc()
        reg.counter('paddle_jit_compile_seconds_total',
                    'seconds spent in XLA backend compile').inc(secs)
        _synthetic_span('jit.compile', secs)
    elif name.endswith('jaxpr_trace_duration'):
        reg.counter('paddle_jit_trace_seconds_total',
                    'seconds spent tracing python to jaxpr').inc(secs)
        _synthetic_span('jit.trace', secs)


def _on_jax_event(name: str, **kw):
    """Instant-event listener: the persistent compilation cache emits
    `/jax/compilation_cache/cache_hits` when a backend "compile" was
    actually served from disk. `paddle_jit_compiles_total` ticks either
    way (the duration event wraps the whole compile-or-get-cached
    call), so REAL compiles in a window = compiles delta minus cache
    hits delta — the program store's zero-compile warm-restart guards
    assert that difference is zero."""
    if not _metrics.enabled():
        return
    if name.endswith('/compilation_cache/cache_hits'):
        _metrics.get_registry().counter(
            'paddle_jit_cache_hits_total',
            'XLA backend compiles served from the persistent '
            'compilation cache').inc()
    elif name.endswith('/compilation_cache/cache_misses'):
        _metrics.get_registry().counter(
            'paddle_jit_cache_misses_total',
            'XLA backend compiles that missed the persistent '
            'compilation cache and were written to it').inc()


def _dispatch_collector(reg: '_metrics.MetricsRegistry'):
    """Scrape-time mirror of the dispatch cache's raw counters."""
    from .. import _dispatch
    s = _dispatch.stats()
    calls = reg.counter('paddle_dispatch_calls_total',
                        'eager apply_op dispatches by result', ('result',))
    for key in ('hits', 'misses', 'retraces', 'fallbacks', 'errors'):
        c = calls.labels(result=key)
        c.value = float(s[key])   # mirror, not accumulate
    reg.gauge('paddle_dispatch_hit_rate',
              'dispatch cache hit rate').set(s['hit_rate'])
    reg.gauge('paddle_dispatch_cache_entries',
              'compiled entries resident in the dispatch cache').set(
                  s['cache_size'])
    ev = reg.counter('paddle_dispatch_evictions_total',
                     'dispatch-cache LRU evictions')
    ev._sole().value = float(s['evictions'])   # mirror, not accumulate


_gc_pause = [0.0, 0.0]    # seconds the collector has run; its start


def _on_gc(phase: str, info: dict):
    """`gc.callbacks` hook: runs only when a collection does, on the
    thread that set it off (which holds the interpreter meanwhile)."""
    if phase == 'start':
        _gc_pause[1] = time.perf_counter()
    else:
        _gc_pause[0] += time.perf_counter() - _gc_pause[1]


def gc_pause_seconds() -> float:
    """Seconds the cyclic collector has run in this process since
    `install()`; a caller reads it at both ends of a region (the router
    step's slow-step record)."""
    return _gc_pause[0]


def _gc_collector(reg: '_metrics.MetricsRegistry'):
    """Scrape-time mirror of the collector's running time."""
    fam = reg.counter('paddle_gc_pause_seconds_total',
                      'seconds the cyclic garbage collector ran')
    fam._sole().value = _gc_pause[0]   # mirror, not accumulate


def install():
    """Idempotent: register the jax.monitoring listeners, the `gc`
    hook, and the dispatch and gc collectors on the default registry.
    Runs at package import; safe to call again (e.g. after
    jax.monitoring.clear_event_listeners in a test)."""
    reg = _metrics.get_registry()
    reg.register_collector(_dispatch_collector)
    reg.register_collector(_gc_collector)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if _installed[0]:
        return
    try:
        from jax import monitoring as _mon
        _mon.register_event_duration_secs_listener(_on_jax_duration)
        _mon.register_event_listener(_on_jax_event)
        _installed[0] = True
    except Exception:  # paddle-lint: disable=swallowed-exception -- jax without monitoring hooks: compile metrics stay at zero, documented
        pass   # jax without monitoring: compile metrics stay at zero


def note_jit_cache_entry(kind: str = 'to_static'):
    """Called by jit.StaticLayer (and friends) when a new executable
    lands in a python-side jit cache."""
    if not _metrics.enabled():
        return
    _metrics.get_registry().gauge(
        'paddle_jit_cache_entries',
        'executables held by python-side jit caches', ('kind',)).labels(
            kind=kind).inc()


def collective_totals(reg: Optional['_metrics.MetricsRegistry'] = None
                      ) -> dict:
    """Sum the per-(op, axis) collective counters into totals plus a
    per-label breakdown: {'calls', 'bytes', 'per_op': {(op, axis):
    {'calls', 'bytes'}}}."""
    reg = reg if reg is not None else _metrics.get_registry()
    out = {'calls': 0.0, 'bytes': 0.0, 'per_op': {}}
    for metric, field in (('paddle_collective_calls_total', 'calls'),
                          ('paddle_collective_bytes_total', 'bytes')):
        fam = reg.get(metric)
        if fam is None:
            continue
        for key, child in fam.children():
            out[field] += child.value
            row = out['per_op'].setdefault(key, {'calls': 0.0, 'bytes': 0.0})
            row[field] += child.value
    return out


def device_memory_bytes() -> int:
    """Current device-memory footprint: the backend's `memory_stats()`
    when available (TPU/GPU), else the sum of live jax array bytes (the
    CPU backend reports no allocator stats)."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:  # paddle-lint: disable=swallowed-exception -- memory_stats unsupported on this backend; live-array fallback below
        stats = None
    if stats:
        for key in ('peak_bytes_in_use', 'bytes_in_use'):
            if stats.get(key):
                return int(stats[key])
    try:
        return int(sum(a.nbytes for a in jax.live_arrays()))
    except Exception:  # paddle-lint: disable=swallowed-exception -- live-array sum is the last-resort probe; 0 means unknown
        return 0


class StepTelemetry:
    """Per-step training telemetry into the shared registry.

    `step(loss=..., tokens=...)` once per optimizer step updates:
    paddle_steps_total, paddle_tokens_total, paddle_steps_per_sec /
    paddle_tokens_per_sec (trailing-window rates), paddle_loss_last,
    and the paddle_memory_watermark_bytes high-water gauge.
    """

    def __init__(self, registry: Optional['_metrics.MetricsRegistry'] = None,
                 window: int = 20, memory_every: int = 1):
        reg = registry if registry is not None else _metrics.get_registry()
        self._steps = reg.counter('paddle_steps_total',
                                  'optimizer steps taken')
        self._tokens = reg.counter('paddle_tokens_total',
                                   'training tokens consumed')
        self._sps = reg.gauge('paddle_steps_per_sec',
                              'trailing-window steps/sec')
        self._tps = reg.gauge('paddle_tokens_per_sec',
                              'trailing-window tokens/sec')
        self._loss = reg.gauge('paddle_loss_last', 'last observed loss')
        self._mem = reg.gauge('paddle_memory_watermark_bytes',
                              'device-memory high-water mark')
        self._times = collections.deque(maxlen=max(window, 2))
        self._tok_hist = collections.deque(maxlen=max(window, 2))
        self._memory_every = max(int(memory_every), 1)
        self._n = 0

    def step(self, loss=None, tokens: Optional[int] = None):
        if not _metrics.enabled():
            return self
        from . import flight as _flight
        from .server import note_progress
        now = time.perf_counter()
        self._times.append(now)
        self._n += 1
        self._steps.inc()
        if tokens:
            self._tokens.inc(tokens)
            self._tok_hist.append(tokens)
        if loss is not None:
            try:
                self._loss.set(float(loss))
            except (TypeError, ValueError):
                pass
        if len(self._times) >= 2:
            dt = self._times[-1] - self._times[0]
            if dt > 0:
                n = len(self._times) - 1
                self._sps.set(n / dt)
                if self._tok_hist:
                    # rate over the steps the window actually spans
                    tok = sum(list(self._tok_hist)[-n:])
                    self._tps.set(tok / dt)
        if self._n % self._memory_every == 0:
            mem = device_memory_bytes()
            self._mem.set_to_max(mem)
            _flight.get_flight_recorder().record_memory(mem)
        # liveness heartbeat (/healthz) + flight-recorder ring sample
        note_progress('step')
        _flight.get_flight_recorder().record_step(
            loss=self._loss.value if loss is not None else None,
            tokens_per_sec=self._tps.value, step=self._n)
        return self

    def phase(self, name: str, **attrs):
        """Step-phase waterfall sub-span: `with telemetry.phase(
        'data_wait'): batch = next(loader)` records a `step.{name}`
        span the goodput ledger classifies (step.data_wait ->
        host_wait, step.compute -> step_compute, ...) and the chrome
        trace renders as the per-step waterfall."""
        from . import events as _events
        return _events.span(f'step.{name}', **attrs)

    def update_memory_watermark(self):
        if _metrics.enabled():
            self._mem.set_to_max(device_memory_bytes())
        return self

    def summary(self) -> dict:
        return {'steps': self._steps.value,
                'tokens': self._tokens.value,
                'steps_per_sec': self._sps.value,
                'tokens_per_sec': self._tps.value,
                'loss_last': self._loss.value,
                'memory_watermark_bytes': self._mem.value}
