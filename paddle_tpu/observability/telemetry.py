"""Runtime instrumentation: jit-compile listeners, dispatch collector,
device-memory watermark, and per-step training telemetry.

Wires the passive sources into the registry:
- `jax.monitoring` duration listeners turn every backend compile into
  `paddle_jit_compiles_total` / `paddle_jit_compile_seconds_total` —
  the host-side view of "where did my step go" that xprof's device
  traces assume the framework provides (upstream analogue: the
  to_static program-cache hit logs).
- while the program store has a build open (`ProgramBuild`, around
  `StoredJit._build`), the same listener books jax's own split of it —
  trace, lowering, compile-or-fetch, the cache's retrieval — to that
  build: `paddle_program_build_seconds_total{phase}`. With
  `paddle_setup_seconds_total{phase}` (`import`, `construct`) these are
  the counters a process's set-up is read from once its spans have
  left the event ring.
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
import functools
import gc
import threading
import time
from typing import Callable, List, Optional

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


# jax's duration events that make up a program's coming into being, by
# the last part of their names -> the phase they are booked under
_PHASE_OF = {
    'jaxpr_trace_duration': 'trace',
    'jaxpr_to_mlir_module_duration': 'lower',
    'backend_compile_duration': 'backend',
    'cache_retrieval_time_sec': 'cache_retrieval',
}

BUILD_PHASES = ('wall', 'trace', 'lower', 'backend', 'cache_retrieval',
                'first_call')


def _on_jax_duration(name: str, secs: float, **kw):
    if not _metrics.enabled():
        return
    phase = _PHASE_OF.get(name.rsplit('/', 1)[-1])
    if phase is None:
        return
    reg = _metrics.get_registry()
    if phase == 'backend':
        reg.counter('paddle_jit_compiles_total',
                    'XLA backend compiles').inc()
        reg.counter('paddle_jit_compile_seconds_total',
                    'seconds spent in XLA backend compile').inc(secs)
        _synthetic_span('jit.compile', secs)
    elif phase == 'trace':
        reg.counter('paddle_jit_trace_seconds_total',
                    'seconds spent tracing python to jaxpr').inc(secs)
        _synthetic_span('jit.trace', secs)
    build = _build_of_this_event()
    if build is not None:
        build.book(phase, secs)


# ---------------------------------------------------------------------------
# a program's build, booked by phase
# ---------------------------------------------------------------------------
_open_builds: List['ProgramBuild'] = []   # append / remove under the GIL


def _build_of_this_event() -> Optional['ProgramBuild']:
    """The build a duration event belongs to: the one this thread has
    open, else the newest open one — the attribution follows the build,
    not the thread, so a compile the store hands to a helper thread is
    still the build's. None outside any build (the harness's own jits,
    an eager op): such an event moves the process-wide counters alone."""
    if not _open_builds:
        return None
    tid = threading.get_ident()
    for build in reversed(_open_builds):
        if build.tid == tid:
            return build
    return _open_builds[-1]


def _build_counter(family: str, help_: str, label: str, value: str):
    return _metrics.get_registry().counter(
        family, help_, (label,)).labels(**{label: value})


def note_build_seconds(phase: str, secs: float):
    _build_counter('paddle_program_build_seconds_total',
                   'seconds the program store spent bringing programs '
                   'into being, by phase: wall, and inside it trace, '
                   'lower, backend (compile, or fetch from jax\'s cache '
                   'and load; cache_retrieval lies inside it), then '
                   'first_call', 'phase', phase).inc(secs)


class ProgramBuild:
    """One program coming into being, `with`-ed around
    `StoredJit._build`: its wall time and, inside it, jax's own split,
    which `_on_jax_duration` books here while the build is open. On exit
    the numbers go to the program's `ProgramRecord`, to
    `paddle_program_build_seconds_total{phase}` and to
    `paddle_program_builds_total{source}`.

    jax fires a duration for every region as it ENDS, so for every
    jitted function traced inside another's trace before the outer one:
    an interval that lies inside a later one is taken back out when the
    later one arrives, and the phases of a build never sum past its
    wall. `cache_retrieval` lies inside `backend` by jax's own nesting;
    it is kept beside the split, not in it.

    A build opened on a thread that already has one open (a stored
    program called while another's function is being traced) is not a
    build of its own: it books nothing, the outer one's trace covers it
    (`live` False, as with observability off). The record's `wall` is
    the catalog's and is kept either way, like `host_seconds`."""

    __slots__ = ('record', 'source', 'seconds', 'live', 'tid', '_t0',
                 '_top')

    def __init__(self, record):
        self.record = record
        self.source = 'compile'       # the store says: compile|disk|memory
        self.seconds = dict.fromkeys(BUILD_PHASES, 0.0)
        self.live = False
        self.tid = threading.get_ident()
        self._top: list = []          # [start, end, phase]: not nested

    def __enter__(self) -> 'ProgramBuild':
        self.live = _metrics.enabled() and not any(
            b.tid == self.tid for b in _open_builds)
        if self.live:
            _open_builds.append(self)
        self._t0 = time.perf_counter()
        return self

    def book(self, phase: str, secs: float):
        if phase == 'cache_retrieval':
            self.seconds[phase] += secs
            return
        end = time.perf_counter()
        start = end - secs
        top = self._top
        # an earlier interval whose middle lies in this one is nested in
        # it (the two clocks' jitter is far below half a region)
        while top and (top[-1][0] + top[-1][1]) / 2 >= start:
            was = top.pop()
            self.seconds[was[2]] -= was[1] - was[0]
        top.append((start, end, phase))
        self.seconds[phase] += secs

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._t0
        rec, s = self.record, self.seconds
        s['wall'] = wall
        rec.build_seconds += wall
        if self.source != 'memory':
            rec.compile_seconds += wall
        if not self.live:
            return
        _open_builds.remove(self)
        rec.trace_seconds += s['trace']
        rec.lower_seconds += s['lower']
        rec.backend_seconds += s['backend']
        rec.cache_retrieval_seconds += s['cache_retrieval']
        for phase in BUILD_PHASES[:-1]:
            note_build_seconds(phase, s[phase])
        _build_counter('paddle_program_builds_total',
                       'programs the store brought into being, by where '
                       'the executable came from', 'source',
                       self.source).inc()

    def first_call(self, secs: float):
        """The first execution of the program this build made has
        returned after `secs`: the last phase, and the build's one
        `program_built` event with all six."""
        from . import events as _events
        s = self.seconds
        s['first_call'] = secs
        self.record.first_call_seconds += secs
        if not _metrics.enabled():      # switched off since the build
            return
        note_build_seconds('first_call', secs)
        _events.emit('program_built', program=self.record.name,
                     kind=self.record.kind, source=self.source,
                     **{f'{p}_seconds': round(s[p], 6)
                        for p in BUILD_PHASES})


# ---------------------------------------------------------------------------
# the set-up outside the builds: the import and the constructors
# ---------------------------------------------------------------------------
def note_setup(phase: str, secs: float):
    """`paddle_setup_seconds_total{phase}`: `import` (the package's own
    body and whatever it is first to import) and `construct`
    (`InferenceEngine.__init__`, `TrainStep.__init__`); no others."""
    if _metrics.enabled():
        _metrics.get_registry().counter(
            'paddle_setup_seconds_total',
            'seconds of a process\'s set-up outside its programs\' '
            'builds, by phase', ('phase',)).labels(phase=phase).inc(secs)


def constructing(span_name: str,
                 attrs: Optional[Callable[[object], dict]] = None):
    """Decorator for a constructor that is part of set-up: one span of
    `span_name` around it (with `attrs(self)` once it has run), booked
    to `paddle_setup_seconds_total{phase="construct"}`."""
    def wrap(init):
        @functools.wraps(init)
        def timed(self, *args, **kwargs):
            from . import events as _events
            with _events.span(span_name) as sp:
                init(self, *args, **kwargs)
                if attrs is not None and _metrics.enabled():
                    sp.set(**attrs(self))
            if sp.dur:
                note_setup('construct', sp.dur)
        return timed
    return wrap


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
