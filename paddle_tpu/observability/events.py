"""Structured event log + span tracing with real timestamps.

Upstream analogue: paddle.profiler's RecordEvent host regions and the
fleet loss-spike logs — here unified as one bounded in-process
`EventLog` of JSON-able events carrying *actual* begin timestamps and
durations (not fabricated running sums), so the chrome-trace export is a
true timeline and JSONL tailing works for long fleet runs.

`span(name, **attrs)` is the tracing API every subsystem uses: a context
manager that records perf_counter begin/end, nesting depth, and thread
id into the event log and a `paddle_span_seconds{name}` histogram in the
metrics registry. `emit(name, **attrs)` records an instant event (e.g.
`loss_spike` from debug.LossSpikeDetector).
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from . import metrics as _metrics
from ..analysis.runtime import concurrency as _concurrency

# one process-wide clock origin so event timestamps from every thread /
# subsystem land on a single comparable timeline
_EPOCH = time.perf_counter()


def _now() -> float:
    return time.perf_counter() - _EPOCH


# ---------------------------------------------------------------------------
# declared event types: every `emit()` name the runtime may produce.
# The schema is the contract dashboards/flight-bundle consumers parse
# against, so drive-by event additions must land HERE first — a tier-1
# lint walks the source tree and fails on any emit() literal missing
# from this registry, and emit() itself counts undeclared names into
# `paddle_events_undeclared_total` so dynamic names can't slip past the
# static scan either. Span names are NOT events — they stay free-form
# (profiler RecordEvent regions carry user strings).
# ---------------------------------------------------------------------------
EVENT_SCHEMA: Dict[str, str] = {
    # debug / training anomalies
    'loss_spike': 'LossSpikeDetector flagged a step loss',
    'bad_step': 'FaultTolerantStep rolled back a NaN/spike step',
    'skip_budget_exhausted': 'bad-step skip budget exceeded; run dies',
    'hang_suspected': 'watchdog step deadline exceeded',
    'retry': 'transient error re-attempted with backoff',
    'preemption_signal': 'SIGTERM/SIGINT flagged by PreemptionHandler',
    'preempt_save': 'forced sync checkpoint on preemption',
    'checkpoint_corrupt': 'manifest checksum mismatch on restore',
    # fleet / elastic
    'fleet_init': 'mesh initialized',
    'topology_change': 'mesh rebuilt over a new device set',
    'topology_change_rejected': 'unusable device count; resize skipped',
    'device_probe_failed': 'device_source poll raised',
    # program store
    'program_cache_hit': 'program served from memory/disk tier',
    'program_cache_miss': 'program compiled fresh',
    'program_cache_reject': 'stored program found but unusable',
    'program_store_persist': 'program exported to the persistent tier',
    'program_store_persist_skipped': 'program not persistable',
    'program_store_preload': 'bulk preload completed',
    'program_store_invalidate': 'fingerprint refresh dropped entries',
    'program_store_wipe': 'persistent tier deleted on disk',
    'program_built': 'a program came into being and has run once: '
                     'program, kind, source (compile|disk|memory) and '
                     'the seconds of its build by phase (wall, trace, '
                     'lower, backend, cache_retrieval, first_call)',
    'serving_pool_recovered': 'donated decode failed mid-call; pool '
                              'rows rebuilt',
    # serving engine / router / tenancy
    'serving_request_failed': 'request failed; engine survives',
    'serving_drain_begin': 'graceful drain started',
    'serving_drain_complete': 'graceful drain finished',
    'serving_slow_step': 'a router step took over router.SLOW_STEP_S; '
                         'carries, as scalars, the span name with the '
                         'largest self time inside it, the thread\'s CPU '
                         'time, the collector\'s and the programs built',
    'prefix_hit': 'radix prefix-cache hit on admission',
    'prefix_evict': 'retained prefix slot reclaimed',
    # paged KV pool (serving/kv_pool.PagedSlotPool)
    'paged_cow': 'copy-on-write split of a shared KV page at admission',
    'page_pool_exhausted': 'page reservation failed after reclaiming '
                           'retention; request requeued',
    'request_shed': 'admission rejected under load shedding',
    'request_promoted': 'starvation promotion across QoS classes',
    'router_failover': 'accepted requests resubmitted to survivors',
    'router_failover_storm': 'failover budget exhausted',
    'breaker_open': 'replica circuit breaker opened',
    'breaker_half_open': 'breaker cooldown elapsed; probing',
    'breaker_closed': 'breaker probe succeeded; replica back',
    # online weight updates (trainer→serving hot-swap)
    'weight_publish': 'trainer published a weight version to the store',
    'weight_swap_begin': 'replica drain for a weight hot-swap started',
    'weight_swap_complete': 'replica rejoined on the new weight version',
    'weight_swap_failed': 'swap health gate failed; replica reverted',
    'weight_rollback': 'replica restored its previous weight version',
    'weight_version_quarantined':
        'weight version quarantined after a failed gate or load',
    'weight_writer_stale':
        'dead mid-commit weight publisher detected; marker+tmp swept',
    'rollout_iteration':
        'one serve→score→train→publish→swap turn of the rollout loop',
    # concurrency sanitizer (analysis/runtime/concurrency.py)
    'sanitizer_violation': 'runtime concurrency sanitizer report: '
                           'lock-order cycle, non-reentrant re-entry, '
                           'or lockset race',
    # goodput-driven autoscaling (serving/autoscaler.py)
    'autoscale_up': 'autoscaler provisioned a replica (warm '
                    'program-store path) and joined it to the fleet',
    'autoscale_down_begin': 'autoscaler cordoned a replica; graceful '
                            'drain toward removal started',
    'autoscale_down_complete': 'drained replica removed from the '
                               'fleet; no request dropped',
    # fleet observability plane (observability/{wire,shipper,aggregator,slo})
    'segment_shipped': 'fleet-plane telemetry segments committed to '
                       'the spool',
    'segment_quarantined': 'spool segment failed decode/sha256 '
                           'verification; renamed aside, not applied',
    'slo_breach': 'multi-window burn-rate alert fired for an SLO '
                  'objective',
    'slo_recovered': 'burn-rate alert cleared; short window cooled',
    'slo_capture': 'bounded jax.profiler capture started on breach',
    'fleet_signals_stale': 'FleetSignalSource fell back to the local '
                           'router: every per-process signal was stale',
    # process fleet runtime (serving/{supervisor,remote,replica_main})
    'replica_spawn': 'supervisor launched a replica process',
    'replica_ready': 'replica process warm-started and answering RPC',
    'replica_exit': 'replica process exited (rc + classification)',
    'replica_crash': 'replica process died uncleanly (crash or hang)',
    'replica_hang': 'heartbeat deadline exceeded on a live pid; '
                    'escalated to SIGKILL',
    'replica_restart': 'respawn scheduled with exponential backoff',
    'replica_quarantined': 'crash-looping replica circuit-broken out '
                           'of the respawn loop',
    'replica_retired': 'replica process retired through graceful drain',
    'replica_orphan_reaped': 'stale replica process from a previous '
                             'supervisor incarnation SIGKILLed',
    # multi-tenant adapter serving (serving/adapters/bank.py)
    'adapter_load': 'LoRA adapter factors written into a bank slot',
    'adapter_publish': 'adapter version committed to its weight store',
    'adapter_evict': 'zero-ref adapter slot reclaimed (LRU) for a '
                     'newcomer',
    'adapter_load_reject': 'adapter manifest failed verification; '
                           'version quarantined, bank keeps serving',
    'adapter_bank_saturated': 'adapter bank full of referenced slots; '
                              'request requeued (adapter_pinned) '
                              'instead of failed',
    # per-request latency ledger (observability/reqledger.py)
    'request_slow': 'request finished over the slow threshold '
                    '(N x the ttft_p99 SLO); carries the dominant '
                    'phase as the suspected driver',
}


def declare_event(name: str, help: str = ''):
    """Register an event type at runtime (deployment-specific emitters,
    fault-injection tests). Idempotent; returns the name."""
    EVENT_SCHEMA.setdefault(name, help or name)
    return name


class EventLog:
    """Bounded, thread-safe ring of structured events (oldest dropped).
    The default holds a serving run of a minute with room to spare (some
    11 router steps a second, 10 spans a step), so that a reader of the
    newest N steps finds all of them."""

    def __init__(self, capacity: int = 32768):
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._lock = _concurrency.Lock('EventLog._lock')
        self._dropped = 0
        self._seq = 0
        self._listeners: List = []

    @property
    def capacity(self) -> int:
        return self._events.maxlen

    @property
    def dropped(self) -> int:
        return self._dropped

    def append(self, event: Dict[str, Any]):
        with self._lock:
            # monotone per-log sequence: the /events?since= cursor that
            # survives ring eviction (timestamps alone can collide)
            self._seq += 1
            event.setdefault('seq', self._seq)
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(event)
        # listeners run OUTSIDE the lock (a listener may read the log,
        # e.g. the flight recorder dumping on an anomaly event)
        for fn in list(self._listeners):
            try:
                fn(event)
            except Exception:
                # a broken listener must not break emit sites — but it
                # must not break them SILENTLY either (a dead flight
                # recorder or goodput ledger looks exactly like "no
                # anomalies" otherwise)
                _metrics.count_suppressed('event_listener')

    def add_listener(self, fn):
        """`fn(event)` runs after every append (anomaly triggers)."""
        if fn not in self._listeners:
            self._listeners.append(fn)
        return fn

    def remove_listener(self, fn):
        if fn in self._listeners:
            self._listeners.remove(fn)

    def emit(self, name: str, **attrs):
        """Record an instant (zero-duration) event at the current time.
        Undeclared names (missing from EVENT_SCHEMA) are still logged
        but counted — the runtime complement of the static source lint."""
        if not _metrics.enabled():
            return
        if name not in EVENT_SCHEMA:
            _metrics.get_registry().counter(
                'paddle_events_undeclared_total',
                'emit() calls whose event type is not in EVENT_SCHEMA',
                ('event',)).labels(event=name).inc()
        self.append({'name': name, 'ph': 'i', 'ts': _now(),
                     'tid': threading.get_ident(), 'attrs': attrs})

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def spans_under(self, root_id: int) -> List[Dict[str, Any]]:
        """The recorded spans that descend from span `root_id`, itself
        included, newest first. One pass back over the ring's tail: a
        span is appended when it ENDS, so after its children and before
        its parent; the pass stops at the first span that ended before
        the root began. For the caller that has just closed a long span
        and asks what it fell under — never on a hot path."""
        ids, out, t_root = {root_id}, [], None
        for e in reversed(self.events()):
            if e.get('ph') != 'X':
                continue
            if e.get('id') == root_id:
                t_root = e['ts']
            elif e.get('parent') in ids:
                ids.add(e['id'])
            elif t_root is not None and e['ts'] + e['dur'] < t_root:
                break
            else:
                continue
            out.append(e)
        return out

    def clear(self):
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def __len__(self):
        return len(self._events)

    def to_jsonl(self, path: Optional[str] = None) -> str:
        text = '\n'.join(json.dumps(e) for e in self.events())
        if text:
            text += '\n'
        if path is not None:
            with open(path, 'w') as f:
                f.write(text)
        return text

    def to_chrome_trace(self, path: Optional[str] = None) -> Dict[str, Any]:
        from .exporters import to_chrome_trace
        return to_chrome_trace(self, path)


_default_log = EventLog()


def get_event_log() -> EventLog:
    return _default_log


@_metrics.get_registry().register_collector
def _dropped_collector(reg):
    """Scrape-time mirror: events silently aged out of the bounded ring
    are visible on /metrics, so trace truncation is never a surprise."""
    fam = reg.counter('paddle_events_dropped_total',
                      'events dropped by the bounded EventLog')
    fam._sole().value = float(_default_log.dropped)   # mirror


def emit(name: str, **attrs):
    _default_log.emit(name, **attrs)


class _SpanState(threading.local):
    def __init__(self):
        self.stack: List[int] = []   # ids of this thread's open spans


_span_state = _SpanState()
_span_ids = itertools.count(1)       # next() is atomic under the GIL


def _record(log, name, ts, dur, depth, span_id, parent, attrs):
    ev = {'name': name, 'ph': 'X', 'ts': ts, 'dur': dur,
          'tid': threading.get_ident(), 'depth': depth,
          'id': span_id, 'parent': parent}
    if attrs:
        ev['attrs'] = attrs
    log.append(ev)
    _metrics.get_registry().histogram(
        'paddle_span_seconds', 'span(name) wall time',
        ('name',)).labels(name=name).observe(dur)


class Span:
    """Timed region recorded into the EventLog + span histogram, and —
    through a `jax.profiler.TraceAnnotation` of the same name — into the
    host plane of a running profiler trace, on the device ops' clock
    (the one place in the package that opens one; with no trace running
    it costs an atomic check). Each span has an `id` and its `parent`,
    the innermost span open on the thread when it began (0: none);
    request-scoped spans carry the request's id as the `request_id`
    attribute. Nestable; a context manager, or explicit begin()/end().
    Once ended it keeps its duration (`dur`; 0.0 for a span that
    recorded nothing), for the caller that acts on a long one."""

    __slots__ = ('name', 'attrs', 'id', 'parent', 'dur', '_t0', '_log',
                 '_ann')

    def __init__(self, name: str, _log: Optional[EventLog] = None, **attrs):
        self.name = name
        self.attrs = attrs
        # `is None`, not truthiness: an EMPTY EventLog is falsy
        # (__len__ == 0) and `or` would silently reroute the span to
        # the default log
        self._log = _default_log if _log is None else _log
        self.id = self.parent = 0
        self.dur = self._t0 = 0.0
        self._ann = None             # the open annotation: span is active

    def begin(self) -> 'Span':
        if _metrics.enabled():
            stack = _span_state.stack
            self.parent = stack[-1] if stack else 0
            self.id = next(_span_ids)
            stack.append(self.id)
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            self._t0 = _now()
        return self

    def set(self, **attrs):
        """Counts known only once the work is done (`admitted=2`);
        scalars, so that a span costs no list or dict of its own."""
        if self._ann is not None:
            self.attrs.update(attrs)

    def end(self):
        if self._ann is None:
            return
        self.dur = dur = _now() - self._t0
        self._ann.__exit__(None, None, None)
        self._ann = None
        stack = _span_state.stack
        depth = len(stack)
        if self.id in stack:
            # a span left open inside this one goes with it
            del stack[stack.index(self.id):]
        _record(self._log, self.name, self._t0, dur, depth, self.id,
                self.parent, self.attrs)

    def __enter__(self) -> 'Span':
        return self.begin()

    def __exit__(self, *exc):
        self.end()


def record_span(name: str, t_begin: float, **attrs):
    """A region whose two ends lie in different calls (a request's wait
    in the queue, submit to admission): recorded when it ends, from the
    `time.perf_counter()` reading taken when it began. It nests in
    nothing — no parent, depth 0 — and is not put on the profiler's
    timeline, where it would lie across the spans of the steps it
    outlasts."""
    if _metrics.enabled():
        ts = t_begin - _EPOCH
        _record(_default_log, name, ts, _now() - ts, 0, next(_span_ids),
                0, attrs)


def span(name: str, **attrs) -> Span:
    """`with span('fleet.dist_train_step', step=i): ...` — records a real
    begin/end timestamped event and a duration histogram sample."""
    return Span(name, **attrs)
