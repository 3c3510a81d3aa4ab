"""paddle_tpu.observability — unified metrics + tracing layer.

One process-wide `MetricsRegistry` (labeled Counter/Gauge/Histogram),
one bounded `EventLog` of real-timestamped spans/events, and three
exporters (Prometheus text, JSONL, chrome-trace). Every subsystem
reports here — eager dispatch cache (via a scrape-time collector), jit
compiles (jax.monitoring listeners), eager collectives (per-axis
call/byte counters), optimizer host-offload (H2D/D2H bytes), and hapi
train loops (StepTelemetry) — so `debug.observability_summary()` or a
single export answers "where did this step's time, bytes, and compiles
go". Upstream Paddle scatters these across paddle.profiler,
FLAGS_check_nan_inf, and per-worker fleet logs; MegaScale
(arXiv:2402.15627) is the reference for why one substrate matters at
pod scale.

Multi-host: every exported sample is tagged with the host's
process_index; `distributed.fleet_utils.gather_registry()` merges
per-host snapshots over the existing collectives.

Cross-PROCESS (the fleet plane): `wire` is the versioned JSONL segment
format, `Shipper` spools a process's metric deltas / events / spans to
a shared directory, `Aggregator` tails spools into one merged view and
stitches skew-corrected cross-process traces, and `SLOEngine` judges
declarative objectives over the fleet view with multi-window burn-rate
alerting (breaches trigger flight-recorder bundles). The server gains
`/fleet/metrics`, `/fleet/trace`, and `/slo`.
"""
from __future__ import annotations

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      DEFAULT_BUCKETS, QUANTILES, SlidingWindow,
                      count_suppressed, enable, enabled, disable,
                      get_registry, merge_snapshots)
from .events import (EVENT_SCHEMA, EventLog, Span, declare_event, emit,
                     get_event_log, record_span, span)
from .exporters import (chrome_track_metadata, fleet_to_prometheus_text,
                        read_jsonl, to_chrome_trace, to_jsonl,
                        to_prometheus_text)
from .wire import (WIRE_VERSION, WireError, decode_segment,
                   encode_segment, make_segment, metrics_delta,
                   process_uid, read_segment, write_segment)
from .shipper import Shipper
from .aggregator import (Aggregator, FleetSignalSource, get_aggregator,
                         set_aggregator)
from .slo import (Objective, SLOEngine, default_objectives,
                  get_engine as get_slo_engine,
                  set_engine as set_slo_engine)
from .telemetry import (StepTelemetry, collective_totals,
                        device_memory_bytes, gc_pause_seconds, install,
                        note_jit_cache_entry)
from .cost import (MfuWindow, ProgramCatalog, ProgramRecord,
                   aggregate_mfu, device_peaks, record_roofline,
                   roofline_summary, get_catalog as program_catalog)
from .goodput import (CATEGORIES as GOODPUT_CATEGORIES, GoodputLedger,
                      get_ledger)
from .reqledger import (BLOCKED_REASONS, PHASES as REQUEST_PHASES,
                        RequestLedger, RequestRecord,
                        get_ledger as get_request_ledger)
from .flight import FlightRecorder, get_flight_recorder
from .server import (ObservabilityServer, clear_degraded, degraded_states,
                     hang_suspected, health, note_degraded, note_progress,
                     note_weight_version, start_server, weight_versions)
from . import cost as _cost
from . import flight as _flight
from . import goodput as _goodput
from . import reqledger as _reqledger

__all__ = [
    'Counter', 'Gauge', 'Histogram', 'MetricsRegistry', 'DEFAULT_BUCKETS',
    'QUANTILES', 'SlidingWindow',
    'enable', 'enabled', 'disable', 'get_registry', 'merge_snapshots',
    'EVENT_SCHEMA', 'EventLog', 'Span', 'declare_event', 'emit',
    'get_event_log', 'record_span', 'span',
    'read_jsonl', 'to_chrome_trace', 'to_jsonl', 'to_prometheus_text',
    'chrome_track_metadata', 'fleet_to_prometheus_text',
    'WIRE_VERSION', 'WireError', 'decode_segment', 'encode_segment',
    'make_segment', 'metrics_delta', 'process_uid', 'read_segment',
    'write_segment',
    'Shipper', 'Aggregator', 'FleetSignalSource', 'get_aggregator',
    'set_aggregator',
    'Objective', 'SLOEngine', 'default_objectives', 'get_slo_engine',
    'set_slo_engine',
    'StepTelemetry', 'collective_totals', 'device_memory_bytes',
    'gc_pause_seconds', 'install', 'note_jit_cache_entry',
    'MfuWindow', 'ProgramCatalog', 'ProgramRecord',
    'program_catalog',
    'aggregate_mfu', 'device_peaks', 'record_roofline', 'roofline_summary',
    'GOODPUT_CATEGORIES', 'GoodputLedger', 'get_ledger',
    'BLOCKED_REASONS', 'REQUEST_PHASES', 'RequestLedger',
    'RequestRecord', 'get_request_ledger',
    'FlightRecorder', 'get_flight_recorder',
    'ObservabilityServer', 'clear_degraded', 'degraded_states',
    'hang_suspected', 'health', 'note_degraded', 'note_progress',
    'note_weight_version', 'start_server', 'weight_versions',
]

# register the jax.monitoring listeners + dispatch collector once at
# import; all hooks are no-ops while observability is disabled
install()
# program-catalog collector (paddle_program_* mirror), the always-on
# flight recorder's anomaly listener, and the always-on goodput ledger
# on the default event log
_cost.install()
_flight.install()
_goodput.install()
_reqledger.install()
