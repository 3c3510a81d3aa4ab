"""paddle_tpu.serving — continuous-batching inference engine.

The batch-synchronous `generate()` path admits a whole batch together
and every sequence waits for the slowest one. This subsystem serves
heavy mixed-length traffic instead: an `InferenceEngine` owning a
preallocated fixed-slot KV-cache pool (`kv_pool.SlotPool`, N slots x
max_length with length-bucketed prefill), an iteration-level FCFS
scheduler (`scheduler.FCFSScheduler`) that admits and retires requests
BETWEEN decode steps (Orca, OSDI'22; pooled-cache management after
vLLM/PagedAttention, SOSP'23 — fixed slots instead of paged blocks
because TPU programs want static shapes), and ONE compiled decode step
carrying per-slot positions, active mask, and sampling params as
arrays. Greedy outputs are token-for-token identical to `generate()`;
everything reports into the shared observability registry
(`paddle_serving_*`), and host<->device transfers ride the resilience
retry layer with request-level (not engine-level) failure.

    from paddle_tpu.serving import InferenceEngine, SamplingParams

    eng = InferenceEngine(model, num_slots=8, max_length=256)
    h = eng.submit(prompt_ids, SamplingParams(max_new_tokens=32))
    for tok in h.stream():
        ...                       # per-token, as slots advance
    hs = eng.generate_many(prompts)   # continuous-batched batch API

Latency stack (ISSUE 9), all composable and parity-preserving: a radix
prefix cache over the slot pool (`prefix_cache.py` — shared prompt
prefixes prefill once), chunked prefill (`prefill_chunk_tokens=` —
long prompts interleave with decode rounds instead of stalling TTFT),
and per-slot speculative decoding (`draft_model=` — k draft proposals
verified in one target forward, exactly greedy for any draft):

    eng = InferenceEngine(model, num_slots=16, max_length=256,
                          prefix_cache=0.25, prefill_chunk_tokens=32,
                          draft_model=draft)

Paged KV (ISSUE 16): `kv_page_size=` switches the pool to the
page-table layout (`kv_pool.PagedSlotPool`) — fixed-size pages with
per-slot page tables, reservation-based admission, copy-on-write
page sharing through the prefix cache (`PagedPrefixCache`), optional
`kv_quant='int8'` with per-(page, head) scales, and `kv_pages=` to
oversubscribe HBM so short requests admit at page (not slot-row)
granularity. Greedy outputs stay bit-identical to the row pool:

    eng = InferenceEngine(model, num_slots=32, max_length=256,
                          kv_page_size=16, kv_pages=257,
                          prefix_cache=0.25)

Fleet layer (`router.py` + `tenancy.py`): a `Router` over a
`ReplicaSet` of N engines adds health-checked least-loaded placement,
mid-flight failover with per-replica circuit breakers, and per-tenant
QoS (token-bucket rates, concurrency caps, priority classes, typed
fast-fail load shedding):

    from paddle_tpu.serving import ReplicaSet, Router
    router = Router(ReplicaSet(model, 2, num_slots=8, max_length=256),
                    tenants='paid:priority=high;free:priority=low,rate=2',
                    shed_queue_depth=64)
    h = router.submit(prompt_ids, tenant='paid')

Online weight updates (`hotswap.py`, ISSUE 12): a trainer-side
`WeightPublisher` streams versioned, sha256-manifested snapshots into a
`WeightStore`; a `ReplicaUpdater` rolls them across the router's
replicas one at a time (drain → swap → health-gate → rejoin) with zero
dropped requests, zero XLA recompiles, version-tagged responses, and
automatic rollback + quarantine on a failed gate:

    from paddle_tpu.serving import (WeightStore, WeightPublisher,
                                    ReplicaUpdater)
    store = WeightStore('/ckpt/weights')
    publisher = WeightPublisher(train_model, store, interval_steps=50)
    updater = ReplicaUpdater(router, store)
    ...                      # trainer: publisher.maybe_publish(step)
    updater.poll()           # server: swap when a new version lands

Goodput-driven autoscaling (`autoscaler.py`, ISSUE 14): an
`Autoscaler` grows/shrinks the fleet from the router's sliding-window
signals (TTFT p99 vs SLO, queued work per replica, capacity-shed rate)
with hysteresis and cooldown so it never flaps; scale-up provisions
through the shared ProgramStore (the new replica loads, not compiles)
and accounts for the measured provision latency, scale-down reuses the
graceful-drain path so no request drops. `paddle_tpu.loadgen` builds
the deterministic Poisson/diurnal/burst traffic to drive it — the full
loop in ten lines:

    from paddle_tpu import loadgen
    from paddle_tpu.serving import (Autoscaler, AutoscalerConfig,
                                    InferenceEngine, ReplicaSet, Router)
    eng_kw = dict(num_slots=8, max_length=256)
    router = Router(ReplicaSet(model, 1, **eng_kw), shed_queue_depth=64)
    scaler = Autoscaler(router, lambda: InferenceEngine(model, **eng_kw),
                        AutoscalerConfig(max_replicas=4, slo_ttft_s=0.5))
    trace = loadgen.make_trace(
        loadgen.DiurnalSchedule(2.0, 20.0, period_s=120.0), 120.0,
        seed=7, prompt_lengths=loadgen.LognormalLengths(12, 0.6, 4, 64))
    print(loadgen.LoadReplayer(router, trace, autoscaler=scaler)
          .run().report(slo_ttft_s=0.5))

Process fleet runtime (`remote.py` / `replica_main.py` /
`supervisor.py`, ISSUE 18): replicas become supervised OS processes.
A `Supervisor` spawns `python -m paddle_tpu.serving.replica_main`
children that warm-start from the shared ProgramStore (load, never
compile) and pull weights from the `WeightStore`; the parent talks to
each over a checksummed framed RPC socket through a `RemoteReplica` —
the same duck-type surface as an in-process engine, so Router
placement, QoS, breakers, failover, hot-swap rollouts, and the
Autoscaler work unchanged across the process boundary. SIGKILL a
replica mid-decode and the router fails its accepted requests over to
survivors bit-exactly while the supervisor respawns the victim
(backoff + jitter, crash-loop quarantine, hang detection, orphan
reaping):

    from paddle_tpu.serving import (ReplicaSpec, Router, Replica,
                                    Supervisor)
    spec = ReplicaSpec('my_models:tiny_gpt',
                       engine_kwargs=dict(num_slots=8, max_length=256),
                       program_store_dir='/store/programs',
                       weight_store_dir='/store/weights')
    sup = Supervisor('/run/fleet', spec)
    router = Router([Replica(i, sup.spawn()) for i in range(2)])
    scaler = Autoscaler(router, sup.replica_factory(), config)

Multi-tenant adapter serving (`adapters/`, ISSUE 19): an
`AdapterBank` packs up to `capacity` LoRA adapters as device-resident
`[capacity+1, ...]` A/B factor banks per target projection (slot 0 =
the base model's zero delta). Per-slot adapter indices flow through
decode/prefill/spec programs as ARRAY inputs — one compiled decode
block serves any adapter mix, with zero recompiles across mixes and
hot-swaps. Requests pin their adapter version at admission (publish
never disturbs a pinned slot; LRU eviction only claims zero-ref
slots), the radix prefix cache namespaces on (adapter_id, version),
and tenants may carry a default `adapter=` in their spec; a missing
adapter fast-fails typed as
`AdmissionRejected(reason='adapter_unavailable')`:

    from paddle_tpu.serving import AdapterBank, InferenceEngine
    bank = AdapterBank(model, capacity=8, rank=8)
    eng = InferenceEngine(model, num_slots=8, max_length=256,
                          adapter_bank=bank)
    bank.load('tenant-a', factors_a)       # or publish()/store-backed
    h = eng.submit(prompt_ids, adapter_id='tenant-a')

Flags: `FLAGS_autoscale` (gate the poll loop),
`FLAGS_autoscale_min_replicas` / `FLAGS_autoscale_max_replicas`
(fleet bounds), `FLAGS_autoscale_cooldown_s` (decision spacing); all
env-overridable. Every decision emits an `autoscale_*` event, and the
goodput ledger books provisioning/retirement under the `scale_up` /
`scale_down` categories — the bench's proof the machinery costs <3%.
"""
from __future__ import annotations

from .adapters import (AdapterBank, AdapterUnavailable,
                       make_adapter_factors)
from .api import (FAILED, FINISHED, GREEDY, PRIORITY_HIGH, PRIORITY_LOW,
                  PRIORITY_NAMES, PRIORITY_NORMAL, QUEUED, RUNNING,
                  SAMPLING, RequestHandle, SamplingParams)
from .autoscaler import Autoscaler, AutoscalerConfig
from .engine import InferenceEngine, sample_rows
from .hotswap import (CanaryGate, ReplicaUpdater, SwapFailed,
                      WeightLoadError, WeightPublisher, WeightStore,
                      finite_weights_gate)
from .kv_pool import (PageHold, PagePoolExhausted, PagedSlotPool,
                      PoolLostError, PromptTooLongError, SlotPool,
                      default_buckets)
from .prefix_cache import PagedPrefixCache, RadixPrefixCache
from .remote import (FrameChecksumError, IncompleteFrameError,
                     RemoteFatalError, RemoteReplica, RemoteTransientError,
                     RpcClient)
from .router import (CircuitBreaker, Replica, ReplicaFailure, ReplicaSet,
                     Router, RouterHandle)
from .scheduler import FCFSScheduler
from .supervisor import ReplicaSpec, Supervisor
from .tenancy import (AdmissionRejected, Tenant, TenantRegistry,
                      TokenBucket, estimate_queue_rounds,
                      parse_tenant_spec, prefill_rounds)

__all__ = [
    'FAILED', 'FINISHED', 'GREEDY', 'QUEUED', 'RUNNING', 'SAMPLING',
    'PRIORITY_HIGH', 'PRIORITY_NORMAL', 'PRIORITY_LOW', 'PRIORITY_NAMES',
    'RequestHandle', 'SamplingParams', 'InferenceEngine', 'sample_rows',
    'SlotPool', 'default_buckets', 'FCFSScheduler', 'RadixPrefixCache',
    'PagedSlotPool', 'PagedPrefixCache', 'PageHold',
    'PagePoolExhausted', 'PoolLostError', 'PromptTooLongError',
    'CircuitBreaker', 'Replica', 'ReplicaFailure', 'ReplicaSet',
    'Router', 'RouterHandle',
    'AdmissionRejected', 'Tenant', 'TenantRegistry', 'TokenBucket',
    'parse_tenant_spec', 'prefill_rounds', 'estimate_queue_rounds',
    'CanaryGate', 'ReplicaUpdater', 'SwapFailed', 'WeightLoadError',
    'WeightPublisher', 'WeightStore', 'finite_weights_gate',
    'Autoscaler', 'AutoscalerConfig',
    'RemoteReplica', 'RpcClient', 'IncompleteFrameError',
    'FrameChecksumError', 'RemoteTransientError', 'RemoteFatalError',
    'ReplicaSpec', 'Supervisor',
    'AdapterBank', 'AdapterUnavailable', 'make_adapter_factors',
]
