"""Failure detection (upstream: paddle.amp.debugging / check_nan_inf,
python/paddle/amp/debugging.py + the fleet loss-spike monitor).

- `check_numerics(x, name)` — raises on NaN/Inf in eager mode; under
  jit it routes through `jax.debug` safe-guarding via checkify-style
  host callback only when enabled (zero overhead when off).
- `enable_check_numerics()` — installs a tape-level hook: every op
  recorded by apply_op is scanned for non-finite outputs (eager only,
  the DyGraph debugging workflow).
- `LossSpikeDetector` — windowed z-score monitor used by hapi/fleet to
  flag divergence (upstream: loss scaling skip-counters + spike logs).
- dispatch telemetry — `dispatch_stats()` / `dispatch_summary()` read the
  eager dispatch cache's hit/miss/retrace/fallback counters
  (paddle_tpu._dispatch); `enable_dispatch_cache(False)` forces every op
  back onto the uncached slow path (A/B debugging, parity checks).
- `observability_summary()` — the one-call report over the shared
  observability registry: dispatch hit-rate, jit compile count/seconds,
  per-axis collective calls + bytes, offload transfer bytes, step/token
  throughput, memory watermark, and host-span timings.
"""
from __future__ import annotations

import collections
import math
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import _dispatch
from . import flags as _flags
from . import observability as _obs
from .tensor import Tensor


class NumericsError(RuntimeError):
    pass


def check_numerics(x, name: str = 'tensor', raise_on_error: bool = True):
    """Assert a tensor is finite. Eager: host check with a precise count.
    Traced: uses jax.debug.callback so the check travels into the XLA
    program (no effect on the computed value)."""
    val = x.value if isinstance(x, Tensor) else jnp.asarray(x)
    if not jnp.issubdtype(val.dtype, jnp.floating):
        return x

    if isinstance(val, jax.core.Tracer):
        def cb(n_nan, n_inf):
            if int(n_nan) or int(n_inf):
                msg = (f'check_numerics({name}): {int(n_nan)} NaN, '
                       f'{int(n_inf)} Inf values')
                if raise_on_error:
                    raise NumericsError(msg)
                print(msg)
        f32 = val.astype(jnp.float32)
        jax.debug.callback(cb, jnp.isnan(f32).sum(),
                           jnp.isinf(f32).sum())
        return x

    f32 = np.asarray(val, np.float32)
    n_nan = int(np.isnan(f32).sum())
    n_inf = int(np.isinf(f32).sum())
    if n_nan or n_inf:
        msg = (f'check_numerics({name}): {n_nan} NaN, {n_inf} Inf of '
               f'{f32.size} values, shape {tuple(f32.shape)}')
        if raise_on_error:
            raise NumericsError(msg)
        print(msg)
    return x


# ---------------------------------------------------------------------------
# tape-level monitor (FLAGS_check_nan_inf)
# ---------------------------------------------------------------------------

def _scan_outputs(out, op_name):
    def scan(t):
        if isinstance(t, Tensor) and not isinstance(
                t.value, jax.core.Tracer):
            check_numerics(t, name=op_name or 'op')
        return t
    jax.tree_util.tree_map(scan, out,
                           is_leaf=lambda v: isinstance(v, Tensor))


def enable_check_numerics(level: int = 0):
    """Scan every eager op output for NaN/Inf via the apply_op hook
    (upstream: FLAGS_check_nan_inf=1). Heavy — debugging only."""
    from . import tensor as tmod
    _flags.set_flags({'FLAGS_check_nan_inf': True,
                      'FLAGS_check_nan_inf_level': level})
    tmod._numerics_hook = _scan_outputs


def disable_check_numerics():
    from . import tensor as tmod
    _flags.set_flags({'FLAGS_check_nan_inf': False})
    tmod._numerics_hook = None


# ---------------------------------------------------------------------------
# eager dispatch cache telemetry (paddle_tpu._dispatch)
# ---------------------------------------------------------------------------

def dispatch_stats() -> dict:
    """Counters for the eager dispatch fast path: hits (op served from a
    cached executable), misses (a trace/compile happened), retraces
    (misses whose op signature had already been compiled — shape/static
    churn), fallbacks (unkeyable calls on the slow path), plus
    hit_rate/cache_size and a per-op breakdown. Steady-state eager
    training should show zero retraces after warmup."""
    return _dispatch.stats()


def reset_dispatch_stats():
    _dispatch.reset_stats()


def clear_dispatch_cache():
    """Drop every cached executable (counters survive; pair with
    reset_dispatch_stats() for a clean measurement window)."""
    _dispatch.clear()


def enable_dispatch_cache(enable: bool = True):
    """Toggle the eager dispatch cache (FLAGS_eager_dispatch_cache).
    Disabling routes every apply_op through the per-call jax.vjp slow
    path — the pre-cache behavior — for A/B parity or debugging."""
    _dispatch.enable(enable)


def disable_dispatch_cache():
    _dispatch.enable(False)


def dispatch_summary(max_rows: int = 15) -> str:
    """Human-readable dispatch-cache report (global counters + the
    hottest ops by call count)."""
    s = _dispatch.stats()
    lines = [
        'eager dispatch cache: '
        f'{"enabled" if s["enabled"] else "DISABLED"}',
        f'  calls {s["calls"]}  hits {s["hits"]}  misses {s["misses"]}'
        f'  retraces {s["retraces"]}  fallbacks {s["fallbacks"]}'
        f'  hit_rate {s["hit_rate"]:.1%}',
        f'  cache_size {s["cache_size"]}  evictions {s["evictions"]}'
        f'  errors {s["errors"]}',
    ]
    per = sorted(s['per_op'].items(),
                 key=lambda kv: -(kv[1]['hits'] + kv[1]['misses']
                                  + kv[1]['fallbacks']))
    if per:
        lines.append(f'  {"op":<28}{"hits":>8}{"misses":>8}{"fallbacks":>10}')
        for name, row in per[:max_rows]:
            lines.append(f'  {name or "<unnamed>":<28}{row["hits"]:>8}'
                         f'{row["misses"]:>8}{row["fallbacks"]:>10}')
    return '\n'.join(lines)


def _observability_data(max_rows: int = 10) -> dict:
    """The machine-readable structure behind observability_summary():
    one JSON-able dict per section, read off the same registry snapshot
    the text report formats."""
    reg = _obs.get_registry()
    snap = reg.snapshot()   # runs collectors (dispatch mirror) first
    ds = _dispatch.stats()
    comm = _obs.collective_totals(reg)
    spans = reg.get('paddle_span_seconds')
    span_rows = []
    if spans is not None:
        for key, child in sorted(spans.children(),
                                 key=lambda kv: -kv[1].sum)[:max_rows]:
            span_rows.append({
                'name': key[0], 'calls': child.count,
                'total_s': child.sum,
                'avg_ms': (child.sum / child.count * 1e3
                           if child.count else 0.0)})
    log = _obs.get_event_log()
    return {
        'process_index': snap['process_index'],
        'dispatch': {
            'calls': ds['calls'], 'hit_rate': ds['hit_rate'],
            'misses': ds['misses'], 'retraces': ds['retraces'],
            'fallbacks': ds['fallbacks'], 'cache_size': ds['cache_size']},
        'jit': {
            'compiles': int(reg.value('paddle_jit_compiles_total')),
            'compile_seconds': reg.value(
                'paddle_jit_compile_seconds_total'),
            'cache_entries': _jit_cache_entries(reg)},
        'collectives': {
            'calls': int(comm['calls']), 'bytes': int(comm['bytes']),
            'per_op': [{'op': op, 'axis': axis,
                        'calls': int(row['calls']),
                        'bytes': int(row['bytes'])}
                       for (op, axis), row
                       in sorted(comm['per_op'].items())[:max_rows]]},
        'offload': {
            'h2d_bytes': int(reg.value('paddle_offload_h2d_bytes_total')),
            'd2h_bytes': int(reg.value('paddle_offload_d2h_bytes_total'))},
        'steps': {
            'total': int(reg.value('paddle_steps_total')),
            'steps_per_sec': reg.value('paddle_steps_per_sec'),
            'tokens_per_sec': reg.value('paddle_tokens_per_sec'),
            'loss_last': reg.value('paddle_loss_last'),
            # trailing-window step-time percentiles off the train.step
            # span histogram (the windowed quantile sketch — no
            # Prometheus-side bucket math)
            'step_time_quantiles_ms': _span_quantiles_ms(
                reg, 'train.step') or _span_quantiles_ms(
                    reg, 'fleet.dist_train_step')
            or _span_quantiles_ms(reg, 'step.compute')},
        'memory': {
            'watermark_bytes': reg.value('paddle_memory_watermark_bytes')},
        'resilience': {
            'retries': int(_labeled_total(
                reg, 'paddle_resilience_retries_total')),
            'rollbacks': int(reg.value(
                'paddle_resilience_rollbacks_total')),
            'skipped_batches': int(reg.value(
                'paddle_resilience_skipped_batches_total')),
            'preempt_saves': int(reg.value(
                'paddle_resilience_preempt_saves_total')),
            'hangs': int(reg.value('paddle_resilience_hangs_total'))},
        'checkpoints': {
            'saves': int(reg.value('paddle_checkpoint_saves_total')),
            'save_bytes': int(reg.value(
                'paddle_checkpoint_save_bytes_total')),
            'restores': int(reg.value('paddle_checkpoint_restores_total')),
            'restore_bytes': int(reg.value(
                'paddle_checkpoint_restore_bytes_total'))},
        'serving': {
            'submitted': int(reg.value('paddle_serving_requests_total',
                                       status='submitted')),
            'completed': int(reg.value('paddle_serving_requests_total',
                                       status='completed')),
            'failed': int(reg.value('paddle_serving_requests_total',
                                    status='failed')),
            'queue_depth': int(reg.value('paddle_serving_queue_depth')),
            'active_slots': int(reg.value('paddle_serving_active_slots')),
            'slots': int(reg.value('paddle_serving_slots')),
            'tokens': int(reg.value('paddle_serving_tokens_total')),
            'ttft_avg_ms': _hist_avg_ms(reg, 'paddle_serving_ttft_seconds'),
            'tpot_avg_ms': _hist_avg_ms(reg, 'paddle_serving_tpot_seconds'),
            'ttft_quantiles_ms': _hist_quantiles_ms(
                reg, 'paddle_serving_ttft_seconds'),
            'tpot_quantiles_ms': _hist_quantiles_ms(
                reg, 'paddle_serving_tpot_seconds'),
            'prefills': int(_labeled_total(
                reg, 'paddle_serving_prefills_total')),
            'decode_steps': int(reg.value(
                'paddle_serving_decode_steps_total')),
            'prefix': {
                'hits': int(reg.value(
                    'paddle_serving_prefix_hits_total')),
                'misses': int(reg.value(
                    'paddle_serving_prefix_misses_total')),
                'tokens_reused': int(reg.value(
                    'paddle_serving_prefix_tokens_reused_total')),
                'retained_slots': int(reg.value(
                    'paddle_serving_prefix_retained_slots')),
                'evictions': int(reg.value(
                    'paddle_serving_prefix_evictions_total'))},
            'chunk': {
                'rounds': int(reg.value(
                    'paddle_serving_chunk_rounds_total')),
                'tokens': int(reg.value(
                    'paddle_serving_chunk_tokens_total'))},
            'spec': {
                'rounds': int(reg.value(
                    'paddle_serving_spec_rounds_total')),
                'proposed': int(reg.value(
                    'paddle_serving_spec_proposed_total')),
                'accepted': int(reg.value(
                    'paddle_serving_spec_accepted_total'))}},
        'router': _router_data(reg),
        'elastic': _elastic_data(reg),
        'goodput': _obs.get_ledger().report(),
        'roofline': _obs.roofline_summary(max_rows=max_rows),
        'programs': _obs.program_catalog().top_programs(n=max_rows),
        'program_store': _program_store_data(),
        'spans': span_rows,
        'events': {'logged': len(log), 'dropped': log.dropped,
                   'flight_dumps': int(_labeled_total(
                       reg, 'paddle_flight_dumps_total'))},
    }


def _program_store_data() -> dict:
    """Program-store view: tiers, warm/cold posture, cold-start wall
    time (the first-class availability number for restarts)."""
    try:
        from .programs import get_store
        return get_store().stats()
    except Exception:  # paddle-lint: disable=swallowed-exception -- summary section degrades to an explicit empty-store posture dict
        return {'persistent': False, 'dir': None, 'memory_entries': 0,
                'programs': 0, 'loaded_from_disk': 0, 'hits_memory': 0,
                'hits_disk': 0, 'misses': 0, 'rejects': 0,
                'persisted': 0, 'persist_skips': 0, 'invalidated': 0,
                'preload': None, 'coldstart_seconds': None,
                'disk_entries': 0}


def _router_data(reg) -> dict:
    """Serving-router view: fleet counters + per-replica breaker state,
    load, and active degraded states (the /summary per-replica health)."""
    breaker_names = {0: 'closed', 1: 'half_open', 2: 'open'}
    per_replica = []
    fam = reg.get('paddle_router_breaker_state')
    out_fam = reg.get('paddle_router_outstanding_tokens')
    if fam is not None:
        for (rid,), child in sorted(fam.children()):
            outstanding = 0
            if out_fam is not None:
                oc = out_fam._children.get((rid,))
                outstanding = int(oc.value) if oc is not None else 0
            per_replica.append({
                'replica': rid,
                'breaker': breaker_names.get(int(child.value),
                                             str(child.value)),
                'outstanding_tokens': outstanding,
                'health_states': sorted(
                    _obs.degraded_states(scope=f'replica:{rid}')),
            })
    outcomes: dict = {}
    req_fam = reg.get('paddle_router_requests_total')
    if req_fam is not None:
        for (tenant, outcome), child in req_fam.children():
            outcomes[outcome] = outcomes.get(outcome, 0) + int(child.value)
    return {
        'replicas': int(reg.value('paddle_router_replicas')),
        'available': int(reg.value('paddle_router_available_replicas')),
        'queue_depth': int(reg.value('paddle_router_queue_depth')),
        'failovers': int(_labeled_total(
            reg, 'paddle_router_failovers_total')),
        'shed': int(_labeled_total(reg, 'paddle_router_shed_total')),
        'outcomes': outcomes,
        'per_replica': per_replica,
    }


def _elastic_data(reg) -> dict:
    """Elastic-training view: current mesh devices + the resize history
    every shrink/grow transition appends (fleet.rebuild_mesh)."""
    try:
        from .distributed import env, fleet
        history = fleet.resize_history()
        devices = int(env.get_mesh(auto_init=False).size) \
            if env.has_mesh() else 0
    except Exception:  # paddle-lint: disable=swallowed-exception -- summary section degrades to devices=0; report must render without a mesh
        history, devices = [], 0
    return {'devices': devices, 'resizes': len(history),
            'history': history}


def observability_summary(max_rows: int = 10, as_dict: bool = False):
    """One report over the single shared observability registry: where
    this process's time, bytes, and compiles went (upstream: stitched
    together by hand from paddle.profiler output + fleet worker logs).

    Sections always print (zeros included) so tooling can grep fields:
    dispatch hit-rate, jit compile count + seconds, per-(op, axis)
    collective calls/bytes, offload H2D/D2H transfer bytes, step/token
    throughput + last loss, device-memory watermark, serving engine
    traffic (requests/queue/slots/TTFT/TPOT), per-program XLA cost
    attribution (ProgramCatalog), and the hottest host spans.

    `as_dict=True` returns the machine-readable structure backing the
    text (the /summary?format=json payload); both views are rendered
    from the SAME snapshot so their headline counters always agree.
    """
    d = _observability_data(max_rows)
    if as_dict:
        return d
    ds, jit = d['dispatch'], d['jit']
    lines = [f'observability summary (process {d["process_index"]})',
             f'  dispatch: {ds["calls"]} calls  '
             f'hit_rate {ds["hit_rate"]:.1%}  ({ds["misses"]} misses, '
             f'{ds["retraces"]} retraces, {ds["fallbacks"]} fallbacks, '
             f'cache_size {ds["cache_size"]})',
             f'  jit: {jit["compiles"]} compiles  '
             f'{jit["compile_seconds"]:.3f} s '
             f'compile time  cache entries: {jit["cache_entries"]}']
    comm = d['collectives']
    lines.append(f'  collectives: {comm["calls"]} calls  '
                 f'{comm["bytes"]} bytes')
    for row in comm['per_op']:
        lines.append(f'    {row["op"]:<16} axis={row["axis"]:<6} '
                     f'{row["calls"]:>6} calls {row["bytes"]:>12} '
                     f'bytes')
    lines.append(
        f'  offload: {d["offload"]["h2d_bytes"]} H2D bytes  '
        f'{d["offload"]["d2h_bytes"]} D2H bytes')
    st = d['steps']
    lines.append(
        f'  steps: {st["total"]} total  '
        f'{st["steps_per_sec"]:.2f} steps/s  '
        f'{st["tokens_per_sec"]:.1f} tokens/s  '
        f'loss {st["loss_last"]:.4f}')
    if st['step_time_quantiles_ms']:
        qs = st['step_time_quantiles_ms']
        lines.append('    step time ' + '  '.join(
            f'p{float(q) * 100:g} {v:.2f} ms' for q, v in sorted(
                qs.items(), key=lambda kv: float(kv[0]))))
    lines.append(
        f'  memory: watermark '
        f'{d["memory"]["watermark_bytes"] / 2**20:.1f} MiB')
    rs = d['resilience']
    lines.append(
        f'  resilience: {rs["retries"]} retries  '
        f'{rs["rollbacks"]} rollbacks  '
        f'{rs["skipped_batches"]} skipped batches  '
        f'{rs["preempt_saves"]} preempt saves  '
        f'{rs["hangs"]} hangs')
    ck = d['checkpoints']
    lines.append(
        f'  checkpoints: {ck["saves"]} saves ({ck["save_bytes"]} bytes)  '
        f'{ck["restores"]} restores ({ck["restore_bytes"]} bytes)')
    sv = d['serving']
    lines.append(
        f'  serving: {sv["submitted"]} requests '
        f'({sv["completed"]} done, {sv["failed"]} failed)  '
        f'queue {sv["queue_depth"]}  '
        f'slots {sv["active_slots"]}/{sv["slots"]}  '
        f'{sv["tokens"]} tokens')
    lines.append(
        f'    ttft avg {sv["ttft_avg_ms"]:.2f} ms  '
        f'tpot avg {sv["tpot_avg_ms"]:.2f} ms  '
        f'{sv["prefills"]} prefills  '
        f'{sv["decode_steps"]} decode steps')
    if sv['ttft_quantiles_ms']:
        ttft_q = '  '.join(f'p{float(q) * 100:g} {v:.2f}'
                           for q, v in sorted(
                               sv['ttft_quantiles_ms'].items(),
                               key=lambda kv: float(kv[0])))
        tpot_q = '  '.join(f'p{float(q) * 100:g} {v:.2f}'
                           for q, v in sorted(
                               sv['tpot_quantiles_ms'].items(),
                               key=lambda kv: float(kv[0])))
        lines.append(f'    ttft ms: {ttft_q}'
                     + (f'  |  tpot ms: {tpot_q}' if tpot_q else ''))
    px, chk, spc = sv['prefix'], sv['chunk'], sv['spec']
    hit_rate = (px['hits'] / (px['hits'] + px['misses'])
                if px['hits'] + px['misses'] else 0.0)
    lines.append(
        f'    prefix cache: {px["hits"]} hits / {px["misses"]} misses '
        f'({hit_rate:.1%})  {px["tokens_reused"]} tokens reused  '
        f'{px["retained_slots"]} retained  {px["evictions"]} evicted')
    spec_rate = (spc['accepted'] / spc['proposed']
                 if spc['proposed'] else 0.0)
    lines.append(
        f'    chunked prefill: {chk["rounds"]} rounds '
        f'{chk["tokens"]} tokens  |  speculation: {spc["rounds"]} '
        f'rounds  accept {spc["accepted"]}/{spc["proposed"]} '
        f'({spec_rate:.1%})')
    rt = d['router']
    lines.append(
        f'  router: {rt["replicas"]} replicas '
        f'({rt["available"]} available)  queue {rt["queue_depth"]}  '
        f'{rt["failovers"]} failovers  {rt["shed"]} shed')
    for row in rt['per_replica']:
        states = ','.join(row['health_states']) or 'healthy'
        lines.append(
            f'    replica {row["replica"]}: breaker {row["breaker"]}  '
            f'{states}  outstanding {row["outstanding_tokens"]} tokens')
    el = d['elastic']
    lines.append(f'  elastic: {el["devices"]} devices  '
                 f'{el["resizes"]} resizes')
    for h in el['history'][-max_rows:]:
        lines.append(
            f'    {h["kind"]:<7} {h["from_devices"]}->{h["to_devices"]} '
            f'devices  mesh {h["to"]}  ({h["reason"]})')
    gp = d['goodput']
    lines.append(
        f'  goodput: {gp["wall_seconds"]:.1f} s wall  '
        f'{gp["attributed_seconds"]:.1f} s attributed  '
        f'residual {gp["fractions"]["residual"]:.1%}'
        + (f'  (+{gp["overcount_seconds"]:.1f} s concurrent overcount)'
           if gp['overcount_seconds'] > 0 else ''))
    for cat, secs in gp['categories'].items():
        if secs > 0:
            lines.append(f'    {cat:<20}{secs:>10.3f} s '
                         f'{gp["fractions"][cat]:>7.1%}')
    rf = d['roofline']
    if rf['mfu'] is not None:
        lines.append(
            f'  roofline: MFU {rf["mfu"]:.3f} on {rf["device_kind"]} '
            f'(peak {rf["peak_flops"] / 1e12:.0f} TFLOP/s, '
            f'{rf["source"]})  '
            f'{rf["bound_counts"]["compute"]} compute-bound / '
            f'{rf["bound_counts"]["bandwidth"]} bandwidth-bound '
            f'programs')
        for row in rf['programs']:
            bound = row['bound'] or '?'
            lines.append(f'    {row["name"][:31]:<32} mfu '
                         f'{row["mfu"]:.3f}  {bound}-bound  '
                         f'{row["host_seconds"]:.3f} s')
    else:
        lines.append(
            f'  roofline: MFU unknown (device {rf["device_kind"]!r} '
            f'not in the peak table; set PADDLE_PEAK_FLOPS / '
            f'PADDLE_PEAK_HBM_GBPS)')
    ps = d['program_store']
    tier = (f'persistent @ {ps["dir"]}' if ps['persistent']
            else 'memory-only')
    lines.append(
        f'  program store: {tier}  {ps["memory_entries"]} resident '
        f'({ps["loaded_from_disk"]} warm-loaded)  '
        f'hits {ps["hits_memory"]}m/{ps["hits_disk"]}d  '
        f'misses {ps["misses"]}  rejects {ps["rejects"]}')
    if ps.get('coldstart_seconds') is not None:
        pl = ps.get('preload') or {}
        lines.append(
            f'    cold start: warm at {ps["coldstart_seconds"]:.3f}s '
            f'(preload {pl.get("loaded", 0)} programs in '
            f'{pl.get("seconds", 0.0):.3f}s, '
            f'{pl.get("rejected", 0)} rejected)')
    lines.append(f'  programs: {len(d["programs"])} tracked '
                 f'(top by host time)')
    for p in d['programs']:
        lines.append(
            f'    {p["name"][:31]:<32} {p["invocations"]:>6} calls '
            f'{p["host_seconds"]:>9.3f} s  '
            f'{p["flops"] / 1e9:>9.3f} GFLOP  '
            f'{p["bytes_accessed"] / 1e9:>8.3f} GB  '
            f'peak {p["peak_memory_bytes"] / 2**20:>8.1f} MiB')
    lines.append(f'  host spans: {len(d["spans"])} region(s), '
                 f'event log {d["events"]["logged"]} events '
                 f'({d["events"]["dropped"]} dropped, '
                 f'{d["events"]["flight_dumps"]} flight dumps)')
    for row in d['spans']:
        lines.append(f'    {row["name"]:<32} {row["calls"]:>6} calls '
                     f'{row["total_s"]:>10.4f} s  avg '
                     f'{row["avg_ms"]:>8.2f} ms')
    return '\n'.join(lines)


def _jit_cache_entries(reg) -> int:
    fam = reg.get('paddle_jit_cache_entries')
    if fam is None:
        return 0
    return int(fam.total())


def _labeled_total(reg, name: str) -> float:
    """Sum a labeled counter family across all label values."""
    fam = reg.get(name)
    if fam is None:
        return 0.0
    return fam.total()


def _hist_avg_ms(reg, name: str) -> float:
    """Mean of an unlabeled histogram family, in milliseconds."""
    fam = reg.get(name)
    if fam is None:
        return 0.0
    child = fam._children.get(())
    if child is None or not child.count:
        return 0.0
    return child.sum / child.count * 1e3


def _hist_quantiles_ms(reg, name: str) -> dict:
    """Windowed p50/p95/p99 of an unlabeled histogram, in ms."""
    fam = reg.get(name)
    if fam is None:
        return {}
    child = fam._children.get(())
    if child is None:
        return {}
    return {q: v * 1e3 for q, v in child.window_quantiles().items()}


def _span_quantiles_ms(reg, span_name: str) -> dict:
    """Windowed quantiles of one `paddle_span_seconds{name=}` child."""
    fam = reg.get('paddle_span_seconds')
    if fam is None:
        return {}
    child = fam._children.get((span_name,))
    if child is None:
        return {}
    return {q: v * 1e3 for q, v in child.window_quantiles().items()}


class LossSpikeDetector:
    """Windowed spike detector: flags a step whose loss exceeds
    mean + k*std of the trailing window, or is non-finite.

    Flagged values are EXCLUDED from the trailing window — a spike (or a
    level shift that registers as one) must not inflate its own baseline
    mean/std, which would mask every subsequent spike. Each flagged step
    also emits a `loss_spike` event into the observability EventLog."""

    def __init__(self, window: int = 20, threshold_sigma: float = 6.0,
                 min_steps: int = 5):
        self.window: Deque[float] = collections.deque(maxlen=window)
        self.k = threshold_sigma
        self.min_steps = min_steps
        self.spikes: List[int] = []
        self._step = 0

    def _note_spike(self, value: float):
        self.spikes.append(self._step)
        _obs.emit('loss_spike', step=self._step, loss=value,
                  window=len(self.window))

    def update(self, loss: float) -> bool:
        """Returns True if this step is a spike."""
        v = float(loss)
        self._step += 1
        if not math.isfinite(v):
            self._note_spike(v)
            return True
        spiked = False
        if len(self.window) >= self.min_steps:
            mean = sum(self.window) / len(self.window)
            var = sum((x - mean) ** 2 for x in self.window) \
                / len(self.window)
            std = math.sqrt(var)
            if v > mean + self.k * max(std, 1e-12):
                spiked = True
                self._note_spike(v)
        if not spiked:
            self.window.append(v)
        return spiked
