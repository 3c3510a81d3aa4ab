"""Per-program XLA cost attribution: the ProgramCatalog.

Every compiled executable this framework creates — the jitted train
step, to_static programs, the serving engine's decode block and
per-bucket prefills, the eager dispatch cache's per-op entries — already
carries free introspection data XLA computes at compile time
(`compiled.cost_analysis()` FLOPs / bytes accessed,
`compiled.memory_analysis()` peak HBM) that we previously threw away.
The catalog records it per *named program* together with compile time,
cumulative invocation count, and host wall time, so
`top_programs()` answers "which programs is this step/decode round
actually spending its time and FLOPs in" — train step vs. decode block
vs. prefill buckets — without a profiler attached.

Zero extra compiles by construction: the program store
(the `programs` package) owns compilation — `ProgramStore.wrap_jit`
compiles a jitted callable ONCE per input signature through the AOT
path and then invokes the captured `Compiled` object directly, so the
cost/memory analyses are read off the very executable that serves the
traffic (guarded by the serving zero-recompile tests over
`paddle_jit_compiles_total`). THIS catalog is the bookkeeping the store
writes into: every program is tracked exactly once (tier-1
catalog==store guard), and nothing here imports the store.

Hot paths never pay: the eager dispatch cache reports only from its
cold miss path (`note_dispatch_compile`) and its per-op invocation
counts are mirrored at scrape time by a registry collector, exactly
like the `paddle_dispatch_*` metrics.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Tuple

from . import metrics as _metrics
from ..analysis.runtime import concurrency as _concurrency

# ---------------------------------------------------------------------------
# roofline peaks: per-device-kind peak bf16 FLOP/s + HBM bandwidth.
# Public TPU spec-sheet numbers (same table bench.py's MFU headline
# uses); keyed by substring of jax's `device_kind`. Override per
# deployment with PADDLE_PEAK_FLOPS (FLOP/s) / PADDLE_PEAK_HBM_GBPS
# (GB/s) — the only honest path on CPU or unlisted hardware, where the
# fallback is an explicit 'unknown' (no MFU published) rather than a
# silently-wrong guess.
# ---------------------------------------------------------------------------
PEAK_SPECS: Dict[str, Dict[str, float]] = {
    'v6 lite': {'flops': 918e12, 'hbm_gbps': 1640.0},
    'v6e': {'flops': 918e12, 'hbm_gbps': 1640.0},
    'v5 lite': {'flops': 197e12, 'hbm_gbps': 819.0},
    'v5e': {'flops': 197e12, 'hbm_gbps': 819.0},
    'v5p': {'flops': 459e12, 'hbm_gbps': 2765.0},
    'v5': {'flops': 459e12, 'hbm_gbps': 2765.0},
    'v4': {'flops': 275e12, 'hbm_gbps': 1228.0},
    'v3': {'flops': 123e12, 'hbm_gbps': 900.0},
    'v2': {'flops': 45e12, 'hbm_gbps': 700.0},
}


def device_peaks(device=None) -> Dict[str, Any]:
    """Resolve the roofline peaks for `device` (default: devices()[0]).

    Returns {'device_kind', 'peak_flops', 'peak_hbm_bytes_per_s',
    'source'} where source is 'env' (operator override), 'table'
    (PEAK_SPECS match), or 'unknown' (peaks are None — MFU/roofline
    gauges are NOT published rather than normalized against a guess)."""
    kind = ''
    if device is None:
        try:
            import jax
            device = jax.devices()[0]
        except Exception:  # paddle-lint: disable=swallowed-exception -- device probe; kind stays unknown and no MFU gauge is published against a guess
            device = None
    if device is not None:
        kind = str(getattr(device, 'device_kind', '') or '')
    env_flops = os.environ.get('PADDLE_PEAK_FLOPS')
    env_bw = os.environ.get('PADDLE_PEAK_HBM_GBPS')
    if env_flops:
        try:
            return {'device_kind': kind or 'env-override',
                    'peak_flops': float(env_flops),
                    'peak_hbm_bytes_per_s': (float(env_bw) * 1e9
                                             if env_bw else None),
                    'source': 'env'}
        except ValueError:
            pass   # malformed override falls through to the table
    low = kind.lower()
    for key, spec in PEAK_SPECS.items():
        if key in low:
            return {'device_kind': kind, 'peak_flops': spec['flops'],
                    'peak_hbm_bytes_per_s': spec['hbm_gbps'] * 1e9,
                    'source': 'table'}
    return {'device_kind': kind or 'unknown', 'peak_flops': None,
            'peak_hbm_bytes_per_s': None, 'source': 'unknown'}


def _ledger_window() -> 'Tuple[Optional[float], Dict[str, int]]':
    """The goodput ledger's measurement window: (wall seconds since the
    ledger's last reset, per-program invocation baseline captured at
    that reset). MFU is FLOPs-over-WALL — per-call host timing cannot
    see device time under async dispatch (a call returns in
    microseconds while the chip works for milliseconds), so the only
    honest denominator is the wall clock of a window whose invocation
    counts we also know."""
    try:
        from .goodput import get_ledger
        return get_ledger().mfu_window()
    except Exception:  # paddle-lint: disable=swallowed-exception -- ledger optional; (None, {}) window disables MFU rather than faking it
        return None, {}


def record_roofline(rec: 'ProgramRecord',
                    peaks: Optional[Dict[str, Any]] = None,
                    wall_seconds: Optional[float] = None,
                    baseline: Optional[Dict[str, int]] = None
                    ) -> Dict[str, Any]:
    """MFU contribution + roofline classification for one program.

    mfu = (per-invocation cost_analysis FLOPs x invocations in the
    window) / (window WALL seconds) / peak FLOP/s — the program's
    contribution to machine utilization, PaLM-style: per-program MFUs
    sum to the aggregate, and every overhead second (compile,
    checkpoint, backoff — the goodput ledger's categories) shows up as
    MFU lost, not hidden. Roofline bound compares the program's
    arithmetic intensity (FLOPs / bytes accessed) with the machine
    balance (peak FLOPs / peak bandwidth): below the ridge the program
    cannot be compute-bound no matter how good the kernels are. Fields
    are None when the record has no analysis or the device peaks are
    unknown. The window defaults to the goodput ledger's (wall since
    its last reset; invocation baseline captured there)."""
    peaks = peaks or device_peaks()
    if wall_seconds is None:
        wall_seconds, baseline = _ledger_window()
    baseline = baseline or {}
    out = {'mfu': None, 'roofline_bound': None,
           'arithmetic_intensity': None}
    if rec.flops > 0 and rec.bytes_accessed > 0:
        out['arithmetic_intensity'] = rec.flops / rec.bytes_accessed
    pf, pb = peaks['peak_flops'], peaks['peak_hbm_bytes_per_s']
    if pf and rec.flops > 0 and wall_seconds and wall_seconds > 0:
        d_inv = rec.invocations - baseline.get(rec.name, 0)
        if d_inv > 0:
            out['mfu'] = rec.flops * d_inv / wall_seconds / pf
    if pf and pb and out['arithmetic_intensity'] is not None:
        balance = pf / pb
        out['roofline_bound'] = ('compute'
                                 if out['arithmetic_intensity'] >= balance
                                 else 'bandwidth')
    return out


def aggregate_mfu(records: List['ProgramRecord'],
                  peaks: Optional[Dict[str, Any]] = None,
                  wall_seconds: Optional[float] = None,
                  baseline: Optional[Dict[str, int]] = None
                  ) -> Dict[str, Any]:
    """Aggregate MFU: total model FLOPs executed in the window / window
    WALL seconds / peak — the number bench.py's headline derives
    analytically, here measured off XLA's own cost_analysis. Programs
    without cost analysis contribute nothing (their time is invisible
    to MFU, which the goodput ledger's residual makes loud instead)."""
    peaks = peaks or device_peaks()
    if wall_seconds is None:
        wall_seconds, baseline = _ledger_window()
    baseline = baseline or {}
    flops = sum(r.flops * max(r.invocations - baseline.get(r.name, 0), 0)
                for r in records if r.flops > 0)
    out = {'flops_total': flops, 'wall_seconds': wall_seconds,
           'mfu': None, 'peaks': peaks}
    if peaks['peak_flops'] and wall_seconds and wall_seconds > 0:
        out['mfu'] = flops / wall_seconds / peaks['peak_flops']
    return out


class MfuWindow:
    """Bounded MFU measurement: wall clock + per-program invocation
    counts snapshot at `__enter__`, deltas at `result()` — the same
    FLOPs-over-wall estimator as `paddle_mfu`, but over exactly the
    code between enter and result (the bench goodput phase runs its
    timed GPT loop inside one and cross-checks the analytic MFU)."""

    def __init__(self, catalog: Optional['ProgramCatalog'] = None,
                 peaks: Optional[Dict[str, Any]] = None):
        # `is None`: an empty ProgramCatalog must not be swapped out
        self._catalog = catalog if catalog is not None else get_catalog()
        self._peaks = peaks or device_peaks()
        self._before: Dict[str, int] = {}
        self._t0 = 0.0

    def __enter__(self) -> 'MfuWindow':
        self._before = {r.name: r.invocations
                        for r in self._catalog.records()}
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        pass

    def result(self) -> Dict[str, Any]:
        wall = time.perf_counter() - self._t0
        return aggregate_mfu(self._catalog.records(), self._peaks,
                             wall_seconds=wall, baseline=self._before)


# what a build of a program cost, by phase (`telemetry.ProgramBuild`):
# the wall of `StoredJit._build`; inside it jax's trace, lowering and
# compile-or-fetch-and-load (the cache's retrieval lies inside that);
# then the first execution until the call returned
BUILD_FIELDS = ('build_seconds', 'trace_seconds', 'lower_seconds',
                'backend_seconds', 'cache_retrieval_seconds',
                'first_call_seconds')


class ProgramRecord:
    """One named compiled program's cumulative accounting."""

    __slots__ = ('name', 'kind', 'compile_count', 'compile_seconds',
                 *BUILD_FIELDS,
                 'invocations', 'host_seconds', 'flops', 'bytes_accessed',
                 'peak_memory_bytes', 'argument_bytes', 'output_bytes',
                 'temp_bytes', 'analyzed', 'note')

    def __init__(self, name: str, kind: str = 'jit'):
        self.name = name
        self.kind = kind
        self.compile_count = 0
        self.compile_seconds = 0.0
        for field in BUILD_FIELDS:
            setattr(self, field, 0.0)
        self.invocations = 0
        self.host_seconds = 0.0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.peak_memory_bytes = 0
        self.argument_bytes = 0
        self.output_bytes = 0
        self.temp_bytes = 0
        self.analyzed = False
        self.note = ''

    def as_dict(self) -> Dict[str, Any]:
        return {
            'name': self.name, 'kind': self.kind,
            'compile_count': self.compile_count,
            'compile_seconds': self.compile_seconds,
            **{f: getattr(self, f) for f in BUILD_FIELDS},
            'invocations': self.invocations,
            'host_seconds': self.host_seconds,
            'flops': self.flops, 'bytes_accessed': self.bytes_accessed,
            'peak_memory_bytes': self.peak_memory_bytes,
            'argument_bytes': self.argument_bytes,
            'output_bytes': self.output_bytes,
            'temp_bytes': self.temp_bytes,
            'analyzed': self.analyzed, 'note': self.note,
        }


def _read_analysis(compiled, record: ProgramRecord):
    """Fill a record from a jax `Compiled` object's free introspection.
    Cumulative across signatures: a program recompiled at a second
    shape (to_static buckets) keeps the LARGEST figures — the report
    attributes the expensive variant."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            record.flops = max(record.flops, float(ca.get('flops', 0.0)))
            record.bytes_accessed = max(
                record.bytes_accessed, float(ca.get('bytes accessed', 0.0)))
            record.analyzed = True
    except Exception:  # paddle-lint: disable=swallowed-exception -- cost_analysis unavailable on this backend; record.analyzed stays False and the report marks it
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            arg = int(getattr(ma, 'argument_size_in_bytes', 0) or 0)
            out = int(getattr(ma, 'output_size_in_bytes', 0) or 0)
            tmp = int(getattr(ma, 'temp_size_in_bytes', 0) or 0)
            alias = int(getattr(ma, 'alias_size_in_bytes', 0) or 0)
            peak = int(getattr(ma, 'peak_memory_in_bytes', 0) or 0)
            if not peak:
                # CPU/older backends report no live peak: the resident
                # footprint bound is args + temps + outputs - aliased
                peak = max(arg + tmp + out - alias, 0)
            record.peak_memory_bytes = max(record.peak_memory_bytes, peak)
            record.argument_bytes = max(record.argument_bytes, arg)
            record.output_bytes = max(record.output_bytes, out)
            record.temp_bytes = max(record.temp_bytes, tmp)
    except Exception:  # paddle-lint: disable=swallowed-exception -- memory_analysis unavailable on this backend; record fields stay 0 and the report marks it
        pass


class ProgramCatalog:
    """Registry of every named compiled program in the process."""

    def __init__(self):
        self._lock = _concurrency.RLock('ProgramCatalog._lock')
        self._records: Dict[str, ProgramRecord] = {}

    # -- enrollment ---------------------------------------------------------
    def record(self, name: str, kind: str = 'jit') -> ProgramRecord:
        with self._lock:
            rec = self._records.get(name)
            if rec is None:
                rec = self._records[name] = ProgramRecord(name, kind)
            return rec

    def note_invocation(self, name: str, seconds: float = 0.0, n: int = 1,
                        kind: str = 'jit'):
        rec = self.record(name, kind)
        with self._lock:
            rec.invocations += n
            rec.host_seconds += seconds
        return rec

    def note_compile(self, name: str, seconds: float, kind: str = 'jit'):
        rec = self.record(name, kind)
        with self._lock:
            rec.compile_count += 1
            rec.compile_seconds += seconds
        return rec

    # -- dispatch-cache mirror ----------------------------------------------
    def _sync_dispatch(self):
        """Mirror the eager dispatch cache's per-op call counts into
        `eager:{op}` records (compile times arrive from the cache's own
        cold miss path via `note_dispatch_compile`). Mirrors, not
        accumulates — runs at report/scrape time only."""
        try:
            from .. import _dispatch
            per_op = _dispatch.stats()['per_op']
        except Exception:  # paddle-lint: disable=swallowed-exception -- dispatch cache absent: nothing to mirror at scrape time
            return
        with self._lock:
            for op, row in per_op.items():
                rec = self.record(f'eager:{op}', kind='dispatch')
                rec.invocations = row['hits'] + row['misses']

    # -- reporting ----------------------------------------------------------
    def records(self) -> List[ProgramRecord]:
        with self._lock:
            return list(self._records.values())

    def top_programs(self, n: int = 10, sort_by: str = 'host_seconds',
                     kind: Optional[str] = None) -> List[Dict[str, Any]]:
        """The attribution report: programs ranked by `sort_by`
        ('host_seconds', 'flops', 'bytes_accessed', 'invocations',
        'compile_seconds', a build phase of `BUILD_FIELDS`, 'mfu').
        Every row carries the roofline view
        — 'mfu', 'roofline_bound' ('compute'|'bandwidth'), and
        'arithmetic_intensity' — None where the device peaks are
        unknown or the program has no cost analysis. Pure dict reads —
        never compiles."""
        self._sync_dispatch()
        peaks = device_peaks()
        wall, baseline = _ledger_window()
        rows = []
        for r in self.records():
            if kind is not None and r.kind != kind:
                continue
            row = r.as_dict()
            row.update(record_roofline(r, peaks, wall, baseline))
            rows.append(row)
        rows.sort(key=lambda r: (-(r.get(sort_by) or 0.0), r['name']))
        return rows[:n]

    def snapshot(self) -> Dict[str, Any]:
        self._sync_dispatch()
        return {'programs': [r.as_dict() for r in self.records()]}

    def report(self, max_rows: int = 12) -> str:
        """Human-readable program-attribution table."""
        rows = self.top_programs(n=max_rows)
        peaks = device_peaks()
        wall, baseline = _ledger_window()
        agg = aggregate_mfu(self.records(), peaks, wall, baseline)
        head = f'program catalog: {len(self.records())} program(s)'
        if agg['mfu'] is not None:
            head += (f'  aggregate MFU {agg["mfu"]:.3f} '
                     f'({peaks["device_kind"]}, peak '
                     f'{peaks["peak_flops"] / 1e12:.0f} TFLOP/s, '
                     f'{peaks["source"]})')
        else:
            head += (f'  MFU unknown (device {peaks["device_kind"]!r} '
                     f'not in peak table; set PADDLE_PEAK_FLOPS)')
        lines = [head,
                 f'  {"program":<28}{"kind":<10}{"calls":>8}'
                 f'{"host s":>10}{"compile s":>10}{"build s":>9}'
                 f'{"trace s":>9}{"lower s":>9}{"backend s":>10}'
                 f'{"1st call s":>11}{"GFLOPs":>10}'
                 f'{"GB moved":>10}{"peak MiB":>10}{"mfu":>7}'
                 f'{"bound":>11}']
        for r in rows:
            mfu = f'{r["mfu"]:.3f}' if r['mfu'] is not None else '-'
            bound = r['roofline_bound'] or '-'
            lines.append(
                f'  {r["name"][:27]:<28}{r["kind"]:<10}'
                f'{r["invocations"]:>8}'
                f'{r["host_seconds"]:>10.3f}'
                f'{r["compile_seconds"]:>10.3f}'
                f'{r["build_seconds"]:>9.3f}{r["trace_seconds"]:>9.3f}'
                f'{r["lower_seconds"]:>9.3f}{r["backend_seconds"]:>10.3f}'
                f'{r["first_call_seconds"]:>11.3f}'
                f'{r["flops"] / 1e9:>10.3f}'
                f'{r["bytes_accessed"] / 1e9:>10.3f}'
                f'{r["peak_memory_bytes"] / 2**20:>10.1f}'
                f'{mfu:>7}{bound:>11}')
        return '\n'.join(lines)

    def reset(self):
        with self._lock:
            self._records.clear()


_catalog = ProgramCatalog()


def get_catalog() -> ProgramCatalog:
    return _catalog


def roofline_summary(max_rows: int = 5) -> Dict[str, Any]:
    """The /summary roofline section: device peaks (+ how they were
    resolved), aggregate MFU, per-bound program counts, and the top
    analyzed programs by MFU-weighted host time."""
    peaks = device_peaks()
    wall, baseline = _ledger_window()
    records = _catalog.records()
    agg = aggregate_mfu(records, peaks, wall, baseline)
    rows = []
    for r in records:
        roof = record_roofline(r, peaks, wall, baseline)
        if roof['mfu'] is None:
            continue
        rows.append({'name': r.name, 'host_seconds': r.host_seconds,
                     'mfu': roof['mfu'],
                     'bound': roof['roofline_bound'],
                     'intensity': roof['arithmetic_intensity']})
    rows.sort(key=lambda r: -r['mfu'])
    bounds = {'compute': 0, 'bandwidth': 0}
    for r in rows:
        if r['bound'] in bounds:
            bounds[r['bound']] += 1
    return {'device_kind': peaks['device_kind'],
            'peak_flops': peaks['peak_flops'],
            'peak_hbm_bytes_per_s': peaks['peak_hbm_bytes_per_s'],
            'source': peaks['source'],
            'mfu': agg['mfu'],
            'flops_total': agg['flops_total'],
            'window_wall_seconds': agg['wall_seconds'],
            'bound_counts': bounds,
            'programs': rows[:max_rows]}


def note_dispatch_compile(op_name: str, seconds: float):
    """Cold-path hook for paddle_tpu._dispatch: one cache entry was
    traced+compiled (the building call's wall time)."""
    _catalog.note_compile(f'eager:{op_name}', seconds, kind='dispatch')


def _program_collector(reg: '_metrics.MetricsRegistry'):
    """Scrape-time mirror of the catalog into `paddle_program_*`
    metrics (mirror, not accumulate — same contract as the dispatch
    collector)."""
    cat = _catalog
    cat._sync_dispatch()
    inv = reg.counter('paddle_program_invocations_total',
                      'compiled-program invocations', ('program',))
    host = reg.counter('paddle_program_host_seconds_total',
                       'host wall seconds inside compiled programs',
                       ('program',))
    comp = reg.counter('paddle_program_compile_seconds_total',
                       'seconds compiling each program', ('program',))
    flops = reg.gauge('paddle_program_flops',
                      'XLA cost_analysis FLOPs per invocation',
                      ('program',))
    byts = reg.gauge('paddle_program_bytes_accessed',
                     'XLA cost_analysis bytes accessed per invocation',
                     ('program',))
    peak = reg.gauge('paddle_program_peak_memory_bytes',
                     'XLA memory_analysis peak bytes', ('program',))
    pmfu = reg.gauge('paddle_program_mfu',
                     'model-FLOPs utilization per program '
                     '(cost_analysis FLOPs / host seconds / device peak)',
                     ('program',))
    bound = reg.gauge(
        'paddle_roofline_bound',
        'programs on each side of the roofline ridge '
        '(arithmetic intensity vs machine balance)', ('bound',))
    agg = reg.gauge('paddle_mfu',
                    'aggregate model-FLOPs utilization across analyzed '
                    'programs (0 while device peaks are unknown)')
    peaks = device_peaks()
    wall, baseline = _ledger_window()
    counts = {'compute': 0, 'bandwidth': 0}
    records = cat.records()
    for r in records:
        inv.labels(program=r.name).value = float(r.invocations)
        host.labels(program=r.name).value = float(r.host_seconds)
        comp.labels(program=r.name).value = float(r.compile_seconds)
        flops.labels(program=r.name).set(r.flops)
        byts.labels(program=r.name).set(r.bytes_accessed)
        peak.labels(program=r.name).set(r.peak_memory_bytes)
        roof = record_roofline(r, peaks, wall, baseline)
        if roof['mfu'] is not None:
            pmfu.labels(program=r.name).set(roof['mfu'])
        if roof['roofline_bound'] is not None:
            counts[roof['roofline_bound']] += 1
    for b, n in counts.items():
        bound.labels(bound=b).set(n)
    a = aggregate_mfu(records, peaks, wall, baseline)
    agg.set(a['mfu'] if a['mfu'] is not None else 0.0)


def install(registry: Optional['_metrics.MetricsRegistry'] = None):
    """Idempotent: register the scrape-time program collector."""
    reg = registry if registry is not None else _metrics.get_registry()
    reg.register_collector(_program_collector)
