"""One run of one cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic, limits and metric readers by
name (`spec.py`), dispatches on the traffic file's `kind` to a driver
under `kinds/`, and prints as the LAST line of stdout one JSON object:
`correct`, `attempted`, `failed`, `metrics`, `device` and, traced,
`breakdown`. Earlier lines are the evidence: per-interval times or
per-second tokens, and every number compared with its limit.

Exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for. Nothing falls back to a CPU.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()      # set-up counts from here

import argparse   # noqa: E402
import gc         # noqa: E402
import importlib  # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(_HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(_HERE))

from benchmarks import counts, log, spec as _spec, xtrace   # noqa: E402


class Run:
    """What a driver gets, and what it hands back."""

    def __init__(self, spec, cell, seed, seconds, trace, control=None,
                 t_process=None):
        self.spec, self.cell = spec, cell
        self.config, self.traffic = cell['config'], cell['traffic']
        self.limits, self.chips = cell['limits'], cell['chips']
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.control = bool(trace), control
        self.t_process = _T_PROCESS if t_process is None else t_process
        self.trace_dir = os.path.join(spec.root, '.bench_trace', cell['name'])
        self.on_tpu = True
        # filled by the driver
        self.raw = {}
        self.attempted = 0
        self.failed = 0
        self.checks = []          # (name, value, limit, ok)
        self.trace_summary = None
        self.memory_peak_bytes = None

    def clock(self):
        return time.perf_counter()

    def window_opens(self, t_open):
        """Set-up ends here: start of the process to the open window."""
        self.raw['setup_s'] = t_open - self.t_process

    def check(self, name, value, limit, ok=None):
        """One number compared beside its limit; printed in every run."""
        ok = (value <= limit) if ok is None else bool(ok)
        self.checks.append((name, value, limit, ok))
        log(f'check {name}: {value!r} (limit {limit!r}) '
            f'{"ok" if ok else "NOT CORRECT"}')
        return ok

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        except (AttributeError, TypeError):
            jax.profiler.start_trace(self.trace_dir)

    def stop_trace(self):
        import jax
        jax.profiler.stop_trace()

    def reduce_trace(self):
        trace = xtrace.load(xtrace.find_xplane(self.trace_dir))
        sample = os.environ.get('BENCH_TRACE_SAMPLE')
        if sample:      # a builder's look at a trace; never set by a check
            xtrace.dump_sample(trace, sample)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        if not xtrace.device_planes(trace) and not self.on_tpu:
            log('no device plane in the trace (not a TPU): trace metrics '
                'are left out')      # a rehearsal; a TPU run raises below
            return
        self.trace_summary = xtrace.reduce(trace)

    def note_xla_estimate(self):
        """XLA's own estimate of the largest program's peak, from the
        program store's catalog (filled as each program compiles or
        loads); the allocator's peak beside it, because they disagree."""
        from paddle_tpu import programs
        recs = programs.get_store().catalog.records()
        peak = max((r.peak_memory_bytes for r in recs), default=0)
        self.raw['hbm_xla_estimate_bytes'] = int(peak) or None

    def read_memory_peak(self):
        """Peak bytes in use on the fullest chip, read when the window
        has closed and BEFORE the reference runs, so it is the
        program's."""
        import jax
        peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                 for d in jax.local_devices()]
        self.memory_peak_bytes = int(max(peaks))
        self.raw['hbm_peak_bytes'] = self.memory_peak_bytes
        self.note_xla_estimate()


def device_or_exit(chips, require_chip=True):
    import jax
    devs = jax.devices()
    plat = devs[0].platform
    if require_chip and (plat != 'tpu' or len(devs) < chips):
        log(f'needs {chips} TPU chip(s); JAX found {len(devs)} x {plat!r}')
        raise SystemExit(3)
    return {'platform': plat, 'kind': devs[0].device_kind,
            'count': len(devs)}


def setup_compile_cache():
    """The persistent compile cache at the path the program's
    `ensure_compile_cache` fixes (`JAX_COMPILATION_CACHE_DIR`, else
    `<checkout>/.jax_cache`), and every program admitted to it however
    short its compile, so that only a checkout's first run compiles."""
    import jax
    from paddle_tpu import programs
    path = programs.ensure_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    return path


def run_cell(spec, name, seed, seconds, trace, control=None, overrides=None,
             require_chip=True, t_process=None):
    """Everything but the process boundary; the tests call this."""
    cell = spec.cell(name)
    for key, value in (overrides or {}).items():
        cell['traffic'][key] = value
    device = device_or_exit(cell['chips'], require_chip)
    cache = setup_compile_cache()
    log(f'cell {name}: config {cell["config_name"]}, traffic '
        f'{cell["traffic_name"]} ({cell["traffic"]["kind"]}), seed {seed}, '
        f'{seconds} s, trace {int(trace)}; device {device}; cache {cache}')
    run = Run(spec, cell, seed, seconds, trace, control, t_process)
    run.on_tpu = device['platform'] == 'tpu'
    kind = importlib.import_module(
        f'benchmarks.kinds.{cell["traffic"]["kind"]}')
    gc.collect()
    kind.run(run)

    correct = all(ok for *_, ok in run.checks) and bool(run.checks)
    peaks = spec.peaks(device['kind']) if device['platform'] == 'tpu' \
        else None
    ctx = _spec.ReadContext(cell, run.raw, run.trace_summary, peaks, counts)
    group = 'per_layer' if trace else 'end_to_end'
    metrics = {}
    for m in spec.metrics_of(name, group):
        value = spec.read_metric(m['name'], ctx)
        if value is not None:
            metrics[m['name']] = {'value': float(value), 'unit': m['unit']}
    device['memory_peak_bytes'] = run.memory_peak_bytes
    out = {'correct': bool(correct), 'attempted': int(run.attempted),
           'failed': int(run.failed), 'metrics': metrics, 'device': device}
    if trace and run.trace_summary is not None:
        ts = run.trace_summary
        device['busy_s'] = ts['busy_s']
        device['window_s'] = ts['window_s']
        out['breakdown'] = {'device_ops': ts['device_ops'],
                            'idle_gaps': ts['idle_gaps']}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    # the builder's knobs; the driver's command never gives them
    ap.add_argument('--bench-root', default=None)
    ap.add_argument('--control', default=None,
                    help='also read the control (fp8 | int8) numbers')
    ap.add_argument('--set', action='append', default=[],
                    metavar='KEY=JSON', help='override a traffic key')
    args = ap.parse_args(argv)
    overrides = {}
    for item in args.set:
        key, _, value = item.partition('=')
        overrides[key] = json.loads(value)
    spec = _spec.Spec(args.bench_root)
    out = run_cell(spec, args.workload, args.seed, args.seconds, args.trace,
                   control=args.control, overrides=overrides)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
