"""A set of runs of one cell, one new process each, as the driver makes
them — the builder's tool for spreads, readings of `correct` and its
control. The parent touches neither jax nor the program (one process per
chip). Every child's whole output goes to `chiprun_out/<tag>.log`; the
result lines go to `chiprun_out/<tag>.jsonl`; the spreads (distance
between the quartiles of `statistics.quantiles(n=4)` over the median)
are printed at the end.

    python3 benchmarks/sets.py --workload train-1chip --seeds 11,12,13 \\
        --seconds 45 [--trace 0] [--control fp8] [--tag name] [--set k=v]
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, default=0)
    ap.add_argument('--control', default=None)
    ap.add_argument('--tag', default=None)
    ap.add_argument('--set', action='append', default=[])
    ap.add_argument('--show', default='interval_s|per_second|check |control|'
                    'reference|offered|backlog',
                    help='evidence lines to echo, a regex alternation')
    args = ap.parse_args(argv)
    show = re.compile(args.show)
    tag = args.tag or args.workload
    out_dir = os.path.join(ROOT, 'chiprun_out')
    os.makedirs(out_dir, exist_ok=True)
    results = []
    with open(os.path.join(out_dir, f'{tag}.log'), 'a') as logf, \
            open(os.path.join(out_dir, f'{tag}.jsonl'), 'a') as resf:
        for seed in args.seeds.split(','):
            cmd = [sys.executable, os.path.join(ROOT, 'benchmarks', 'run.py'),
                   '--workload', args.workload, '--seed', seed,
                   '--seconds', str(args.seconds), '--trace', str(args.trace)]
            if args.control:
                cmd += ['--control', args.control]
            for item in args.set:
                cmd += ['--set', item]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                  stderr=subprocess.STDOUT, text=True)
            wall = time.time() - t0
            logf.write(f'==== {" ".join(cmd)}\n{proc.stdout}\n'
                       f'==== exit {proc.returncode} wall {wall:.1f}s\n')
            logf.flush()
            lines = proc.stdout.strip().splitlines()
            for ln in lines:
                if ln.startswith('[bench]') and show.search(ln):
                    print(ln[:700])
            res = None
            if proc.returncode == 0 and lines:
                try:
                    res = json.loads(lines[-1])
                except ValueError:
                    res = None
            if res is None:
                print(f'seed {seed}: NO RESULT (exit {proc.returncode}); '
                      f'tail:\n' + '\n'.join(lines[-40:]))
                break       # a fault: do not spend the chip on the rest
            res['seed'], res['wall_s'] = int(seed), wall
            resf.write(json.dumps(res) + '\n')
            resf.flush()
            results.append(res)
            print(f'seed {seed}: wall {wall:.1f}s correct {res["correct"]} '
                  f'failed {res["failed"]}/{res["attempted"]} ' + ' '.join(
                      f'{k}={v["value"]:.6g}'
                      for k, v in res['metrics'].items())
                  + f' peak_gib={(res["device"]["memory_peak_bytes"] or 0) / 2**30:.2f}',
                  flush=True)
    names = sorted({k for r in results for k in r['metrics']})
    for k in names:
        vals = [r['metrics'][k]['value'] for r in results if k in r['metrics']]
        sp = spread(vals)
        print(f'{tag} {k}: n={len(vals)} median={statistics.median(vals):.6g} '
              f'min={min(vals):.6g} max={max(vals):.6g} '
              f'spread={"n/a" if sp is None else f"{100 * sp:.3f}%"}')
    return 0


if __name__ == '__main__':
    sys.exit(main())
