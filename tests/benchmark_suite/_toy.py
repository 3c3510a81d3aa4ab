"""Helpers of the benchmark's tests: a toy root (the benchmark's own data
directories under a toy BENCHMARK.json) and a run of one toy cell in a
process of its own, with the harness's look for a chip skipped — the
process is a CPU rehearsal and says so (`platform: cpu`)."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TOY_BENCH = os.path.join(HERE, 'data', 'toy_BENCHMARK.json')

_DRIVER = r'''
import json, sys
sys.path.insert(0, {repo!r})
{patch}
from benchmarks import run, spec
out = run.run_cell(spec.Spec({root!r}), {workload!r}, {seed}, {seconds}, {trace},
                   control={control!r}, require_chip=False)
print(json.dumps(out))
'''


def make_root(tmp, copy=False):
    """<tmp>/BENCHMARK.json (toy) + <tmp>/benchmarks (link or copy)."""
    root = os.path.join(str(tmp), 'root')
    os.makedirs(root)
    src = os.path.join(REPO, 'benchmarks')
    if copy:
        shutil.copytree(src, os.path.join(root, 'benchmarks'),
                        ignore=shutil.ignore_patterns('__pycache__'))
    else:
        os.symlink(src, os.path.join(root, 'benchmarks'))
    shutil.copy(TOY_BENCH, os.path.join(root, 'BENCHMARK.json'))
    return root


def run_toy(root, workload, seed=5000000001, seconds=1.5, trace=0,
            control=None, patch='', timeout=600):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('BENCH_RUN', None)
    code = _DRIVER.format(repo=REPO, root=root, workload=workload, seed=seed,
                          seconds=seconds, trace=trace, control=control,
                          patch=patch)
    proc = subprocess.run([sys.executable, '-c', code], cwd=REPO, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    return json.loads(lines[-1]), lines[:-1]


def logged(lines, needle):
    """The text after `needle` on the evidence lines that hold it."""
    return [ln.split(needle, 1)[1].strip() for ln in lines if needle in ln]
