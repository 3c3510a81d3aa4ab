"""Finding a cell's files by name.

`BENCHMARK.json` names workloads; a workload names a configuration and a
traffic mix; every metric has a file of its own that names its reader.
Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds one by adding files and entries.

    <root>/BENCHMARK.json
    <root>/benchmarks/configs/<config>.json
    <root>/benchmarks/traffic/<traffic>.json      ('kind' picks the driver)
    <root>/benchmarks/limits/<workload>.json      (limits of `correct`)
    <root>/benchmarks/metrics/<metric>.json       ('reader' + 'args')
    <root>/benchmarks/readers/<reader>.py         (one function, `read`)
    <root>/benchmarks/peaks.json
"""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path):
    with open(path) as f:
        return json.load(f)


class Spec:
    def __init__(self, root=None):
        self.root = os.path.abspath(root or ROOT)
        self.dir = os.path.join(self.root, 'benchmarks')
        self.bench = _load(os.path.join(self.root, 'BENCHMARK.json'))
        self._readers = {}

    def data(self, kind, name):
        return _load(os.path.join(self.dir, kind, f'{name}.json'))

    def workload(self, name):
        for w in self.bench['workloads']:
            if w['name'] == name:
                return w
        raise KeyError(f'no workload {name!r} in BENCHMARK.json')

    def cell(self, name):
        w = self.workload(name)
        entry = next(c for c in self.bench['configs']
                     if c['name'] == w['config'])
        return {
            'name': name, 'chips': int(w['chips']),
            'config_name': w['config'], 'traffic_name': w['traffic'],
            'config': _load(os.path.join(self.root, entry['file'])),
            'traffic': self.data('traffic', w['traffic']),
            'limits': self.data('limits', name),
        }

    def metrics_of(self, name, group):
        """The `end_to_end` or `per_layer` entries the cell reports: all
        of the group without a `workloads` key, and those that list it.
        A per-layer metric without the key reports wherever the
        end-to-end metric it moves is reported."""
        e2e = {m['name']: m for m in self.bench['end_to_end']}

        def reports(m):
            if 'workloads' in m:
                return name in m['workloads']
            if group == 'per_layer':
                return reports(e2e[m['moves']])
            return True
        return [m for m in self.bench[group] if reports(m)]

    def peaks(self, device_kind):
        table = _load(os.path.join(self.dir, 'peaks.json'))['devices']
        for key, row in table.items():
            if key.lower() in device_kind.lower():
                return row
        raise KeyError(f'device kind {device_kind!r} is not in peaks.json: '
                       f'an unknown device is an error, not a default')

    def reader(self, name):
        if name not in self._readers:
            path = os.path.join(self.dir, 'readers', f'{name}.py')
            spec = importlib.util.spec_from_file_location(
                f'benchmarks_reader_{name}', path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._readers[name] = mod.read
        return self._readers[name]

    def read_metric(self, metric_name, ctx):
        """-> value or None (a reader that finds nothing to read)."""
        meta = self.data('metrics', metric_name)
        return self.reader(meta['reader'])(ctx, **meta.get('args', {}))


class ReadContext:
    """What a metric's reader may look at."""

    def __init__(self, cell, raw, trace, peaks, counts):
        self.cell = cell
        self.config = cell['config']
        self.traffic = cell['traffic']
        self.chips = cell['chips']
        self.raw = raw            # numbers and lists the driver measured
        self.trace = trace        # xtrace.reduce() of the traced window
        self.peaks = peaks        # this device's row of peaks.json
        self.counts = counts      # the benchmarks.counts module
