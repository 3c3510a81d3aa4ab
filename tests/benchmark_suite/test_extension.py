"""Driven by data: a cell, a configuration, a traffic mix and a per-layer
metric are each added by new files and new entries alone — no file that
is there is edited — and the toy rehearsal runs through them."""
import json
import os

import _toy


def _write(path, obj):
    with open(path, 'w') as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def test_add_cell_config_traffic_and_metric_by_files_alone(tmp_path):
    root = _toy.make_root(tmp_path, copy=True)
    bdir = os.path.join(root, 'benchmarks')
    before = {}
    for dirpath, _, files in os.walk(bdir):
        for f in files:
            p = os.path.join(dirpath, f)
            before[p] = os.path.getmtime(p), os.path.getsize(p)

    with open(os.path.join(bdir, 'configs', 'toy-llama.json')) as f:
        cfg = json.load(f)
    cfg.update(name='toy-llama-wide', num_attention_heads=2,
               num_key_value_heads=1)
    _write(os.path.join(bdir, 'configs', 'toy-llama-wide.json'), cfg)
    with open(os.path.join(bdir, 'traffic', 'toy-docs.json')) as f:
        tr = json.load(f)
    tr.update(slots=2, output={'kind': 'fixed', 'value': 8})
    _write(os.path.join(bdir, 'traffic', 'toy-fixed.json'), tr)
    with open(os.path.join(bdir, 'limits', 'toy-docs.json')) as f:
        _write(os.path.join(bdir, 'limits', 'toy-new.json'), json.load(f))
    _write(os.path.join(bdir, 'readers', 'rounds_per_s.py'),
           'def read(ctx, scale=1.0):\n'
           '    if "decode_rounds" not in ctx.raw:\n'
           '        return None\n'
           '    return scale * ctx.raw["decode_rounds"] / ctx.raw["window_s"]\n')
    _write(os.path.join(bdir, 'metrics', 'decode_rounds_per_s.json'),
           {'unit': '1/s', 'reader': 'rounds_per_s', 'args': {'scale': 1.0}})

    bench_path = os.path.join(root, 'BENCHMARK.json')
    with open(bench_path) as f:
        bench = json.load(f)
    bench['configs'].append({
        'name': 'toy-llama-wide', 'source': 'none', 'reduced': [],
        'file': 'benchmarks/configs/toy-llama-wide.json', 'why': 'added'})
    bench['workloads'].append({
        'name': 'toy-new', 'config': 'toy-llama-wide',
        'traffic': 'toy-fixed', 'chips': 1, 'why': 'added by files alone'})
    for m in bench['end_to_end']:
        if m['name'] in ('tpot_p50_ms', 'out_tokens_per_s'):
            m['workloads'].append('toy-new')
    bench['per_layer'].append({
        'name': 'decode_rounds_per_s', 'unit': '1/s', 'better': 'higher',
        'source': 'program_counter', 'moves': 'tpot_p50_ms',
        'layer': 'engine step: serving/engine.py', 'workloads': ['toy-new']})
    _write(bench_path, bench)

    out, lines = _toy.run_toy(root, 'toy-new', seed=3, trace=1)
    assert out['correct'] is True, lines[-12:]
    assert out['metrics']['decode_rounds_per_s']['value'] > 0
    assert out['metrics']['decode_rounds_per_s']['unit'] == '1/s'
    out, _ = _toy.run_toy(root, 'toy-new', seed=4)
    assert set(out['metrics']) == {'tpot_p50_ms', 'out_tokens_per_s',
                                   'setup_s'}
    for p, stamp in before.items():
        assert (os.path.getmtime(p), os.path.getsize(p)) == stamp, p
