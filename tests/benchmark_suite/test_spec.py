"""BENCHMARK.json and the data files against the contract's rules of
form: names, units, lengths, and that every `moves` names an end-to-end
metric each reporting cell reports."""
import json
import os
import re

import pytest

from benchmarks import spec

SPEC = spec.Spec()
B = SPEC.bench
NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_.\-/]{1,200}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
METRICS = B['end_to_end'] + B['per_layer']
CELLS = [w['name'] for w in B['workloads']]


def test_top_level_keys_and_sizes():
    assert set(B) == {'command', 'paths', 'run_seconds', 'configs',
                      'workloads', 'end_to_end', 'per_layer'}
    assert 1 <= B['run_seconds'] <= 51 and isinstance(B['run_seconds'], int)
    assert os.path.getsize(os.path.join(SPEC.root, 'BENCHMARK.json')) < 65536
    assert all(PATH.match(p) and not p.startswith('/') and '..' not in p
               for p in B['paths'])
    assert len(B['command']) <= 32
    assert sum(w['chips'] == 4 for w in B['workloads']) <= max(
        1, len(B['workloads']) // 4)


@pytest.mark.parametrize('m', METRICS, ids=lambda m: m['name'])
def test_metric_entry_form_and_its_file(m):
    e2e = m in B['end_to_end']
    allowed = {'name', 'unit', 'better', 'source', 'workloads'} | (
        {'bound'} if e2e else {'layer', 'moves'})
    assert set(m) <= allowed and allowed - {'workloads'} <= set(m)
    assert NAME.match(m['name']) and UNIT.match(m['unit'])
    assert m['better'] in ('lower', 'higher') and m['source'] in SOURCES
    if e2e:
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.1
    else:
        assert 1 <= len(m['layer']) <= 200 and '\n' not in m['layer']
    meta = SPEC.data('metrics', m['name'])
    assert meta['unit'] == m['unit']
    assert callable(SPEC.reader(meta['reader']))


def test_names_are_unique_and_setup_s_is_there():
    names = [m['name'] for m in METRICS]
    assert len(names) == len(set(names))
    assert len(CELLS) == len(set(CELLS))
    assert 'setup_s' in {m['name'] for m in B['end_to_end']}
    pairs = [(w['config'], w['traffic']) for w in B['workloads']]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize('m', B['per_layer'], ids=lambda m: m['name'])
def test_moves_names_an_end_to_end_metric_every_reporting_cell_reports(m):
    e2e = {x['name'] for x in B['end_to_end']}
    assert m['moves'] in e2e
    cells = [c for c in CELLS
             if m in SPEC.metrics_of(c, 'per_layer')]
    assert cells, 'a per-layer metric nobody reports'
    for c in cells:
        assert m['moves'] in {x['name']
                              for x in SPEC.metrics_of(c, 'end_to_end')}
    for c in m.get('workloads', []):
        assert c in CELLS


@pytest.mark.parametrize('cell', CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m['name'] for m in SPEC.metrics_of(cell, 'end_to_end')}
    assert 'setup_s' in e2e and len(e2e) >= 2
    assert SPEC.metrics_of(cell, 'per_layer')
    c = SPEC.cell(cell)
    assert os.path.exists(os.path.join(
        SPEC.dir, 'kinds', c['traffic']['kind'] + '.py'))
    w = SPEC.workload(cell)
    assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
    assert NAME.match(w['name']) and NAME.match(w['traffic'])
    assert 1 <= len(w['why']) <= 200 and w['chips'] in (1, 4)


@pytest.mark.parametrize('c', B['configs'], ids=lambda c: c['name'])
def test_config_entry_and_file(c):
    assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
    assert NAME.match(c['name']) and len(c['reduced']) <= 16
    assert any(c['file'].startswith(p + '/') for p in B['paths'])
    assert c['name'] in {w['config'] for w in B['workloads']}
    with open(os.path.join(SPEC.root, c['file'])) as f:
        cfg = json.load(f)
    assert cfg['source'] == c['source'] and cfg['reduced'] == c['reduced']
    forbidden = re.compile(r'(_dim|_rank)$|hidden_size|intermediate|head')
    assert not [k for k in c['reduced'] if forbidden.search(k)]


def test_files_under_paths_are_named_from_allowed_characters():
    for p in B['paths']:
        for dirpath, dirs, files in os.walk(os.path.join(SPEC.root, p)):
            dirs[:] = [d for d in dirs if d != '__pycache__']
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), SPEC.root)
                assert PATH.match(rel), rel
