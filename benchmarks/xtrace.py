"""The one reduction from a profiler trace to numbers.

`load()` turns an `.xplane.pb` (through `jax.profiler.ProfileData`) or a
recorded `.json` of the same structure into plain planes / lines /
events; `reduce()` turns those into: device busy time (the union of the
intervals in which an operation runs), idle share, time per operation
and idle gaps attributed to what the host was doing.

Nothing of the program is imported. Times are seconds.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

# lines of a device plane that do not hold single operations
_NOT_OP_LINES = ('Steps', 'XLA Modules', 'XLA TraceMe', 'Framework Ops',
                 'Framework Name Scope', 'Source code', 'Host Offload')
_CONTAINERS = ('while', 'conditional', 'call')
_HOST_SPAN = re.compile(r'^(bench\.|reqledger\.|serving\.|train\.)')


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return paths[-1]


def load(path):
    """-> {'planes': [{'name', 'lines': [{'name', 'events': [[name,
    start_s, dur_s, {stat: value}], ...]}]}]}."""
    if path.endswith('.json') or path.endswith('.json.gz'):
        opener = gzip.open if path.endswith('.gz') else open
        with opener(path, 'rt') as f:
            return json.load(f)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        is_dev = plane.name.startswith('/device:')
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                name = ev.name
                if not is_dev and not _HOST_SPAN.match(name):
                    continue        # host planes: only named spans matter
                stats = {}
                if is_dev:
                    for k, v in ev.stats:
                        if k in ('long_name', 'hlo_category', 'tf_op',
                                 'kernel_details', 'name'):
                            stats[k] = v if isinstance(
                                v, (int, float)) else str(v)[:400]
                events.append([name, ev.start_ns * 1e-9,
                               ev.duration_ns * 1e-9, stats])
            if events:
                lines.append({'name': line.name, 'events': events})
        if lines:
            planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def union(intervals):
    """Merge [start, end) intervals; -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def device_planes(trace):
    return [p for p in trace['planes']
            if p['name'].startswith('/device:') and 'TPU' in p['name']
            or p['name'].startswith('/device:GPU')]


def op_events(plane):
    """Single-operation events of a device plane: the 'XLA Ops' line
    where the plane has one, else every line that is not a step, module
    or name-scope line."""
    named = [l for l in plane['lines'] if l['name'] == 'XLA Ops']
    lines = named or [l for l in plane['lines']
                      if l['name'] not in _NOT_OP_LINES]
    return [ev for l in lines for ev in l['events']]


def host_spans(trace):
    out = []
    for p in trace['planes']:
        if p['name'].startswith('/device:'):
            continue
        for l in p['lines']:
            out += [ev for ev in l['events'] if _HOST_SPAN.match(ev[0])]
    return out


_parsed = {}


def parse_hlo(text):
    """An op event's name on a TPU is the HLO instruction's text:
    '%fusion.568 = bf16[50304,2048]{1,0:T(8,128)(2,1)} fusion(...)'.
    -> (name, 'bf16_50304_2048' or '', opcode). A plain name (a recorded
    or hand-made trace) parses as (name, '', name without its number)."""
    got = _parsed.get(text)
    if got is not None:
        return got
    name, sep, rest = text.partition(' = ')
    name = name.lstrip('%')
    shape, opcode = '', re.sub(r'[.\d]+$', '', name)
    if sep:
        if rest.startswith('('):            # a tuple shape: skip it whole
            depth = 0
            for i, ch in enumerate(rest):
                depth += (ch == '(') - (ch == ')')
                if depth == 0:
                    break
            first, after = rest[1:i], rest[i + 1:].lstrip()
        else:
            first, _, after = rest.partition(' ')
        m = re.match(r'\(*([a-z]+\d*)\[([\d,]*)\]', first)
        if m:
            shape = (m.group(1) + '_' + m.group(2).replace(',', '_')).rstrip('_')
        opcode = after.partition('(')[0].strip() or opcode
    if len(_parsed) < 200000:
        _parsed[text] = (name, shape, opcode)
    return name, shape, opcode


def op_label(ev):
    """A stable, readable name: the HLO name plus the output's dtype and
    shape ('fusion.568_bf16_50304_2048')."""
    name, shape, _ = parse_hlo(ev[0])
    return f'{name}_{shape}' if shape else name


def is_custom_call(ev):
    """A Mosaic (Pallas) kernel: an HLO custom call whose target is
    `tpu_custom_call` (XLA's own `ConcatBitcast` custom calls and ops
    that merely read a kernel's result are not). A trace without HLO
    texts (hand-made) names them `custom-call`."""
    if parse_hlo(ev[0])[2] != 'custom-call':
        return False
    return 'custom_call_target=' not in ev[0] or \
        'custom_call_target="tpu_custom_call"' in ev[0]


def kernel_name(ev):
    """The kernel's name is the instruction's: `jvp_jit_flash_attention__`,
    `flash_mha_bwd_dq_...`, without the trailing number."""
    return re.sub(r'[.\d]+$', '', parse_hlo(ev[0])[0])


def reduce(trace, min_gap_s=50e-6):
    """The whole reduction. The window of a device is from its first
    operation's start to its last operation's end."""
    devs = device_planes(trace)
    if not devs:
        raise ValueError('the trace holds no device plane')
    spans = host_spans(trace)
    per_dev = []
    for plane in devs:
        evs = op_events(plane)
        if not evs:
            continue
        busy = union([[e[1], e[1] + e[2]] for e in evs])
        # a loop or a call holds its body's ops on the same line: it
        # counts as busy time, never as an operation of its own
        evs = [e for e in evs if parse_hlo(e[0])[2] not in _CONTAINERS]
        per_dev.append({
            'plane': plane['name'], 'events': evs, 'busy': busy,
            'busy_s': total(busy), 'window_s': busy[-1][1] - busy[0][0],
        })
    if not per_dev:
        raise ValueError('no operation ran on a device in the trace')
    d0 = per_dev[0]
    modules = {}
    for l in devs[0]['lines']:
        if l['name'] == 'XLA Modules':
            for e in l['events']:
                t, n = modules.get(e[0], (0.0, 0))
                modules[e[0]] = (t + e[2], n + 1)
    ops = {}
    for e in d0['events']:
        ops[op_label(e)] = ops.get(op_label(e), 0.0) + e[2]
    gaps = {}
    for (a, b), (c, d) in zip(d0['busy'], d0['busy'][1:]):
        if c - b < min_gap_s:
            continue
        mid = (b + c) / 2
        # the innermost host span over the gap's midpoint names it
        cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
        name = min(cover, key=lambda s: s[2])[0] if cover else 'host:unnamed'
        gaps[name] = gaps.get(name, 0.0) + (c - b)
    top = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        'devices': len(per_dev),
        'busy_s': sum(d['busy_s'] for d in per_dev) / len(per_dev),
        'window_s': sum(d['window_s'] for d in per_dev) / len(per_dev),
        'idle_share0': 1.0 - d0['busy_s'] / d0['window_s'],
        'busy_s0': d0['busy_s'], 'window_s0': d0['window_s'],
        'events0': d0['events'], 'modules0': modules,
        'device_ops': top(ops), 'idle_gaps': top(gaps),
    }


def module_time(trace_summary, match):
    """(seconds, calls) of the compiled programs on device 0 whose name
    holds `match`."""
    sel = [(t, n) for name, (t, n) in trace_summary['modules0'].items()
           if match in name.lower()]
    return sum(t for t, _ in sel), sum(n for _, n in sel)


def time_of(events, pred):
    """Summed device time and count of the events `pred` accepts."""
    sel = [e for e in events if pred(e)]
    return sum(e[2] for e in sel), len(sel)


def dump_sample(trace, path, slice_s=0.02, cap=600):
    """A trimmed copy of a trace for a person to look at and for the
    tests' recorded fixture: every line cut to the first `slice_s`
    seconds of the longest compiled program on device 0 (at most `cap`
    events a line, op texts cut to 300 characters), and the distinct
    custom calls' whole texts."""
    devs = device_planes(trace)
    mods = [e for l in devs[0]['lines'] if l['name'] == 'XLA Modules'
            for e in l['events']] if devs else []
    t0 = max(mods, key=lambda e: e[2])[1] if mods else 0.0
    keep = lambda e: e[1] + e[2] >= t0 and e[1] <= t0 + slice_s
    out = {'planes': [{'name': p['name'], 'lines': [
        {'name': l['name'], 'n_events': len(l['events']),
         'events': [[e[0][:300], e[1] - t0, e[2], e[3]]
                    for e in l['events'] if keep(e)][:cap]}
        for l in p['lines']]} for p in trace['planes']]}
    calls = {}
    for p in devs:
        for e in op_events(p):
            if is_custom_call(e):
                calls.setdefault(parse_hlo(e[0])[0], e[0][:3000])
    out['custom_calls'] = calls
    with open(path, 'w') as f:
        json.dump(out, f)
