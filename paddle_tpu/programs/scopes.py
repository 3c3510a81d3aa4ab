"""Which named scope does a compiled instruction belong to.

The layers of what is compiled are traced under `jax.named_scope`s of
one fixed vocabulary (`SCOPES`: nlp/gpt.py, nlp/llama.py, nlp/afmoe.py's
expert layer, nlp/lfm2.py's short convolution and the update of its
state, nlp/ling3.py's KDA layers, nlp/jamba.py's state-space layers,
the loss, the optimizer's functional update, the engine's sampling and
KV writes), so
every HLO instruction's `op_name` metadata says where it came from:
`jit(step_fn)/jvp(mlp)/dot_general` is the forward pass of an MLP,
`.../transpose(jvp(attention))/...` the backward pass of attention,
`.../optimizer/sub` the update. A device trace names its events by the
optimized HLO's instruction names; `scope_table()` is the join from
those names back to `op_name`s, read from the executables the program
store's memory tier holds.

Built on request only — never when a program compiles or loads: the HLO
text of a 1.3B-parameter train step is tens of megabytes.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

from .store import get_store

# the vocabulary; tests/test_spans_scopes.py pins it to what the programs
# carry
SCOPES = ('embed', 'attention', 'mlp', 'norm', 'lm_head', 'loss', 'sample',
          'kv_write', 'optimizer', 'moe/router', 'moe/experts', 'moe/shared',
          'conv', 'state_write', 'latent_absorb', 'mhc', 'kda', 'ssm')

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r'^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$')
_SHAPE = re.compile(r'^\(*([a-z]+\d*)\[([\d,]*)\]')
_REF = re.compile(r'%([\w.\-]+)')
_CALLED = re.compile(r'\b(calls|to_apply|body|condition)=%?([\w.\-]+)')
_MAX_HOPS = 16     # how far an instruction looks for a neighbour's op_name


def _shape_label(rest: str) -> str:
    """'bf16[50304,2048]{1,0:T(8,128)} fusion(...)' -> 'bf16_50304_2048'
    (a tuple's first element), the form device-trace readers label an
    operation's result with."""
    m = _SHAPE.match(rest)
    if not m:
        return ''
    return (m.group(1) + '_' + m.group(2).replace(',', '_')).rstrip('_')


def parse_hlo_scopes(text: str) -> Dict[str, Tuple[str, str, tuple, str]]:
    """{instruction name: (op_name, result shape label, inner scopes,
    how)} of an optimized HLO module's text. `inner scopes` are the
    vocabulary scopes of the instructions a fusion holds: the compiler
    fuses across scopes (the AdamW update rides the weight-gradient
    matmul as its output epilogue), and the one `op_name` it leaves on a
    fused kernel is the matmul's.

    `how` says where the op_name came from. 'own': the instruction's
    metadata. 'callee': its callee's root's, or the callee's most
    frequent one (a fusion whose metadata the compiler dropped; what it
    holds says what it is). The rest are BORROWED from a neighbour, a
    guess that a reader has to count apart: 'user', the nearest user's
    (a copy or a prefetch the compiler made belongs to what consumes its
    result); 'operand', the nearest operand's; 'caller', that of the
    instruction that calls its computation (the loop a scatter was
    expanded into keeps the scatter's name, its body's instructions
    none). An argument's name (`pool[4][1][0]`, no '/') is not an
    op_name. Still without, op_name and how are ''."""
    instrs: Dict[str, Tuple[str, str, Optional[str]]] = {}
    operands: Dict[str, Tuple[str, ...]] = {}
    roots: Dict[str, str] = {}             # computation -> root op_name
    common: Dict[str, Dict[str, int]] = {}  # computation -> op_name counts
    home: Dict[str, str] = {}              # instruction -> its computation
    caller: Dict[str, str] = {}            # computation -> who calls it
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and ' = ' not in line.split('(', 1)[0]:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        body = line[m.end():]
        op = _OP_NAME.search(body)
        op_name = op.group(1) if op and '/' in op.group(1) else ''
        home[name] = comp
        callee = None       # the computation a fusion or a reduce holds
        for how, called in _CALLED.findall(body):
            caller.setdefault(called, name)
            if callee is None and how in ('calls', 'to_apply'):
                callee = called
        instrs[name] = (op_name, _shape_label(body), callee)
        operands[name] = tuple(_REF.findall(body.split(', metadata=')[0]))
        if comp is not None and op_name:
            if line.lstrip().startswith('ROOT'):
                roots[comp] = op_name
            tally = common.setdefault(comp, {})
            tally[op_name] = tally.get(op_name, 0) + 1
    named: Dict[str, str] = {}
    how: Dict[str, str] = {}
    for name, (op_name, _, callee) in instrs.items():
        how[name] = 'own'
        if not op_name and callee is not None:
            how[name] = 'callee'
            op_name = roots.get(callee, '')
            if not op_name and callee in common:
                op_name = max(common[callee], key=common[callee].get)
        if op_name:
            named[name] = op_name
    users: Dict[str, list] = {}
    for name, refs in operands.items():
        for ref in refs:
            if ref in instrs and ref != name:
                users.setdefault(ref, []).append(name)

    def nearest(start, edges):
        seen, front = {start}, [start]
        for _ in range(_MAX_HOPS):
            nxt = []
            for at in front:
                for to in edges.get(at, ()):
                    if to in named:
                        return named[to]
                    if to in instrs and to not in seen:
                        seen.add(to)
                        nxt.append(to)
            front = nxt
        return ''

    def from_caller(name):
        for _ in range(_MAX_HOPS):
            name = caller.get(home.get(name))
            if name is None:
                return ''
            if name in named:
                return named[name]
        return ''

    borrow = (('user', lambda name: nearest(name, users)),
              ('operand', lambda name: nearest(name, operands)),
              ('caller', from_caller))
    out = {}
    for name, (_, shape, callee) in instrs.items():
        op_name, via = named.get(name, ''), how[name]
        for via_next, find in borrow:
            if op_name:
                break
            op_name, via = find(name), via_next
        inner = {scope for op in common.get(callee, ())
                 for scope in scope_path(op)[-1:]}
        out[name] = (op_name, shape, tuple(sorted(inner)),
                     via if op_name else '')
    return out


def scope_table() -> Dict[str, Dict[str, Tuple[str, str, tuple, str]]]:
    """{program name: {instruction name: (op_name, result shape label,
    inner scopes, how)}} (see `parse_hlo_scopes`) for every program in
    the store's memory tier, parsed from the optimized HLO of the
    executable held there. A program whose executable gives no text
    (served by a plain jitted call) is left out. Two entries of one name
    (one program at two signatures) merge; the later one wins a shared
    instruction name."""
    store = get_store()
    with store._lock:
        entries = [(e.name, e.callable) for e in store._mem.values()]
    out: Dict[str, Dict[str, Tuple[str, str, tuple, str]]] = {}
    for name, call in entries:
        as_text = getattr(call, 'as_text', None)
        if as_text is None:
            continue
        out.setdefault(name, {}).update(parse_hlo_scopes(as_text()))
    return out


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The vocabulary scopes an `op_name` lies under, outermost first:
    `jit(f)/transpose(jvp(attention))/kv_write/scatter` ->
    ('attention', 'kv_write'). A name of the vocabulary may span two
    segments (`moe/router`)."""
    parts = []
    for part in op_name.split('/'):
        while '(' in part:                  # jvp(x), transpose(jvp(x))
            part = part[part.index('(') + 1:].rstrip(')')
        parts.append(part)
    out, i = [], 0
    while i < len(parts):
        pair = '/'.join(parts[i:i + 2])
        if pair in SCOPES:
            out.append(pair)
            i += 2
            continue
        if parts[i] in SCOPES:
            out.append(parts[i])
        i += 1
    return tuple(out)
