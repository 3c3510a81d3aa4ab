"""Of the op time of the compiled programs whose name holds `decode`,
the share under one named scope, in percent. NOT of all traced op time:
a prefill that falls into the traced tail moves nothing here.

The join from a traced event to its `op_name`, and the rule for what
counts as placed, are `scope_time`'s (an op_name of its own or of what
the instruction holds; a name borrowed from a neighbour only while the
borrowed names stay under `BORROWED_LIMIT` of the programs' time; under
`MIN_PLACED` placed, nothing is reported). An instruction counts under
the OUTERMOST vocabulary scope of its op_name, so the scopes' shares
are disjoint and sum to at most 100: the norms of Q and K and the
cache's write inside `attention` are attention's, `state_write` inside
`conv` is conv's.
"""
from benchmarks import log
from benchmarks.readers.scope_time import (BORROWED, BORROWED_LIMIT,
                                           MIN_PLACED, _placed)

PROGRAMS = 'decode'


def read(ctx, scope):
    if ctx.trace is None:
        return None
    got = _placed(ctx)
    if got is None:
        return None
    placed, scope_path, logged = got
    mine = [(op, how, e[2]) for e, prog, op, _, how in placed
            if prog is not None and PROGRAMS in prog.lower()]
    total = sum(t for *_, t in mine)
    if not total:
        return None
    borrowed = 100.0 * sum(t for op, how, t in mine
                           if op and how in BORROWED) / total
    by_scope = {}
    for op, how, t in mine:
        if op and (borrowed <= BORROWED_LIMIT or how not in BORROWED):
            outer = (scope_path(op) or ('(no scope)',))[0]
            by_scope[outer] = by_scope.get(outer, 0.0) + t
    named = 100.0 * sum(by_scope.values()) / total
    if PROGRAMS not in logged:      # the evidence once a trace
        logged.add(PROGRAMS)
        log(f'decode_scope_share: {named:.2f}% of the {PROGRAMS} '
            f'programs\' op time placed (borrowed {borrowed:.2f}% '
            + ('counts' if borrowed <= BORROWED_LIMIT else 'does not')
            + '); by outermost scope: ' + ', '.join(
                f'{k} {100.0 * t / total:.2f}%' for k, t in
                sorted(by_scope.items(), key=lambda kv: -kv[1])))
    if named < MIN_PLACED:
        return None
    return 100.0 * by_scope.get(scope, 0.0) / total
