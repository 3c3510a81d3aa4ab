"""Device time by the named scope the program compiled each instruction
under, in percent. A trace's op events are named by the optimized HLO's
instruction names; `paddle_tpu.programs.scope_table()` maps those names
back to `op_name`s (`jit(step_fn)/transpose(jvp(mlp))/dot_general`) from
the executables the program store still holds, and says HOW each was
found. An event joins a program by its instruction name and its
result's shape; where two programs in the store share both, it goes to
the program the events before it ran in.

What counts as placed: an instruction with an op_name of its own, or of
the computation it holds ('own', 'callee'). A name BORROWED from a
neighbour ('user', 'operand', 'caller': see `parse_hlo_scopes`) is a
guess: its time is logged apart on every read, it counts while it stays
under `BORROWED_LIMIT` of the program's op time, and above that it is
unplaced like the time of events no program knows or without any
op_name. A read that places less than `MIN_PLACED` reports nothing.

`part` splits one program's time by KERNEL: `optimizer`, a kernel that
holds an instruction under that scope wherever the compiler put it (the
AdamW update rides the weight-gradient matmul as its epilogue, and that
kernel's one op_name is the matmul's: matmul and update are one number,
the time inside a fused kernel cannot be split); `backward`, any other
kernel with `transpose(jvp(` in its op_name; `forward`, the rest that is
placed. Each is a share of all the traced op time.

Only the train step is read this way. The decode programs are not: on
the chip 40% (serve-chat) and 15% (serve-docs) of their op time has only
a borrowed name, the pool's rows being stacked into the scan's carry
and unstacked again by copies the compiler made (PERF.md section 5).
"""
from benchmarks import log, xtrace

BORROWED = ('user', 'operand', 'caller')
BORROWED_LIMIT = 3.0    # % of the program's op time a guess may place
MIN_PLACED = 90.0       # % of the program's op time a read has to place

_cache = {}     # id(trace summary) -> (placed events, scope_path, logged)


def _placed(ctx):
    """The traced events joined to the program's table, once a trace."""
    key = id(ctx.trace)
    if key not in _cache:
        _cache.clear()
        try:
            from paddle_tpu.programs import scope_path, scope_table
        except ImportError:
            return None     # a program without the table: nothing to read
        index = {}
        for prog, rows in scope_table().items():
            for name, (op_name, shape, *more) in rows.items():
                inner, how = (tuple(more) + ((), 'own'))[:2]
                index.setdefault(name, []).append(
                    (prog, op_name, shape, inner, how))
        _cache[key] = (place(ctx.trace['events0'], index), scope_path, set())
    return _cache[key]


def place(events, index):
    """`index`: {instruction name: [(program, op_name, shape label, inner
    scopes, how), ...]} -> [(event, program or None, op_name, inner
    scopes, how)] in time order."""
    out, current = [], None
    for e in sorted(events, key=lambda e: e[1]):
        name, shape, _ = xtrace.parse_hlo(e[0])
        cands = index.get(name, ())
        same = [c for c in cands if c[2] == shape] or cands
        progs = {c[0] for c in same}
        if len(progs) == 1:
            current = same[0][0]
        pick = [c for c in same if c[0] == current]
        if not pick:
            out.append((e, None, '', (), ''))
            continue
        out.append((e, *pick[0][:2], *pick[0][3:]))
    return out


def part_of(op_name, inner, scope_path):
    if not op_name:
        return None
    if 'optimizer' in inner or 'optimizer' in scope_path(op_name):
        return 'optimizer'
    return 'backward' if 'transpose(jvp(' in op_name else 'forward'


def read(ctx, program, part):
    if ctx.trace is None:
        return None
    got = _placed(ctx)
    if got is None:
        return None
    placed, scope_path, logged = got
    total = sum(e[2] for e, *_ in placed)
    rows = [row for row in placed
            if row[1] is not None and program in row[1].lower()]
    mine = [row[2:] + (row[0][2],) for row in rows]
    time_mine = sum(t for *_, t in mine)
    if not total or not time_mine:
        return None
    by_how = {}
    for op, _, how, t in mine:
        by_how[how if op else 'none'] = by_how.get(how if op else 'none',
                                                   0.0) + t
    borrowed = 100.0 * sum(by_how.get(h, 0.0) for h in BORROWED) / time_mine
    if borrowed > BORROWED_LIMIT:
        mine = [('' if how in BORROWED else op, inner, how, t)
                for op, inner, how, t in mine]
    unplaced = 100.0 * sum(t for op, *_, t in mine if not op) / time_mine
    nowhere = sum(row[0][2] for row in placed if row[1] is None)
    say = log if program not in logged else (lambda *_: None)
    logged.add(program)     # a program's evidence once a trace
    say(f'scope_time {program}: {100.0 * time_mine / total:.2f}% of op '
        f'time in the program, {100.0 * nowhere / total:.2f}% in none; '
        'of the program\'s, named by: ' + ', '.join(
            f'{how} {100.0 * t / time_mine:.2f}%'
            for how, t in sorted(by_how.items(), key=lambda kv: -kv[1]))
        + f'; borrowed {borrowed:.2f}% '
        + ('counts' if borrowed <= BORROWED_LIMIT else
           f'is over {BORROWED_LIMIT}% and unplaced')
        + f'; unplaced {unplaced:.2f}%')
    if borrowed:
        kinds = {}      # what the guesses are, by opcode
        for e, _, _, _, how in rows:
            if how in BORROWED:
                opcode = xtrace.parse_hlo(e[0])[2]
                kinds[opcode] = kinds.get(opcode, 0.0) + e[2]
        say(f'scope_time {program}: borrowed names, by opcode: ' + ', '.join(
            f'{k} {100.0 * t / time_mine:.2f}%' for k, t in
            sorted(kinds.items(), key=lambda kv: -kv[1])[:5]))
    if 100.0 - unplaced < MIN_PLACED:
        return None
    got = [(op, t) for op, inner, _, t in mine
           if part_of(op, inner, scope_path) == part]
    fused = sum(t for op, t in got if part == 'optimizer'
                and part not in scope_path(op))
    if fused:
        log(f'scope_time {program}: of {part!r}, '
            f'{100.0 * fused / total:.2f}% of op time is in kernels '
            'whose own op_name lies elsewhere')
    return 100.0 * sum(t for _, t in got) / total
