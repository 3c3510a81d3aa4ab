"""kind `serve_backlog`: a closed backlog. Every slot is kept busy and a
short queue waits behind them; a request is due when it is submitted. A
first wave of short warm requests staggers the slots; the window opens
when every slot has finished one. The run is not correct if the backlog
runs out inside the window."""
from __future__ import annotations

import gc

from benchmarks import traffic as T
from benchmarks.kinds import _serve
from benchmarks import log


def run(run):
    tr = run.traffic
    server = _serve.Server(run)
    server.warm_programs()
    reqs = T.backlog_schedule(tr, run.seconds, server.slots)
    server.make_tokens(reqs)
    warm = {r.index for r in reqs if r.region == 'warm'}
    log('backlog (requests, prompt tokens, output tokens):',
        T.offered(reqs))
    gc.collect()
    in_system = server.slots + int(tr['queue_depth'])
    nxt, t_open, t_close, emptied = 0, None, None, False
    compiles0 = compiles = None
    tracing, trace_until = False, None
    while True:
        while nxt < len(reqs) and len(server.live) < in_system:
            server.submit(reqs[nxt])
            nxt += 1
        if nxt >= len(reqs) and len(server.live) < in_system \
                and t_close is None:
            emptied = True
        stamp = server.step()
        if t_open is None:
            if not (warm & set(server.live)):
                # every slot has finished its warm request
                t_open = stamp
                run.window_opens(t_open)
                compiles0 = server.compiles()
                server.reset_counts()
            continue
        if t_close is None and stamp - t_open >= run.seconds:
            t_close = stamp
            compiles = server.compiles() - compiles0
            run.read_memory_peak()
            if not run.trace:
                break
            run.start_trace()
            tracing, trace_until = True, stamp + float(tr['trace_s'])
        if tracing and stamp >= trace_until:
            run.stop_trace()
            break
    # the cell's requests: everything that was in the system while the
    # window was open and is not a warm request
    window_reqs = [r for r in reqs[:nxt] if r.region == 'window']
    _serve.finish(run, server, t_open, t_close, window_reqs, compiles, {})
    run.check('backlog_never_empty', int(emptied), 0)
