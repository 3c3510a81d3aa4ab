"""kind `serve_open`: open-loop arrivals at the traffic file's fixed
rate. Warm phase, window, cool phase: three stratified regions of one
schedule (`traffic.open_loop_schedule`), the same for every seed: the
seed makes the weights and the token ids."""
from __future__ import annotations

import gc
import time

from benchmarks import traffic as T
from benchmarks import window
from benchmarks.kinds import _serve
from benchmarks import log


def run(run):
    tr = run.traffic
    server = _serve.Server(run)
    server.warm_programs()
    reqs = T.open_loop_schedule(tr, run.seconds)
    server.make_tokens(reqs)
    window_reqs = [r for r in reqs if r.region == 'window']
    log('offered in window (requests, prompt tokens, output tokens):',
        T.offered(reqs))
    gc.collect()

    warm_s = float(tr['warm_s'])
    t_open = time.perf_counter() + warm_s + 0.05
    for r in reqs:
        r.due = t_open + r.due          # absolute host-clock instants
    t_close_due = t_open + run.seconds
    run.window_opens(t_open)
    nxt, late, t_close, compiles0, compiles = 0, {}, None, None, None
    trace_until, tracing = None, False
    pending_first = {r.index for r in window_reqs}
    t_stop = t_close_due + float(tr['cool_s'])
    while True:
        now = time.perf_counter()
        if compiles0 is None and now >= t_open:
            compiles0 = server.compiles()
            server.reset_counts()
            log(f'in the system at window open: {len(server.live)}')
        while nxt < len(reqs) and reqs[nxt].due <= now:
            r = reqs[nxt]
            server.submit(r)
            late[r.index] = server.submit_at[r.index] - r.due
            nxt += 1
        if server.live:
            stamp = server.step()
        else:
            wait = (reqs[nxt].due - now) if nxt < len(reqs) else 0.001
            time.sleep(min(max(wait, 0.0), 0.002))
            stamp = time.perf_counter()
        if t_close is None and stamp >= t_close_due:
            t_close = stamp             # the first sync at or after
            log(f'in the system at window close: {len(server.live)}')
            compiles = server.compiles() - compiles0
            run.read_memory_peak()
            if run.trace:               # the traced tail follows the window
                run.start_trace()
                tracing, trace_until = True, stamp + float(tr['trace_s'])
        if tracing and stamp >= trace_until:
            run.stop_trace()
            tracing = False
        if t_close is not None and not tracing:
            pending_first = {i for i in pending_first
                             if server.em.first_token(i) is None
                             and i not in server.failed}
            if not pending_first or stamp >= t_stop:
                break
    t_end = time.perf_counter()
    for r in window_reqs:               # never answered: failed
        if r.index in pending_first:
            server.failed.add(r.index)
    ttft = window.ttft_samples(window_reqs, server.em, t_open, t_end,
                               failed=server.failed)
    # due -> admission: the generator's lateness plus the wait the
    # program's own request ledger recorded
    wait = {i: late.get(i, 0.0) + w for i, w in
            server.queue_waits([r.index for r in window_reqs])}
    _serve.finish(run, server, t_open, t_close, window_reqs, compiles, {
        'ttft_s': ttft,
        'late_s': [late[r.index] for r in window_reqs if r.index in late],
        'queue_wait_s': list(wait.values()),
        'ttft_tail_queue_share': _tail_queue_share(window_reqs, ttft, wait),
    })


def _tail_queue_share(window_reqs, ttft, wait):
    """Of the requests at or above the 95th percentile of TTFT, the
    share of their TTFT that was waiting to be admitted, in percent."""
    cut = window.percentile(ttft, 95)
    tail = [(t, wait[r.index]) for r, t in zip(window_reqs, ttft)
            if t >= cut and r.index in wait]
    if not tail:
        return None
    return 100.0 * sum(w for _, w in tail) / sum(t for t, _ in tail)
