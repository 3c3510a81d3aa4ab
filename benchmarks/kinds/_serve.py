"""The serving drivers' shared part: the model behind
`Router(ReplicaSet(model, 1))`, the benchmark's own driver loop, and the
check of what was served.

Rules A.4 and A.5: the window opens in steady state (a warm phase of the
same traffic runs first), every token emitted inside the window counts,
latency samples are the requests DUE inside the window and TTFT counts
from the due instant; arrival instants and token ids are computed before
the window and the generator only submits; its lateness is a metric.

`correct` (contract, "How correct is decided", a model that is served):
once the window has closed and the program's state is freed, a sample
drawn from the seed of the requests the run finished, the longest among
them, goes through the plain float32 reference once — the prompt with
its served tokens — and the number compared is the widest gap by which a
served token's logit lies below the reference's best at that position.
All requests are greedy.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmarks import traffic as T
from benchmarks import window
from benchmarks.models import adapter, fill
from benchmarks.reference import common as C
from benchmarks import free_arrays, log


SLOW_STEP_S = 0.6     # a router step this long is logged as a stall


class Server:
    """The system under test and the loop that drives it."""

    def __init__(self, run):
        import jax
        from paddle_tpu import observability as obs
        from paddle_tpu.serving import ReplicaSet, Router
        self.run, self.jax = run, jax
        cfg, tr = run.config, run.traffic
        self.ad = adapter(cfg['model_class'])
        self.refmod = importlib.import_module(
            f'benchmarks.reference.{self.ad.reference}')
        self.shapes = self.refmod.param_shapes(cfg)
        weights = C.make_weights(self.shapes, run.seed, cfg['param_dtype'])
        model = fill(self.ad.build(cfg), weights, self.ad.name_map(cfg))
        del weights
        model.eval()
        self.slots = int(tr['slots'])
        self.max_length = int(tr['max_length'])
        self.decode_block = int(tr['decode_block'])
        self.router = Router(ReplicaSet(
            model, 1, num_slots=self.slots, max_length=self.max_length,
            decode_block=self.decode_block, buckets=list(tr['buckets']),
            dtype=cfg['kv_dtype'], eos_token_id=-1))
        self.reg = obs.get_registry()
        self.em = window.Emissions()
        self.live = {}            # index -> [handle, tokens seen, request]
        self.finished = {}        # index -> handle
        self.failed = set()
        self.submit_at = {}       # index -> host clock at submit
        self.rows_sum = 0.0       # real KV rows, summed over rounds
        self.rounds = 0
        self.tokens = {}          # index -> prompt token ids

    def compiles(self):
        return self.reg.value('paddle_jit_compiles_total')

    def make_tokens(self, requests):
        vocab = self.run.config['vocab_size']
        for r in requests:
            self.tokens[r.index] = T.prompt_tokens(
                self.run.seed, r.index, r.prompt_len, vocab).tolist()

    def submit(self, r):
        from paddle_tpu.serving import SamplingParams
        with self.jax.profiler.TraceAnnotation('bench.submit'):
            now = time.perf_counter()
            self.submit_at[r.index] = now
            try:
                h = self.router.submit(
                    self.tokens[r.index], SamplingParams(
                        max_new_tokens=r.output_len, eos_token_id=-1))
            except Exception as exc:    # refused: counts as failed
                log(f'request {r.index} refused: {exc!r}')
                self.failed.add(r.index)
                return
            self.live[r.index] = [h, 0, r]

    def step(self):
        """One router step and its synced stamp; emissions noted."""
        t0 = time.perf_counter()
        with self.jax.profiler.TraceAnnotation('bench.router_step'):
            self.router.step()
        stamp = time.perf_counter()
        rows, done, firsts = 0, [], []
        for idx, rec in self.live.items():
            h, seen, r = rec
            n = len(h.tokens)
            if n > seen:
                self.em.note(idx, stamp, n - seen)
                if not seen:
                    firsts.append(r.prompt_len)
                rec[1] = n
            if n or h.status == 'RUNNING':      # seated: its rows are real
                rows += r.prompt_len + n
            if h.done:
                done.append(idx)
        self.rows_sum += rows
        self.rounds += 1
        if stamp - t0 > SLOW_STEP_S:         # evidence for a stall
            log(f'slow step: {stamp - t0:.3f} s at {stamp:.3f}, first '
                f'tokens of prompts {firsts}, {len(self.live)} in the system')
        for idx in done:
            h, _, r = self.live.pop(idx)
            if h.error is not None or len(h.tokens) != r.output_len:
                log(f'request {idx} failed: status={h.status} '
                    f'tokens={len(h.tokens)}/{r.output_len} '
                    f'error={h.error!r}')
                self.failed.add(idx)
            else:
                self.finished[idx] = (h, r)
        return stamp

    def warm_programs(self):
        """Run every prefill bucket and the decode block once, before any
        clock: what compiles (or loads from the cache) does so here."""
        tr = self.run.traffic
        reqs = []
        for i, b in enumerate(tr['buckets']):
            plen = min(int(b), self.max_length - 2 * self.decode_block)
            reqs.append(T.Request(-1 - i, None, plen, 2 * self.decode_block,
                                  'programs'))
        self.make_tokens(reqs)
        for r in reqs:
            self.submit(r)
        while self.live:
            self.step()
        if self.failed:
            raise RuntimeError(f'warm-up requests failed: {self.failed}')
        self.finished.clear()
        self.em = window.Emissions()
        self.reset_counts()

    def reset_counts(self):
        self.rows_sum, self.rounds = 0.0, 0

    def queue_waits(self, indices):
        """Seconds from submit to admission by the program's own request
        ledger, where it kept a record."""
        out = []
        for idx in indices:
            h = self.finished.get(idx, (None,))[0]
            rec = getattr(h, '_ledger_rec', None) if h is not None else None
            if rec is None and h is not None and h.inner is not None:
                rec = getattr(h.inner, '_ledger_rec', None)
            if rec is not None:
                out.append((idx, float(rec.phases.get('queue_wait', 0.0))))
        return out

    def free(self):
        """Drop the program's state so that the reference fits."""
        served = {i: (list(h.tokens), r) for i, (h, r) in
                  self.finished.items()}
        freed = 0
        for rep in self.router.replicas:
            eng = rep.engine
            freed += free_arrays(
                [p._data for p in eng.model.parameters()],
                [v for v in vars(eng).values()
                 if not callable(v) and not hasattr(v, '__dict__')],
                eng.pool.rows)
        log(f'program state freed: {freed / 2**30:.2f} GiB')
        self.router = None
        self.live.clear()
        self.finished.clear()
        gc.collect()
        self.jax.clear_caches()
        gc.collect()
        return served


def check_served(run, server, served, candidates, max_out):
    """The reference pass over a seeded sample of finished requests."""
    import jax
    import jax.numpy as jnp
    cfg, tr, refmod = run.config, run.traffic, server.refmod
    n_check = int(tr['check_requests'])
    pool = sorted(i for i in candidates if i in served)
    if not pool:
        run.check('served_requests_to_check', 0, 1, ok=False)
        return
    longest = max(pool, key=lambda i: (served[i][1].prompt_len
                                       + served[i][1].output_len, i))
    rest = [i for i in pool if i != longest]
    order = T.rng(run.seed, 30).permutation(len(rest))
    sample = [longest] + [rest[k] for k in order[:n_check - 1]]
    max_len = server.max_length

    def gaps_fn(mode):
        def f(params, ids, start):
            h = refmod.hidden_states(cfg, params, ids[None], mode)[0]
            rows = jax.lax.dynamic_slice_in_dim(
                jnp.pad(h, ((0, max_out), (0, 0))), start, max_out, 0)
            return refmod.logits_of(cfg, params, rows, mode)
        return jax.jit(f)

    ref_fn = gaps_fn('f32')
    ctl_fn = gaps_fn(run.control) if run.control else None
    weights = C.make_weights(server.shapes, run.seed, cfg['param_dtype'])
    worst, worst_ctl, n_tok, agree = 0.0, 0.0, 0, 0
    t0 = time.perf_counter()
    for idx in sample:
        toks, r = served[idx]
        prompt = server.tokens[idx]
        n = len(toks)
        ids = np.zeros(max_len, np.int32)
        seq = (prompt + toks)[:len(prompt) + n - 1]
        ids[:len(seq)] = seq
        tk = np.zeros(max_out, np.int32)
        tk[:n] = toks
        logits = ref_fn(weights, jnp.asarray(ids), len(prompt) - 1)
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, jnp.asarray(tk)[:, None], -1)[:, 0]
        gap = np.asarray(best - got)[:n]
        worst = max(worst, float(gap.max()))
        agree += int((gap == 0).sum())
        n_tok += n
        if ctl_fn is not None:
            lc = ctl_fn(weights, jnp.asarray(ids), len(prompt) - 1)
            first = jnp.argmax(lc, -1)
            gc_ = np.asarray(best - jnp.take_along_axis(
                logits, first[:, None], -1)[:, 0])[:n]
            worst_ctl = max(worst_ctl, float(gc_.max()))
            log(f'control {run.control} request {idx}: widest gap '
                f'{float(gc_.max())!r}, argmax agreement '
                f'{float((gc_ == 0).mean()):.3f}')
    log(f'reference: {len(sample)} requests, {n_tok} served tokens, '
        f'{agree} equal to the reference argmax, '
        f'{time.perf_counter() - t0:.1f} s')
    run.check('served_logit_gap_widest', worst, run.limits['served_gap'])
    run.raw['served_tokens_checked'] = n_tok
    if ctl_fn is not None:
        log(f'control {run.control} served_logit_gap_widest {worst_ctl!r}')


def finish(run, server, t_open, t_close, window_reqs, compiles, extra_raw):
    """From stamps to raw numbers, then the check."""
    em = server.em
    per_s = window.per_second_tokens(em, t_open, t_close)
    log('per_second_tokens', per_s)
    tpot = window.tpot_samples(em, t_open, t_close)
    run.raw.update({
        'out_tokens_per_s': window.out_tokens_per_s(em, t_open, t_close),
        'tpot_s': tpot,
        'window_s': t_close - t_open,
        'real_rows_share': 100.0 * server.rows_sum / max(server.rounds, 1)
        / (server.slots * server.max_length),
        'real_rows_mean': server.rows_sum / max(server.rounds, 1),
        'decode_rounds': server.rounds,
        'slots': server.slots, 'max_length': server.max_length,
        'decode_block': server.decode_block,
        'compiles_in_window': compiles,
    })
    run.raw.update(extra_raw)
    run.attempted = len(window_reqs)
    run.failed = len([r for r in window_reqs if r.index in server.failed])
    if run.trace:
        run.reduce_trace()
    candidates = [r.index for r in window_reqs]
    served = server.free()
    check_served(run, server, served, candidates,
                 max(r.output_len for r in window_reqs))
    run.check('compiles_in_window', compiles, 0)
    run.check('failed_requests', run.failed, 0)
