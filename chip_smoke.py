"""chip_smoke.py — the standing proof that paddle_tpu starts on the chip.

Drives the two normal entry points once, end to end, at the widths of
the one model this repo has a chip record for (`LlamaForCausalLM` over
`bench.GPT3_SHAPE`: h2048, 16 x 128 heads, ff 5504, vocab 50,304; bf16):

  train1  `jit.TrainStep` + `AdamW(moment_dtype='bfloat16')`, batch
          2 x 2048 at the full 24 layers, a few steps on one fixed batch.
  serve1  the same 24 layers behind `Router(ReplicaSet(model, 1, ...))`
          -> `InferenceEngine`, 8 slots x 1024, twelve greedy requests
          (two through an `AdapterBank`), row layout and then
          `kv_page_size=16`.
  fleet4  only when four chips are visible: `fleet.init` (dp2 x mp2,
          ZeRO) -> `fleet.DistTrainStep`, global batch 4 x 2048.

It checks what comes out by the repo's own means (loss near ln V and
falling, the Mosaic custom calls counted in the lowered step, first
tokens against `model.generate`, the adapter kernel against its lax
reference, zero compiles after warm-up) and FAILS — non-zero exit, no
result line — when any phase fails or when JAX finds no TPU.

One process per chip: this parent imports neither jax nor paddle_tpu
(a process that touched JAX holds the chip and its children then cannot
attach); every phase is a child process run one after another, the
first of which only reports the devices.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --rehearse-cpu  # toy sizes on the CPU, kernels
                                         # interpreted; prints platform:
                                         # cpu and proves nothing about
                                         # the chip

The last line of stdout on success is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`;
the line before it is the full summary (versions, per-phase wall and
compile seconds, persistent-cache hits and misses, peak HBM), also
written to `chiprun_out/chip_smoke.json`.
"""
from __future__ import annotations

import json
import math
import os
import signal
import subprocess
import sys
import time

BUDGET_S = 1140          # the contract allows 1200 s, compilation included
HERE = os.path.dirname(os.path.abspath(__file__))

# The real sizes: full width AND full depth in every phase (a cold
# compile of all of it fits the time limit — CHANGES.md PR 21 has the
# walls), so the HBM peaks are the model's own.
FULL = dict(
    train_layers=24, train_batch=2, seq=2048, steps=4,
    serve_layers=24, slots=8, max_length=1024,
    prompt_lens=(64, 120, 200, 256, 310, 384, 450, 512, 96, 180, 333, 500),
    new_tokens=(16, 24, 32, 40, 48, 64, 20, 28, 36, 44, 52, 60),
    page_size=16, fleet_batch=4)
# The rehearsal: same code path, toy sizes that still satisfy the kernel
# gates (head_dim >= 64, seq % 128 == 0, vocab >= 8192 and % 128 == 0).
TOY = dict(
    train_layers=2, train_batch=2, seq=128, steps=3,
    serve_layers=2, slots=4, max_length=64,
    prompt_lens=(8, 14, 20, 30, 9, 17),
    new_tokens=(4, 6, 8, 5, 7, 4),
    page_size=16, fleet_batch=4)
TOY_WIDTHS = dict(vocab_size=8192, hidden_size=256, intermediate_size=512,
                  num_attention_heads=2, num_key_value_heads=2,
                  max_position_embeddings=256)
ADAPTERS = {2: 'ad0', 5: 'ad1'}    # request index -> adapter it runs under


def _log(msg):
    print(f'[chip_smoke] {msg}', file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the parent: orchestration only — no jax, no paddle_tpu
# ---------------------------------------------------------------------------

def _run_child(phase, rehearse, timeout_s, env):
    """Run one phase in its own process (own session, so a timeout can
    stop whatever it started). Returns the phase's JSON result, or an
    `{'ok': False, 'error': ...}` record."""
    cmd = [sys.executable, os.path.abspath(__file__), '--phase', phase]
    if rehearse:
        cmd.append('--rehearse-cpu')
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env,
                            cwd=HERE, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=max(timeout_s, 1))
    except subprocess.TimeoutExpired:
        out = ''
    finally:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    wall = round(time.monotonic() - t0, 1)
    lines = [ln for ln in (out or '').strip().splitlines() if ln.strip()]
    res = None
    if lines:
        try:
            res = json.loads(lines[-1])
        except ValueError:
            res = None
    if not isinstance(res, dict):
        res = {'ok': False,
               'error': f'phase produced no result (exit {proc.returncode})'}
    if proc.returncode != 0:
        res['ok'] = False
        res.setdefault('error', f'exit {proc.returncode}')
    res['wall_s'] = wall
    return res


def main(argv):
    rehearse = '--rehearse-cpu' in argv
    if '--phase' in argv:
        return _child_main(argv[argv.index('--phase') + 1], rehearse)
    t_start = time.monotonic()
    env = dict(os.environ)
    if rehearse:
        env['JAX_PLATFORMS'] = 'cpu'
        if '--xla_force_host_platform_device_count' not in env.get(
                'XLA_FLAGS', ''):
            env['XLA_FLAGS'] = (env.get('XLA_FLAGS', '') + ' --xla_force_'
                                'host_platform_device_count=8').strip()

    def left():
        return BUDGET_S - (time.monotonic() - t_start)

    summary = {'rehearsal': rehearse, 'phases': {}}
    probe = _run_child('probe', rehearse, min(300, left()), env)
    summary.update({k: probe.get(k) for k in (
        'platform', 'device_kind', 'device_count', 'jax', 'jaxlib',
        'libtpu', 'python')})
    failed = []
    if not probe.get('ok'):
        failed.append(f'probe: {probe.get("error")}')
    elif probe['platform'] != 'tpu' and not rehearse:
        failed.append(
            f'no TPU found: jax.devices()[0].platform is '
            f'{probe["platform"]!r} (JAX_PLATFORMS='
            f'{os.environ.get("JAX_PLATFORMS")!r}); this check only means '
            f'something on the chip — use --rehearse-cpu for a toy run')
    if not failed:
        phases = ['train1', 'serve1']
        if probe['device_count'] >= 4:
            phases.append('fleet4')
        else:
            summary['phases']['fleet4'] = (
                f'not run ({probe["device_count"]} device)')
            _log(f'fleet4: not run ({probe["device_count"]} device)')
        for phase in phases:
            _log(f'phase {phase} starting ({left():.0f}s of budget left)')
            res = _run_child(phase, rehearse, left(), env)
            summary['phases'][phase] = res
            _log(f'phase {phase}: ok={res.get("ok")} wall={res["wall_s"]}s')
            if not res.get('ok'):
                failed.append(f'{phase}: {res.get("error")}')
    summary['wall_s'] = round(time.monotonic() - t_start, 1)
    summary['ok'] = not failed
    summary['failed'] = failed
    try:
        os.makedirs(os.path.join(HERE, 'chiprun_out'), exist_ok=True)
        with open(os.path.join(HERE, 'chiprun_out', 'chip_smoke.json'),
                  'w') as f:
            json.dump(summary, f, indent=1)
    except OSError as exc:
        _log(f'could not write chiprun_out/chip_smoke.json: {exc}')
    if failed:
        # no result on stdout: the summary of a failed run is a log
        print(json.dumps(summary), file=sys.stderr)
        for f_ in failed:
            _log(f'FAILED {f_}')
        return 1
    print(f'platform: {summary["platform"]}')
    print(json.dumps(summary))
    print(json.dumps({'ok': True, 'device': {
        'platform': summary['platform'], 'kind': summary['device_kind'],
        'count': summary['device_count']}}))
    return 0


# ---------------------------------------------------------------------------
# the children: one phase each, in a process of its own
# ---------------------------------------------------------------------------

def _child_main(phase, rehearse):
    import traceback
    try:
        if phase == 'probe':
            res = _phase_probe()
        else:
            h = _Harness(rehearse)
            res = {'train1': _phase_train1, 'serve1': _phase_serve1,
                   'fleet4': _phase_fleet4}[phase](h)
            res.update(h.compile_report())
        res['ok'] = True
    except Exception as exc:   # the phase boundary: report, then fail
        traceback.print_exc()
        res = {'ok': False, 'error': f'{type(exc).__name__}: {exc}'[:2000]}
    print(json.dumps(res), flush=True)
    return 0 if res['ok'] else 1


def _phase_probe():
    """Report what JAX sees and exit — jax only, nothing of the repo."""
    import platform
    import jax
    import jaxlib
    devs = jax.devices()
    from importlib import metadata
    try:
        libtpu = metadata.version('libtpu')
    except metadata.PackageNotFoundError:
        libtpu = None
    return {'ok': True, 'platform': devs[0].platform,
            'device_kind': devs[0].device_kind, 'device_count': len(devs),
            'jax': jax.__version__, 'jaxlib': jaxlib.__version__,
            'libtpu': libtpu, 'python': platform.python_version()}


class _Harness:
    """What every phase shares: the sizes, the compile cache, the compile
    counters, and — in the rehearsal only — the Pallas gate forced on
    with every kernel interpreted (the process ends with its phase, so
    nothing is restored)."""

    def __init__(self, rehearse):
        import jax
        from paddle_tpu import observability as obs
        from paddle_tpu import programs
        self.rehearse = rehearse
        self.sizes = TOY if rehearse else FULL
        self.pallas_traced = 0
        self.cache_dir = programs.ensure_compile_cache()
        self.reg = obs.get_registry()
        self.platform = jax.devices()[0].platform
        if rehearse:
            # force the gates on and interpret every kernel (the generic
            # interpreter: the TPU one runs on io_callbacks, which
            # jax.checkpoint refuses)
            from jax.experimental import pallas as pl
            from paddle_tpu.ops import pallas
            pallas._pallas_enabled = lambda: True
            pallas.pallas_ce_enabled.cache_clear()
            call = pl.pallas_call

            def interpreted(*a, **kw):
                self.pallas_traced += 1
                return call(*a, **dict(kw, interpret=True))
            pl.pallas_call = interpreted
        elif self.platform != 'tpu':
            raise RuntimeError(f'phase needs a TPU, found {self.platform!r}')

    def widths(self, layers, **extra):
        import bench
        from paddle_tpu.nlp import LlamaConfig
        w = dict(TOY_WIDTHS if self.rehearse else bench.GPT3_SHAPE)
        w['num_hidden_layers'] = layers
        return LlamaConfig(**w, **extra)

    def compiles(self):
        return self.reg.value('paddle_jit_compiles_total')

    def compile_report(self):
        v = self.reg.value
        return {'compile_s': round(v('paddle_jit_compile_seconds_total'), 1),
                'trace_s': round(v('paddle_jit_trace_seconds_total'), 1),
                'compiles': int(v('paddle_jit_compiles_total')),
                'cache_hits': int(v('paddle_jit_cache_hits_total')),
                'cache_misses': int(v('paddle_jit_cache_misses_total')),
                'cache_dir': self.cache_dir}

    def mosaic_calls(self, lowered):
        """Count the Mosaic custom calls in a lowered step by kernel —
        the proof that the kernels are on the path (a gate's return
        value proves only that the gate returned)."""
        import collections
        import re
        if self.rehearse:
            # interpreted kernels leave no custom call; count the traces
            if self.pallas_traced < 3:
                raise AssertionError(
                    f'only {self.pallas_traced} pallas_call traces: the '
                    f'flash forward, flash backward and fused CE kernels '
                    f'are not all on the path')
            return f'{self.pallas_traced} pallas_call traces, interpreted'
        names = collections.Counter(re.findall(
            r'kernel_name\s*=\s*"([^"]+)"', lowered.as_text()))
        out = {'flash_fwd': 0, 'flash_bwd': 0, 'fused_ce': 0, 'other': 0}
        for name, n in names.items():
            if 'flash' in name:
                bwd = 'dq' in name or 'dkv' in name or 'bwd' in name
                out['flash_bwd' if bwd else 'flash_fwd'] += n
            elif '_ce_' in name:
                out['fused_ce'] += n
            else:
                out['other'] += n
        missing = [k for k in ('flash_fwd', 'flash_bwd', 'fused_ce')
                   if not out[k]]
        if missing:
            raise AssertionError(
                f'compiled step has no Mosaic call for {missing}: '
                f'{dict(names)}')
        return out


def _lm_loss(vocab):
    import paddle_tpu.nn.functional as F

    def loss_fn(logits, labels):
        # the LM objective: predict token t+1 from positions <= t
        return F.cross_entropy(logits[:, :-1].reshape([-1, vocab]),
                               labels[:, 1:].reshape([-1]))
    return loss_fn


def _check_losses(losses, vocab):
    import numpy as np
    ln_v = math.log(vocab)
    if not np.isfinite(losses).all():
        raise AssertionError(f'non-finite loss: {losses}')
    if abs(losses[0] - ln_v) > 0.05 * ln_v:
        raise AssertionError(
            f'step-0 loss {losses[0]:.4f} is not within 5% of '
            f'ln({vocab}) = {ln_v:.4f}')
    if not losses[-1] < losses[0]:
        raise AssertionError(f'loss did not fall on a fixed batch: {losses}')


def _later_steps(h, step, ids, losses):
    """The steps after the first: they must not compile (the state a
    step returns has to be accepted back as it is), and their mean wall
    is the smoke's only steady figure."""
    warm = h.compiles()
    t0 = time.perf_counter()
    n = h.sizes['steps'] - 1
    losses += [float(step(ids, ids).numpy()) for _ in range(n)]
    later_s = (time.perf_counter() - t0) / n
    if h.compiles() != warm:
        raise AssertionError(
            f'{h.compiles() - warm:.0f} compiles after the first step')
    return later_s


def _gb(n):
    return round(n / 2**30, 2)


def _phase_train1(h):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import programs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import LlamaForCausalLM

    sz = h.sizes
    cfg = h.widths(sz['train_layers'], use_recompute='dots_no_batch')
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    on = {next(iter(p.value.devices())).platform
          for p in model.parameters()}
    if on != {h.platform}:
        raise AssertionError(f'parameters live on {on}, not {h.platform}')
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters(),
                                 moment_dtype='bfloat16')
    step = TrainStep(model, _lm_loss(cfg.vocab_size), opt)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (sz['train_batch'], sz['seq']))
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    losses = [float(step(ids, ids).numpy())]
    first_step_s = time.perf_counter() - t0
    later_s = _later_steps(h, step, ids, losses)
    _check_losses(losses, cfg.vocab_size)
    stats = dev.memory_stats() or {}
    record = programs.get_store().catalog.record('train_step', kind='train')
    return {
        'platform': h.platform,
        'params_m': round(sum(int(np.prod(p.shape))
                              for p in model.parameters()) / 1e6, 1),
        'layers': cfg.num_hidden_layers,
        'batch': sz['train_batch'], 'seq': sz['seq'],
        'losses': [round(x, 4) for x in losses],
        'first_step_s': round(first_step_s, 1),
        'step_s': round(later_s, 3),
        'mosaic_calls': h.mosaic_calls(step.lower(ids, ids)),
        'peak_hbm_gb': _gb(stats.get('peak_bytes_in_use', 0)),
        'hbm_limit_gb': _gb(stats.get('bytes_limit', 0)),
        'xla_step_peak_gb': _gb(record.peak_memory_bytes),
    }


def _requests(sz, vocab):
    import numpy as np
    rng = np.random.RandomState(1)
    return [(rng.randint(3, vocab, (n,)).tolist(), m)
            for n, m in zip(sz['prompt_lens'], sz['new_tokens'])]


def _reference_first_tokens(model, prompts):
    """`model.generate` on every prompt at once: left-padded to one
    length so the whole reference is one compile."""
    import numpy as np
    import paddle_tpu as paddle
    width = max(len(p) for p in prompts)
    ids = np.zeros((len(prompts), width), np.int64)
    keep = np.zeros((len(prompts), width), np.int64)
    for i, p in enumerate(prompts):
        ids[i, width - len(p):] = p
        keep[i, width - len(p):] = 1
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=1,
                            decode_strategy='greedy_search',
                            eos_token_id=-1,
                            attention_mask=paddle.to_tensor(keep))
    return out.numpy()[:, 0].tolist()


def _near_tie(model, prompt, tok_ref, tok_got):
    """bf16 tolerance for an argmax: two implementations of the same
    forward may disagree on the winner only when their logits for the
    two candidates are within rounding of each other. One bf16 ulp of
    the largest logit, a few times over for the depth of the stack."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.jit import functional_call, functional_state
    params, frozen, buffers = functional_state(model)
    logits = jax.jit(lambda p, f, b, ids: functional_call(
        model, p, f, b, (ids,), {})[0][0, -1].astype(jnp.float32))(
            params, frozen, buffers, jnp.asarray([prompt]))
    logits = np.asarray(logits)
    tol = 4 * 2.0 ** -8 * float(np.abs(logits).max())
    gap = float(logits.max() - logits[tok_got])
    return gap <= tol, {'gap': round(gap, 4), 'tol': round(tol, 4),
                        'ref': int(tok_ref), 'got': int(tok_got)}


def _check_adapter_kernel(h, bank, hidden):
    """The compiled `adapter_matmul` against its lax reference, on the
    bank the requests ran under, at the decode and the prefill shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_kernels as pk
    arrays = bank.device_arrays()
    fac = next(iter(arrays['factors'].values()))
    rng = np.random.RandomState(2)
    worst = 0.0
    for bsz, t in ((h.sizes['slots'], 1), (1, max(h.sizes['prompt_lens']))):
        x = jnp.asarray(rng.standard_normal((bsz, t, hidden)), jnp.bfloat16)
        # every resident adapter, an empty slot and the zero base slot
        rows = jnp.asarray((np.arange(bsz) + 1) % (bank.capacity + 1),
                           jnp.int32)
        got = jax.jit(lambda *a: pk.adapter_matmul(
            *a, interpret=h.rehearse))(x, fac['a'], fac['b'], rows,
                                       arrays['scale'])
        ref = pk.adapter_matmul_reference(x, fac['a'], fac['b'], rows,
                                          arrays['scale'])
        got, ref = (np.asarray(v.astype(jnp.float32)) for v in (got, ref))
        if not np.isfinite(got).all():
            raise AssertionError('adapter_matmul produced non-finite values')
        if not np.abs(ref).max() > 0:
            raise AssertionError('adapter reference delta is all zero')
        # one bf16 rounding of the output plus the bf16 passes of an f32
        # matmul on the MXU
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        worst = max(worst, err)
        if err > 2.0 ** -6:
            raise AssertionError(
                f'adapter_matmul [{bsz},{t},{hidden}] is {err:.4f} of the '
                f'reference range away from adapter_matmul_reference')
    return round(worst, 5)


def _serve_layout(h, model, bank, requests, **layout):
    """All requests through Router -> ReplicaSet(1) -> InferenceEngine.
    Warm-up ends at the first retirement: every prefill bucket has run
    by then (the first `slots` prompts cover them all), and every later
    admission refills a retired seat — none may compile."""
    from paddle_tpu.serving import ReplicaSet, Router, SamplingParams
    sz = h.sizes
    router = Router(ReplicaSet(model, 1, num_slots=sz['slots'],
                               max_length=sz['max_length'],
                               adapter_bank=bank, **layout))
    t0 = time.perf_counter()
    handles = [
        router.submit(p, SamplingParams(max_new_tokens=m, eos_token_id=-1),
                      adapter_id=ADAPTERS.get(i))
        for i, (p, m) in enumerate(requests)]
    warm = None
    while not all(hd.done for hd in handles):
        router.step()
        if warm is None and any(hd.done for hd in handles):
            warm = h.compiles()
    for i, (hd, (_, m)) in enumerate(zip(handles, requests)):
        if hd.error is not None or len(hd.tokens) != m:
            raise AssertionError(
                f'request {i} did not finish: status={hd.status} '
                f'tokens={len(hd.tokens)}/{m} error={hd.error!r}')
    after_warm = h.compiles() - warm
    if after_warm:
        raise AssertionError(
            f'{after_warm:.0f} compiles after warm-up (layout {layout})')
    eng = router.replicas[0].engine
    return {'tokens': [list(hd.tokens) for hd in handles],
            'wall_s': round(time.perf_counter() - t0, 1),
            'decode_rounds': eng._counts['decode_rounds'],
            'programs': sorted(k for k, v in eng._trace_counts.items() if v),
            'compiles_after_warmup': int(after_warm)}


def _phase_serve1(h):
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaForCausalLM
    from paddle_tpu.serving import AdapterBank, make_adapter_factors

    sz = h.sizes
    cfg = h.widths(sz['serve_layers'])
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    requests = _requests(sz, cfg.vocab_size)
    base = [i for i in range(len(requests)) if i not in ADAPTERS]
    ref_first = _reference_first_tokens(model, [requests[i][0] for i in base])

    bank = AdapterBank(model, capacity=4, rank=8,
                       targets=('q_proj', 'v_proj'))
    for seed, name in enumerate(sorted(set(ADAPTERS.values())), 1):
        bank.load(name, make_adapter_factors(bank, seed=seed))
    out = {'platform': h.platform, 'layers': cfg.num_hidden_layers,
           'slots': sz['slots'], 'max_length': sz['max_length'],
           'requests': len(requests), 'adapter_sites': len(bank.sites)}
    tokens = {}
    for name, layout in (('row', {}),
                         ('paged', {'kv_page_size': sz['page_size']})):
        gc.collect()    # the previous layout's KV pool must be gone first
        out[name] = run = _serve_layout(h, model, bank, requests, **layout)
        tokens[name] = toks = run.pop('tokens')
        exact, ties = 0, []
        for i, want in zip(base, ref_first):
            got = toks[i][0]
            if got == want:
                exact += 1
                continue
            ok, detail = _near_tie(model, requests[i][0], want, got)
            if not ok:
                raise AssertionError(
                    f'{name} request {i}: first token {got} is not '
                    f'model.generate\'s {want} and not a bf16 near-tie: '
                    f'{detail}')
            ties.append(dict(detail, request=i))
        run.update(first_token_exact=f'{exact}/{len(base)}',
                   first_token_near_ties=ties)
    row, paged = tokens['row'], tokens['paged']
    same = [a == b for a, b in zip(row, paged)]
    out['row_vs_paged'] = {
        'identical_requests': f'{sum(same)}/{len(same)}',
        'first_divergence': {
            str(i): next(k for k, (x, y) in enumerate(zip(a, b)) if x != y)
            for i, (a, b) in enumerate(zip(row, paged)) if a != b}}
    out['adapter_kernel_max_rel_err'] = _check_adapter_kernel(
        h, bank, cfg.hidden_size)
    out['peak_hbm_gb'] = _gb((jax.devices()[0].memory_stats() or {}).get(
        'peak_bytes_in_use', 0))
    return out


def _phase_fleet4(h):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env, fleet
    from paddle_tpu.distributed.parallel_layers import get_sharding
    from paddle_tpu.nlp import LlamaForCausalLM

    sz = h.sizes
    n = len(jax.devices())
    dev0 = jax.devices()[0]
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': n // 2, 'mp_degree': 2,
                               'pp_degree': 1, 'sep_degree': 1}
    strategy.sharding = True
    fleet.init(is_collective=True, strategy=strategy)
    cfg = h.widths(sz['train_layers'], use_recompute='dots_no_batch',
                   tensor_parallel=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    dev0_peak_at_init = (dev0.memory_stats() or {}).get(
        'peak_bytes_in_use', 0)
    model = fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=model.parameters(),
        moment_dtype='bfloat16'))
    step = fleet.DistTrainStep(model, _lm_loss(cfg.vocab_size), opt)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (sz['fleet_batch'], sz['seq']))
    t0 = time.perf_counter()
    losses = [float(step(ids, ids).numpy())]
    first_step_s = time.perf_counter() - t0
    later_s = _later_steps(h, step, ids, losses)
    _check_losses(losses, cfg.vocab_size)
    whole = [name for name, p in model.named_parameters()
             if 'mp' in tuple(get_sharding(p) or ())
             and any(s.device == dev0 and s.data.shape == p.value.shape
                     for s in p.value.addressable_shards)]
    if whole:
        raise AssertionError(
            f'mp-sharded parameters resident whole on device 0: {whole[:5]}')
    in_use = [(d.memory_stats() or {}).get('bytes_in_use', 0)
              for d in jax.devices()]
    if h.platform == 'tpu' and max(in_use) > 1.5 * min(in_use):
        raise AssertionError(
            f'per-device bytes_in_use differ by more than 1.5x: {in_use}')
    return {
        'platform': h.platform, 'mesh': dict(env.get_mesh().shape),
        'layers': cfg.num_hidden_layers,
        'batch': sz['fleet_batch'], 'seq': sz['seq'],
        'losses': [round(x, 4) for x in losses],
        'first_step_s': round(first_step_s, 1),
        'step_s': round(later_s, 3),
        'mosaic_calls': h.mosaic_calls(step.lower(ids, ids)),
        'bytes_in_use_gb': [_gb(b) for b in in_use],
        'peak_hbm_gb': [_gb((d.memory_stats() or {}).get(
            'peak_bytes_in_use', 0)) for d in jax.devices()],
        'device0_peak_at_init_gb': _gb(dev0_peak_at_init),
    }


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
