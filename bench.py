"""Single-chip headline benchmark: GPT-3-1.3B-class decoder pretraining
step — tokens/sec + MFU on the available chip (SURVEY.md §6,
BASELINE.json configs[2]).

Prints exactly ONE JSON line:
  {"metric": ..., "value": tokens/sec, "unit": "tokens/s",
   "vs_baseline": MFU / 0.40, ...}
vs_baseline normalizes against the reference's A100-class MFU bar
(BASELINE.json: ">= A100 MFU (~40%)" on matmul-dominant decoders).

The headline model is the GPT-3 XL shape (h=2048, L=24, 16 heads x 128,
seq 2048, ~1.3B params) built on the Llama block (RMSNorm/SwiGLU/RoPE —
the TPU-native decoder this framework optimizes); `use_recompute='dots'`
plus bf16 Adam moments are what fit params+optimizer+activations into a
single v5e's 16 GB HBM. Falls back to the round-2 740M config (and
reports so) if the 1.3B step OOMs on smaller chips.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _peak_flops(device) -> float:
    """Peak bf16 FLOP/s by device kind. Delegates to the shared
    observability.cost table (one source of truth for the headline MFU
    here and the paddle_mfu/roofline gauges; PADDLE_PEAK_FLOPS
    overrides both identically)."""
    from paddle_tpu.observability.cost import device_peaks
    peaks = device_peaks(device)
    if not peaks['peak_flops']:
        # an MFU normalized against a guessed peak is a wrong number
        # under a device metric's name
        raise RuntimeError(
            f'no peak FLOP/s known for device_kind '
            f'{peaks["device_kind"]!r} (source={peaks["source"]}): add '
            f'it to observability.cost.PEAK_SPECS or set '
            f'PADDLE_PEAK_FLOPS')
    return peaks['peak_flops']


# the GPT-3 XL geometry shared by the headline train phase and the
# decode phase
GPT3_SHAPE = dict(vocab_size=50304, hidden_size=2048,
                  intermediate_size=5504, num_hidden_layers=24,
                  num_attention_heads=16, num_key_value_heads=16,
                  max_position_embeddings=4096)


def _configs(on_tpu):
    from paddle_tpu.nlp import LlamaConfig
    if not on_tpu:
        return [('llama_tiny', LlamaConfig.tiny(), 2, 64, 3, 1, 'float32')]
    # remat policy (r4 sweep on v5e, BENCH experiments E1-E4):
    # 'dots_no_batch' keeps weight-matmul outputs and recomputes only
    # attention + elementwise in backward — at batch 2 the saved outputs
    # (~2.5 GB) fit beside params+moments and MFU jumps 0.50 -> 0.64
    # vs full-block remat at batch 8 (whose extra forward is ~1/4 of
    # step flops). Full-remat rungs remain as OOM fallbacks.
    gpt3_dots = LlamaConfig(use_recompute='dots_no_batch', **GPT3_SHAPE)
    gpt3_full = LlamaConfig(use_recompute=True, **GPT3_SHAPE)
    m740 = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5504,
        num_hidden_layers=12, num_attention_heads=16,
        num_key_value_heads=16, max_position_embeddings=4096)
    return [
        # b4 first: the pallas CE avoids the fp32 [B*S, V] logits buffer,
        # which is what OOMed b4 in r4 — falls through to b2 if it still
        # doesn't fit
        ('gpt3_1p3b', gpt3_dots, 4, 2048, 10, 2, 'bfloat16'),
        ('gpt3_1p3b', gpt3_dots, 2, 2048, 10, 2, 'bfloat16'),
        ('gpt3_1p3b', gpt3_full, 8, 2048, 10, 2, 'bfloat16'),
        ('gpt3_1p3b', gpt3_full, 4, 2048, 10, 2, 'bfloat16'),
        ('llama_740m', m740, 4, 2048, 10, 2, 'bfloat16'),
    ]


def _7b_configs():
    """Llama-2 7B-shaped ladder (BASELINE headline #2): FULL 7B
    hidden/FFN/head geometry (h=4096, ffn=11008, 32 heads, seq 4096).

    r5: Adam moments host-offloaded (optimizer offload='host',
    VERDICT r4 #3 — upstream fleet sharding `offload`) so HBM holds only
    bf16 params + grads + activations: 24 layers ≈ 10.3 GB params and
    16 layers ≈ 7.1 GB now fit where the r4 in-HBM-moments ceiling was
    8 layers. Deepest-first ladder; each rung flags depth + offload, and
    the streamed-moment transfer cost shows up honestly in step_time."""
    from paddle_tpu.nlp import LlamaConfig

    def mk(layers, remat):
        return LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_attention_heads=32, num_key_value_heads=32,
            max_position_embeddings=4096, num_hidden_layers=layers,
            use_recompute=remat)
    # throughput ladder: deepest config whose FULL state (params + grads
    # + bf16 moments) lives in HBM — this is the tokens/sec-per-chip
    # number comparable run to run
    fast = [
        ('llama2_7b_shape_8L', mk(8, 'dots_no_batch'), 1, 4096, 6, 2,
         'bfloat16', None),
        ('llama2_7b_shape_8L', mk(8, True), 2, 2048, 6, 2, 'bfloat16',
         None),
    ]
    # depth rung (reported separately): 16L with Adam moments
    # host-offloaded — 2x the in-HBM depth ceiling. The moment streaming
    # crosses the host link every step, so its step_time measures the
    # offload tradeoff, not model throughput.
    # No 24L rung: bf16 params+grads alone are 20.6 GB — past the chip's
    # HBM no matter where the moments live.
    deep = [
        ('llama2_7b_shape_16L', mk(16, True), 1, 2048, 3, 1, 'bfloat16',
         'host'),
    ]
    return fast, deep


def _run_config(name, cfg, batch, seq, steps, warmup, dtype,
                offload=None):
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if dtype == 'bfloat16':
        model.bfloat16()
    big = sum(int(np.prod(p.shape)) for p in model.parameters()) > 1e9
    opt = paddle.optimizer.AdamW(
        learning_rate=3e-4, parameters=model.parameters(),
        multi_precision=(dtype == 'bfloat16' and not big),
        # >1B params: bf16 moments are the difference between fitting a
        # single 16GB chip and OOM (fp32 m+v alone would be 10.7 GB)
        moment_dtype=('bfloat16' if big else None),
        offload=offload)

    def loss_fn(logits, labels):
        # true LM objective: predict token t+1 from positions <= t
        lg = logits[:, :-1].reshape([-1, cfg.vocab_size])
        lb = labels[:, 1:].reshape([-1])
        return F.cross_entropy(lg, lb)

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    batches = [rng.randint(0, cfg.vocab_size, (batch, seq))
               for _ in range(4)]  # rotate data: no single-batch cache luck

    for i in range(warmup):
        loss = step(batches[i % 4], batches[i % 4])
    float(loss.numpy())  # sync

    t0 = time.perf_counter()
    for i in range(steps):
        loss = step(batches[i % 4], batches[i % 4])
    final_loss = float(loss.numpy())  # sync on the last step
    dt = (time.perf_counter() - t0) / steps

    peak_hbm = 0
    try:
        ma = step.memory_analysis(batches[0], batches[0])
        peak_hbm = int(getattr(ma, 'peak_memory_in_bytes', 0)) or (
            int(ma.argument_size_in_bytes) + int(ma.temp_size_in_bytes)
            + int(ma.output_size_in_bytes) - int(ma.alias_size_in_bytes))
    except Exception:  # paddle-lint: disable=swallowed-exception -- AOT introspection is best-effort; never kill the bench
        pass  # AOT introspection is best-effort; never kill the bench

    result_offload = offload is not None
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # model FLOPs: 3x forward (fwd + 2x bwd); fwd = 2*N_matmul*B*S weight
    # matmuls + 4*B*S^2*H attention matmuls per layer (remat recompute
    # FLOPs deliberately NOT counted — MFU measures model math only)
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    qkvo = h * (cfg.num_attention_heads * cfg.head_dim) * 2 \
        + h * (cfg.num_key_value_heads * cfg.head_dim) * 2
    n_matmul = L * (qkvo + 3 * h * cfg.intermediate_size) \
        + h * cfg.vocab_size  # lm head included, embed gather excluded
    fwd_flops = (2 * n_matmul * batch * seq
                 + L * 4 * batch * seq * seq * h)
    step_flops = 3 * fwd_flops
    mfu = step_flops / dt / _peak_flops(jax.devices()[0])
    return {
        'tokens_per_sec': batch * seq / dt,
        'mfu': mfu,
        'step_time_s': dt,
        'loss': final_loss,
        'params_m': round(n_params / 1e6, 1),
        'batch': batch, 'seq': seq, 'dtype': dtype,
        'peak_hbm_gb': round(peak_hbm / 2**30, 2),
        'offload_optimizer': result_offload,
        'layers': cfg.num_hidden_layers,
    }


def _run_7b_overfit(steps=300, target=7.0):
    """Correctness signal for the 7B geometry (VERDICT r4 Weak #3 / #4):
    up to 300 AdamW steps on ONE fixed small batch must drive the loss well
    under ln(32000)=10.37 — a throughput-shaped block that can't learn
    would stay pinned near random init."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_attention_heads=32, num_key_value_heads=32,
        max_position_embeddings=4096, num_hidden_layers=8,
        use_recompute=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        moment_dtype='bfloat16')
    step = TrainStep(
        model, lambda logits, labels: F.cross_entropy(
            logits[:, :-1].reshape([-1, cfg.vocab_size]),
            labels[:, 1:].reshape([-1])),
        opt)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (1, 512))
    first = None
    losses = []
    for i in range(steps):
        loss = float(step(ids, ids).numpy())
        losses.append(loss)
        if first is None:
            first = loss
        if loss < target and i >= 20:
            break
    return {'first_loss': round(first, 4),
            'last_loss': round(losses[-1], 4),
            'steps': len(losses), 'target': target,
            'reached_target': losses[-1] < target}


def _bench_flash_kernels():
    """Own pallas flash (fwd+bwd) vs jax library kernel (VERDICT r2 #8:
    measured justification for the kernel choice). The timing loop runs
    ON DEVICE (lax.fori_loop chaining q through the gradient), so the
    host's dispatch cost stays out of a millisecond-scale kernel time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    try:
        rng = np.random.RandomState(0)
        shape = (4, 2048, 16, 128)
        q0 = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        k0 = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        v0 = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        n = 10

        def time_fn(f):
            def body(i, q):
                dq = jax.grad(lambda a: jnp.sum(
                    f(a, k0, v0).astype(jnp.float32)))(q)
                return (q + dq * jnp.bfloat16(1e-4)).astype(jnp.bfloat16)
            g = jax.jit(lambda q: jax.lax.fori_loop(0, n, body, q))
            jax.block_until_ready(g(q0))  # compile + warm
            t0 = time.perf_counter()
            jax.block_until_ready(g(q0))
            return (time.perf_counter() - t0) / n * 1e3

        own_ms = time_fn(lambda a, b, c: pk.flash_attention_own(
            a, b, c, True, 512, 512, False))
        lib_ms = time_fn(lambda a, b, c: pk.flash_attention(a, b, c,
                                                            causal=True))
        return {'flash_own_ms': round(own_ms, 2),
                'flash_lib_ms': round(lib_ms, 2)}
    except Exception as e:  # never let the micro-bench kill the headline
        print(f'# flash bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        return {'flash_bench_error': type(e).__name__}


def _bench_fused_ce():
    """Pallas online-softmax CE vs the XLA custom_vjp CE the models
    otherwise use — the real fallback, not a strawman (VERDICT r4 #5:
    a pallas battle XLA can lose — the [B*S, V] logits dominate HBM
    traffic at LM head shapes, and the pallas forward reads them once
    where XLA's max+expsum lowering reads twice). Headline 1.3B LM-head
    shape: [4096 rows, 50304 vocab] bf16."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional import _fused_softmax_ce_xla
    from paddle_tpu.ops import pallas_kernels as pk
    try:
        rng = np.random.RandomState(0)
        n, v = 4096, 50304
        x0 = jnp.asarray(rng.standard_normal((n, v)), jnp.bfloat16)
        lab = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
        valid = jnp.ones((n,), bool)
        reps = 10

        def xla_ce(x):
            return jnp.sum(_fused_softmax_ce_xla(x, lab, valid))

        def time_fn(f):
            def body(i, x):
                dx = jax.grad(f)(x)
                return (x - dx * jnp.bfloat16(1e-4)).astype(jnp.bfloat16)
            g = jax.jit(lambda x: jax.lax.fori_loop(0, reps, body, x))
            jax.block_until_ready(g(x0))  # compile + warm
            t0 = time.perf_counter()
            jax.block_until_ready(g(x0))
            return (time.perf_counter() - t0) / reps * 1e3

        own = time_fn(lambda x: jnp.sum(
            pk.softmax_cross_entropy(x, lab)))
        ref = time_fn(xla_ce)
        return {'fused_ce_pallas_ms': round(own, 2),
                'fused_ce_xla_ms': round(ref, 2),
                'fused_ce_speedup_pct': round((ref / own - 1) * 100, 1)}
    except Exception as e:
        print(f'# fused_ce bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        return {'fused_ce_bench_error': type(e).__name__}


def _phase_decode():
    """Serving throughput: KV-cache greedy decode on the 1.3B geometry
    (batch 8, prompt 128, 128 new tokens) — decode tokens/sec/chip.
    The whole decode is one XLA program (prefill + while_loop), so this
    measures the incremental-decode path end to end."""
    import time as _t

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.default_backend() not in ('cpu',)
    if on_tpu:
        cfg = LlamaConfig(**GPT3_SHAPE)
        batch, prompt_len, new_tokens, dtype = 8, 128, 128, 'bfloat16'
    else:
        cfg = LlamaConfig.tiny()
        batch, prompt_len, new_tokens, dtype = 2, 8, 8, 'float32'
    paddle.seed(0)
    model = LlamaForCausalLM(cfg).eval()
    if dtype == 'bfloat16':
        model.bfloat16()
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, prompt_len))
    t_ids = paddle.to_tensor(ids)
    kw = dict(max_new_tokens=new_tokens,
              decode_strategy='greedy_search', eos_token_id=-1)
    out, _ = model.generate(t_ids, **kw)          # compile + warm
    assert out.shape == [batch, new_tokens]
    t0 = _t.perf_counter()
    reps = 3
    for _ in range(reps):
        out, _ = model.generate(t_ids, **kw)
    float(out.numpy()[0, 0])                      # sync
    dt = (_t.perf_counter() - t0) / reps
    result = {'decode_1p3b': {
        'tokens_per_sec': round(batch * new_tokens / dt, 1),
        'batch': batch, 'prompt_len': prompt_len,
        'new_tokens': new_tokens, 'time_per_call_s': round(dt, 4),
        'dtype': dtype}}

    # speculative decoding (batch-1 latency): same-width 2-layer draft.
    # With a real distilled draft the acceptance rate, and therefore the
    # speedup, would be far higher — this measures the machinery cost +
    # whatever a random-init draft happens to accept.
    try:
        draft_cfg = type(cfg)(**{**cfg.__dict__, 'num_hidden_layers': 2})
        paddle.seed(1)
        draft = LlamaForCausalLM(draft_cfg).eval()
        if dtype == 'bfloat16':
            draft.bfloat16()
        one = ids[:1]
        kw1 = dict(max_new_tokens=new_tokens, num_draft_tokens=4,
                   eos_token_id=-1)
        kw_plain = dict(max_new_tokens=new_tokens,
                        decode_strategy='greedy_search', eos_token_id=-1)
        one_t = paddle.to_tensor(one)
        model.speculative_generate(draft, one, **kw1)   # compile + warm
        model.generate(one_t, **kw_plain)               # batch-1 compile
        t0 = _t.perf_counter()
        _, stats = model.speculative_generate(draft, one, **kw1)
        spec_dt = _t.perf_counter() - t0
        t0 = _t.perf_counter()
        out_plain, _ = model.generate(one_t, **kw_plain)
        float(out_plain.numpy()[0, 0])   # sync: measure execution, not
        plain_dt = _t.perf_counter() - t0  # async dispatch
        result['speculative_decode'] = {
            'tokens_per_sec': round(new_tokens / spec_dt, 1),
            'plain_tokens_per_sec': round(new_tokens / plain_dt, 1),
            'acceptance_rate': round(stats['acceptance_rate'], 3),
            'rounds': stats['rounds'],
            'draft_layers': draft_cfg.num_hidden_layers,
            'note': 'random-init draft = worst case (acceptance ~0); '
                    'speedup requires a distilled draft — this measures '
                    'machinery overhead'}
    except Exception as e:
        print(f'# spec decode bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        result['speculative_decode'] = {'error': type(e).__name__}
    return result


def eager_mlp_loop(steps=20, warmup=3, batch=32, in_dim=64, hidden=128,
                   classes=10, use_cache=True, instrument=False,
                   resilience=False):
    """Eager-dispatch micro-bench loop (also imported by the tier-1
    regression test): a plain DyGraph MLP train step — forward, CE loss,
    tape backward, eager SGD — with NO TrainStep jit, so every op rides
    `apply_op`. Returns wall-clock rates plus the dispatch-cache counter
    window covering only the post-warmup steps; with `use_cache` the
    telemetry must show zero retraces there.

    `instrument=True` runs the SAME loop with the observability layer
    active per step — a span around the step body plus StepTelemetry
    updates — for the obs-overhead A/B (`bench.py obs` phase and the
    tier-1 <3% overhead guard).

    `resilience=True` instead routes every step through a
    FaultTolerantStep wrapper (per-step loss finiteness + spike check,
    host snapshot every 10 steps) for the resilience-overhead A/B
    (`bench.py resilience` phase and its tier-1 <3% guard)."""
    import time as _t

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu import debug as pdebug
    from paddle_tpu import observability as obs

    was_enabled = pdebug.dispatch_stats()['enabled']
    obs_was_enabled = obs.enabled()
    obs.enable(instrument)
    pdebug.enable_dispatch_cache(use_cache)
    pdebug.clear_dispatch_cache()
    try:
        paddle.seed(0)
        model = nn.Sequential(
            nn.Linear(in_dim, hidden), nn.ReLU(),
            nn.Linear(hidden, hidden), nn.ReLU(),
            nn.Linear(hidden, classes))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(
            rng.standard_normal((batch, in_dim)).astype('float32'))
        y = paddle.to_tensor(rng.randint(0, classes, (batch,)))

        def one_step():
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        telemetry = obs.StepTelemetry(memory_every=10) if instrument \
            else None

        ft = None
        if resilience:
            import jax.numpy as jnp
            from paddle_tpu import resilience as res

            def snap():
                return {n: np.asarray(p.value)
                        for n, p in model.named_parameters()}

            def rest(s):
                pm = dict(model.named_parameters())
                for n, v in s.items():
                    pm[n]._data = jnp.asarray(v)
                    pm[n]._node = None
            ft = res.FaultTolerantStep(
                lambda: one_step(), snapshot_fn=snap, restore_fn=rest,
                snapshot_interval=10)

        for _ in range(warmup):
            loss = one_step()
        float(loss.numpy())                  # drain warmup dispatch
        pdebug.reset_dispatch_stats()
        t0 = _t.perf_counter()
        if ft is not None:
            # resilience arm: the wrapper syncs the loss each step (the
            # finiteness check needs the value on host) — that sync IS
            # part of the fault-tolerance cost being measured
            for _ in range(steps):
                loss = ft()
        elif telemetry is not None:
            # instrumented arm: span + per-step telemetry (loss is NOT
            # synced per step — the A/B measures instrumentation cost,
            # not a forced device round-trip)
            for _ in range(steps):
                with obs.span('bench.eager_step'):
                    loss = one_step()
                telemetry.step(tokens=batch)
        else:
            for _ in range(steps):
                loss = one_step()
        final_loss = float(loss.numpy())     # sync
        dt = _t.perf_counter() - t0
        stats = pdebug.dispatch_stats()
        return {
            'steps_per_sec': round(steps / dt, 1),
            'ops_per_sec': round(stats['calls'] / dt, 1),
            'ops_per_step': stats['calls'] // steps,
            'loss': round(final_loss, 4),
            'cache_enabled': use_cache,
            'hits': stats['hits'], 'misses': stats['misses'],
            'retraces': stats['retraces'],
            'fallbacks': stats['fallbacks'],
            'hit_rate': round(stats['hit_rate'], 4),
        }
    finally:
        pdebug.enable_dispatch_cache(was_enabled)
        pdebug.clear_dispatch_cache()
        obs.enable(obs_was_enabled)


def obs_overhead_ab(steps=30, trials=3):
    """A/B the eager MLP loop with observability instrumentation on vs
    off (also imported by the tier-1 overhead guard). Takes the best
    steps/sec of `trials` alternating runs per arm — min-noise on a
    shared CPU — and reports the on/off overhead ratio."""
    best_on = best_off = 0.0
    for _ in range(trials):
        off = eager_mlp_loop(steps=steps, instrument=False)
        on = eager_mlp_loop(steps=steps, instrument=True)
        best_off = max(best_off, off['steps_per_sec'])
        best_on = max(best_on, on['steps_per_sec'])
    overhead = best_off / best_on - 1 if best_on else float('inf')
    return {
        'instrumented_steps_per_sec': best_on,
        'plain_steps_per_sec': best_off,
        'overhead_ratio': round(best_off / best_on, 4) if best_on else 0.0,
        'overhead_pct': round(overhead * 100, 2),
    }


def scrape_overhead_ab(steps=30, trials=3, hz=4.0):
    """Scrape-under-load A/B (also imported by the tier-1 overhead
    guard): the instrumented eager MLP loop with a background HTTP
    client hitting the live /metrics endpoint at `hz` vs the same loop
    unscraped. Measures what a real Prometheus scraper costs the hot
    path — the registry lock is only held per family copy, so the
    answer should match the instrumentation guard (~0, <3% gated).
    Every scraped body is parse-checked; a single unparseable scrape
    fails the bench (concurrent export must never tear)."""
    import threading
    import urllib.request

    from paddle_tpu import observability as obs

    srv = obs.start_server(0)
    stop = threading.Event()
    counts = {'scrapes': 0, 'failures': 0}

    def scraper():
        url = f'{srv.url}/metrics'
        while not stop.is_set():
            try:
                body = urllib.request.urlopen(url, timeout=2).read()
                if b'# TYPE' not in body:
                    counts['failures'] += 1
                counts['scrapes'] += 1
            except Exception:
                counts['failures'] += 1
            stop.wait(1.0 / hz)

    try:
        best_on = best_off = 0.0
        ratios = []
        for _ in range(trials):
            off = eager_mlp_loop(steps=steps, instrument=True)
            t = threading.Thread(target=scraper, daemon=True)
            stop.clear()
            t.start()
            try:
                on = eager_mlp_loop(steps=steps, instrument=True)
            finally:
                stop.set()
                t.join(timeout=5)
            best_off = max(best_off, off['steps_per_sec'])
            best_on = max(best_on, on['steps_per_sec'])
            # min of adjacent-pair ratios, not best-of-N across arms:
            # on a loaded single-core box the bests can land in
            # different noise regimes and report phantom overhead; the
            # least-noisy pair is closest to the uncontended truth
            if on['steps_per_sec']:
                ratios.append(off['steps_per_sec'] / on['steps_per_sec'])
        overhead = min(ratios) - 1 if ratios else float('inf')
        return {
            'scraped_steps_per_sec': best_on,
            'plain_steps_per_sec': best_off,
            'overhead_pct': round(overhead * 100, 2),
            'scrapes': counts['scrapes'],
            'scrape_failures': counts['failures'],
            'scrape_hz': hz,
        }
    finally:
        stop.set()
        srv.stop()


def sanitizer_overhead_ab(steps=30, trials=3):
    """Concurrency-sanitizer report-mode vs off A/B on the instrumented
    eager MLP loop (also imported by the tier-1 <3% overhead guard).
    Both arms run the SAME instrumentation — spans and StepTelemetry
    take the registry/event-log locks every step — so the ratio
    isolates what the sanitizer's held-stack + acquisition-graph
    tracking costs a lock-heavy hot path. Report-only mode is the
    production posture this guard protects; STRICT mode is reserved
    for tests (the chaos gauntlets), where raising beats speed.
    Min-of-adjacent-pair ratios, same estimator as the scrape guard."""
    from paddle_tpu.analysis import runtime as _rt

    prev = _rt.mode()
    ratios = []
    best_on = best_off = 0.0
    try:
        for _ in range(trials):
            _rt.disable()
            off = eager_mlp_loop(steps=steps, instrument=True)
            _rt.enable('report')
            on = eager_mlp_loop(steps=steps, instrument=True)
            best_off = max(best_off, off['steps_per_sec'])
            best_on = max(best_on, on['steps_per_sec'])
            if on['steps_per_sec']:
                ratios.append(off['steps_per_sec'] / on['steps_per_sec'])
    finally:
        _rt.enable(prev)
    overhead = min(ratios) - 1 if ratios else float('inf')
    return {
        'sanitized_steps_per_sec': best_on,
        'plain_steps_per_sec': best_off,
        'overhead_pct': round(overhead * 100, 2),
        'mode': 'report',
        'lock_classes_observed': _rt.stats()['lock_classes'],
    }


def _phase_obs():
    """Observability overhead phase: instrumentation on vs off on the
    eager hot path, the /metrics scrape-under-load A/B, and the
    concurrency-sanitizer report-mode A/B; the JSON carries the
    measured ratios (the tier-1 guards pin each under 3% on CPU)."""
    out = {}
    try:
        out['obs_overhead'] = obs_overhead_ab()
    except Exception as e:
        print(f'# obs bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['obs_overhead'] = {'error': type(e).__name__}
    try:
        out['scrape_overhead'] = scrape_overhead_ab()
    except Exception as e:
        print(f'# scrape bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['scrape_overhead'] = {'error': type(e).__name__}
    try:
        out['sanitizer_overhead'] = sanitizer_overhead_ab()
    except Exception as e:
        print(f'# sanitizer bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['sanitizer_overhead'] = {'error': type(e).__name__}
    return out


def fleet_obs_overhead_ab(steps=30, trials=3, interval_s=0.1):
    """Fleet-shipper on/off A/B (also imported by the tier-1 <3%
    overhead guard): the instrumented eager MLP loop with a background
    Shipper spooling registry deltas + event segments at `interval_s`
    vs the same loop unshipped. The shipper never touches the hot path
    — it snapshots on its own daemon thread — so the cost is registry
    lock contention during snapshots, which this pins under 3%.
    Min-of-adjacent-pair ratios, same estimator as the scrape guard."""
    import tempfile

    from paddle_tpu import observability as obs

    ratios = []
    best_on = best_off = 0.0
    with tempfile.TemporaryDirectory() as spool:
        for _ in range(trials):
            off = eager_mlp_loop(steps=steps, instrument=True)
            sh = obs.Shipper(spool, interval_s=interval_s).start()
            try:
                on = eager_mlp_loop(steps=steps, instrument=True)
            finally:
                sh.stop(flush=True)
            best_off = max(best_off, off['steps_per_sec'])
            best_on = max(best_on, on['steps_per_sec'])
            if on['steps_per_sec']:
                ratios.append(off['steps_per_sec'] / on['steps_per_sec'])
    overhead = min(ratios) - 1 if ratios else float('inf')
    return {
        'shipped_steps_per_sec': best_on,
        'plain_steps_per_sec': best_off,
        'overhead_pct': round(overhead * 100, 2),
        'ship_interval_s': interval_s,
    }


def fleet_roundtrip_smoke():
    """Spool roundtrip smoke: ship the live registry once, aggregate,
    and check the merged `paddle_steps_total` matches the local truth —
    the single-process degenerate case of the fleet merge invariant
    (the multi-process version lives in tests/test_fleet_obs.py)."""
    import tempfile

    from paddle_tpu import observability as obs

    with tempfile.TemporaryDirectory() as spool:
        sh = obs.Shipper(spool)
        sh.ship_now()
        agg = obs.Aggregator(spool)
        counts = agg.poll()
        merged = agg.merged()
        local = obs.get_registry().value('paddle_steps_total')
        fleet = 0.0
        for m in merged.get('metrics', []):
            if m['name'] == 'paddle_steps_total':
                fleet = sum(s['value'] for s in m['samples'])
        return {
            'segments_applied': counts['applied'],
            'local_steps_total': local,
            'fleet_steps_total': fleet,
            'merged_matches_local': fleet == local,
            'processes': agg.process_uids(),
        }


_FLEET_PROMPTS = [[5, 6, 7], [11, 12], [3, 1, 4, 1, 5],
                  [23, 29, 31, 37], [2, 4], [9, 8, 7, 6, 5, 4]]
_FLEET_ENGINE_KW = dict(num_slots=2, max_length=64, decode_block=2)


def _fleet_proc_factory_spec():
    """Model factory for replica children, addressed by file path so
    the child interpreter needs no installed test package."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'tests', '_fleet_factory.py') + ':tiny_gpt'


def _fleet_proc_supervisor(run_dir, program_store_dir):
    from paddle_tpu.serving import ReplicaSpec, Supervisor
    spec = ReplicaSpec(_fleet_proc_factory_spec(),
                       engine_kwargs=dict(_FLEET_ENGINE_KW),
                       program_store_dir=program_store_dir,
                       drain_deadline_s=20.0)
    return Supervisor(run_dir, spec, spawn_timeout_s=180.0,
                      backoff_base_s=0.05, backoff_cap_s=0.5,
                      max_restarts=5)


def _fleet_proc_local_engine():
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import InferenceEngine
    paddle.seed(7)   # same weights as tests/_fleet_factory.py:tiny_gpt
    model = GPTForCausalLM(GPTConfig.tiny()).eval()
    return InferenceEngine(model, **_FLEET_ENGINE_KW)


def fleet_rpc_overhead_ab(trials=3, max_new_tokens=16):
    """In-process engine vs ONE supervised replica process, same seeded
    tiny-GPT greedy workload (also imported by the tier-1 guard). The
    ratio isolates what the process boundary costs a serving batch:
    framed-RPC round trips per step + JSON mirror updates vs direct
    method calls. Both arms warm first (spawn already blocks on child
    readiness), so compiles never land in a measured window.
    Min-of-adjacent-pair ratios, same estimator as the scrape guard."""
    import tempfile
    import time as _t

    from paddle_tpu.serving import SamplingParams

    sp = SamplingParams(max_new_tokens=max_new_tokens, eos_token_id=-1)
    local = _fleet_proc_local_engine()

    def run_local():
        t0 = _t.perf_counter()
        hs = local.generate_many(_FLEET_PROMPTS, sp)
        dt = _t.perf_counter() - t0
        return dt, [h.tokens for h in hs]

    with tempfile.TemporaryDirectory() as tmp:
        sup = _fleet_proc_supervisor(
            os.path.join(tmp, 'run'), os.path.join(tmp, 'programs'))
        try:
            rr = sup.spawn('bench0')

            def run_remote():
                t0 = _t.perf_counter()
                hs = rr.generate_many(_FLEET_PROMPTS, sp)
                dt = _t.perf_counter() - t0
                return dt, [h.tokens for h in hs]

            _, ref = run_local()       # warm both arms off the clock
            _, remote_toks = run_remote()
            parity = remote_toks == ref
            ratios, best_local, best_remote = [], float('inf'), \
                float('inf')
            for _ in range(trials):
                t_local, _ = run_local()
                t_remote, _ = run_remote()
                best_local = min(best_local, t_local)
                best_remote = min(best_remote, t_remote)
                if t_local > 0:
                    ratios.append(t_remote / t_local)
            overhead = min(ratios) - 1 if ratios else float('inf')
            return {
                'local_s': round(best_local, 4),
                'remote_s': round(best_remote, 4),
                'overhead_pct': round(overhead * 100, 2),
                'tokens_per_arm': len(_FLEET_PROMPTS) * max_new_tokens,
                'parity': parity,
            }
        finally:
            sup.stop_all(deadline_s=10.0)


def fleet_proc_scaling(max_new_tokens=16, repeats=4):
    """The 2-process scaling row: the SAME workload through a Router
    over one replica process vs two. Before this PR a second 'replica'
    shared the parent's Python process (GIL + one runtime): added
    replicas moved latency, never throughput. Two OS processes are the
    first configuration where the scaling ratio can genuinely
    exceed 1."""
    import tempfile
    import time as _t

    from paddle_tpu.serving import Replica, Router, SamplingParams

    sp = SamplingParams(max_new_tokens=max_new_tokens, eos_token_id=-1)
    prompts = _FLEET_PROMPTS * repeats

    def run(router):
        t0 = _t.perf_counter()
        handles = [router.submit(p, sp) for p in prompts]
        while any(not h.done for h in handles):
            router.step()
        dt = _t.perf_counter() - t0
        done = sum(1 for h in handles if h.status == 'FINISHED')
        return dt, done

    with tempfile.TemporaryDirectory() as tmp:
        sup = _fleet_proc_supervisor(
            os.path.join(tmp, 'run'), os.path.join(tmp, 'programs'))
        try:
            ra, rb = sup.spawn('s0'), sup.spawn('s1')
            ra.generate_many(_FLEET_PROMPTS, sp)   # warm off the clock
            rb.generate_many(_FLEET_PROMPTS, sp)
            t1, done1 = run(Router([Replica(0, ra)]))
            t2, done2 = run(Router([Replica(0, ra), Replica(1, rb)]))
            return {
                'offered': len(prompts),
                'one_proc_s': round(t1, 4), 'one_proc_completed': done1,
                'two_proc_s': round(t2, 4), 'two_proc_completed': done2,
                'speedup': round(t1 / t2, 3) if t2 > 0 else 0.0,
            }
        finally:
            sup.stop_all(deadline_s=10.0)


def fleet_proc_kill_smoke(max_new_tokens=8):
    """Kill-mid-trace smoke (also imported by the tier-1 guard):
    SIGKILL one of two replica processes mid-decode under live traffic
    and count what the fleet lost. The contract is ZERO: every accepted
    request fails over to the survivor and finishes bit-exact."""
    import tempfile

    from paddle_tpu.serving import Replica, Router, SamplingParams

    sp = SamplingParams(max_new_tokens=max_new_tokens, eos_token_id=-1)
    with tempfile.TemporaryDirectory() as tmp:
        sup = _fleet_proc_supervisor(
            os.path.join(tmp, 'run'), os.path.join(tmp, 'programs'))
        try:
            ra, rb = sup.spawn('k0'), sup.spawn('k1')
            ref = [h.tokens
                   for h in ra.generate_many(_FLEET_PROMPTS, sp)]
            router = Router([Replica(0, ra), Replica(1, rb)])
            handles = [router.submit(p, sp) for p in _FLEET_PROMPTS]
            for _ in range(200):
                router.step()
                if ra._slot_req and rb._slot_req \
                        and any(not h.done and h.tokens for h in handles):
                    break
            sup.kill('k0')
            rounds = 0
            while any(not h.done for h in handles) and rounds < 3000:
                router.step()
                rounds += 1
            finished = sum(1 for h in handles if h.status == 'FINISHED')
            return {
                'offered': len(handles),
                'finished': finished,
                'lost_requests': len(handles) - finished,
                'bit_exact': [h.tokens for h in handles] == ref,
            }
        finally:
            sup.stop_all(deadline_s=10.0)


def _phase_fleet_proc():
    """Process fleet runtime phase (ISSUE 18): in-proc vs cross-process
    RPC overhead A/B, the 2-process scaling row, and the kill-mid-trace
    zero-loss smoke."""
    out = {}
    for key, fn in (('fleet_rpc_overhead', fleet_rpc_overhead_ab),
                    ('fleet_scaling', fleet_proc_scaling),
                    ('fleet_kill', fleet_proc_kill_smoke)):
        try:
            out[key] = fn()
        except Exception as e:
            print(f'# {key} bench failed: {type(e).__name__}: {e}',
                  file=sys.stderr)
            out[key] = {'error': type(e).__name__}
    return out


def _phase_fleet_obs():
    """Fleet observability plane phase: shipper on/off overhead A/B on
    the eager hot path (tier-1 pins it <3%) plus a single-process spool
    roundtrip smoke (ship -> aggregate -> merged equals local)."""
    out = {}
    try:
        out['fleet_obs_overhead'] = fleet_obs_overhead_ab()
    except Exception as e:
        print(f'# fleet_obs bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['fleet_obs_overhead'] = {'error': type(e).__name__}
    try:
        out['fleet_roundtrip'] = fleet_roundtrip_smoke()
    except Exception as e:
        print(f'# fleet roundtrip smoke failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['fleet_roundtrip'] = {'error': type(e).__name__}
    return out


def resilience_overhead_ab(steps=30, trials=3):
    """A/B the eager MLP loop through a FaultTolerantStep wrapper vs
    plain (also imported by the tier-1 overhead guard). Same best-of-N
    protocol as obs_overhead_ab."""
    best_on = best_off = 0.0
    for _ in range(trials):
        off = eager_mlp_loop(steps=steps, resilience=False)
        on = eager_mlp_loop(steps=steps, resilience=True)
        best_off = max(best_off, off['steps_per_sec'])
        best_on = max(best_on, on['steps_per_sec'])
    overhead = best_off / best_on - 1 if best_on else float('inf')
    return {
        'ft_steps_per_sec': best_on,
        'plain_steps_per_sec': best_off,
        'overhead_ratio': round(best_off / best_on, 4) if best_on else 0.0,
        'overhead_pct': round(overhead * 100, 2),
    }


def elastic_overhead_ab(steps=30, trials=3, batch=32):
    """A/B a fleet DistTrainStep driven bare vs through
    ElasticTrainLoop.step (also imported by the tier-1 overhead guard).

    The elastic per-step cost is the device-source poll + mesh
    comparison + checkpoint-interval check; the transition itself
    (checkpoint/re-mesh/restore) only happens when topology actually
    moves, so the steady-state wrapper must be ~free. Checkpoint writes
    are excluded (interval >> steps) — the guard targets the wrapper,
    not disk bandwidth."""
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import fleet
    from paddle_tpu.resilience.elastic import ElasticTrainLoop

    if not fleet._fleet.initialized:
        fleet.init(is_collective=True)
    rng = np.random.RandomState(0)
    x = rng.standard_normal((batch, 64)).astype('float32')
    y = rng.randint(0, 10, (batch,))

    def loss_fn(out, lab):
        return F.cross_entropy(out, lab)

    def run(elastic):
        import time as _t
        paddle.seed(0)
        model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                              nn.Linear(128, 10))
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=model.parameters())
        if elastic:
            loop = ElasticTrainLoop(model, loss_fn, opt,
                                    ckpt_dir=tempfile.mkdtemp(),
                                    ckpt_interval=10 ** 9)
            step = loop.step
        else:
            fleet.distributed_model(model)
            step = fleet.DistTrainStep(model, loss_fn, opt)
        xs, ys = paddle.to_tensor(x), paddle.to_tensor(y)
        loss = step(xs, ys)          # compile outside the timed window
        float(loss.numpy())
        t0 = _t.perf_counter()
        for _ in range(steps):
            loss = step(xs, ys)
        float(loss.numpy())          # sync
        return steps / (_t.perf_counter() - t0)

    best_on = best_off = 0.0
    ratios = []
    for _ in range(trials):
        off = run(elastic=False)
        on = run(elastic=True)
        best_off = max(best_off, off)
        best_on = max(best_on, on)
        # overhead from the MIN of adjacent-pair ratios: shared-box
        # contention noise is strictly additive and drift moves both
        # members of a pair together, so the least-noisy pair is the
        # closest to the uncontended truth (best-of-N across arms can
        # land its bests in different noise regimes and report phantom
        # overhead); a real regression shows up in every pair
        if on:
            ratios.append(off / on)
    overhead = min(ratios) - 1 if ratios else float('inf')
    return {
        'elastic_steps_per_sec': round(best_on, 1),
        'plain_steps_per_sec': round(best_off, 1),
        'overhead_ratio': round(best_off / best_on, 4) if best_on else 0.0,
        'overhead_pct': round(overhead * 100, 2),
    }


def _phase_resilience():
    """Fault-tolerance overhead phase: FaultTolerantStep wrapper on vs
    off on the eager hot path, plus the elastic-wrapper A/B on the
    fleet step (mirrors the obs phase; tier-1 guards each ratio under
    3% on CPU)."""
    out = {}
    try:
        out['resilience_overhead'] = resilience_overhead_ab()
    except Exception as e:
        print(f'# resilience bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['resilience_overhead'] = {'error': type(e).__name__}
    try:
        out['elastic_overhead'] = elastic_overhead_ab()
    except Exception as e:
        print(f'# elastic bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['elastic_overhead'] = {'error': type(e).__name__}
    return out


def serving_trace(num_requests=24, seed=0, vocab=512):
    """Deterministic mixed-length request trace for the serving A/B:
    (prompt tokens, max_new_tokens) pairs cycling through a few length
    buckets so both arms compile a bounded shape set."""
    rng = np.random.RandomState(seed)
    lens = [4, 7, 12, 15, 20, 28]
    news = [32, 40, 48]
    return [(rng.randint(0, vocab, (lens[i % len(lens)],)).tolist(),
             news[i % len(news)])
            for i in range(num_requests)]


def serving_ab(num_requests=24, num_slots=12, max_length=96, decode_block=8,
               trials=3):
    """Continuous batching vs a sequential `generate()` loop on a
    mixed-length trace (also imported by the tier-1 serving guard).

    Both arms decode the SAME requests greedily with eos disabled (fixed
    token counts — a throughput comparison, not an early-exit lottery).
    Reports tokens/sec for each arm, the speedup, engine mean TTFT, and
    two correctness fields the tier-1 test asserts: `parity` (engine
    tokens bit-identical to per-request generate()) and
    `recompiles_after_warmup` (compile-trace growth across the timed
    run — continuous batching must admit into freed slots without
    recompiling).

    The model is deliberately weight-heavy for its size (h=256, 4L —
    ~3M params, past L2): single-stream decode is then memory-bound on
    weight streaming, so batched slots amortize each weight read — the
    same physics that makes continuous batching the serving unlock on
    real accelerators. (At toy widths the weights sit in cache and
    batching shows nothing.)"""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=384, num_hidden_layers=4,
                    num_attention_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg).eval()
    trace = serving_trace(num_requests, vocab=cfg.vocab_size)
    params = [SamplingParams(max_new_tokens=mn, eos_token_id=-1)
              for _, mn in trace]
    prompts = [p for p, _ in trace]

    # --- sequential arm: one generate() call per request ----------------
    def run_sequential():
        outs = []
        for p, mn in trace:
            out, _ = model.generate(
                paddle.to_tensor(np.array([p])), max_new_tokens=mn,
                decode_strategy='greedy_search', eos_token_id=-1)
            outs.append(out.numpy()[0].tolist())
        return outs

    expected = run_sequential()          # compile + warm every shape
    best_seq = float('inf')
    for _ in range(trials):
        t0 = time.perf_counter()
        run_sequential()
        best_seq = min(best_seq, time.perf_counter() - t0)

    # --- engine arm: ONE engine, warmed, timed over the same trace ------
    engine = InferenceEngine(model, num_slots=num_slots,
                             max_length=max_length,
                             decode_block=decode_block)
    engine.generate_many(prompts[:num_slots + 1],
                         params[:num_slots + 1])   # warm all buckets
    traces_after_warmup = dict(engine.stats()['traces'])
    best_eng, handles = float('inf'), None
    for _ in range(trials):
        engine.reset_stats()
        t0 = time.perf_counter()
        hs = engine.generate_many(prompts, params)
        dt = time.perf_counter() - t0
        if dt < best_eng:
            best_eng, handles = dt, hs

    tokens = sum(mn for _, mn in trace)
    got = [h.tokens for h in handles]
    parity = got == expected
    recompiles = sum(engine.stats()['traces'].values()) \
        - sum(traces_after_warmup.values())
    ttfts = [h.ttft for h in handles if h.ttft is not None]
    return {
        'engine_tokens_per_sec': round(tokens / best_eng, 1),
        'sequential_tokens_per_sec': round(tokens / best_seq, 1),
        'speedup': round(best_seq / best_eng, 2),
        'mean_ttft_ms': round(sum(ttfts) / len(ttfts) * 1e3, 2),
        'num_requests': num_requests, 'num_slots': num_slots,
        'decode_block': decode_block, 'tokens': tokens,
        'parity': parity,
        'recompiles_after_warmup': recompiles,
    }


def _serving_model(max_pos=128):
    """The weight-heavy serving-bench GPT (see serving_ab's physics
    note: single-stream decode is weight-streaming-bound at this width,
    so batching/speculation/caching effects measure what they measure
    on real accelerators)."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=384, num_hidden_layers=4,
                    num_attention_heads=4, max_position_embeddings=max_pos,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    return GPTForCausalLM(cfg).eval()


def _ref_outputs(model, trace):
    """Per-request generate() greedy references for an engine trace."""
    import paddle_tpu as paddle
    outs = []
    for p, mn in trace:
        out, _ = model.generate(
            paddle.to_tensor(np.array([p])), max_new_tokens=mn,
            decode_strategy='greedy_search', eos_token_id=-1)
        outs.append(out.numpy()[0].tolist())
    return outs


def prefix_trace(num_requests=16, system_len=48, seed=0, vocab=512):
    """Shared-system-prompt trace: every request is the SAME system_len
    prefix + a short unique suffix — the production shape RadixAttention
    targets (the prefix cache should collapse prefill to suffixes)."""
    rng = np.random.RandomState(seed)
    system = rng.randint(0, vocab, (system_len,)).tolist()
    suffix_lens = [4, 10, 7, 13]
    news = [24, 32]
    return [(system + rng.randint(0, vocab,
                                  (suffix_lens[i % 4],)).tolist(),
             news[i % 2])
            for i in range(num_requests)]


def prefix_ab(num_requests=12, num_slots=16, max_length=96,
              decode_block=8, system_len=48, trials=2):
    """Prefix-cache A/B on the shared-system-prompt trace (also imported
    by the tier-1 prefix guard): the same engine config with the radix
    cache off (cold: every prompt prefills in full) vs on (the system
    prefix prefills once; later requests gather the retained KV row and
    prefill only their suffix). Reports the prefill-token reduction and
    the TTFT ratio, plus the tier-1 fields: bit-exact greedy parity vs
    per-request generate() on BOTH arms and zero recompiles across the
    timed trace.

    Measured at SUB-SATURATION concurrency (slots >= burst + retention
    budget) — the TTFT-sensitive regime the cache targets, where every
    admission's prefill is on the first-token critical path. At full
    slot saturation, retained entries displace decode concurrency
    instead (the decode block computes every slot each round, occupied
    or not), so the win shrinks: size num_slots = target concurrency +
    retention budget (the README runbook)."""
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    model = _serving_model()
    trace = prefix_trace(num_requests, system_len=system_len)
    prompts = [p for p, _ in trace]
    params = [SamplingParams(max_new_tokens=mn, eos_token_id=-1)
              for _, mn in trace]
    expected = _ref_outputs(model, trace)
    tokens = sum(mn for _, mn in trace)

    def run(cache_on):
        eng = InferenceEngine(
            model, num_slots=num_slots, max_length=max_length,
            decode_block=decode_block,
            prefix_cache=0.25 if cache_on else None)
        # warmup compiles every program the trace needs AND seeds the
        # cache: request 0 alone first (inserts happen at retirement,
        # so a concurrent warmup wave would all miss), then a wave that
        # HITS it — compiling both suffix chunk buckets and the
        # full-prompt-hit row copy
        eng.generate_many(prompts[:1], params[:1])
        eng.generate_many(prompts[:4], params[:4])
        warm_traces = dict(eng.stats()['traces'])
        best = None
        for _ in range(trials):
            eng.reset_stats()
            t0 = time.perf_counter()
            hs = eng.generate_many(prompts, params)
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, hs, dict(eng.stats()))
        dt, hs, st = best
        ttfts = sorted(h.ttft for h in hs)
        return {
            'dt': dt, 'parity': [h.tokens for h in hs] == expected,
            'recompiles': sum(eng.stats()['traces'].values())
            - sum(warm_traces.values()),
            'prefill_tokens': st['prefill_tokens'],
            'ttft_mean_ms': sum(ttfts) / len(ttfts) * 1e3,
            'ttft_p50_ms': ttfts[len(ttfts) // 2] * 1e3,
            'stats': st,
        }

    cold = run(cache_on=False)
    cached = run(cache_on=True)
    px = cached['stats'].get('prefix_cache', {})
    reduction = (1 - cached['prefill_tokens'] / cold['prefill_tokens']
                 if cold['prefill_tokens'] else 0.0)
    return {
        'prefill_tokens_cold': cold['prefill_tokens'],
        'prefill_tokens_cached': cached['prefill_tokens'],
        'prefill_token_reduction': round(reduction, 4),
        'ttft_mean_ms_cold': round(cold['ttft_mean_ms'], 2),
        'ttft_mean_ms_cached': round(cached['ttft_mean_ms'], 2),
        'ttft_ratio': round(cached['ttft_mean_ms']
                            / cold['ttft_mean_ms'], 4)
        if cold['ttft_mean_ms'] else 0.0,
        'tokens_per_sec_cold': round(tokens / cold['dt'], 1),
        'tokens_per_sec_cached': round(tokens / cached['dt'], 1),
        'cache_hits': px.get('hits', 0),
        'cache_tokens_reused': px.get('tokens_reused', 0),
        'parity': cold['parity'] and cached['parity'],
        'recompiles_after_warmup': cold['recompiles']
        + cached['recompiles'],
        'num_requests': num_requests, 'system_len': system_len,
    }


def chunked_ab(num_short=10, long_len=224, short_len=6, short_new=16,
               long_new=8, num_slots=12, max_length=256, decode_block=2,
               chunk=32, trials=2):
    """Chunked-prefill A/B on the long-plus-shorts trace (also imported
    by the tier-1 chunk guard): ONE long prompt arrives first, then
    many short requests. Unchunked, every short request's TTFT eats the
    whole long prefill (head-of-line); chunked, the long prompt
    prefills one bucket-shaped chunk per decode round and the shorts
    start streaming immediately. Reports p50 short-request TTFT for
    both arms; tier-1 guards parity + zero recompiles (the latency
    ratio is asserted on the full bench run, where the gap is x-large,
    not in the noise-prone tier-1 environment)."""
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    model = _serving_model(max_pos=max_length)
    rng = np.random.RandomState(3)
    vocab = model.config.vocab_size
    long_prompt = rng.randint(0, vocab, (long_len,)).tolist()
    shorts = [rng.randint(0, vocab, (short_len,)).tolist()
              for _ in range(num_short)]
    trace = [(long_prompt, long_new)] + [(p, short_new) for p in shorts]
    prompts = [p for p, _ in trace]
    params = [SamplingParams(max_new_tokens=mn, eos_token_id=-1)
              for _, mn in trace]
    expected = _ref_outputs(model, trace)

    def run(chunk_on):
        eng = InferenceEngine(
            model, num_slots=num_slots, max_length=max_length,
            decode_block=decode_block,
            prefill_chunk_tokens=chunk if chunk_on else None)
        eng.generate_many(prompts[:2], params[:2])   # warm both shapes
        warm_traces = dict(eng.stats()['traces'])
        best = None
        for _ in range(trials):
            eng.reset_stats()
            hs = [eng.submit(p, sp) for p, sp in zip(prompts, params)]
            eng.run()
            short_ttfts = sorted(h.ttft for h in hs[1:])
            sample = (short_ttfts[len(short_ttfts) // 2],
                      hs[0].ttft, hs)
            if best is None or sample[0] < best[0]:
                best = sample
        p50_short, long_ttft, hs = best
        return {
            'p50_short_ttft_ms': p50_short * 1e3,
            'long_ttft_ms': long_ttft * 1e3,
            'parity': [h.tokens for h in hs] == expected,
            'recompiles': sum(eng.stats()['traces'].values())
            - sum(warm_traces.values()),
            'chunk_rounds': eng.stats()['chunk_rounds'],
        }

    plain = run(chunk_on=False)
    chunked = run(chunk_on=True)
    return {
        'p50_short_ttft_ms_unchunked': round(plain['p50_short_ttft_ms'],
                                             2),
        'p50_short_ttft_ms_chunked': round(chunked['p50_short_ttft_ms'],
                                           2),
        'short_ttft_ratio': round(chunked['p50_short_ttft_ms']
                                  / plain['p50_short_ttft_ms'], 4)
        if plain['p50_short_ttft_ms'] else 0.0,
        'long_ttft_ms_unchunked': round(plain['long_ttft_ms'], 2),
        'long_ttft_ms_chunked': round(chunked['long_ttft_ms'], 2),
        'chunk_rounds': chunked['chunk_rounds'],
        'parity': plain['parity'] and chunked['parity'],
        'recompiles_after_warmup': plain['recompiles']
        + chunked['recompiles'],
        'long_len': long_len, 'num_short': num_short, 'chunk': chunk,
    }


def distill_draft(model, sequences, hidden=128, steps=150, lr=3e-3,
                  seed=123):
    """Train a 1-layer draft on the TARGET's own greedy continuations —
    the standard draft-model construction (distill on the serving
    distribution) shrunk to bench scale. Returns the draft in eval()."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM

    cfg = model.config
    paddle.seed(seed)
    draft = GPTForCausalLM(GPTConfig(
        vocab_size=cfg.vocab_size, hidden_size=hidden,
        num_hidden_layers=1, num_attention_heads=4,
        intermediate_size=2 * hidden,
        max_position_embeddings=cfg.max_position_embeddings,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=draft.parameters())
    step = TrainStep(
        draft,
        lambda logits, labels: F.cross_entropy(
            logits[:, :-1].reshape([-1, cfg.vocab_size]),
            labels[:, 1:].reshape([-1])),
        opt)
    seqs = np.asarray(sequences, np.int32)
    for _ in range(steps):
        step(seqs, seqs)
    return draft.eval()


def spec_ab(num_requests=12, prompt_len=12, max_new=32, num_slots=6,
            max_length=96, decode_block=8, k=4, distill_steps=150,
            trials=2):
    """Speculative-decoding A/B (also imported by the tier-1 spec
    guard): the same continuous-batching trace decoded by a plain
    engine vs a speculating one whose 1-layer draft was distilled on
    the target's greedy continuations of these prompts (the
    draft-for-the-serving-distribution construction). The weight-heavy
    target makes each decode round weight-streaming-bound, so a
    k+1-position verify costs about one round — accepted drafts are
    nearly free tokens. Reports acceptance rate and the tokens/sec
    ratio (>= 1 on the full bench run); tier-1 guards bit-exact greedy
    parity + zero recompiles + nonzero acceptance."""
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    model = _serving_model()
    rng = np.random.RandomState(11)
    vocab = model.config.vocab_size
    prompts = [rng.randint(0, vocab, (prompt_len,)).tolist()
               for _ in range(num_requests)]
    trace = [(p, max_new) for p in prompts]
    params = [SamplingParams(max_new_tokens=max_new, eos_token_id=-1)
              for _ in trace]
    expected = _ref_outputs(model, trace)
    sequences = [p + out for (p, _), out in zip(trace, expected)]
    draft = distill_draft(model, sequences, steps=distill_steps)
    tokens = sum(mn for _, mn in trace)

    def run(draft_model):
        eng = InferenceEngine(
            model, num_slots=num_slots, max_length=max_length,
            decode_block=decode_block, draft_model=draft_model,
            num_draft_tokens=k)
        eng.generate_many(prompts[:2], params[:2])
        warm_traces = dict(eng.stats()['traces'])
        best = None
        for _ in range(trials):
            eng.reset_stats()
            t0 = time.perf_counter()
            hs = eng.generate_many(prompts, params)
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, hs, dict(eng.stats()))
        dt, hs, st = best
        return {
            'dt': dt,
            'parity': [h.tokens for h in hs] == expected,
            'recompiles': sum(eng.stats()['traces'].values())
            - sum(warm_traces.values()),
            'stats': st,
        }

    plain = run(None)
    spec = run(draft)
    sp = spec['stats'].get('spec', {})
    return {
        'tokens_per_sec_plain': round(tokens / plain['dt'], 1),
        'tokens_per_sec_spec': round(tokens / spec['dt'], 1),
        'speedup': round(plain['dt'] / spec['dt'], 4),
        'acceptance_rate': round(sp.get('acceptance_rate', 0.0), 4),
        'spec_rounds': sp.get('rounds', 0),
        'k': k, 'distill_steps': distill_steps,
        'parity': plain['parity'] and spec['parity'],
        'recompiles_after_warmup': plain['recompiles']
        + spec['recompiles'],
        'num_requests': num_requests, 'tokens': tokens,
    }


def stack_ab(num_requests=12, num_slots=10, max_length=96,
             decode_block=4, chunk=16, k=3, system_len=24):
    """The COMPOSED latency stack (also imported by the tier-1 stack
    guard): prefix cache + chunked prefill + speculative decoding all
    enabled on one engine, driven over a mixed trace — shared-prefix
    prompts, chunk-spanning prompts, greedy AND seeded-sampling
    requests. The guard fields: greedy outputs bit-identical to
    per-request generate(), and compiles after warmup zero by BOTH
    counters (python trace counts and `paddle_jit_compiles_total`)."""
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    model = _serving_model()
    paddle.seed(5)
    draft = GPTForCausalLM(GPTConfig(
        vocab_size=model.config.vocab_size, hidden_size=96,
        num_hidden_layers=1, num_attention_heads=4,
        intermediate_size=192,
        max_position_embeddings=model.config.max_position_embeddings,
        hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)).eval()
    rng = np.random.RandomState(17)
    vocab = model.config.vocab_size
    system = rng.randint(0, vocab, (system_len,)).tolist()
    suffix_lens = [3, 30, 9, 44]     # short suffixes AND chunk-spanners
    trace = []
    for i in range(num_requests):
        prompt = system + rng.randint(
            0, vocab, (suffix_lens[i % 4],)).tolist()
        if i % 3 == 2:
            sp = SamplingParams(max_new_tokens=10, strategy='sampling',
                                temperature=1.2, top_k=32, seed=i,
                                eos_token_id=-1)
        else:
            sp = SamplingParams(max_new_tokens=12, eos_token_id=-1)
        trace.append((prompt, sp))
    greedy_refs = {
        i: _ref_outputs(model, [(p, sp.max_new_tokens)])[0]
        for i, (p, sp) in enumerate(trace) if sp.strategy != 'sampling'}

    eng = InferenceEngine(
        model, num_slots=num_slots, max_length=max_length,
        decode_block=decode_block, prefix_cache=0.3,
        prefill_chunk_tokens=chunk, draft_model=draft,
        num_draft_tokens=k)
    # warmup: seed the cache, then a wave touching every program shape
    # (chunk buckets, suffix hits, full hit, spec round, draft buckets)
    eng.generate_many([trace[0][0]], [trace[0][1]])
    eng.generate_many([p for p, _ in trace[:5]],
                      [sp for _, sp in trace[:5]])
    warm_traces = dict(eng.stats()['traces'])
    reg = obs.get_registry()
    compiles0 = reg.value('paddle_jit_compiles_total')

    eng.reset_stats()
    t0 = time.perf_counter()
    handles = eng.generate_many([p for p, _ in trace],
                                [sp for _, sp in trace])
    dt = time.perf_counter() - t0
    parity = all(handles[i].tokens == ref
                 for i, ref in greedy_refs.items())
    st = eng.stats()
    return {
        'parity': parity,
        'recompiles_after_warmup': sum(eng.stats()['traces'].values())
        - sum(warm_traces.values()),
        'jit_compiles_delta': reg.value('paddle_jit_compiles_total')
        - compiles0,
        'tokens_per_sec': round(sum(len(h.tokens) for h in handles)
                                / dt, 1),
        'completed': sum(1 for h in handles if h.status == 'FINISHED'),
        'prefix_hits': st['prefix_cache']['hits'],
        'chunk_rounds': st['chunk_rounds'],
        'spec_acceptance': round(st['spec']['acceptance_rate'], 4),
        'num_requests': num_requests,
    }


# int8 KV quality bound: relative decode-logit RMSE vs the float32 cache,
# measured by paged_int8_rmse below and documented in the README Paged-KV
# section. Guarded in tier-1 (tests/test_paged_kv.py) with the same value.
PAGED_INT8_RMSE_BOUND = 0.05


def paged_int8_rmse(prompt_len=56, steps=8, page_size=16, seed=0):
    """Teacher-forced decode-logit drift for int8 KV: prefill one prompt
    through the shared `cached_forward` contract, roundtrip the KV slab
    page-wise through the per-(page, head) absmax int8 path (exactly
    what the quantized paged pool stores), then decode `steps` tokens
    against BOTH caches teacher-forced on the float32 greedy trajectory.
    Reports absolute and relative logit RMSE — the README's documented
    int8 quality bound (relative RMSE <= PAGED_INT8_RMSE_BOUND) is the
    number this function measures. The quantized arm re-roundtrips its
    cache after every step, matching the pool (every settled page lives
    in int8; nothing stays float between rounds)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import functional_state
    from paddle_tpu.nlp.generation import cached_forward
    from paddle_tpu.quantization import (kv_dequantize_page,
                                         kv_page_scales, kv_quantize_page)

    model = _serving_model()
    params, frozen, buffers = functional_state(model)
    fwd = cached_forward(model, params, frozen, buffers)
    maxlen = -(-(prompt_len + steps) // page_size) * page_size

    def roundtrip(cache):
        def rt(leaf):
            b, length, h, d = leaf.shape
            pages = leaf.reshape(b * (length // page_size),
                                 page_size, h, d)
            scales = kv_page_scales(pages)
            dq = kv_dequantize_page(
                kv_quantize_page(pages, scales), scales, leaf.dtype)
            return dq.reshape(leaf.shape)
        return jax.tree_util.tree_map(rt, cache)

    rng = np.random.RandomState(seed)
    ids = jnp.asarray(rng.randint(0, model.config.vocab_size,
                                  (1, prompt_len)), jnp.int32)
    cache = model.init_cache(1, maxlen)
    logits, cache = fwd(ids, cache, jnp.int32(0), jnp.int32(0), None)
    cache_q = roundtrip(cache)
    k_pos = jnp.arange(maxlen, dtype=jnp.int32)

    tok = int(np.asarray(logits[0, -1]).argmax())
    sq_err = sq_ref = 0.0
    agree = 0
    for i in range(steps):
        pos = jnp.full((1,), prompt_len + i, jnp.int32)
        mask = (k_pos[None, :] <= pos[:, None])[:, None, None, :]
        tok_dev = jnp.full((1, 1), tok, jnp.int32)
        la, cache = fwd(tok_dev, cache, pos, pos, mask)
        lq, cache_q = fwd(tok_dev, cache_q, pos, pos, mask)
        cache_q = roundtrip(cache_q)
        la = np.asarray(la[0, -1], np.float64)
        lq = np.asarray(lq[0, -1], np.float64)
        sq_err += float(((la - lq) ** 2).sum())
        sq_ref += float(((la - la.mean()) ** 2).sum())
        agree += int(la.argmax() == lq.argmax())
        tok = int(la.argmax())      # teacher-force the float32 path
    n = steps * la.shape[-1]
    rmse = (sq_err / n) ** 0.5
    rel = (sq_err / sq_ref) ** 0.5 if sq_ref else 0.0
    return {
        'logit_rmse': round(rmse, 6),
        'logit_rmse_rel': round(rel, 6),
        'rmse_bound': PAGED_INT8_RMSE_BOUND,
        'within_bound': rel <= PAGED_INT8_RMSE_BOUND,
        'greedy_agree_rate': round(agree / steps, 4),
        'prompt_len': prompt_len, 'steps': steps,
    }


def paged_ab(num_requests=12, system_len=48, max_length=96,
             decode_block=8, page_size=16, cap_requests=24, trials=2):
    """Row-vs-paged KV A/B at EQUAL HBM budget (also imported by the
    tier-1 paged guard). Both arms get the same number of KV rows:
    the row arm as 4 monolithic max_length slots, the paged arm as
    (4 * max_length / page_size) pages shared by 16 seats — the pool
    byte counts are asserted equal-or-better so the comparison is
    capacity-per-byte, never extra memory.

    Three sections:
    - capacity: a burst of short requests is submitted to both arms and
      stepped once; the row arm seats at most its 4 slots (every seat
      strands max_length - ~14 rows), the paged arm seats one page per
      request — the >= 3x concurrent-admission acceptance bar.
    - throughput/reuse: the shared-system-prompt trace (prefix_trace)
      with the prefix cache on in both arms. The paged arm retains the
      system prefix as SHARED pages (COW refcounts) instead of a whole
      retained slot, so reuse survives at equal HBM. Reports tokens/sec,
      prefill tokens reused, bit-exact greedy parity vs generate(), and
      zero recompiles after warmup per arm.
    - int8: the paged_int8_rmse teacher-forced logit-drift measurement
      for the quantized-KV mode, with the documented bound.
    """
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    model = _serving_model()
    vocab = model.config.vocab_size
    kv_pages = (4 * max_length) // page_size
    row_kw = dict(num_slots=4, max_length=max_length,
                  decode_block=decode_block)
    paged_kw = dict(num_slots=16, max_length=max_length,
                    decode_block=decode_block,
                    kv_page_size=page_size, kv_pages=kv_pages)

    # --- capacity: short-request burst, peak seats after one step ----
    # each request spans exactly ONE page (prompt + max_new == page
    # size) and outlives the first decode block, so seats are read
    # while everyone is still resident
    cap_new = decode_block + 4
    cap_len = max(1, page_size - cap_new)
    rng = np.random.RandomState(11)
    cap_prompts = [rng.randint(0, vocab, (cap_len,)).tolist()
                   for _ in range(cap_requests)]

    def capacity(kw):
        eng = InferenceEngine(model, **kw)
        hs = [eng.submit(p, SamplingParams(max_new_tokens=cap_new,
                                           eos_token_id=-1))
              for p in cap_prompts]
        eng.step()
        seated = eng.pool.used_count
        eng.run()
        done = sum(1 for h in hs if h.status == 'FINISHED')
        return seated, done, eng.pool.pool_bytes

    row_seated, row_done, row_bytes = capacity(row_kw)
    paged_seated, paged_done, paged_bytes = capacity(paged_kw)

    # --- throughput + prefill reuse on the shared-prefix trace -------
    trace = prefix_trace(num_requests, system_len=system_len,
                         vocab=vocab)
    prompts = [p for p, _ in trace]
    sparams = [SamplingParams(max_new_tokens=mn, eos_token_id=-1)
               for _, mn in trace]
    expected = _ref_outputs(model, trace)
    tokens = sum(mn for _, mn in trace)

    def run_arm(kw):
        eng = InferenceEngine(model, prefix_cache=0.25, **kw)
        # warmup: request 0 alone seeds the cache (inserts happen at
        # retirement), then a wave that HITS it — compiling the suffix
        # chunk buckets, the hit path, and the decode step
        eng.generate_many(prompts[:1], sparams[:1])
        eng.generate_many(prompts[:4], sparams[:4])
        warm = dict(eng.stats()['traces'])
        best = None
        for _ in range(trials):
            eng.reset_stats()
            t0 = time.perf_counter()
            hs = eng.generate_many(prompts, sparams)
            dt = time.perf_counter() - t0
            if best is None or dt < best[0]:
                best = (dt, hs, dict(eng.stats()))
        dt, hs, st = best
        return {
            'dt': dt, 'parity': [h.tokens for h in hs] == expected,
            'recompiles': sum(eng.stats()['traces'].values())
            - sum(warm.values()),
            'reused': st.get('prefix_cache', {}).get('tokens_reused', 0),
        }

    row = run_arm(row_kw)
    paged = run_arm(paged_kw)

    return {
        'row_pool_bytes': row_bytes,
        'paged_pool_bytes': paged_bytes,
        'equal_hbm': paged_bytes <= row_bytes,
        'concurrent_row': row_seated,
        'concurrent_paged': paged_seated,
        'capacity_ratio': round(paged_seated / row_seated, 2)
        if row_seated else 0.0,
        'cap_completed': min(row_done, paged_done),
        'tokens_per_sec_row': round(tokens / row['dt'], 1),
        'tokens_per_sec_paged': round(tokens / paged['dt'], 1),
        'prefill_reuse_row': row['reused'],
        'prefill_reuse_paged': paged['reused'],
        'parity': row['parity'] and paged['parity'],
        'recompiles_after_warmup': row['recompiles']
        + paged['recompiles'],
        'int8': paged_int8_rmse(page_size=page_size),
        'num_requests': num_requests, 'cap_requests': cap_requests,
        'page_size': page_size, 'kv_pages': kv_pages,
    }


def adapter_ab(num_adapters=3, requests_per_group=3, num_slots=4,
               max_length=96, decode_block=8, max_new=12, trials=2):
    """Heterogeneous-adapter batched-decode A/B (also imported by the
    tier-1 adapter guard). One base GPT + `num_adapters` LoRA adapters
    in a packed `AdapterBank`, over a deterministic mixed trace that
    round-robins base + every adapter. Three guard fields:

    - parity: every request's greedy output in the MIXED batch is
      bit-identical to running its adapter alone on a fresh
      single-adapter engine (base requests check against generate()).
    - zero recompiles after warmup — by python trace counters AND
      `paddle_jit_compiles_total` — across arbitrary adapter mixes
      AND a store-backed hot-swap (publish v2 of one adapter mid-run:
      new pins pick it up, outputs under it change, nothing retraces).
    - throughput: the mixed batch beats sequential per-adapter group
      serving on tokens/sec (homogeneous groups under-fill the slots;
      the packed bank lets one decode wave serve any mix).
    """
    import shutil
    import tempfile

    from paddle_tpu import observability as obs
    from paddle_tpu.serving import (AdapterBank, InferenceEngine,
                                    SamplingParams, make_adapter_factors)

    store_dir = tempfile.mkdtemp(prefix='adapter_bench_')
    try:
        model = _serving_model()
        vocab = model.config.vocab_size
        ids = [None] + [f'ad{i}' for i in range(num_adapters)]
        bank = AdapterBank(model, capacity=num_adapters + 1, rank=8,
                           store_dir=store_dir)
        for i, aid in enumerate(ids[1:]):
            bank.load(aid, make_adapter_factors(bank, seed=i + 1))

        # deterministic mixed trace: round-robin base + every adapter
        rng = np.random.RandomState(23)
        plens = [5, 11, 8, 14]
        trace = []
        for i in range(len(ids) * requests_per_group):
            prompt = rng.randint(1, vocab, (plens[i % 4],)).tolist()
            trace.append((prompt, ids[i % len(ids)]))
        sp = SamplingParams(max_new_tokens=max_new, eos_token_id=-1)

        # alone references: each adapter on a FRESH single-adapter
        # engine (identical weights — _serving_model reseeds), base
        # against per-request generate()
        expected = {}
        for gi, aid in enumerate(ids):
            group = [(j, p) for j, (p, a) in enumerate(trace) if a == aid]
            if aid is None:
                refs = _ref_outputs(model, [(p, max_new) for _, p in group])
                for (j, _), ref in zip(group, refs):
                    expected[j] = ref
                continue
            m = _serving_model()
            b = AdapterBank(m, capacity=2, rank=8)
            b.load(aid, make_adapter_factors(b, seed=gi))
            e = InferenceEngine(m, num_slots=num_slots,
                                max_length=max_length,
                                decode_block=decode_block, adapter_bank=b)
            for j, p in group:
                h = e.submit(p, sp, adapter_id=aid)
                e.run()
                expected[j] = h.tokens

        eng = InferenceEngine(model, num_slots=num_slots,
                              max_length=max_length,
                              decode_block=decode_block, adapter_bank=bank)

        def run_mixed(order=None):
            picks = order if order is not None else range(len(trace))
            t0 = time.perf_counter()
            hs = {j: eng.submit(trace[j][0], sp, adapter_id=trace[j][1])
                  for j in picks}
            eng.run()
            return time.perf_counter() - t0, hs

        # warmup covers every prompt bucket under every adapter, then
        # both compile counters must stay FLAT to the end
        run_mixed()
        warm = dict(eng.stats()['traces'])
        reg = obs.get_registry()
        compiles0 = reg.value('paddle_jit_compiles_total')

        best_mixed, hs = min((run_mixed() for _ in range(trials)),
                             key=lambda t: t[0])
        parity = all(hs[j].tokens == expected[j] for j in hs)

        # a PERMUTED mix, still zero recompiles
        perm = list(reversed(range(len(trace))))
        _, hs_perm = run_mixed(perm)
        parity = parity and all(hs_perm[j].tokens == expected[j]
                                for j in hs_perm)

        # sequential per-adapter-group serving: same engine, same
        # requests, but homogeneous waves (what an engine without
        # heterogeneous batching is forced into)
        def run_sequential():
            t0 = time.perf_counter()
            for aid in ids:
                for j, (p, a) in enumerate(trace):
                    if a == aid:
                        eng.submit(p, sp, adapter_id=aid)
                eng.run()
            return time.perf_counter() - t0

        best_seq = min(run_sequential() for _ in range(trials))

        # store-backed hot-swap: publish ad0 v2; the next pins load it
        # into a fresh slot — outputs under ad0 change, every other
        # request stays bit-exact, and NOTHING retraces
        bank.publish('ad0', make_adapter_factors(bank, seed=101))
        _, hs_swap = run_mixed()
        swap_changed = any(hs_swap[j].tokens != expected[j]
                           for j in hs_swap if trace[j][1] == 'ad0')
        swap_others_exact = all(hs_swap[j].tokens == expected[j]
                                for j in hs_swap if trace[j][1] != 'ad0')

        tokens = len(trace) * max_new
        return {
            'parity': parity,
            'recompiles_after_warmup': sum(eng.stats()['traces'].values())
            - sum(warm.values()),
            'jit_compiles_delta': reg.value('paddle_jit_compiles_total')
            - compiles0,
            'tokens_per_sec_mixed': round(tokens / best_mixed, 1),
            'tokens_per_sec_sequential': round(tokens / best_seq, 1),
            'mixed_speedup': round(best_seq / best_mixed, 2),
            'hot_swap_outputs_changed': swap_changed,
            'hot_swap_others_bit_exact': swap_others_exact,
            'num_adapters': num_adapters,
            'num_requests': len(trace),
            'bank': bank.stats(),
        }
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)


def adapters_smoke(duration_s=4.0, rate=8.0, seed=77, time_scale=0.2):
    """Tier-1 smoke (`bench.py adapters --smoke`): a deterministic
    mixed-adapter loadgen trace — two tenants, one with a per-tenant
    adapter mix, one pure base — replayed through a Router onto a
    bank-backed engine. The guard asserts the trace is bit-identical
    across two builds from the same seed (adapter draws included),
    zero requests dropped, and at least two different adapters
    actually served."""
    from paddle_tpu.loadgen import (FixedLength, LoadReplayer,
                                    PoissonSchedule, TenantClass,
                                    make_trace, trace_stats)
    from paddle_tpu.serving import (AdapterBank, InferenceEngine,
                                    PRIORITY_HIGH, PRIORITY_LOW,
                                    Replica, Router,
                                    make_adapter_factors)
    from paddle_tpu.serving.tenancy import TenantRegistry

    model = _serving_model()
    bank = AdapterBank(model, capacity=4, rank=8)
    bank.load('ad0', make_adapter_factors(bank, seed=1))
    bank.load('ad1', make_adapter_factors(bank, seed=2))
    eng = InferenceEngine(model, num_slots=4, max_length=96,
                          decode_block=8, adapter_bank=bank)

    tenants = [
        TenantClass(name='paid', weight=2.0, priority=PRIORITY_HIGH,
                    adapters=(('ad0', 2.0), ('ad1', 1.0), (None, 1.0))),
        TenantClass(name='free', weight=1.0, priority=PRIORITY_LOW),
    ]
    kw = dict(schedule=PoissonSchedule(rate), duration_s=duration_s,
              seed=seed, prompt_lengths=FixedLength(8),
              output_lengths=FixedLength(6), tenants=tenants,
              vocab_size=model.config.vocab_size)
    trace = make_trace(**kw)
    deterministic = make_trace(**kw) == trace

    reg = TenantRegistry()
    reg.add('paid', priority=PRIORITY_HIGH)
    reg.add('free', priority=PRIORITY_LOW)
    router = Router([Replica(0, eng)], tenants=reg)
    report = LoadReplayer(router, trace, time_scale=time_scale,
                          max_wall_s=60.0).run().report(slo_ttft_s=2.0)
    stats = trace_stats(trace)
    return {
        'trace_deterministic': deterministic,
        'offered': report['offered'],
        'completed': report['completed'],
        'dropped': report['dropped'],
        'by_adapter': stats.get('by_adapter', {}),
        'adapters_served': len(stats.get('by_adapter', {})),
        'bank': bank.stats(),
    }


def _phase_adapters():
    """Multi-tenant adapter phase: the heterogeneous-adapter batched
    decode A/B (parity / zero-recompile / mixed-vs-sequential — the
    ISSUE 19 acceptance fields) plus the loadgen mixed-adapter smoke."""
    out = {}
    for key, fn in (('adapter_ab', adapter_ab),
                    ('adapters_smoke', adapters_smoke)):
        try:
            out[key] = fn()
        except Exception as e:
            print(f'# {key} bench failed: {type(e).__name__}: {e}',
                  file=sys.stderr)
            out[key] = {'error': type(e).__name__}
    return out


def _phase_serving():
    """Serving phase: continuous-batching throughput vs the sequential
    generate() loop, then the latency stack — prefix-cache, chunked-
    prefill, and speculative-decoding A/Bs plus the composed-stack
    guard (tier-1 guards parity + zero recompiles on each; the
    speedup/reduction/TTFT numbers are the headline serving figures)."""
    out = {}
    for key, fn in (('serving', serving_ab), ('prefix', prefix_ab),
                    ('chunked', chunked_ab), ('spec', spec_ab),
                    ('stack', stack_ab), ('paged', paged_ab)):
        try:
            out[key] = fn()
        except Exception as e:
            print(f'# {key} bench failed: {type(e).__name__}: {e}',
                  file=sys.stderr)
            out[key] = {'error': type(e).__name__}
    return out


def router_ab(num_requests=24, num_slots=6, max_length=96, decode_block=8,
              trials=2, kill_at_round=3):
    """Replicated-serving A/B on the PR-4 mixed trace (also imported by
    the tier-1 router guard). Four arms over the same weight-heavy GPT:

    - bare: one `InferenceEngine` (num_slots), no router — the overhead
      baseline.
    - router1: the same capacity behind a 1-replica `Router`; the
      no-fault overhead ratio vs bare is tier-1-guarded under 3%.
    - router2: 2 replicas x num_slots — the scaling number (2x the
      slots amortizing each weight stream; the 'add a replica, serve
      more' story).
    - chaos: 2 replicas with replica 0 fault-injected to die (transient
      UNAVAILABLE) mid-trace at decode round `kill_at_round`. Reports
      `lost_requests` — accepted requests that neither finished nor
      failed with a typed error — which the tier-1 guard pins at 0, and
      the throughput-degradation ratio vs the no-fault 2-replica run.

    Plus a `qos` section: a 1-replica overload with a protected
    high-priority tenant and a sheddable low-priority flood
    (shed_queue_depth), reporting per-class p50 TTFT and the shed
    count — the 'rejected fast, paid traffic unaffected' numbers.
    """
    import paddle_tpu as paddle
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    from paddle_tpu.resilience import TransientError
    from paddle_tpu.serving import (AdmissionRejected, InferenceEngine,
                                    ReplicaSet, Router, SamplingParams)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=384, num_hidden_layers=4,
                    num_attention_heads=4, max_position_embeddings=128,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg).eval()
    trace = serving_trace(num_requests, vocab=cfg.vocab_size)
    prompts = [p for p, _ in trace]
    params = [SamplingParams(max_new_tokens=mn, eos_token_id=-1)
              for _, mn in trace]
    tokens = sum(mn for _, mn in trace)
    eng_kw = dict(num_slots=num_slots, max_length=max_length,
                  decode_block=decode_block)

    def timed(fn):
        t0 = time.perf_counter()
        hs = fn()
        return time.perf_counter() - t0, hs

    # warm every arm first, then INTERLEAVE the timed trials: the
    # bare-vs-router overhead ratio is a few percent at most, so drift
    # between non-adjacent runs (CI neighbours, GC) must not land on
    # one arm only (same best-of-N protocol as obs_overhead_ab)
    engine = InferenceEngine(model, **eng_kw)
    engine.generate_many(prompts[:num_slots + 1], params[:num_slots + 1])
    router1 = Router(ReplicaSet(model, 1, **eng_kw))
    router1.generate_many(prompts[:num_slots + 1], params[:num_slots + 1])
    router2 = Router(ReplicaSet(model, 2, **eng_kw))
    router2.generate_many(prompts[:num_slots + 1], params[:num_slots + 1])

    best_bare = best_r1 = best_r2 = float('inf')
    r1_handles = r2_handles = None
    ratios = []
    for _ in range(trials):
        bare_dt, _hs = timed(lambda: engine.generate_many(prompts, params))
        best_bare = min(best_bare, bare_dt)
        dt, hs = timed(lambda: router1.generate_many(prompts, params))
        if dt < best_r1:
            best_r1, r1_handles = dt, hs
        # the overhead estimate pairs ADJACENT runs and takes the MIN
        # ratio: contention noise on a shared (here single-core) box is
        # strictly additive, so the least-noisy pair is the closest to
        # the uncontended truth, and drift moves both members of a pair
        # together — where best-of-N across arms can land its bests in
        # different noise regimes and report phantom overhead. A real
        # regression shows up in EVERY pair, so the min still catches it.
        ratios.append(dt / bare_dt)
        dt, hs = timed(lambda: router2.generate_many(prompts, params))
        if dt < best_r2:
            best_r2, r2_handles = dt, hs
    bare_tps = tokens / best_bare
    r1_tps = tokens / best_r1
    r2_tps = tokens / best_r2
    overhead = min(ratios) - 1

    # --- chaos arm: replica 0 dies mid-trace, failover must lose 0 ----
    rs = ReplicaSet(model, 2, **eng_kw)
    router = Router(rs)
    router.generate_many(prompts[:num_slots + 1], params[:num_slots + 1])
    calls = [0]
    victim = rs[0].engine
    real_step = victim.step

    def dying_step():
        calls[0] += 1
        if calls[0] == kill_at_round:
            raise TransientError('UNAVAILABLE: injected replica loss')
        return real_step()

    victim.step = dying_step
    try:
        t0 = time.perf_counter()
        chaos_handles = router.generate_many(prompts, params)
        chaos_dt = time.perf_counter() - t0
    finally:
        victim.step = real_step
    lost = sum(1 for h in chaos_handles
               if not (h.status == 'FINISHED'
                       or (h.status == 'FAILED' and h.error is not None)))
    chaos_tps = tokens / chaos_dt
    failed_over = sum(1 for h in chaos_handles if h.failovers)

    # --- qos arm: protected high tenant under a sheddable flood -------
    qrouter = Router(
        ReplicaSet(model, 1, **eng_kw),
        tenants=('paid:priority=high;'
                 f'free:priority=low,concurrency={max(num_slots // 2, 1)}'),
        shed_queue_depth=num_slots)
    qrouter.generate_many(prompts[:num_slots + 1], params[:num_slots + 1])
    accepted, shed = [], 0
    for i, (p, sp) in enumerate(zip(prompts, params)):
        tenant = 'paid' if i % 3 == 0 else 'free'
        try:
            accepted.append((tenant, qrouter.submit(p, sp, tenant=tenant)))
        except AdmissionRejected:
            shed += 1
        qrouter.step()    # interleave decode so the queue drains/overloads
    qrouter.run()

    def p50(vals):
        vals = sorted(vals)
        return round(vals[len(vals) // 2] * 1e3, 2) if vals else None

    qos = {
        'shed': shed,
        'accepted': len(accepted),
        'p50_ttft_ms_high': p50([h.ttft for t, h in accepted
                                 if t == 'paid' and h.ttft is not None]),
        'p50_ttft_ms_low': p50([h.ttft for t, h in accepted
                                if t == 'free' and h.ttft is not None]),
    }

    return {
        'bare_tokens_per_sec': round(bare_tps, 1),
        'router1_tokens_per_sec': round(r1_tps, 1),
        'router2_tokens_per_sec': round(r2_tps, 1),
        'scaling_2_replica': round(r2_tps / r1_tps, 2) if r1_tps else 0.0,
        'scaling_note': 'replicas share one driver thread + one CPU '
                        'here, so 2-replica scaling measures router '
                        'overhead at 2x capacity, not hardware scaling; '
                        'on a fleet each replica owns its own chips',
        'router_overhead_pct': round(overhead * 100, 2),
        'num_requests': num_requests, 'num_slots': num_slots,
        'tokens': tokens,
        'parity': ([h.tokens for h in r2_handles]
                   == [h.tokens for h in r1_handles]),
        'chaos': {
            'tokens_per_sec': round(chaos_tps, 1),
            'lost_requests': lost,
            'failed_over_requests': failed_over,
            'completed': sum(1 for h in chaos_handles
                             if h.status == 'FINISHED'),
            'failed_typed': sum(1 for h in chaos_handles
                                if h.status == 'FAILED'),
            'degradation_vs_2_replica': round(chaos_tps / r2_tps, 3)
            if r2_tps else 0.0,
        },
        'qos': qos,
    }


def _phase_router():
    """Replicated-serving phase: router overhead + 2-replica scaling +
    the chaos (replica killed mid-trace) and QoS-shedding numbers
    (tier-1 guards lost_requests == 0 and overhead < 3%)."""
    try:
        return {'router': router_ab()}
    except Exception as e:
        print(f'# router bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        return {'router': {'error': type(e).__name__}}


def coldstart_child(opts):
    """One restart measurement, run IN A FRESH PROCESS (bench.py
    --coldstart-child '<json>'): build the small GPT, preload the
    program store, then measure wall time AND XLA compile counts around
    the first train step and the first served tokens. With an empty
    store dir this is the cold arm (compiles happen inside the measured
    windows); re-run against the now-populated dir it is the warm arm —
    the tier-1 guard asserts the warm windows contain ZERO backend
    compiles (`paddle_jit_compiles_total`) for the unchanged signatures,
    and that losses/tokens are bit-identical to the cold run."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import observability as obs
    from paddle_tpu import programs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import InferenceEngine, SamplingParams

    store_dir = opts.get('store_dir') or None
    steps = int(opts.get('steps', 3))
    vocab, seq, batch = 256, 32, 4
    if store_dir:
        programs.configure(store_dir)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    intermediate_size=256, max_position_embeddings=seq,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    step = TrainStep(
        model,
        lambda logits, labels: F.cross_entropy(
            logits[:, :-1].reshape([-1, vocab]),
            labels[:, 1:].reshape([-1])),
        opt)
    ids = ((np.random.RandomState(0).randint(0, vocab - seq, (batch, 1))
            + np.arange(seq)) % vocab)
    # warm the incidental non-store programs (RNG fold-in, host<->device
    # converts, optimizer-state zero-fills — a real resume restores opt
    # state from the checkpoint instead) OUTSIDE the measured windows so
    # the compile deltas isolate the store-owned executables — the ones
    # worth minutes at production scale
    from paddle_tpu.jit import functional_state
    _ = jax.random.fold_in(step._step_key_root, 0)
    _ = np.asarray(jnp.asarray(ids))
    _ = float(np.asarray(jnp.asarray(0.001, jnp.float32)))
    _params, _, _ = functional_state(model)
    step._opt_state = opt.init_state(_params)
    reg = obs.get_registry()

    def real_compiles(marks):
        # backend-compile ticks NOT served by the persistent XLA cache
        return int((reg.value('paddle_jit_compiles_total') - marks[0])
                   - (reg.value('paddle_jit_cache_hits_total')
                      - marks[1]))

    def marks():
        return (reg.value('paddle_jit_compiles_total'),
                reg.value('paddle_jit_cache_hits_total'))

    t0 = time.perf_counter()
    pre = programs.get_store().preload()
    m0 = marks()
    losses = [float(step(ids, ids).numpy()) for _ in range(steps)]
    train_compiles = real_compiles(m0)
    t_first_step = time.perf_counter() - t0

    model.eval()
    engine = InferenceEngine(model, num_slots=2, max_length=seq,
                             decode_block=2)
    prompts = [((np.arange(5) + 7) % vocab).tolist(),
               ((np.arange(9) + 3) % vocab).tolist()]
    t1 = time.perf_counter()
    m1 = marks()
    handles = engine.generate_many(
        prompts, [SamplingParams(max_new_tokens=6, eos_token_id=-1)] * 2)
    decode_compiles = real_compiles(m1)
    t_first_tokens = time.perf_counter() - t1

    return {
        'store_dir': store_dir,
        'preload': pre,
        'time_to_first_step_s': round(t_first_step, 4),
        'time_to_first_tokens_s': round(t_first_tokens, 4),
        'train_compiles_measured': train_compiles,
        'decode_compiles_measured': decode_compiles,
        'losses': losses,
        'tokens': [h.tokens for h in handles],
        'store': {k: v for k, v in programs.get_store().stats().items()
                  if k in ('hits_disk', 'misses', 'rejects', 'persisted',
                           'disk_entries', 'coldstart_seconds')},
    }


def coldstart_ab(steps=3, timeout_s=420):
    """A/B process restart against an empty vs populated program store
    (also imported by the tier-1 coldstart guard). Pure orchestration —
    this function never imports jax: a chip has one client, and a parent
    holding it would lock its own children out. Reports the warm/cold
    ratio of time-to-first-(step|tokens) and the two warm-path compile
    counts the guard pins to zero, plus bit-exactness of the warm run's
    losses and greedy tokens vs the cold run's."""
    import subprocess
    import tempfile

    store_dir = tempfile.mkdtemp(prefix='bench_coldstart_')
    # the cold arm must really compile, so this A/B places its children's
    # compile cache from outside, in a directory as fresh as the store
    # (programs.ensure_compile_cache honours the variable)
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(store_dir, 'xla'))

    def run_child():
        proc = subprocess.run(
            [sys.executable, __file__, '--coldstart-child',
             json.dumps({'store_dir': store_dir, 'steps': steps})],
            capture_output=True, text=True, timeout=timeout_s, env=env)
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(f'coldstart child failed: '
                               f'exit {proc.returncode}')
        return json.loads(proc.stdout.strip().splitlines()[-1])

    cold = run_child()
    warm = run_child()
    cold_work = (cold['time_to_first_step_s']
                 + cold['time_to_first_tokens_s'])
    warm_work = (warm['time_to_first_step_s']
                 + warm['time_to_first_tokens_s'])
    return {
        'cold_first_work_s': round(cold_work, 4),
        'warm_first_work_s': round(warm_work, 4),
        'warm_cold_ratio': round(cold_work / warm_work, 2)
        if warm_work else 0.0,
        'warm_train_compiles': warm['train_compiles_measured'],
        'warm_decode_compiles': warm['decode_compiles_measured'],
        'cold_train_compiles': cold['train_compiles_measured'],
        'cold_decode_compiles': cold['decode_compiles_measured'],
        'warm_loaded_from_disk': warm['preload']['loaded'],
        'warm_rejects': warm['store']['rejects'],
        'parity_losses': warm['losses'] == cold['losses'],
        'parity_tokens': warm['tokens'] == cold['tokens'],
        'steps': steps,
    }


def _phase_coldstart():
    """Cold-restart phase: empty-store vs populated-store process
    restart A/B (warm path guarded to zero XLA compiles + bit-exact),
    entirely in subprocesses."""
    out = {}
    try:
        out['coldstart'] = coldstart_ab()
    except Exception as e:
        print(f'# coldstart bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        out['coldstart'] = {'error': type(e).__name__}
    return out


def goodput_overhead_ab(steps=30, trials=3):
    """Goodput-ledger on vs off A/B on the instrumented eager MLP loop
    (also imported by the tier-1 <3% overhead guard). Both arms run the
    SAME instrumentation (spans + StepTelemetry); only the ledger's
    EventLog listener toggles — so the ratio isolates what the ledger's
    interval bookkeeping costs the hot path. Min-of-adjacent-pair
    ratios, same estimator as the scrape guard (best-of-N across arms
    reports phantom overhead on a loaded 1-core box)."""
    from paddle_tpu import observability as obs

    led = obs.get_ledger()
    was_running = led.running
    ratios = []
    best_on = best_off = 0.0
    try:
        for _ in range(trials):
            led.stop()
            off = eager_mlp_loop(steps=steps, instrument=True)
            led.start()
            on = eager_mlp_loop(steps=steps, instrument=True)
            best_off = max(best_off, off['steps_per_sec'])
            best_on = max(best_on, on['steps_per_sec'])
            if on['steps_per_sec']:
                ratios.append(off['steps_per_sec'] / on['steps_per_sec'])
    finally:
        led.start() if was_running else led.stop()
    overhead = min(ratios) - 1 if ratios else float('inf')
    return {
        'ledger_steps_per_sec': best_on,
        'plain_steps_per_sec': best_off,
        'overhead_pct': round(overhead * 100, 2),
    }


def goodput_gpt_mfu(steps=12, warmup=3, batch=4, seq=128,
                    peak_flops=1e12):
    """MFU cross-check on a GPT train loop (also imported by the tier-1
    within-10% guard): the observability layer's windowed aggregate MFU
    (XLA cost_analysis FLOPs over catalog host seconds, compile
    excluded — what `paddle_mfu` publishes) vs the analytic matmul-FLOPs
    MFU this bench derives independently, against the SAME fixed peak.
    Two unrelated estimators agreeing is the evidence the gauge can be
    trusted on the real chip."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import observability as obs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    # matmul-dominant small shape: big enough that weight matmuls dwarf the
    # elementwise/attention FLOPs the analytic formula under-counts
    cfg = LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=688,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=max(2 * seq, 256))
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())

    def loss_fn(logits, labels):
        lg = logits[:, :-1].reshape([-1, cfg.vocab_size])
        lb = labels[:, 1:].reshape([-1])
        return F.cross_entropy(lg, lb)

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    batches = [rng.randint(0, cfg.vocab_size, (batch, seq))
               for _ in range(4)]
    for i in range(warmup):
        loss = step(batches[i % 4], batches[i % 4])
    float(loss.numpy())

    peaks = {'device_kind': 'bench-fixed', 'peak_flops': float(peak_flops),
             'peak_hbm_bytes_per_s': None, 'source': 'fixed'}
    with obs.MfuWindow(peaks=peaks) as win:
        t0 = time.perf_counter()
        for i in range(steps):
            loss = step(batches[i % 4], batches[i % 4])
        float(loss.numpy())
        dt = (time.perf_counter() - t0) / steps
    measured = win.result()

    # the same analytic model-FLOPs formula the headline phase uses
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    qkvo = h * (cfg.num_attention_heads * cfg.head_dim) * 2 \
        + h * (cfg.num_key_value_heads * cfg.head_dim) * 2
    n_matmul = L * (qkvo + 3 * h * cfg.intermediate_size) \
        + h * cfg.vocab_size
    fwd_flops = (2 * n_matmul * batch * seq
                 + L * 4 * batch * seq * seq * h)
    bench_mfu = 3 * fwd_flops / dt / peak_flops

    paddle_mfu = measured['mfu'] or 0.0
    rel_err = abs(paddle_mfu / bench_mfu - 1.0) if bench_mfu else 1.0
    return {
        'bench_mfu': round(bench_mfu, 6),
        'paddle_mfu': round(paddle_mfu, 6),
        'rel_err_pct': round(rel_err * 100, 2),
        'step_time_s': round(dt, 5),
        'window_flops': measured['flops_total'],
        'window_wall_s': round(measured['wall_seconds'], 4),
    }


def goodput_fault_ledger(steps=12, step_sleep=0.02, backoff_s=0.3):
    """Fault-injected ledger closure (also imported by the tier-1
    guard): an eager train loop with per-step spans takes exactly one
    transient retry (fixed backoff, no jitter), one NaN rollback, and
    one checkpoint save. Returns the goodput report plus the injected
    ground truth so the guard can assert (a) the books close — category
    seconds + residual == wall within 1% — and (b) each injected second
    landed in ITS category: backoff in retry_backoff, the bad step's
    compute in rollback, the save in checkpoint_save."""
    import tempfile

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu import observability as obs
    from paddle_tpu import resilience as res
    from paddle_tpu.utils.checkpoint import CheckpointManager

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=model.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.standard_normal((8, 16)).astype('float32'))
    y = paddle.to_tensor(rng.randint(0, 4, (8,)))

    calls = {'n': 0}
    fail_at, nan_at, ckpt_at = 3, 6, 9

    def one_step():
        calls['n'] += 1
        with obs.span('bench.eager_step'):
            time.sleep(step_sleep)   # give every step deterministic mass
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            if calls['n'] == fail_at:
                raise res.TransientError('injected transient blip')
        if calls['n'] == nan_at:
            import jax.numpy as jnp
            return paddle.Tensor(jnp.float32(float('nan')))
        return loss

    def snap():
        return {n: np.asarray(p.value)
                for n, p in model.named_parameters()}

    def rest(s):
        import jax.numpy as jnp
        pm = dict(model.named_parameters())
        for n, v in s.items():
            pm[n]._data = jnp.asarray(v)
            pm[n]._node = None

    policy = res.RetryPolicy(max_retries=1, base_delay=backoff_s,
                             jitter=0.0, multiplier=1.0)
    # check_spikes=False: only the injected NaN triggers a rollback, so
    # the ground truth stays exactly 1 retry + 1 rollback + 1 checkpoint
    ft = res.FaultTolerantStep(one_step, snapshot_fn=snap, restore_fn=rest,
                               retry_policy=policy, skip_budget=2,
                               snapshot_interval=1, check_spikes=False)

    one_step()   # warm the dispatch cache outside the measured window
    calls['n'] = 0

    ledger = obs.get_ledger()
    was_running = ledger.running
    ledger.start(reset=True)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        i = 0
        while calls['n'] < steps:
            loss = ft()
            i += 1
            if i == ckpt_at:
                mgr.save(i, snap(), force=True)
    wall = time.perf_counter() - t0
    report = ledger.report()
    if not was_running:
        ledger.stop()
    report['loop_wall_seconds'] = wall
    report['injected'] = {'backoff_s': backoff_s,
                          'step_sleep_s': step_sleep,
                          'retries': 1, 'rollbacks': 1, 'checkpoints': 1,
                          'steps': calls['n']}
    report['ft_stats'] = ft.stats()
    return report


def reqledger_overhead_ab(trials=3, n_requests=12, max_new=8):
    """Request-ledger on vs off A/B on a routed serving trace (also
    imported by the tier-1 <3% overhead guard). Both arms run the SAME
    router/engine path; only the per-request ledger toggles — the ratio
    isolates what phase bookkeeping (queue spans, per-round fair-share
    attribution, finalize) costs the serving hot loop. Min-of-
    adjacent-pair ratios, same estimator as the scrape guard
    (best-of-N across arms reports phantom overhead on a loaded
    1-core box)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.serving import (InferenceEngine, Replica, Router,
                                    SamplingParams)

    model = _serving_model()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, model.config.vocab_size, (s,)).tolist()
               for s in ([5, 9, 13, 7, 11, 6] * n_requests)[:n_requests]]

    def run_arm():
        eng = InferenceEngine(model, num_slots=4, max_length=64,
                              decode_block=8)
        router = Router([Replica(0, eng)])
        t0 = time.perf_counter()
        handles = [router.submit(
            p, SamplingParams(max_new_tokens=max_new, eos_token_id=-1))
            for p in prompts]
        while not all(h.done for h in handles):
            router.step()
        wall = time.perf_counter() - t0
        toks = sum(len(h.tokens) for h in handles)
        return toks / wall if wall > 0 else 0.0

    led = obs.get_request_ledger()
    was_on = led.is_enabled
    ratios = []
    best_on = best_off = 0.0
    try:
        run_arm()                      # warm compile caches off-ledger
        for _ in range(trials):
            led.disable()
            off = run_arm()
            led.enable()
            on = run_arm()
            best_off = max(best_off, off)
            best_on = max(best_on, on)
            if on:
                ratios.append(off / on)
    finally:
        led.enable() if was_on else led.disable()
    overhead = min(ratios) - 1 if ratios else float('inf')
    return {
        'ledger_tokens_per_sec': best_on,
        'plain_tokens_per_sec': best_off,
        'overhead_pct': round(overhead * 100, 2),
    }


def _phase_goodput():
    """Goodput/MFU phase: ledger overhead A/B, the MFU cross-check, and
    the fault-injected ledger-closure run — the tier-1 guards pin
    overhead <3%, MFU agreement <10%, and closure-within-1% on CPU."""
    out = {}
    for key, fn in (('goodput_overhead', goodput_overhead_ab),
                    ('reqledger_overhead', reqledger_overhead_ab),
                    ('gpt_mfu', goodput_gpt_mfu),
                    ('fault_ledger', goodput_fault_ledger)):
        try:
            out[key] = fn()
        except Exception as e:
            print(f'# goodput bench {key} failed: {type(e).__name__}: {e}',
                  file=sys.stderr)
            out[key] = {'error': type(e).__name__}
    return out


def _autoscale_schedule(pattern, duration_s, rate):
    """The three traffic shapes of the autoscale A/B, all peaking at
    `rate` req/s so the static comparison fleet is sized once."""
    from paddle_tpu import loadgen
    if pattern == 'poisson':
        return loadgen.PoissonSchedule(rate)
    if pattern == 'diurnal':
        # one full cycle: quiet -> peak (mid-trace) -> quiet, trough at
        # a fifth of the peak — the day/night swing scale-down feeds on
        return loadgen.DiurnalSchedule(rate / 5.0, rate,
                                       period_s=duration_s)
    if pattern == 'burst':
        # flash crowd: a fifth of the trace's volume lands inside 50 ms
        # mid-trace — arrival concentration beats any box's drain rate,
        # so the backlog (and the autoscaler's reaction to it) is real
        # on fast hardware too, unlike a merely-elevated rate
        herd = max(rate * duration_s * 0.2, 8.0)
        return loadgen.BurstSchedule(rate / 4.0, herd / 0.05,
                                     burst_start_s=duration_s * 0.4,
                                     burst_len_s=0.05)
    raise ValueError(f'unknown traffic pattern {pattern!r}')


def autoscale_arm(model, trace, *, autoscaled, replicas, max_replicas,
                  slo_ttft_s, eng_kw, time_scale=1.0, max_wall_s=120.0,
                  signal_window_s=3.0, cooldown_s=0.5,
                  down_stable_s=1.0):
    """Replay ONE trace against a fresh fleet and close the goodput
    books around it (also imported by the tier-1 guards).

    Static arm: `replicas` engines for the whole trace. Autoscaled
    arm: start at 1, let the `Autoscaler` (forced on, flag-independent
    — this IS the A/B) grow to `max_replicas` and shrink back on the
    windowed signals. Both arms report the user-felt numbers (p99-TTFT
    SLO attainment, replica-seconds, attainment per replica-hour) plus
    the ledger's verdict on what the machinery cost: scale_up /
    scale_down seconds, their fraction of wall, and closure — the
    books must still sum to wall within 1% with the new categories in
    play."""
    from paddle_tpu import loadgen, observability as obs
    from paddle_tpu.serving import (Autoscaler, AutoscalerConfig,
                                    InferenceEngine, ReplicaSet, Router)

    router = Router(ReplicaSet(model, 1 if autoscaled else replicas,
                               **eng_kw),
                    signal_window_s=signal_window_s)
    scaler = None
    if autoscaled:
        scaler = Autoscaler(
            router, lambda: InferenceEngine(model, **eng_kw),
            AutoscalerConfig(min_replicas=1, max_replicas=max_replicas,
                             slo_ttft_s=slo_ttft_s,
                             cooldown_s=cooldown_s,
                             down_stable_s=down_stable_s),
            force=True)
    ledger = obs.get_ledger()
    was_running = ledger.running
    ledger.start(reset=True)
    report = loadgen.LoadReplayer(router, trace, autoscaler=scaler,
                                  time_scale=time_scale,
                                  max_wall_s=max_wall_s).run()
    books = ledger.report()
    if not was_running:
        ledger.stop()
    wall = books['wall_seconds']
    closure = abs(sum(books['categories'].values())
                  + books['residual_seconds'] - wall)
    cats = books['categories']
    out = report.report(slo_ttft_s)
    out.update({
        'autoscaled': bool(autoscaled),
        'replicas_start': 1 if autoscaled else replicas,
        'replicas_final': len(router.replicas),
        'ledger': {
            'wall_s': round(wall, 3),
            'closure_err_pct': round(100.0 * closure / wall, 4)
            if wall else 0.0,
            'scale_up_s': round(cats.get('scale_up', 0.0), 4),
            'scale_down_s': round(cats.get('scale_down', 0.0), 4),
            'machinery_pct': round(
                100.0 * (cats.get('scale_up', 0.0)
                         + cats.get('scale_down', 0.0)) / wall, 3)
            if wall else 0.0,
            'serving_decode_s': round(cats.get('serving_decode', 0.0), 3),
        },
    })
    if scaler is not None:
        s = scaler.stats()
        out['autoscaler'] = {'decisions': s['decisions'],
                             'provision_ema_s': s['provision_ema_s']}
    return out


def autoscale_ab(duration_s=10.0, rate=60.0, seed=1234, slo_ttft_s=2.0,
                 max_replicas=3, patterns=('poisson', 'diurnal', 'burst')):
    """The ISSUE-14 headline: p99-TTFT SLO attainment per replica-hour,
    static peak-sized fleet vs autoscaled, across the three traffic
    patterns — with the goodput ledger proving the autoscaling
    machinery costs <3% of wall and the books still close within 1%.

    The static arm runs `max_replicas` engines for the whole trace
    (the 'provision for the peak' posture); the autoscaled arm starts
    at one replica and follows the windowed signals. Same seed ⇒ both
    arms replay bit-identical traces."""
    import paddle_tpu as paddle
    from paddle_tpu import loadgen
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny()).eval()
    eng_kw = dict(num_slots=4, max_length=64, decode_block=4)
    # warm every prefill bucket + the decode block OUTSIDE the arms:
    # arms run sequentially in one process and share the in-memory
    # program store, so whichever arm ran first would otherwise eat the
    # compiles and bias the comparison
    from paddle_tpu.serving import InferenceEngine, SamplingParams
    warm_rng = np.random.RandomState(0)
    InferenceEngine(model, **eng_kw).generate_many(
        [warm_rng.randint(1, 64, (l,)).tolist() for l in (4, 8, 16, 32)],
        [SamplingParams(max_new_tokens=6, eos_token_id=-1)] * 4)
    out = {'slo_ttft_s': slo_ttft_s, 'max_replicas': max_replicas,
           'duration_s': duration_s, 'peak_rate': rate}
    for pattern in patterns:
        trace = loadgen.make_trace(
            _autoscale_schedule(pattern, duration_s, rate), duration_s,
            seed=seed,
            prompt_lengths=loadgen.LognormalLengths(10, 0.5, 4, 32),
            output_lengths=loadgen.FixedLength(6),
            tenants=[loadgen.TenantClass('paid', 1, 0),
                     loadgen.TenantClass('free', 2, 2)],
            vocab_size=min(getattr(model.config, 'vocab_size', 128), 128))
        loadgen.validate_trace(trace, eng_kw['max_length'])
        arms = {}
        for name, autoscaled in (('static', False), ('autoscaled', True)):
            arms[name] = autoscale_arm(
                model, trace, autoscaled=autoscaled,
                replicas=max_replicas, max_replicas=max_replicas,
                slo_ttft_s=slo_ttft_s, eng_kw=eng_kw,
                max_wall_s=6.0 * duration_s)
        st, au = arms['static'], arms['autoscaled']
        arms['trace'] = loadgen.trace_stats(trace)
        arms['replica_seconds_saved_pct'] = round(
            100.0 * (1.0 - au['replica_seconds']
                     / st['replica_seconds']), 2) \
            if st['replica_seconds'] else 0.0
        out[pattern] = arms
    return out


def autoscale_smoke(duration_s=5.0, rate=60.0, seed=77):
    """Tier-1 smoke (`bench.py autoscale --smoke`): a 5-second
    deterministic Poisson trace on CPU through the autoscaled arm
    only. The guard asserts the SLO-attainment JSON is produced
    (offered/attainment/replica-seconds all present), zero requests
    dropped, and the goodput ledger — with the scale_up/scale_down
    categories live — closes within 1%."""
    res = autoscale_ab(duration_s=duration_s, rate=rate, seed=seed,
                       patterns=('poisson',), max_replicas=2)
    arm = res['poisson']['autoscaled']
    return {
        'pattern': 'poisson', 'duration_s': duration_s, 'seed': seed,
        'offered': arm['offered'],
        'completed': arm['completed'],
        'dropped': arm['dropped'],
        'slo_attainment': arm['slo_attainment'],
        'ttft_p99_s': arm['ttft_p99_s'],
        'replica_seconds': arm['replica_seconds'],
        'attainment_per_replica_hour': arm['attainment_per_replica_hour'],
        'ledger_closure_err_pct': arm['ledger']['closure_err_pct'],
        'machinery_pct': arm['ledger']['machinery_pct'],
        'decisions': arm.get('autoscaler', {}).get('decisions', {}),
    }


def _phase_autoscale():
    """Autoscaling phase: the three-pattern static-vs-autoscaled A/B
    (tier-1 guards ride the smoke variant + the diurnal acceptance
    test in tests/test_autoscaler.py)."""
    try:
        return {'autoscale': autoscale_ab()}
    except Exception as e:
        print(f'# autoscale bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        return {'autoscale': {'error': type(e).__name__}}


def _bench_eager_dispatch():
    """Eager dispatch fast path A/B: the same DyGraph MLP train loop with
    the dispatch cache on vs off (per-call re-tracing), reporting ops/sec
    and trace counts for each arm."""
    try:
        cached = eager_mlp_loop(steps=30, use_cache=True)
        uncached = eager_mlp_loop(steps=30, use_cache=False)
        speedup = (cached['steps_per_sec'] / uncached['steps_per_sec']
                   if uncached['steps_per_sec'] else 0.0)
        return {'eager_dispatch': {
            'cached': cached, 'uncached': uncached,
            'speedup': round(speedup, 2),
            'parity': abs(cached['loss'] - uncached['loss']) < 1e-4,
        }}
    except Exception as e:   # never let the micro-bench kill the headline
        print(f'# eager dispatch bench failed: {type(e).__name__}: {e}',
              file=sys.stderr)
        return {'eager_dispatch': {'error': type(e).__name__}}


def _free_device_memory():
    """Drop dead device buffers between ladder rungs: the autograd tape
    creates reference cycles, so the previous rung's params/moments wait
    on the cyclic GC — collect them NOW or the next rung sees an HBM
    that is still full (r4: all 7B rungs OOMed behind the 1.3B run's
    garbage)."""
    import gc
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    # bench phases are independent: anything still resident between
    # phases is garbage — delete it outright (tape cycles can survive
    # two gc passes; r5: the 7B overfit's moments kept 7 GB pinned and
    # OOMed the flash micro-bench's input allocation)
    for a in jax.live_arrays():
        try:
            a.delete()
        except Exception:  # paddle-lint: disable=swallowed-exception -- freeing live arrays between phases; a deleted buffer raising is fine
            pass
    gc.collect()


def _run_ladder(configs):
    """Run the first config of a ladder that succeeds; (name, result)
    or (None, None) if every rung fails."""
    for name, cfg, batch, seq, steps, warmup, dtype, *rest in configs:
        try:
            print(f'# rung {name} b{batch} s{seq} {dtype} '
                  f'offload={rest[0] if rest else None}', file=sys.stderr)
            res = _run_config(name, cfg, batch, seq, steps, warmup,
                              dtype, *rest)
            print(f'# rung {name} OK: {res["step_time_s"]:.3f}s/step',
                  file=sys.stderr)
            return name, res
        except Exception:
            # OOM, compiler blow-up, or a rung-specific failure (e.g. the
            # host-offload path on a backend where it is untested): every
            # rung is independent, so log the FULL traceback and fall
            # through to the next smaller config rather than killing the
            # whole phase
            import traceback
            print(f'# rung {name} failed:\n'
                  f'{traceback.format_exc()}', file=sys.stderr)
            _free_device_memory()
            continue
    return None, None


def _phase_headline():
    import jax
    on_tpu = jax.default_backend() not in ('cpu',)
    metric_name, result = _run_ladder(_configs(on_tpu))
    if result is None:
        raise RuntimeError('all bench configs failed')
    # only a different MODEL counts as a fallback (batch shrink within the
    # 1.3B config still benches the 1.3B headline)
    fell_back = on_tpu and metric_name != 'gpt3_1p3b'
    out = {
        'metric': f'{metric_name}_pretrain_tokens_per_sec_per_chip',
        'value': round(result['tokens_per_sec'], 1),
        'unit': 'tokens/s',
        'vs_baseline': round(result['mfu'] / 0.40, 4),
        'mfu': round(result['mfu'], 4),
        'step_time_s': round(result['step_time_s'], 4),
        'loss': round(result['loss'], 4),
        'device': str(jax.devices()[0].device_kind),
        'fell_back_from_1p3b': fell_back,
        'config': {'params_m': result['params_m'],
                   'batch': result['batch'], 'seq': result['seq'],
                   'dtype': result['dtype']},
    }
    if result.get('peak_hbm_gb'):
        out['peak_hbm_gb'] = result['peak_hbm_gb']
    return out


def _report_7b(res):
    return {
        'tokens_per_sec': round(res['tokens_per_sec'], 1),
        'mfu': round(res['mfu'], 4),
        'step_time_s': round(res['step_time_s'], 4),
        'loss': round(res['loss'], 4),
        'params_m': res['params_m'],
        'batch': res['batch'], 'seq': res['seq'],
        'peak_hbm_gb': res.get('peak_hbm_gb'),
        'layers': res['layers'], 'layers_full_7b': 32,
        'depth_reduced_to_fit_hbm': res['layers'] < 32,
        'optimizer_state_host_offload': res['offload_optimizer'],
    }


def _phase_7b():
    fast, deep = _7b_configs()
    out = {}
    _, res7 = _run_ladder(fast)
    if res7 is None:
        out['llama2_7b_shape'] = {'error': 'all 7B-shape rungs failed'}
    else:
        out['llama2_7b_shape'] = _report_7b(res7)
    _free_device_memory()
    _, res16 = _run_ladder(deep)
    if res16 is None:
        out['llama2_7b_deep_offload'] = {'error': '16L offload rung failed'}
    else:
        out['llama2_7b_deep_offload'] = _report_7b(res16)
    return out


def _phase_probe():
    if os.environ.get('BENCH_TEST_PROBE_HANG'):
        # regression-test hook: a backend attach that never returns
        # (the chip is held by another process)
        time.sleep(3600)
    import jax
    d = jax.devices()[0]
    return {'device': jax.default_backend(),
            'device_kind': getattr(d, 'device_kind', '')}


PHASES = {
    'probe': _phase_probe,
    'headline': _phase_headline,
    '7b': _phase_7b,
    'overfit': lambda: {'llama2_7b_overfit': _run_7b_overfit()},
    'flash': _bench_flash_kernels,
    'fused_ce': _bench_fused_ce,
    'decode': _phase_decode,
    'eager': _bench_eager_dispatch,
    'obs': _phase_obs,
    'resilience': _phase_resilience,
    'serving': _phase_serving,
    'adapters': _phase_adapters,
    'router': _phase_router,
    'coldstart': _phase_coldstart,
    'goodput': _phase_goodput,
    'autoscale': _phase_autoscale,
    'fleet_obs': _phase_fleet_obs,
    'fleet_proc': _phase_fleet_proc,
}


def _run_phase_subprocess(phase, timeout_s, env_extra=None):
    """Each phase gets a FRESH process: a failed/OOMed rung cannot
    fragment or leak HBM into the next phase (r5: after a too-deep 7B
    attempt OOMed, even previously-fitting rungs OOMed in-process)."""
    import os
    import subprocess
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    try:
        proc = subprocess.run(
            [sys.executable, __file__, '--phase', phase],
            capture_output=True, text=True, timeout=timeout_s, env=env)
        sys.stderr.write(proc.stderr)
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() \
            else ''
        if proc.returncode != 0 or not line:
            return {f'{phase}_error': f'exit {proc.returncode}'}
        return json.loads(line)
    except subprocess.TimeoutExpired as e:
        # keep the child's partial stderr: the per-rung tracebacks are
        # exactly what diagnoses a hang
        sys.stderr.write((e.stderr.decode() if isinstance(e.stderr, bytes)
                          else e.stderr) or '')
        return {f'{phase}_error': 'timeout'}
    except Exception as e:
        return {f'{phase}_error': type(e).__name__}


def _cpu_phase_plan():
    """(phase, subprocess timeout) pairs for the functional CPU tier
    (`JAX_PLATFORMS=cpu python bench.py`: counts and parity, no device
    metric)."""
    return [('headline', 1500), ('eager', 600), ('obs', 600),
            ('resilience', 600), ('serving', 1200), ('adapters', 900),
            ('router', 900), ('coldstart', 900), ('goodput', 600),
            ('autoscale', 600), ('fleet_obs', 600)]


def main():
    if len(sys.argv) >= 2 and sys.argv[1] == 'autoscale':
        # `bench.py autoscale [--smoke]`: the tier-1 CI entry point —
        # --smoke is the 5-second deterministic Poisson trace whose
        # SLO-attainment JSON + ledger closure the guard asserts
        if '--smoke' in sys.argv[2:]:
            print(json.dumps({'autoscale_smoke': autoscale_smoke()}))
        else:
            print(json.dumps(_phase_autoscale()))
        return 0
    if len(sys.argv) >= 2 and sys.argv[1] == 'adapters':
        # `bench.py adapters [--smoke]`: --smoke is the deterministic
        # mixed-adapter loadgen trace the tier-1 guard asserts on
        if '--smoke' in sys.argv[2:]:
            print(json.dumps({'adapters_smoke': adapters_smoke()}))
        else:
            print(json.dumps(_phase_adapters()))
        return 0
    if len(sys.argv) >= 2 and sys.argv[1] == 'reqledger_overhead_ab':
        # `bench.py reqledger_overhead_ab`: the request-ledger on/off
        # A/B on a routed serving trace (tier-1 guards <3%)
        print(json.dumps(
            {'reqledger_overhead': reqledger_overhead_ab()}))
        return 0
    if len(sys.argv) >= 3 and sys.argv[1] == '--coldstart-child':
        print(json.dumps(coldstart_child(json.loads(sys.argv[2]))))
        return 0
    if len(sys.argv) >= 3 and sys.argv[1] == '--phase':
        if sys.argv[2] != 'probe':
            # every process that starts on the chip shares one compile
            # cache, placed by JAX_COMPILATION_CACHE_DIR or in-checkout
            from paddle_tpu import programs
            programs.ensure_compile_cache()
        print(json.dumps(PHASES[sys.argv[2]]()))
        return 0
    # The orchestrating parent must NOT import jax: a chip belongs to
    # one process at a time, and a parent holding it blocks its own
    # phase subprocesses from attaching (r5: the 7b phase hung for 15
    # min behind the parent's device handle).
    probe = _run_phase_subprocess(
        'probe', int(os.environ.get('BENCH_PROBE_TIMEOUT', '300')))
    if 'device' not in probe:
        # Backend attach failed or hung. A benchmark that finds no
        # device has measured nothing: say so and fail — CPU phases
        # under a green exit code would be read as a device record.
        print(f'# device probe failed ({probe}): no benchmark was run',
              file=sys.stderr)
        return 1
    if str(probe.get('device', '')).lower() == 'cpu':
        out = {}
        for i, (phase, t) in enumerate(_cpu_phase_plan()):
            res = _run_phase_subprocess(phase, t)
            if phase == 'headline' and 'metric' not in res:
                raise RuntimeError(f'headline phase failed: {res}')
            out.update(res)
        print(json.dumps(out))  # CPU smoke: headline + eager/obs benches
        return 0
    # Measure the pallas CE kernel FIRST, then let the model phases use
    # whichever CE implementation actually won on this chip — the kernel
    # choice is data, not faith, and the decision lands in the JSON.
    ce = _run_phase_subprocess('fused_ce', 600)
    ce_wins = ce.get('fused_ce_speedup_pct', 0) > 0
    model_env = None if ce_wins else {'PADDLE_TPU_DISABLE_PALLAS_CE': '1'}
    out = _run_phase_subprocess('headline', 1500, model_env)
    if 'metric' not in out:
        raise RuntimeError(f'headline phase failed: {out}')
    out.update(ce)
    out['pallas_ce_used_in_models'] = ce_wins
    out.update(_run_phase_subprocess('7b', 1500, model_env))
    out.update(_run_phase_subprocess('overfit', 1200, model_env))
    out.update(_run_phase_subprocess('flash', 600))
    out.update(_run_phase_subprocess('decode', 900, model_env))
    out.update(_run_phase_subprocess('eager', 600))
    out.update(_run_phase_subprocess('obs', 600))
    out.update(_run_phase_subprocess('resilience', 600))
    out.update(_run_phase_subprocess('serving', 900))
    out.update(_run_phase_subprocess('router', 900))
    out.update(_run_phase_subprocess('coldstart', 900))
    out.update(_run_phase_subprocess('autoscale', 600))
    out.update(_run_phase_subprocess('fleet_obs', 600))
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
