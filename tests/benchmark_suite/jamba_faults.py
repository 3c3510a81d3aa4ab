"""The three faulty programs that serve-ssm-reason's limit has to refuse
(`benchmarks/limits/serve-ssm-reason.json`), and the witness for the
size of a sound run's gap. Each is a patch of the PROGRAM, applied
before the benchmark's own run; nothing of the benchmark is patched by
a fault. `test_jamba_cell.py` applies the faults at the toy size on the
CPU; on the chip, at the published widths, from the root of a checkout:

    python3 tests/benchmark_suite/jamba_faults.py <name> \
        --workload serve-ssm-reason --seed 4604000017 --seconds 20 --trace 0

`<name>`: `pads_folded_in` (the state seated as the padded bucket
leaves it), `inner_norms_dropped` (the norms of `dt`, `B` and `C`),
`skip_dropped` (`D u`), or `near_ties` (a SOUND program; after the
check, says where the served tokens differ from the reference's and
what the reference itself gives at the program's precision).
"""
import os
import runpy
import sys

_FAULTS = {
    'pads_folded_in': '''
import paddle_tpu.nlp.jamba as _j
_j.folded_tokens = lambda s: s
''',
    # the inner norms are `dt_rank`, `d_state` and `d_state` wide, and no
    # other norm of the model is
    'inner_norms_dropped': '''
import paddle_tpu.nn.norm as _n
_real = _n.RMSNorm.forward
_n.RMSNorm.forward = lambda self, x: x if self.hidden_size in {widths} \\
    else _real(self, x)
''',
    'skip_dropped': '''
import paddle_tpu.nlp.jamba as _j
_step, _scan = _j.mamba_step, _j.mamba_scan
_j.mamba_step = lambda u, dt, b, c, a, d, h: _step(u, dt, b, c, a, 0 * d, h)
_j.mamba_scan = lambda u, dt, b, c, a, d, *r: _scan(u, dt, b, c, a, 0 * d, *r)
'''}


def faults(dt_rank, d_state):
    """name -> the patch's source, for a model of these inner widths."""
    return {name: src.format(widths=(dt_rank, d_state))
            for name, src in _FAULTS.items()}


def near_ties():
    """Wrap the harness's reference pass: the verdict as it is, then, on
    the same sample, (1) the gap at every served token that is not the
    reference's argmax, beside the reference's own margin between its
    two largest logits there; (2) the plain reference computed again at
    the PROGRAM's stated precision (`high`: three bf16 passes a product)
    and held to the float32 reference the same way. A sound program's
    gaps are the size of (2)'s: both are the products' rounding, met at
    a near-tie of the 65,536 logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks import log
    from benchmarks import traffic as T
    from benchmarks.kinds import _serve
    from benchmarks.reference import common as RC
    real = _serve.check_served

    def check_served(run, server, served, candidates, max_out):
        real(run, server, served, candidates, max_out)
        cfg, refmod = run.config, server.refmod
        pool = sorted(i for i in candidates if i in served)
        longest = max(pool, key=lambda i: (served[i][1].prompt_len
                                           + served[i][1].output_len, i))
        rest = [i for i in pool if i != longest]
        order = T.rng(run.seed, 30).permutation(len(rest))
        n_check = int(run.traffic['check_requests'])
        sample = [longest] + [rest[k] for k in order[:n_check - 1]]

        def logits_fn():        # a new jit: traced at RC.HIGHEST as it stands
            def f(params, ids, start):
                h = refmod.hidden_states(cfg, params, ids[None], 'f32')[0]
                rows = jax.lax.dynamic_slice_in_dim(
                    jnp.pad(h, ((0, max_out), (0, 0))), start, max_out, 0)
                return refmod.logits_of(cfg, params, rows, 'f32')
            return jax.jit(f)

        weights = RC.make_weights(server.shapes, run.seed, cfg['param_dtype'])
        f32_fn = logits_fn()
        for idx in sample:
            toks, _ = served[idx]
            prompt, n = server.tokens[idx], len(toks)
            ids = np.zeros(server.max_length, np.int32)
            seq = (prompt + toks)[:len(prompt) + n - 1]
            ids[:len(seq)] = seq
            tk = np.zeros(max_out, np.int32)
            tk[:n] = toks
            args = (weights, jnp.asarray(ids), len(prompt) - 1)
            exact = f32_fn(*args)
            top2 = jax.lax.top_k(exact, 2)[0]
            margin = np.asarray(top2[:, 0] - top2[:, 1])[:n]
            gap = np.asarray(top2[:, 0] - jnp.take_along_axis(
                exact, jnp.asarray(tk)[:, None], -1)[:, 0])[:n]
            RC.HIGHEST = jax.lax.Precision.HIGH
            try:
                rounded = logits_fn()(*args)
            finally:
                RC.HIGHEST = jax.lax.Precision.HIGHEST
            first = jnp.argmax(rounded, -1)
            gap_r = np.asarray(top2[:, 0] - jnp.take_along_axis(
                exact, first[:, None], -1)[:, 0])[:n]
            moved = float(jnp.max(jnp.abs(rounded - exact)[:n]))
            logit_sd = float(jnp.std(exact[:n]))
            off, off_r = np.flatnonzero(gap), np.flatnonzero(gap_r)
            widest = off[np.argsort(gap[off])[::-1][:8]]
            widest_r = off_r[np.argsort(gap_r[off_r])[::-1][:8]]
            log(
                f'near_ties request {idx}: prompt {len(prompt)}, {n} '
                f'tokens; served off the argmax at {len(off)}: gaps '
                f'{gap[widest]} at output tokens {widest}, where the two '
                f'largest reference logits lie {margin[widest]} apart '
                f'(every gap is that margin: '
                f'{bool((gap[off] == margin[off]).all())}); '
                f'the reference at `high` off at {len(off_r)}: gaps '
                f'{gap_r[widest_r]} at {widest_r}, both off at '
                f'{len(np.intersect1d(off, off_r))}; its logits move by '
                f'at most {moved!r} beside a deviation of {logit_sd!r} '
                f'over a row; positions with a margin under 0.15: '
                f'{int((margin < 0.15).sum())}, under 0.05: '
                f'{int((margin < 0.05).sum())}')
            del exact, rounded

    _serve.check_served = check_served


if __name__ == '__main__':
    sys.path.insert(0, os.getcwd())
    name, sys.argv = sys.argv[1], ['benchmarks/run.py'] + sys.argv[2:]
    if name == 'near_ties':
        near_ties()
    else:
        exec(faults(160, 16)[name])
    runpy.run_path('benchmarks/run.py', run_name='__main__')
