"""kind `train`: `jit.TrainStep` on one chip — the loop and its check.

Rule A.3: a trainer's loop. Steps are dispatched back to back and the
loss is read every `log_every` steps; the window is cut into these
intervals, each closed by the loss read. Batches come from a ring of
seeded batches placed on the device in set-up.

`correct` (contract, "How correct is decided"): set-up builds ONE
compiled step with its state, drives it through its first three steps by
the window's own call and feed, and hands the same object to the window.
After the window the program's state is freed and the plain float32
reference follows the same three steps from the same seed: each step's
loss, the first gradient's norm by the worst leaf (read back from the
optimizer's first moment after one step) and the norm of the parameters'
change after the three, by the worst leaf. A norm moves only in second
order under unbiased rounding, so beside it stands the first gradient's
sketch (its projection on a fixed +-1 pattern, `reference.common.signs`),
by the worst leaf: the number a lower precision moves in first order.
"""
from __future__ import annotations

import gc
import importlib
import statistics

import numpy as np

from benchmarks import window
from benchmarks.models import adapter, fill
from benchmarks.reference import common as C
from benchmarks import free_arrays, log

CHECK_STEPS = 3


def lm_loss(vocab):
    import paddle_tpu.nn.functional as F

    def loss_fn(logits, labels):
        return F.cross_entropy(logits[:, :-1].reshape([-1, vocab]),
                               labels[:, 1:].reshape([-1]))
    return loss_fn


def make_ring(seed, n, batch, seq, vocab):
    """n seeded [batch, seq] id batches, made on the device in one call;
    rows all differ."""
    import jax
    import jax.numpy as jnp

    def gen(key):
        return [jax.random.randint(jax.random.fold_in(key, i), (batch, seq),
                                   3, vocab, jnp.int32) for i in range(n)]
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             7 + (int(seed) >> 31))
    return jax.jit(gen)(key)


def hyper(traffic):
    o = traffic['optimizer']
    return {'learning_rate': o['learning_rate'], 'beta1': o['beta1'],
            'beta2': o['beta2'], 'epsilon': o['epsilon'],
            'weight_decay': o['weight_decay'],
            'moment_dtype': o['moment_dtype']}


def make_optimizer(hp, model):
    import paddle_tpu as paddle
    return paddle.optimizer.AdamW(
        learning_rate=hp['learning_rate'], beta1=hp['beta1'],
        beta2=hp['beta2'], epsilon=hp['epsilon'],
        weight_decay=hp['weight_decay'], parameters=model.parameters(),
        moment_dtype=hp['moment_dtype'])


def state_of(step):
    """{'params', 'moment1'} of a `TrainStep`, by the program's
    parameter names."""
    from paddle_tpu.jit import functional_state
    slots = step._opt_state['slots']
    return {'params': functional_state(step.layer)[0],
            'moment1': {k: v['moment1'] for k, v in slots.items()}}


def leaf_sq_and_sketch(tree):
    """Per leaf, the sum of squares and the sketch, in one jitted call."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: (
        {k: jnp.sum(jnp.square(v.astype(jnp.float32))) for k, v in t.items()},
        {k: C.sketch(v) for k, v in t.items()}))(tree)


def delta_sq(params, weights, name_map):
    """Per program leaf, the sum of squares of (parameter - seeded
    weight); the stacked seeded leaves are sliced inside the one jit, so
    no second copy of the model is made."""
    import jax
    import jax.numpy as jnp

    def f(p, w):
        out = {}
        for name, (canon, layer) in name_map.items():
            w0 = w[canon] if layer is None else w[canon][layer]
            out[name] = jnp.sum(jnp.square(
                p[name].astype(jnp.float32) - w0.astype(jnp.float32)))
        return out
    return jax.jit(f)(params, weights)


def _by_program_leaf(ref_tree, name_map):
    """A reference's per-leaf-and-layer numbers under the program's
    parameter names."""
    out = {}
    for name, (canon, layer) in name_map.items():
        v = np.asarray(ref_tree[canon], np.float64)
        out[name] = float(v if layer is None else v[layer])
    return out


def worst_leaf_gap(prog_sq, ref_sq, name_map, prog_sketch=None,
                   ref_sketch=None):
    """max over leaves of |norm_prog - norm_ref| / max(norm_ref, median
    leaf norm_ref): the gap between the norms, not the norm of the
    difference, against the leaf's own norm or the median leaf's,
    whichever is larger (some gradients are all but zero). With the
    sketches given, the gap between the leaf's two sketches takes the
    place of the gap between its two norms, over the same measure."""
    ref = {k: v ** 0.5 for k, v in _by_program_leaf(ref_sq, name_map).items()}
    med = statistics.median(ref.values())
    if prog_sketch is None:
        diff = {k: abs(float(prog_sq[k]) ** 0.5 - r) for k, r in ref.items()}
    else:
        rs = _by_program_leaf(ref_sketch, name_map)
        diff = {k: abs(float(prog_sketch[k]) - rs[k]) for k in ref}
    worst, at = 0.0, None
    for name, r in ref.items():
        gap = diff[name] / max(r, med, 1e-30)
        if gap >= worst:
            worst, at = gap, name
    return worst, at


def build_step(run, ad, weights):
    """-> (step, model, name_map): the program's model filled with the
    seeded weights, its AdamW and the compiled `TrainStep`."""
    from paddle_tpu.jit import TrainStep
    cfg = run.config
    name_map = ad.name_map(cfg)
    model = fill(ad.build(cfg), weights, name_map)
    model.train()
    opt = make_optimizer(hyper(run.traffic), model)
    step = TrainStep(model, lm_loss(cfg['vocab_size']), opt)
    return step, model, name_map


def run(run):
    import jax
    from paddle_tpu import observability as obs

    cfg, tr = run.config, run.traffic
    hp = hyper(tr)
    ad = adapter(cfg['model_class'])
    refmod = importlib.import_module(
        f'benchmarks.reference.{ad.reference}')
    shapes = refmod.param_shapes(cfg)
    batch, seq = int(tr['batch']), int(tr['seq'])
    log_every, ring_n = int(tr['log_every']), int(tr['ring'])
    tokens_per_interval = batch * seq * log_every
    reg = obs.get_registry()

    weights = C.make_weights(shapes, run.seed, cfg['param_dtype'])
    step, model, name_map = build_step(run, ad, weights)
    del weights
    ring = make_ring(run.seed, ring_n, batch, seq, cfg['vocab_size'])
    jax.block_until_ready(ring)

    # -- the first three steps, through the window's own call and feed --
    prog_loss, g1_sq, g1_sk = [], None, None
    for i in range(CHECK_STEPS):
        loss = step(ring[i % ring_n], ring[i % ring_n])
        prog_loss.append(float(loss.numpy()))
        if i == 0:      # moment1 = (1 - beta1) x the first gradient
            sq, sk = leaf_sq_and_sketch(state_of(step)['moment1'])
            g1_sq = {k: float(v) / (1 - hp['beta1']) ** 2
                     for k, v in sq.items()}
            g1_sk = {k: float(v) / (1 - hp['beta1']) for k, v in sk.items()}
    w0 = C.make_weights(shapes, run.seed, cfg['param_dtype'])
    d_sq = {k: float(v) for k, v in delta_sq(
        state_of(step)['params'], w0, name_map).items()}
    del w0
    n_steps = CHECK_STEPS

    # one more interval outside the clock: the loop's own shape, warm
    for _ in range(log_every):
        loss = step(ring[n_steps % ring_n], ring[n_steps % ring_n])
        n_steps += 1
    float(loss.numpy())
    compiles0 = reg.value('paddle_jit_compiles_total')
    gc.collect()

    # -- the window ------------------------------------------------------
    t_open = run.clock()
    run.window_opens(t_open)
    stamps, losses = [], []

    def interval():
        nonlocal n_steps
        with jax.profiler.TraceAnnotation('bench.train_interval'):
            for _ in range(log_every):
                with jax.profiler.TraceAnnotation('bench.train_step'):
                    loss = step(ring[n_steps % ring_n],
                                ring[n_steps % ring_n])
                n_steps += 1
            with jax.profiler.TraceAnnotation('bench.loss_read'):
                return float(loss.numpy())

    while not stamps or not window.closes(t_open, stamps[-1], run.seconds):
        losses.append(interval())
        stamps.append(run.clock())
    compiles = reg.value('paddle_jit_compiles_total') - compiles0
    run.read_memory_peak()
    rates = window.train_rates(t_open, stamps, tokens_per_interval,
                               run.chips)
    log('interval_s', json_list(rates['intervals_s']))
    log('interval_loss', json_list(losses))
    run.raw.update({
        'tokens_per_s_chip': rates['tokens_per_s_chip'],
        'steady_tokens_per_s_chip': rates['steady_tokens_per_s_chip'],
        'step_ms': 1e3 * rates['median_interval_s'] / log_every,
        'compiles_in_window': compiles, 'batch': batch, 'seq': seq,
        'steps_in_window': len(stamps) * log_every,
    })
    run.attempted = len(stamps) * log_every
    if run.trace:
        # the traced intervals follow the window, so that the profiler's
        # start and stop stall nothing that the host clock measured
        run.start_trace()
        for _ in range(int(tr['trace_intervals'])):
            interval()
        run.stop_trace()
        run.raw['traced_steps'] = int(tr['trace_intervals']) * log_every
        run.reduce_trace()

    # -- free the program, then the reference follows the same steps ----
    batches = [jax.device_put(np.asarray(ring[i % ring_n]))
               for i in range(CHECK_STEPS)]          # own copies
    freed = free_arrays([p._data for p in model.parameters()],
                        step._opt_state, ring)
    del step, model, ring
    gc.collect()
    jax.clear_caches()
    log(f'program state freed: {freed / 2**30:.2f} GiB')
    t_ref = run.clock()
    ref = _reference(refmod, cfg, shapes, run.seed, batches, hp, 'f32')
    log(f'reference: {run.clock() - t_ref:.1f} s')
    lim = run.limits
    for i in range(CHECK_STEPS):
        run.check(f'loss_step{i + 1}_gap', abs(prog_loss[i] - ref[0][i]),
                  lim['loss_gap'])
    gap, at = worst_leaf_gap(g1_sq, ref[1], name_map)
    run.check(f'first_grad_norm_worst_leaf_gap[{at}]', gap,
              lim['grad_norm_gap'])
    gap, at = worst_leaf_gap(g1_sq, ref[1], name_map, g1_sk, ref[2])
    run.check(f'first_grad_sketch_worst_leaf_gap[{at}]', gap,
              lim['grad_sketch_gap'])
    gap, at = worst_leaf_gap(d_sq, ref[3], name_map)
    run.check(f'param_change_norm_worst_leaf_gap[{at}]', gap,
              lim['param_change_gap'])
    run.check('compiles_in_window', compiles, 0)
    # lr 2e-4 with no warm-up and no clipping spikes now and then (a
    # loss of 11.9 after 9.9 was seen), so "falls" is the window's best
    # loss against the seeded start, with the limit's margin
    run.check('window_best_loss_minus_first_step_loss',
              min(losses) - prog_loss[0], lim['loss_fall'])
    if run.control:
        ctl = _reference(refmod, cfg, shapes, run.seed, batches, hp,
                         run.control)
        log('control', run.control, 'loss gaps',
            [abs(a - b) for a, b in zip(ctl[0], ref[0])])
        log('control', run.control, 'first_grad_norm_worst_leaf_gap',
            _canon_gap(ctl[1], ref[1]))
        log('control', run.control, 'first_grad_sketch_worst_leaf_gap',
            _canon_gap(ctl[1], ref[1], ctl[2], ref[2]))
        log('control', run.control, 'param_change_norm_worst_leaf_gap',
            _canon_gap(ctl[3], ref[3]))


def _canon_gap(a_sq, b_sq, a_sketch=None, b_sketch=None):
    """worst_leaf_gap between two references (canonical leaves, stacked
    ones per layer)."""
    def flat(tree):
        return tree and {f'{k}.{i}': x for k, v in tree.items() for i, x in
                         enumerate(np.atleast_1d(np.asarray(v, np.float64)))}
    b = flat(b_sq)
    return worst_leaf_gap(flat(a_sq), b, {k: (k, None) for k in b},
                          flat(a_sketch), flat(b_sketch))


def _reference(refmod, cfg, shapes, seed, batches, hp, mode):
    """(losses, first-gradient sq and sketch per leaf/layer, param-change
    sq per leaf/layer) of the plain reference at one precision mode."""
    import jax
    w = C.make_weights(shapes, seed, cfg['param_dtype'])
    losses, g1_sq, g1_sk, p = C.train_reference(
        lambda params, ids: refmod.loss(cfg, params, ids, mode),
        w, batches, hp, len(batches))
    w0 = C.make_weights(shapes, seed, cfg['param_dtype'])
    d_sq = jax.jit(C.sq_delta_per_layer)(p, w0)
    out = (losses, jax.device_get(g1_sq), jax.device_get(g1_sk),
           jax.device_get(d_sq))
    del p, w0
    gc.collect()
    return out


def json_list(values):
    return '[' + ', '.join(f'{v:.6g}' for v in values) + ']'
