"""Rehearsal 3 of the on-chip-measurement guide: compile a cell's
programs at their real size for a v5e that is described, not attached,
and print XLA's memory analysis. Costs no chip time; gives no time and no
numerics. One such process at a time (libtpu's lock).

    JAX_PLATFORMS=cpu python3 benchmarks/aot.py --workload train-1chip [--layers N]

Train kinds compile the step; serve kinds the decode block and the
largest prefill bucket. The program's state is built on the host CPU at
the real widths (a serve cell's pool too), so this wants tens of GB of
host memory at full depth.
"""
from __future__ import annotations

import argparse
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(_HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(_HERE))


def describe_topology(name='v5e:2x2'):
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    os.environ.setdefault('TPU_ACCELERATOR_TYPE', 'v5litepod-4')
    os.environ.setdefault('TPU_WORKER_HOSTNAMES', 'localhost')
    os.environ.setdefault('TPU_SKIP_MDS_QUERY', '1')
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform='tpu', topology_name=name)


def force_kernels_on():
    """The Pallas gates read `jax.default_backend()`, which is the CPU
    here: steer them in this script, not through an option of the
    program."""
    from paddle_tpu.ops import pallas
    pallas._pallas_enabled = lambda: True
    pallas.pallas_ce_enabled.cache_clear()


def abstract(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding),
        tree)


def report(name, compiled):
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    text = compiled.as_text()
    print(f'{name}: peak {getattr(ma, "peak_memory_in_bytes", 0) / gib:.2f} '
          f'GiB, arguments {ma.argument_size_in_bytes / gib:.2f}, outputs '
          f'{ma.output_size_in_bytes / gib:.2f}, temporaries '
          f'{ma.temp_size_in_bytes / gib:.2f}, aliased '
          f'{ma.alias_size_in_bytes / gib:.2f}; tpu_custom_call sites '
          f'{text.count("tpu_custom_call")}', flush=True)
    return ma


def compile_train(cell, one_chip):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import TrainStep, functional_state
    from benchmarks.kinds import train as _train
    from benchmarks.models import adapter
    cfg, tr = cell['config'], cell['traffic']
    model = adapter(cfg['model_class']).build(cfg)
    opt = _train.make_optimizer(_train.hyper(tr), model)
    step = TrainStep(model, _train.lm_loss(cfg['vocab_size']), opt)
    params, frozen, buffers = functional_state(model)   # lazy: shapes only
    dt = jnp.dtype(cfg['param_dtype'])
    params = {k: jax.ShapeDtypeStruct(v.shape, dt, sharding=one_chip)
              for k, v in params.items()}
    state = jax.eval_shape(opt.init_state, params)
    ids = jax.ShapeDtypeStruct((tr['batch'], tr['seq']), jnp.int32,
                               sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    lowered = step._jitted.lower(params, abstract(state, one_chip),
                                 abstract(buffers, one_chip),
                                 abstract(frozen, one_chip), key, lr,
                                 (ids, ids))
    return report('train_step', lowered.compile())


def compile_serve(cell, one_chip):
    """The decode block and the largest prefill bucket of a serve cell,
    from the engine the benchmark itself builds (on the host CPU)."""
    import jax.numpy as jnp
    from benchmarks.kinds import _serve

    class _Run:
        config, traffic, seed = cell['config'], cell['traffic'], 0
    eng = _serve.Server(_Run).router.replicas[0].engine
    state = (eng._params, eng._frozen, eng._buffers)
    decode = eng._decode_jit.lower(*abstract(
        state + (eng.pool.cache, eng._tok, eng._pos, eng._steps, eng._active,
                 eng._temp, eng._topk, eng._topp, eng._greedy, eng._keys),
        one_chip)).compile()
    report('serving.decode_block', decode)
    bucket = max(eng.pool.buckets)
    ids = jnp.zeros((1, bucket), jnp.int32)
    prefill = eng._prefill_jit.lower(
        *abstract(state + (ids,), one_chip)).compile()
    report(f'serving.prefill_{bucket}', prefill)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--layers', type=int, default=None,
                    help='cut the depth (a quick look; not the cell)')
    ap.add_argument('--set', action='append', default=[],
                    metavar='KEY=JSON', help='override a traffic key')
    args = ap.parse_args(argv)
    os.environ['JAX_PLATFORMS'] = 'cpu'
    import jax
    from jax.sharding import SingleDeviceSharding
    from benchmarks import spec
    cell = spec.Spec().cell(args.workload)
    if args.layers:
        cell['config']['num_hidden_layers'] = args.layers
    import json
    for item in args.set:
        key, _, value = item.partition('=')
        cell['traffic'][key] = json.loads(value)
    jax.config.update('jax_enable_compilation_cache', False)
    topo = describe_topology()
    one_chip = SingleDeviceSharding(topo.devices[0])
    force_kernels_on()
    kind = cell['traffic']['kind']
    if kind == 'train':
        compile_train(cell, one_chip)
    elif kind in ('serve_open', 'serve_backlog'):
        compile_serve(cell, one_chip)
    else:
        raise SystemExit(f'no AOT rehearsal for kind {kind!r} yet')
    return 0


if __name__ == '__main__':
    sys.exit(main())
