"""Hybrid-parallel correctness on the 8-device CPU mesh (SURVEY.md §4):
TP == dense, ZeRO step == unsharded step, ring attention == full
attention, pipeline == sequential."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import env, fleet
from paddle_tpu.distributed.pipeline import gpipe, stack_stage_params
from paddle_tpu.distributed.ring_attention import (ring_attention,
                                                   ulysses_attention)
from paddle_tpu.ops.pallas import _attention_xla
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def _mlp_weights(rng, din, dh, dout):
    w1 = rng.standard_normal((din, dh)).astype(np.float32) * 0.1
    b1 = np.zeros(dh, np.float32)
    w2 = rng.standard_normal((dh, dout)).astype(np.float32) * 0.1
    b2 = np.zeros(dout, np.float32)
    return w1, b1, w2, b2


class TPMlp(nn.Layer):
    def __init__(self, din, dh, dout):
        super().__init__()
        self.fc1 = dist.ColumnParallelLinear(din, dh, gather_output=False)
        self.fc2 = dist.RowParallelLinear(dh, dout, input_is_parallel=True)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


def test_tp_linear_equals_dense():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 2, 'mp_degree': 4,
                               'pp_degree': 1, 'sep_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)
    rng = np.random.default_rng(0)
    w1, b1, w2, b2 = _mlp_weights(rng, 16, 32, 16)
    m = TPMlp(16, 32, 16)
    m.set_state_dict({'fc1.weight': w1, 'fc1.bias': b1,
                      'fc2.weight': w2, 'fc2.bias': b2})
    fleet.distributed_model(m)
    # mp-sharded placement really happened
    assert 'mp' in str(dict(m.named_parameters())['fc1.weight']
                       .value.sharding.spec)
    x = rng.standard_normal((8, 16)).astype(np.float32)
    out = m(paddle.to_tensor(x)).numpy()
    want = np.maximum(x @ w1 + b1, 0) @ w2 + b2
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_vocab_parallel_embedding_and_ce():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 1, 'mp_degree': 8,
                               'pp_degree': 1, 'sep_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)
    emb = dist.VocabParallelEmbedding(64, 16)
    fleet.distributed_model(emb)
    ids = np.array([[1, 5, 63], [0, 2, 7]])
    out = emb(paddle.to_tensor(ids))
    w = emb.weight.numpy()
    np.testing.assert_allclose(out.numpy(), w[ids], rtol=1e-6)
    ce = dist.ParallelCrossEntropy()
    logits = paddle.to_tensor(
        np.random.randn(4, 64).astype(np.float32))
    labels = paddle.to_tensor(np.array([1, 2, 3, 4]))
    loss = ce(logits, labels)
    assert loss.shape == [4]


class _Mlp(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 32)
        self.fc2 = nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(F.relu(self.fc1(x)))


@pytest.mark.slow


def test_zero_sharded_step_equals_unsharded():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    y = rng.integers(0, 4, 16)

    def run(sharded):
        paddle.seed(7)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {'dp_degree': 8, 'mp_degree': 1,
                                   'pp_degree': 1, 'sep_degree': 1}
        strategy.sharding = sharded
        fleet.init(is_collective=True, strategy=strategy)
        m = _Mlp()
        fleet.distributed_model(m)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        step = fleet.DistTrainStep(
            m, lambda out, lab: F.cross_entropy(out, lab), opt,
            strategy=strategy)
        losses = [float(step(paddle.to_tensor(x),
                             paddle.to_tensor(y)).numpy())
                  for _ in range(3)]
        return losses

    base = run(False)
    zero = run(True)
    np.testing.assert_allclose(base, zero, rtol=1e-4)
    assert base[2] < base[0]  # actually learning


def test_a_dist_train_steps_construction_is_one_span_booked_as_set_up():
    """`train.step_init`, as around `jit.TrainStep.__init__` (ISSUE 48):
    the placement of the parameters is part of what it times."""
    from paddle_tpu import observability as obs
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 8, 'mp_degree': 1,
                               'pp_degree': 1, 'sep_degree': 1}
    fleet.init(is_collective=True, strategy=strategy)
    m = _Mlp()
    fleet.distributed_model(m)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=m.parameters())
    reg = obs.get_registry()
    before = reg.value('paddle_setup_seconds_total', phase='construct')
    seq = max((e['seq'] for e in obs.get_event_log().events()), default=0)
    fleet.DistTrainStep(m, lambda out, lab: F.cross_entropy(out, lab), opt,
                        strategy=strategy)
    (ev,) = [e for e in obs.get_event_log().events()
             if e['seq'] > seq and e['name'] == 'train.step_init']
    assert reg.value('paddle_setup_seconds_total', phase='construct') \
        - before == pytest.approx(ev['dur'])


@pytest.mark.parametrize('causal', [True, False])
def test_ring_attention_matches_full(causal):
    env.init_parallel_env((1, 1, 8, 1), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(2)
    B, S, H, D = 2, 64, 4, 8
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    full = _attention_xla(jnp.array(q), jnp.array(k), jnp.array(v),
                          causal=causal)
    ring = jax.jit(lambda a, b, c: ring_attention(
        a, b, c, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_gqa():
    env.init_parallel_env((1, 1, 8, 1), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(3)
    B, S, H, HKV, D = 1, 32, 8, 2, 8
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, HKV, D)).astype(np.float32)
    v = rng.standard_normal((B, S, HKV, D)).astype(np.float32)
    full = _attention_xla(jnp.array(q), jnp.array(k), jnp.array(v),
                          causal=True)
    ring = jax.jit(lambda a, b, c: ring_attention(a, b, c,
                                                  causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


def test_ulysses_attention_matches_full():
    env.init_parallel_env((1, 1, 8, 1), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(4)
    B, S, H, D = 2, 64, 8, 8
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    full = _attention_xla(jnp.array(q), jnp.array(k), jnp.array(v),
                          causal=True)
    uly = jax.jit(lambda a, b, c: ulysses_attention(
        a, b, c, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


def test_gpipe_matches_sequential():
    env.init_parallel_env((4, 1, 1, 2), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(5)
    n_pp, d = 4, 16

    def stage_fn(p, x):
        return jnp.tanh(x @ p['w'] + p['b'])

    stages = [{'w': rng.standard_normal((d, d)).astype(np.float32) * 0.3,
               'b': rng.standard_normal((d,)).astype(np.float32) * 0.1}
              for _ in range(n_pp)]
    stacked = stack_stage_params(stages)
    n_micro, mb = 6, 4
    x = rng.standard_normal((n_micro, mb, d)).astype(np.float32)

    out = jax.jit(lambda sp, xx: gpipe(stage_fn, sp, xx))(stacked, x)
    want = x
    for p in stages:
        want = np.tanh(want @ p['w'] + p['b'])
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_gpipe_differentiable():
    env.init_parallel_env((4, 1, 1, 2), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(6)
    n_pp, d = 4, 8

    def stage_fn(p, x):
        return jnp.tanh(x @ p['w'])

    stages = [{'w': rng.standard_normal((d, d)).astype(np.float32) * 0.3}
              for _ in range(n_pp)]
    stacked = stack_stage_params(stages)
    x = rng.standard_normal((4, 2, d)).astype(np.float32)

    def loss(sp):
        return jnp.sum(gpipe(stage_fn, sp, jnp.array(x)) ** 2)

    g = jax.jit(jax.grad(loss))(stacked)
    # reference grad from the sequential program
    def loss_seq(sp):
        y = jnp.array(x)
        for i in range(n_pp):
            y = jnp.tanh(y @ sp['w'][i])
        return jnp.sum(y ** 2)
    g_seq = jax.grad(loss_seq)(stacked)
    np.testing.assert_allclose(np.asarray(g['w']),
                               np.asarray(g_seq['w']), rtol=1e-3, atol=1e-4)


@pytest.mark.slow

def test_moe_identical_experts_equals_dense():
    env.init_parallel_env((1, 8, 1, 1), ('pp', 'dp', 'sp', 'mp'))
    paddle.seed(0)
    m = dist.MoELayer(16, 32, num_experts=4, top_k=2, capacity_factor=8.0)
    # make all experts identical -> MoE == single FFN, routing-independent
    w_in = m.w_in.numpy().copy()
    w_in[:] = w_in[0]
    w_out = m.w_out.numpy().copy()
    w_out[:] = w_out[0]
    m.set_state_dict({'gate': m.gate.numpy(), 'w_in': w_in, 'w_out': w_out})
    x = np.random.default_rng(7).standard_normal((2, 6, 16)) \
        .astype(np.float32)
    out = m(paddle.to_tensor(x)).numpy()
    want = np.asarray(jax.nn.gelu(x @ w_in[0])) @ w_out[0]
    np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-4)
    assert m.aux_loss is not None


@pytest.mark.slow

def test_moe_grad_flows():
    env.init_parallel_env((1, 8, 1, 1), ('pp', 'dp', 'sp', 'mp'))
    m = dist.MoELayer(8, 16, num_experts=4, top_k=1)
    x = paddle.rand([2, 4, 8])
    out = m(x)
    loss = out.sum() + m.aux_loss
    loss.backward()
    assert m.w_in.grad is not None
    assert m.gate.grad is not None


# ---------------------------------------------------------------------------
# round 3: pipeline parallel end-to-end, strategy knobs, ZeRO-2/3, full TP
# ---------------------------------------------------------------------------

def _lm_batch(vocab=128, b=8, s=16, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, vocab, (b, s)), rng.randint(0, vocab, (b, s))


def _make_strategy(pp=1, dp=1, mp=1, **kw):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'pp_degree': pp, 'dp_degree': dp,
                               'sep_degree': 1, 'mp_degree': mp}
    for k, v in kw.items():
        setattr(strategy, k, v)
    return strategy


def _run_lm(strategy, model_cls, cfg_cls, steps=3, seed=7):
    ids, lab = _lm_batch()
    paddle.seed(seed)
    fleet.init(is_collective=True, strategy=strategy)
    cfg = cfg_cls.tiny()
    m = model_cls(cfg)
    fleet.distributed_model(m)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    step = fleet.DistTrainStep(m, loss_fn, opt, strategy)
    losses = [float(step(ids, lab).numpy()) for _ in range(steps)]
    return losses, step


@pytest.mark.slow

def test_pp_llama_matches_single_device():
    """VERDICT r2 #1: Llama-tiny at pp2 x dp4, per-step losses == dense."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    base, _ = _run_lm(_make_strategy(), LlamaForCausalLM, LlamaConfig)
    s = _make_strategy(pp=2, dp=4, pipeline=True)
    s.pipeline_configs = {'accumulate_steps': 2, 'schedule_mode': '1F1B'}
    pp, _ = _run_lm(s, LlamaForCausalLM, LlamaConfig)
    np.testing.assert_allclose(base, pp, rtol=1e-3)
    assert base[-1] < base[0]


@pytest.mark.slow

def test_pp_gpt_matches_single_device():
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    base, _ = _run_lm(_make_strategy(), GPTForCausalLM, GPTConfig)
    s = _make_strategy(pp=2, dp=2, mp=2, pipeline=True)
    s.pipeline_configs = {'accumulate_steps': 4, 'schedule_mode': 'F-then-B'}
    pp, _ = _run_lm(s, GPTForCausalLM, GPTConfig)
    np.testing.assert_allclose(base, pp, rtol=1e-3)


@pytest.mark.slow

def test_tp_generation_matches_dense():
    """Serving parity: KV-cache greedy decode under mp4 tensor
    parallelism produces token-identical output to the dense model —
    GSPMD shards the jitted lax.while_loop decode (upstream analogue:
    PaddleNLP TP inference)."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    fleet.init(is_collective=True, strategy=_make_strategy())
    paddle.seed(5)
    dense = LlamaForCausalLM(LlamaConfig.tiny())
    sd = {k: v.numpy() for k, v in dense.state_dict().items()}
    ids = np.random.RandomState(0).randint(0, 128, (2, 8))
    od = dense.generate(paddle.to_tensor(ids), max_new_tokens=6,
                        decode_strategy='greedy_search')
    od = (od[0] if isinstance(od, tuple) else od).numpy()

    fleet.init(is_collective=True, strategy=_make_strategy(dp=2, mp=4))
    paddle.seed(5)
    tp = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=True))
    tp.set_state_dict(sd)
    fleet.distributed_model(tp)
    ot = tp.generate(paddle.to_tensor(ids), max_new_tokens=6,
                     decode_strategy='greedy_search')
    ot = (ot[0] if isinstance(ot, tuple) else ot).numpy()
    np.testing.assert_array_equal(od, ot)


@pytest.mark.slow

def test_pp_ernie_with_recompute_matches_single_device():
    """BASELINE config #5: ERNIE with pipeline-parallel + recompute
    (upstream fleet/meta_parallel/pipeline_parallel.py + recompute/).
    Losses at pp2 x dp4 with full-block remat == dense single-device."""
    from paddle_tpu.nlp import ErnieConfig, ErnieForMaskedLM
    base, _ = _run_lm(_make_strategy(), ErnieForMaskedLM, ErnieConfig)
    s = _make_strategy(pp=2, dp=4, pipeline=True, recompute=True)
    s.pipeline_configs = {'accumulate_steps': 2, 'schedule_mode': '1F1B'}
    s.recompute_configs = {'granularity': 'full'}
    pp, step = _run_lm(s, ErnieForMaskedLM, ErnieConfig)
    assert step.layer.config.use_recompute  # knob reached the model config
    np.testing.assert_allclose(base, pp, rtol=1e-3)
    assert base[-1] < base[0]


@pytest.mark.slow

def test_ernie_recompute_single_device_matches_plain():
    """Remat must change memory, never math: ERNIE use_recompute=True
    training losses == the plain path bit-for-tolerance."""
    from paddle_tpu.nlp import ErnieConfig, ErnieForMaskedLM
    base, _ = _run_lm(_make_strategy(), ErnieForMaskedLM, ErnieConfig)
    r = _make_strategy(recompute=True)
    rec, _ = _run_lm(r, ErnieForMaskedLM, ErnieConfig)
    np.testing.assert_allclose(base, rec, rtol=1e-4)


@pytest.mark.slow

def test_strategy_gradient_merge():
    """k_steps=4 microbatch accumulation == the full-batch step."""
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    base, _ = _run_lm(_make_strategy(), GPTForCausalLM, GPTConfig)
    gm = _make_strategy(gradient_merge=True)
    gm.gradient_merge_configs = {'k_steps': 4}
    merged, _ = _run_lm(gm, GPTForCausalLM, GPTConfig)
    np.testing.assert_allclose(base, merged, rtol=1e-4)
    # indivisible batch fails loud, proving the scan path is really taken
    bad = _make_strategy(gradient_merge=True)
    bad.gradient_merge_configs = {'k_steps': 3}
    with pytest.raises(Exception):
        _run_lm(bad, GPTForCausalLM, GPTConfig, steps=1)


@pytest.mark.slow

def test_strategy_amp_has_effect():
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    base, _ = _run_lm(_make_strategy(), GPTForCausalLM, GPTConfig)
    a = _make_strategy(amp=True)
    a.amp_configs = {'level': 'O1', 'dtype': 'bfloat16'}
    amp_l, _ = _run_lm(a, GPTForCausalLM, GPTConfig)
    assert all(np.isfinite(amp_l)) and amp_l[-1] < amp_l[0]
    # bf16 matmuls perturb the trajectory: close to fp32 but not identical
    np.testing.assert_allclose(base, amp_l, rtol=5e-2)
    assert not np.allclose(base, amp_l, rtol=1e-7), 'amp knob had no effect'


@pytest.mark.parametrize('granularity', ['dots', 'dots_no_batch'])
@pytest.mark.slow
def test_strategy_recompute_wires_model_config(granularity):
    """Remat policies trade memory for flops — never math: losses under
    each granularity == the no-remat run ('dots_no_batch' is the r4
    bench headline policy)."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    base, _ = _run_lm(_make_strategy(), LlamaForCausalLM, LlamaConfig)
    r = _make_strategy(recompute=True)
    r.recompute_configs = {'granularity': granularity}
    rec, step = _run_lm(r, LlamaForCausalLM, LlamaConfig)
    assert step.layer.config.use_recompute == granularity
    np.testing.assert_allclose(base, rec, rtol=1e-4)


@pytest.mark.parametrize('stage', [2, 3])
@pytest.mark.slow
def test_zero_stage_2_3_match_unsharded(stage):
    """VERDICT r2 #3: stage2/3 == unsharded trajectories + memory shrinks."""
    from paddle_tpu.nlp import GPTConfig, GPTForCausalLM
    base, _ = _run_lm(_make_strategy(), GPTForCausalLM, GPTConfig)
    z = _make_strategy(dp=8, sharding=True)
    z.sharding_configs = {'stage': stage}
    zl, zstep = _run_lm(z, GPTForCausalLM, GPTConfig)
    np.testing.assert_allclose(base, zl, rtol=1e-4)
    # per-device optimizer-moment bytes shrink ~dp for shardable leaves
    leaves = [v for v in jax.tree_util.tree_leaves(zstep._opt_state)
              if hasattr(v, 'sharding') and v.ndim >= 2]
    assert leaves, 'no shardable moment leaves found'
    shrunk = [v for v in leaves
              if np.prod(v.sharding.shard_shape(v.shape)) < v.size]
    assert shrunk, 'ZeRO placement did not shard any moment leaf'
    if stage >= 3:
        pmap = dict(zstep.layer.named_parameters())
        p_shrunk = [p for p in pmap.values()
                    if np.prod(p.value.sharding.shard_shape(
                        p.value.shape)) < p.value.size]
        assert p_shrunk, 'stage 3 did not shard any parameter'


@pytest.mark.slow

def test_tp_llama_full_model_matches_dense():
    """VERDICT r2 #6: Llama-tiny tensor_parallel=True on mp4 — logits and
    one DistTrainStep loss match the dense model bit-for-tolerance."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    ids, lab = _lm_batch(b=8, s=8)

    fleet.init(is_collective=True, strategy=_make_strategy())
    paddle.seed(11)
    dense = LlamaForCausalLM(LlamaConfig.tiny())
    sd = {k: v.numpy() for k, v in dense.state_dict().items()}
    dense_logits = dense(paddle.to_tensor(ids)).numpy()

    strategy = _make_strategy(dp=2, mp=4)
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(11)
    tp = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=True))
    tp.set_state_dict(sd)
    fleet.distributed_model(tp)
    # TP placement really happened on at least one projection weight
    qw = dict(tp.named_parameters())[
        'llama.layers.0.self_attn.q_proj.weight']
    assert 'mp' in str(qw.value.sharding.spec)
    tp_logits = tp(paddle.to_tensor(ids)).numpy()
    np.testing.assert_allclose(dense_logits, tp_logits, rtol=2e-4, atol=2e-5)

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, 128]),
                               labels.reshape([-1]))

    opt_d = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=dense.parameters())
    fleet.init(is_collective=True, strategy=_make_strategy())
    step_d = fleet.DistTrainStep(dense, loss_fn, opt_d)
    dense_loss = float(step_d(ids, lab).numpy())

    fleet.init(is_collective=True, strategy=strategy)
    opt_t = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=tp.parameters())
    step_t = fleet.DistTrainStep(tp, loss_fn, opt_t, strategy)
    tp_loss = float(step_t(ids, lab).numpy())
    np.testing.assert_allclose(dense_loss, tp_loss, rtol=1e-4)


@pytest.mark.slow

def test_pp_llama_interleaved_vpp_matches_single_device():
    """VERDICT r4 #6: interleaved virtual-stage pipeline through fleet
    (hybrid_configs virtual_pp_degree=2, upstream Megatron-style virtual
    pp): Llama-4L at pp2 x vpp2 x dp4, per-step losses == dense."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    def run(strategy, steps=3, seed=7):
        ids, lab = _lm_batch()
        paddle.seed(seed)
        fleet.init(is_collective=True, strategy=strategy)
        cfg = LlamaConfig.tiny(num_hidden_layers=4)
        m = LlamaForCausalLM(cfg)
        fleet.distributed_model(m)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())

        def loss_fn(logits, labels):
            return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                                   labels.reshape([-1]))

        step = fleet.DistTrainStep(m, loss_fn, opt, strategy)
        return [float(step(ids, lab).numpy()) for _ in range(steps)]

    base = run(_make_strategy())
    s = _make_strategy(pp=2, dp=4, pipeline=True)
    s.hybrid_configs['virtual_pp_degree'] = 2
    s.pipeline_configs = {'accumulate_steps': 2}
    vpp = run(s)
    np.testing.assert_allclose(base, vpp, rtol=1e-3)
    assert base[-1] < base[0]


@pytest.mark.slow
def test_group_sharded_parallel_levels_equal_unsharded():
    """paddle.distributed.sharding.group_sharded_parallel (upstream
    python/paddle/distributed/sharding/group_sharded.py): all three
    levels must train bit-identically to the unsharded baseline."""
    from paddle_tpu.distributed import group_sharded_parallel
    import paddle_tpu.distributed as dist
    rng = np.random.default_rng(3)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    y = rng.integers(0, 4, 16)

    def run(level):
        dist.destroy_process_group()
        fleet._fleet.strategy = None
        paddle.seed(7)
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {'dp_degree': 8, 'mp_degree': 1,
                                   'pp_degree': 1, 'sep_degree': 1}
        fleet.init(is_collective=True, strategy=strategy)
        m = _Mlp()
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        if level:
            m, opt, _ = group_sharded_parallel(m, opt, level)
            strategy = fleet._fleet.strategy
        else:
            fleet.distributed_model(m)
        step = fleet.DistTrainStep(
            m, lambda out, lab: F.cross_entropy(out, lab), opt,
            strategy=strategy)
        return [float(step(paddle.to_tensor(x),
                           paddle.to_tensor(y)).numpy())
                for _ in range(3)]

    base = run(None)
    assert base[-1] < base[0]
    for level in ('os', 'os_g', 'p_g_os'):
        np.testing.assert_allclose(base, run(level), rtol=1e-4,
                                   err_msg=level)
    with pytest.raises(ValueError, match='level'):
        group_sharded_parallel(_Mlp(), paddle.optimizer.SGD(
            learning_rate=0.1, parameters=_Mlp().parameters()), 'bogus')
    with pytest.raises(NotImplementedError, match='offload'):
        group_sharded_parallel(_Mlp(), paddle.optimizer.SGD(
            learning_rate=0.1, parameters=_Mlp().parameters()), 'os',
            offload=True)


def test_save_group_sharded_model(tmp_path):
    from paddle_tpu.distributed import (group_sharded_parallel,
                                        save_group_sharded_model)
    import paddle_tpu.distributed as dist
    dist.destroy_process_group()
    fleet._fleet.strategy = None
    paddle.seed(1)
    m = _Mlp()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=m.parameters())
    m, opt, _ = group_sharded_parallel(m, opt, 'os_g')
    save_group_sharded_model(m, str(tmp_path / 'out'), opt)
    import os
    assert os.path.exists(str(tmp_path / 'out' / 'model.pdparams'))
    sd = paddle.load(str(tmp_path / 'out' / 'model.pdparams'))
    m2 = _Mlp()
    m2.set_state_dict(sd)
    x = paddle.to_tensor(np.ones((2, 16), np.float32))
    np.testing.assert_allclose(m(x).numpy(), m2(x).numpy(), rtol=1e-5)


@pytest.mark.parametrize('causal', [True, False])
def test_ring_attention_gradients_match_full(causal):
    """The backward through the ppermute ring (what training actually
    uses) must match full-attention gradients, incl. the blockwise-LSE
    rescaling terms."""
    env.init_parallel_env((1, 1, 8, 1), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(5)
    B, S, H, D = 1, 64, 4, 8
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    w = rng.standard_normal((B, S, H, D)).astype(np.float32)  # cotangent

    def loss_ring(a, b, c):
        return jnp.sum(ring_attention(a, b, c, causal=causal)
                       * jnp.asarray(w))

    def loss_full(a, b, c):
        return jnp.sum(_attention_xla(a, b, c, causal=causal)
                       * jnp.asarray(w))

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f'd{name}')


def test_ulysses_gradients_match_full():
    env.init_parallel_env((1, 1, 8, 1), ('pp', 'dp', 'sp', 'mp'))
    rng = np.random.default_rng(6)
    B, S, H, D = 1, 64, 8, 8
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    k = rng.standard_normal((B, S, H, D)).astype(np.float32)
    v = rng.standard_normal((B, S, H, D)).astype(np.float32)
    w = rng.standard_normal((B, S, H, D)).astype(np.float32)

    gr = jax.jit(jax.grad(
        lambda a, b, c: jnp.sum(ulysses_attention(a, b, c, causal=True)
                                * jnp.asarray(w)),
        argnums=(0, 1, 2)))(q, k, v)
    gf = jax.grad(
        lambda a, b, c: jnp.sum(_attention_xla(a, b, c, causal=True)
                                * jnp.asarray(w)),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f'd{name}')


class TestT5Distributed:
    """The encoder-decoder family on the mesh: a pure-dp DataParallel T5
    train step must match the single-device step bit-for-bit in loss
    trajectory (grads average over a replicated batch = unreplicated)."""

    def _train(self, wrap_dp, steps=3):
        from paddle_tpu.nlp import T5Config, T5ForConditionalGeneration
        paddle.seed(0)
        cfg = T5Config.tiny()
        model = T5ForConditionalGeneration(cfg)
        if wrap_dp:
            strategy = fleet.DistributedStrategy()
            strategy.hybrid_configs = {'dp_degree': 8, 'mp_degree': 1,
                                       'pp_degree': 1, 'sep_degree': 1}
            fleet.init(is_collective=True, strategy=strategy)
            model = dist.DataParallel(model)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        rng = np.random.RandomState(0)
        ids = rng.randint(2, cfg.vocab_size, (8, 10))
        labels = rng.randint(2, cfg.vocab_size, (8, 6))
        losses = []
        for _ in range(steps):
            loss, _ = model(input_ids=ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses

    @pytest.mark.slow
    def test_dp_t5_matches_single_device(self):
        single = self._train(wrap_dp=False)
        dp = self._train(wrap_dp=True)
        np.testing.assert_allclose(dp, single, rtol=1e-5, atol=1e-6)
        assert dp[-1] < dp[0]


@pytest.mark.slow
def test_tp_t5_matches_dense():
    """Encoder-decoder under mp4 tensor parallelism: logits and greedy
    seq2seq generation match the dense model on copied weights."""
    from paddle_tpu.nlp import T5Config, T5ForConditionalGeneration
    fleet.init(is_collective=True, strategy=_make_strategy())
    paddle.seed(6)
    dense = T5ForConditionalGeneration(T5Config.tiny()).eval()
    sd = {k: v.numpy() for k, v in dense.state_dict().items()}
    rng = np.random.RandomState(0)
    ids = rng.randint(2, 96, (2, 8))
    dec = rng.randint(2, 96, (2, 5))
    ld = dense(input_ids=ids, decoder_input_ids=dec).numpy()
    gd, _ = dense.generate(ids, max_new_tokens=6,
                           decode_strategy='greedy_search', eos_token_id=-1)

    fleet.init(is_collective=True, strategy=_make_strategy(dp=2, mp=4))
    paddle.seed(6)
    tp = T5ForConditionalGeneration(
        T5Config.tiny(tensor_parallel=True)).eval()
    tp.set_state_dict(sd)
    fleet.distributed_model(tp)
    lt = tp(input_ids=ids, decoder_input_ids=dec).numpy()
    np.testing.assert_allclose(ld, lt, rtol=1e-4, atol=1e-5)
    gt, _ = tp.generate(ids, max_new_tokens=6,
                        decode_strategy='greedy_search', eos_token_id=-1)
    np.testing.assert_array_equal(gd.numpy(), gt.numpy())


@pytest.mark.slow
def test_fleet_hybrid_t5_step_trains():
    """T5 through fleet.DistTrainStep (dp2 x mp4 + ZeRO-1): tuple inputs
    carry (encoder ids, decoder ids); the jitted hybrid step must train."""
    from paddle_tpu.nlp import T5Config, T5ForConditionalGeneration
    strategy = _make_strategy(dp=2, mp=4)
    strategy.sharding = True
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(8)
    cfg = T5Config.tiny(tensor_parallel=True)
    model = T5ForConditionalGeneration(cfg)
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(learning_rate=1e-3,
                               parameters=model.parameters()))

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    step = fleet.DistTrainStep(model, loss_fn, opt, strategy)
    rng = np.random.RandomState(8)
    src = rng.randint(2, cfg.vocab_size, (8, 10))
    tgt = rng.randint(2, cfg.vocab_size, (8, 6))
    dec_in = np.concatenate(
        [np.full((8, 1), cfg.decoder_start_token_id), tgt[:, :-1]], axis=1)
    losses = [float(step((src, dec_in), tgt).numpy()) for _ in range(4)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_sp_t5_matches_dense():
    """Sequence-parallel T5: training losses at dp2 x sp2 x mp2 equal the
    dense single-device trajectory (sharding must not change math)."""
    from paddle_tpu.nlp import T5Config, T5ForConditionalGeneration

    paddle.seed(9)
    ref_sd = {k: v.numpy() for k, v in T5ForConditionalGeneration(
        T5Config.tiny()).state_dict().items()}

    def run(sp):
        dist.destroy_process_group()   # isolate from earlier mesh state
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {'dp_degree': 2, 'mp_degree': 2,
                                   'pp_degree': 1, 'sep_degree': 2} if sp \
            else {'dp_degree': 1, 'mp_degree': 1, 'pp_degree': 1,
                  'sep_degree': 1}
        fleet.init(is_collective=True, strategy=strategy)
        cfg = T5Config.tiny(tensor_parallel=sp, sequence_parallel=sp)
        model = T5ForConditionalGeneration(cfg)
        # identical weights both ways: parallel layers consume the init
        # PRNG differently, so trajectories are only comparable from a
        # copied state dict (same pattern as test_tp_t5_matches_dense)
        model.set_state_dict(ref_sd)
        if sp:
            fleet.distributed_model(model)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        rng = np.random.RandomState(9)
        src = rng.randint(2, cfg.vocab_size, (4, 8))
        tgt = rng.randint(2, cfg.vocab_size, (4, 8))
        losses = []
        for _ in range(3):
            loss, _ = model(input_ids=src, labels=tgt)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses

    dense = run(False)
    sp = run(True)
    np.testing.assert_allclose(sp, dense, rtol=1e-4)
