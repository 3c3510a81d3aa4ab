"""Parity tests for the repo's own pallas kernels, run in interpret mode
on the CPU mesh (SURVEY.md §4). The XLA reference attention is the
ground truth for both forward values and dq/dk/dv gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import _attention_xla
from paddle_tpu.ops.pallas_kernels import (flash_attention_bwd,
                                           flash_attention_fwd,
                                           flash_attention_own, rms_norm)


def _qkv(b=1, sq=256, sk=256, h=2, hkv=None, d=64, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, sq, h, d)).astype(np.float32)
    k = rng.standard_normal((b, sk, hkv or h, d)).astype(np.float32)
    v = rng.standard_normal((b, sk, hkv or h, d)).astype(np.float32)
    return jnp.array(q), jnp.array(k), jnp.array(v)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.slow
def test_flash_fwd_matches_xla(causal):
    q, k, v = _qkv()
    ours = flash_attention_fwd(q, k, v, causal=causal, interpret=True)
    ref = _attention_xla(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow


def test_flash_fwd_gqa():
    q, k, v = _qkv(h=4, hkv=2)
    ours = flash_attention_fwd(q, k, v, causal=True, interpret=True)
    ref = _attention_xla(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow


def test_flash_fwd_lse():
    q, k, v = _qkv(sq=128, sk=128)
    _, lse = flash_attention_fwd(q, k, v, causal=False, interpret=True,
                                 return_lse=True)
    logits = jnp.einsum('bqhd,bkhd->bhqk', q, k) / np.sqrt(q.shape[-1])
    want = jax.scipy.special.logsumexp(logits, axis=-1)
    assert lse.shape == want.shape + (128,)  # lane-replicated TPU tiling
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_flash_fwd_rejects_indivisible():
    q, k, v = _qkv(sq=130, sk=256)
    with pytest.raises(ValueError, match='divisible'):
        flash_attention_fwd(q, k, v, interpret=True)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.slow
def test_flash_own_backward_matches_xla(causal):
    """VERDICT r2 #8: the repo owns its flash bwd (dq/dk/dv kernels)."""
    q, k, v = _qkv(sq=128, sk=128)

    def loss_own(q, k, v):
        return jnp.sum(flash_attention_own(q, k, v, causal, 128, 128,
                                           True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, causal=causal) ** 2)

    g_own = jax.grad(loss_own, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_own, g_ref, 'q k v'.split()):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), rtol=5e-3, atol=1e-4,
            err_msg=f'd{name} mismatch (causal={causal})')


@pytest.mark.slow

def test_flash_own_backward_gqa():
    q, k, v = _qkv(sq=128, sk=128, h=4, hkv=2)

    def loss_own(q, k, v):
        return jnp.sum(flash_attention_own(q, k, v, True, 128, 128,
                                           True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, causal=True) ** 2)

    g_own = jax.grad(loss_own, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_own, g_ref, 'q k v'.split()):
        assert ours.shape == ref.shape
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), rtol=5e-3, atol=1e-4,
            err_msg=f'd{name} mismatch (gqa)')


@pytest.mark.slow

def test_flash_own_multiblock_causal():
    """Exercise the block-skip paths: 2x2 q/k block grid, causal."""
    q, k, v = _qkv(sq=256, sk=256, d=64, seed=3)

    def loss_own(q, k, v):
        return jnp.sum(flash_attention_own(q, k, v, True, 128, 128,
                                           True) * 0.01)

    def loss_ref(q, k, v):
        return jnp.sum(_attention_xla(q, k, v, causal=True) * 0.01)

    g_own = jax.grad(loss_own, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_own, g_ref, 'q k v'.split()):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), rtol=5e-3, atol=1e-5,
            err_msg=f'd{name} mismatch (multiblock)')


@pytest.mark.slow

def test_rms_norm_kernel_and_grad():
    rng = np.random.default_rng(5)
    x = jnp.array(rng.standard_normal((8, 64)).astype(np.float32))
    w = jnp.array(rng.standard_normal((64,)).astype(np.float32))

    def ref(x, w):
        ms = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w

    ours = rms_norm(x, w, 1e-6, True)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref(x, w)),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lambda a, b: jnp.sum(rms_norm(a, b, 1e-6, True) ** 2),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda a, b: jnp.sum(ref(a, b) ** 2),
                  argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g1[1]), np.asarray(g2[1]),
                               rtol=1e-4, atol=1e-5)


class TestFusedCE:
    """Fused softmax-CE pallas kernel (VERDICT r4 #5): values and grads
    vs the XLA reference, including ragged (non-block-divisible) shapes
    and bf16 logits."""

    @pytest.mark.parametrize('n,v,dtype', [
        (256, 2048, 'float32'),
        pytest.param(200, 5000, 'bfloat16',
                     marks=pytest.mark.slow),  # pad both dims
        pytest.param(64, 50304, 'bfloat16',
                     marks=pytest.mark.slow),  # GPT vocab
    ])
    def test_fwd_bwd_match_xla(self, n, v, dtype):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops import pallas_kernels as pk
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.standard_normal((n, v)), jnp.dtype(dtype))
        lab = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)

        def ref(a):
            af = a.astype(jnp.float32)
            return (jax.nn.logsumexp(af, -1)
                    - jnp.take_along_axis(af, lab[:, None], 1)[:, 0])

        got = pk.softmax_cross_entropy(x, lab, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref(x)),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda a: jnp.sum(
            pk.softmax_cross_entropy(a, lab, True)))(x)
        gr = jax.grad(lambda a: jnp.sum(ref(a)))(x)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(gr, np.float32),
            rtol=1e-4, atol=2e-5)


class TestPagedAttention:
    """Fused paged-attention decode kernel (ISSUE 16): the pallas kernel
    in interpret mode vs the pure-lax gather reference, and both vs
    dense attention on the equivalent contiguous KV."""

    @staticmethod
    def _case(h=4, hkv=4, n=3, p=4, ps=8, d=16, num_pages=20, seed=0,
              quant=False):
        from paddle_tpu.ops import pallas_kernels as pk
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((n, h, d)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((num_pages, ps, hkv, d)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((num_pages, ps, hkv, d)),
                         jnp.float32)
        table = jnp.asarray(rng.integers(0, num_pages, (n, p)), jnp.int32)
        lengths = jnp.asarray(rng.integers(1, p * ps + 1, (n,)), jnp.int32)
        scales = (None, None)
        if quant:
            from paddle_tpu.quantization import (kv_page_scales,
                                                 kv_quantize_page)
            ks = jax.vmap(kv_page_scales)(kp)
            vs = jax.vmap(kv_page_scales)(vp)
            kp = jax.vmap(kv_quantize_page)(kp, ks)
            vp = jax.vmap(kv_quantize_page)(vp, vs)
            scales = (ks, vs)
        return pk, q, kp, vp, table, lengths, scales

    @pytest.mark.parametrize('hkv', [4, 2])
    def test_pallas_matches_reference(self, hkv):
        pk, q, kp, vp, table, lengths, _ = self._case(hkv=hkv, seed=hkv)
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths)
        ours = pk.paged_attention(q, kp, vp, table, lengths,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_pallas_matches_reference_int8(self):
        pk, q, kp, vp, table, lengths, (ks, vs) = self._case(
            hkv=2, seed=7, quant=True)
        assert kp.dtype == jnp.int8
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths,
                                           k_scales=ks, v_scales=vs)
        ours = pk.paged_attention(q, kp, vp, table, lengths, k_scales=ks,
                                  v_scales=vs, interpret=True)
        np.testing.assert_allclose(np.asarray(ours), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_reference_matches_dense_attention(self):
        """Gathered-page attention == dense attention over the same KV
        laid out contiguously, for every slot's actual length."""
        pk, q, kp, vp, table, lengths, _ = self._case(hkv=2, seed=11)
        n, h, d = q.shape
        ps = kp.shape[1]
        got = pk.paged_attention_reference(q, kp, vp, table, lengths)
        k = kp[table].reshape(n, -1, kp.shape[2], d)
        v = vp[table].reshape(n, -1, vp.shape[2], d)
        g = h // kp.shape[2]
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        for i in range(n):
            ln = int(lengths[i])
            s = jnp.einsum('hd,khd->hk', q[i], k[i, :ln]) / np.sqrt(d)
            w = jax.nn.softmax(s, axis=-1)
            want = jnp.einsum('hk,khd->hd', w, v[i, :ln])
            np.testing.assert_allclose(np.asarray(got[i]),
                                       np.asarray(want),
                                       rtol=2e-5, atol=2e-5)

    def test_dispatcher_falls_back_off_tpu(self):
        """On CPU without interpret, dispatch must be the lax reference
        bit-for-bit (tier-1's guarantee that no pallas path runs)."""
        pk, q, kp, vp, table, lengths, _ = self._case(seed=3)
        if jax.default_backend() == 'tpu':
            pytest.skip('fallback path is for non-TPU backends')
        got = pk.paged_attention(q, kp, vp, table, lengths)
        ref = pk.paged_attention_reference(q, kp, vp, table, lengths)
        assert np.array_equal(np.asarray(got), np.asarray(ref))

    def test_null_page_redirect_is_inert(self):
        """Entries past a slot's length may point anywhere (the engine
        parks them on page 0) — they must not change the output."""
        pk, q, kp, vp, table, lengths, _ = self._case(seed=5)
        lengths = jnp.full_like(lengths, int(kp.shape[1]))  # one page used
        base = pk.paged_attention(q, kp, vp, table, lengths,
                                  interpret=True)
        redirected = table.at[:, 1:].set(0)
        got = pk.paged_attention(q, kp, vp, redirected, lengths,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# segmented LoRA adapter matmul (ISSUE 19)
# ---------------------------------------------------------------------------

class TestAdapterMatmul:
    """The fused gather+matmul over a packed adapter bank: pallas
    (interpret mode) against the pure-lax reference, plus the slot-0
    exactly-zero contract the engine's base-request parity rides on."""

    def _case(self, b=4, t=1, h=16, r=4, o=24, c=3, seed=0):
        from paddle_tpu.ops import pallas_kernels as pk
        rng = np.random.default_rng(seed)
        x = jnp.array(rng.standard_normal((b, t, h)).astype(np.float32))
        a = rng.standard_normal((c + 1, h, r)).astype(np.float32) * 0.1
        bb = rng.standard_normal((c + 1, r, o)).astype(np.float32) * 0.1
        a[0], bb[0] = 0.0, 0.0              # slot 0: the zero base row
        scale = rng.uniform(0.5, 2.0, (c + 1,)).astype(np.float32)
        scale[0] = 0.0
        rows = jnp.array(rng.integers(0, c + 1, (b,)), jnp.int32)
        return pk, x, jnp.array(a), jnp.array(bb), rows, jnp.array(scale)

    @pytest.mark.parametrize('t', [1, 8])
    def test_pallas_matches_reference(self, t):
        pk, x, a, b, rows, scale = self._case(t=t, seed=7)
        got = pk.adapter_matmul(x, a, b, rows, scale, interpret=True)
        ref = pk.adapter_matmul_reference(x, a, b, rows, scale)
        assert got.shape == ref.shape == (x.shape[0], t, b.shape[2])
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_slot_zero_rows_are_exactly_zero(self):
        """Rows gathered from bank slot 0 must produce a bit-exact zero
        delta (zero factors x zero scale), in BOTH implementations —
        this is what makes adapter-less requests on a bank-attached
        engine bit-identical to a bank-less one."""
        pk, x, a, b, _, scale = self._case(b=3, seed=9)
        rows = jnp.zeros((3,), jnp.int32)
        for fn in (pk.adapter_matmul_reference,
                   lambda *args: pk.adapter_matmul(*args, interpret=True)):
            out = np.asarray(fn(x, a, b, rows, scale))
            assert np.array_equal(out, np.zeros_like(out))

    def test_mixed_rows_match_per_row_einsum(self):
        """Each row's delta equals the plain x_i @ A[slot] @ B[slot] *
        scale[slot] — the gather never leaks a neighbour's factors."""
        pk, x, a, b, rows, scale = self._case(b=5, c=4, seed=11)
        got = np.asarray(pk.adapter_matmul_reference(x, a, b, rows, scale))
        for i in range(x.shape[0]):
            s = int(rows[i])
            want = (np.asarray(x[i], np.float32)
                    @ np.asarray(a[s]) @ np.asarray(b[s])
                    * float(scale[s]))
            np.testing.assert_allclose(got[i], want, rtol=2e-5, atol=2e-5)

    def test_dispatcher_falls_back_off_tpu(self):
        pk, x, a, b, rows, scale = self._case(seed=3)
        if jax.default_backend() == 'tpu':
            pytest.skip('fallback path is for non-TPU backends')
        got = pk.adapter_matmul(x, a, b, rows, scale)
        ref = pk.adapter_matmul_reference(x, a, b, rows, scale)
        assert np.array_equal(np.asarray(got), np.asarray(ref))

    def test_preserves_input_dtype(self):
        pk, x, a, b, rows, scale = self._case(seed=5)
        xh = x.astype(jnp.bfloat16)
        out = pk.adapter_matmul(xh, a, b, rows, scale, interpret=True)
        assert out.dtype == jnp.bfloat16


class TestNoSilentFallback:
    """On a TPU a kernel error is an error: with the gate forced on and
    a kernel made to raise, the caller sees the exception — the XLA path
    is chosen by the explicit shape conditions only."""

    @pytest.fixture
    def gate_on(self, monkeypatch):
        from paddle_tpu.ops import pallas
        monkeypatch.setattr(pallas, '_pallas_enabled', lambda: True)
        monkeypatch.setattr(pallas, 'pallas_ce_enabled', lambda: True)
        return pallas

    def test_flash_kernel_error_reaches_the_caller(self, gate_on,
                                                   monkeypatch):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.ops import pallas_kernels as pk

        def boom(*a, **k):
            raise RuntimeError('mosaic says no')
        monkeypatch.setattr(pk, 'flash_attention', boom)
        q, k, v = (paddle.to_tensor(np.asarray(t)) for t in _qkv(sq=128,
                                                                 sk=128))
        with pytest.raises(RuntimeError, match='mosaic says no'):
            F.scaled_dot_product_attention(q, k, v, is_causal=True)
        # a shape the kernel does not cover is XLA's by the conditions
        q2, k2, v2 = (paddle.to_tensor(np.asarray(t))
                      for t in _qkv(sq=100, sk=100))
        out = F.scaled_dot_product_attention(q2, k2, v2, is_causal=True)
        assert np.isfinite(out.numpy()).all()

    def test_fused_ce_kernel_error_reaches_the_caller(self, gate_on,
                                                      monkeypatch):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        from paddle_tpu.ops import pallas_kernels as pk

        def boom(*a, **k):
            raise RuntimeError('mosaic says no')
        monkeypatch.setattr(pk, 'softmax_cross_entropy', boom)
        rng = np.random.default_rng(0)
        logits = paddle.to_tensor(
            rng.standard_normal((4, 8192)).astype(np.float32))
        labels = paddle.to_tensor(rng.integers(0, 8192, (4,)))
        with pytest.raises(RuntimeError, match='mosaic says no'):
            F.cross_entropy(logits, labels)
        small = paddle.to_tensor(
            rng.standard_normal((4, 512)).astype(np.float32))
        assert np.isfinite(float(F.cross_entropy(
            small, paddle.to_tensor(rng.integers(0, 512, (4,)))).numpy()))

    def test_expert_kernel_error_reaches_the_caller(self, gate_on,
                                                    monkeypatch):
        import paddle_tpu as paddle
        from paddle_tpu.nlp.afmoe import (BLOCK_ROWS, AfmoeConfig,
                                          AfmoeForCausalLM)
        from paddle_tpu.ops import pallas_kernels as pk

        def boom(*a, **k):
            raise RuntimeError('mosaic says no')
        monkeypatch.setattr(pk, 'moe_decode_experts', boom)
        paddle.seed(0)
        model = AfmoeForCausalLM(AfmoeConfig.tiny()).eval()
        for layer in model.model.layers[1:]:
            for p in (layer.mlp.gate_w, layer.mlp.up_w, layer.mlp.down_w):
                p._data = p._data.astype(jnp.bfloat16)
        with pytest.raises(RuntimeError, match='mosaic says no'):
            model(paddle.to_tensor(np.ones((1, 8), 'int32')))
        # a call wider than one block is the loop's by the conditions
        out = model(paddle.to_tensor(np.ones((1, BLOCK_ROWS + 1), 'int32')))
        assert np.isfinite(out.numpy()).all()


# ---------------------------------------------------------------------------
# routed experts of a decode batch (`moe_decode_experts`, PR 31)
# ---------------------------------------------------------------------------
def _expert_call(t=4, k=2, e=8, h=16, f=256, seed=0, **changed):
    rs = np.random.RandomState(seed)
    call = dict(
        x=jnp.asarray(rs.randn(t, h), jnp.float32),
        sel=jnp.asarray(np.stack([rs.permutation(e)[:k] for _ in range(t)]),
                        jnp.int32),
        w=jnp.asarray(rs.rand(t, k), jnp.float32),
        gate_w=jnp.asarray(0.3 * rs.randn(e, h, f), jnp.bfloat16),
        up_w=jnp.asarray(0.3 * rs.randn(e, h, f), jnp.bfloat16),
        down_w=jnp.asarray(0.3 * rs.randn(e, f, h), jnp.bfloat16))
    call.update(changed)
    return call


@pytest.mark.parametrize('f_tile', [None, 128, 256])
def test_moe_decode_experts_sums_over_tiles_of_f(f_tile):
    """However an expert is cut into grid steps, the sum is the same:
    one whole tile, two of 128, and the tile the kernel picks."""
    from paddle_tpu.ops.pallas_kernels import moe_decode_experts
    call = _expert_call()
    got = np.asarray(moe_decode_experts(**call, f_tile=f_tile,
                                        interpret=True), np.float64)
    x, w = (np.asarray(call[n], np.float64) for n in ('x', 'w'))
    gw, uw, dw = (np.asarray(call[n].astype(jnp.float32), np.float64)
                  for n in ('gate_w', 'up_w', 'down_w'))
    want = np.zeros_like(x)
    for t, row in enumerate(np.asarray(call['sel'])):
        for j, ex in enumerate(row):
            g, u = x[t] @ gw[ex], x[t] @ uw[ex]
            want[t] += w[t, j] * ((g / (1 + np.exp(-g)) * u) @ dw[ex])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()


@pytest.mark.parametrize('changed,match', [
    (dict(x=jnp.zeros((4, 16), jnp.bfloat16)), 'float32 activations'),
    (dict(gate_w=jnp.zeros((8, 16, 256), jnp.float32)), 'bf16 expert'),
    (dict(down_w=jnp.zeros((8, 16, 256), jnp.bfloat16)), 'against leaves'),
    (dict(w=jnp.zeros((4, 3), jnp.float32)), 'against leaves'),
    (dict(f_tile=96), 'multiples of 128'),
    (dict(f_tile=192), 'multiples of 128')],
    ids=['bf16_rows', 'f32_leaves', 'down_not_transposed', 'weights_shape',
         'tile_off_the_lanes', 'tile_not_a_divisor'])
def test_moe_decode_experts_refuses(changed, match):
    from paddle_tpu.ops.pallas_kernels import moe_decode_experts
    with pytest.raises(ValueError, match=match):
        moe_decode_experts(**_expert_call(**changed), interpret=True)


@pytest.mark.parametrize('tokens,k,routed,held,h,f', [
    (32, 8, 64, 4, 4096, 2048),     # serve-swa-reason: a share of the experts
    (8, 8, 32, 4, 2048, 1024),      # serve-moe-docs' expert
    (32, 4, 16, 4, 2048, 1536),     # serve-hybrid-reason's
    (16, 6, 128, 16, 2048, 768)],   # serve-mla-long's: top-6 of 128, f_tile 384
    ids=['4096x2048', '2048x1024', '2048x1536', '2048x768'])
def test_moe_decode_experts_and_the_loop_agree_on_picks_not_held(
        tokens, k, routed, held, h, f):
    """A layer that holds experts 0..held-1 of a router over `routed`
    hands both schedules its picks in its own numbering, `held` (one
    past the last) for a pick it does not hold, with weight zero: such a
    pick is no `hit` of the kernel and no row of the loop's sorted walk.
    At the real tiles of the four cells that run this kernel
    (interpreted), against the loop over the same leaves in float32 at
    `HIGHEST` and against a float64 sum over the held picks."""
    from paddle_tpu.nlp.afmoe import grouped_experts
    from paddle_tpu.ops.pallas_kernels import moe_decode_experts
    rs = np.random.RandomState(tokens + f)
    sel = np.stack([rs.permutation(routed)[:k] for _ in range(tokens)])
    mine = sel < held
    assert mine.any() and not mine.all() and not mine.all(axis=1).any()
    w = np.where(mine, rs.rand(tokens, k), 0.0).astype('float32')
    local = np.where(mine, sel, held).astype('int32')
    x = rs.randn(tokens, h).astype('float32')
    gw, uw = (jnp.asarray(0.02 * rs.randn(held, h, f), jnp.bfloat16)
              for _ in range(2))
    dw = jnp.asarray(0.02 * rs.randn(held, f, h), jnp.bfloat16)
    args = (jnp.asarray(x), jnp.asarray(local), jnp.asarray(w))
    got = np.asarray(moe_decode_experts(*args, gw, uw, dw, interpret=True))
    g32, u32, d32 = (a.astype(jnp.float32) for a in (gw, uw, dw))
    with jax.default_matmul_precision('highest'):
        loop = np.asarray(grouped_experts(*args, g32, u32, d32))
    want = np.zeros((tokens, h))
    for t, e_, j in zip(*np.nonzero(mine), local[mine]):
        g = x[t].astype('float64') @ np.asarray(g32[j], 'float64')
        u = x[t].astype('float64') @ np.asarray(u32[j], 'float64')
        want[t] += w[t, e_] * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(d32[j], 'float64'))
    scale = np.abs(want).max()
    assert scale > 0
    assert np.abs(got - want).max() < 2e-5 * scale
    assert np.abs(loop - want).max() < 2e-5 * scale
    # a row none of whose picks is held gets nothing from either
    none = ~mine.any(axis=1)
    assert (got[none] == 0).all() and (loop[none] == 0).all()


# ---------------------------------------------------------------------------
# decode attention over latent rows (`mla_decode_attention`, PR 38)
# ---------------------------------------------------------------------------
MLA_ROWS, MLA_TILE = 64, 8


def _mla_call(lengths, preset='tiny', dtype='float32', hidden=(), seed=0):
    """One query a slot at a tiny preset's H x C x rope x nope x v against
    `MLA_ROWS` rows; slot b sees rows `< lengths[b]`, less `hidden`
    (pairs (slot, row))."""
    from paddle_tpu.nlp.deepseek_v3 import DeepseekV3Config
    cfg = getattr(DeepseekV3Config, preset)()
    h, lat, rope = (cfg.num_attention_heads, cfg.kv_lora_rank,
                    cfg.qk_rope_head_dim)
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    rs = np.random.RandomState(seed)
    b = len(lengths)
    seen = np.arange(MLA_ROWS)[None, :] < np.asarray(lengths)[:, None]
    for slot, row in hidden:
        seen[slot, row] = False
    return dict(
        q_nope=jnp.asarray(rs.randn(b, 1, h, nope), jnp.float32),
        q_rope=jnp.asarray(rs.randn(b, 1, h, rope), jnp.float32),
        c=jnp.asarray(rs.randn(b, MLA_ROWS, lat), dtype),
        r=jnp.asarray(rs.randn(b, MLA_ROWS, rope), dtype),
        w_kvb=jnp.asarray(0.3 * rs.randn(lat, h, nope + vd), jnp.float32),
        mask=jnp.asarray(seen)[:, None, None, :],
        scale=1.0 / np.sqrt(nope + rope))


@pytest.fixture
def mla_interpreted(monkeypatch):
    """`_latent_attention`'s dispatch answers with the kernel,
    interpreted, at `MLA_TILE` rows a step — at toy widths too, which
    the real conditions leave to XLA — and keeps what it was handed."""
    import functools
    from paddle_tpu.ops import pallas, pallas_kernels
    calls = []

    def kernel(*args, **kw):
        calls.append(args)
        return pallas_kernels.mla_decode_attention(
            *args, tile=MLA_TILE, interpret=True, **kw)
    monkeypatch.setattr(pallas, 'latent_decode_kernel',
                        lambda q, rows, mask: kernel)
    return calls


def _mla_both(call, calls):
    from paddle_tpu.nlp import deepseek_v3
    from paddle_tpu.ops import pallas
    before = len(calls)
    got = np.asarray(deepseek_v3._latent_attention(**call), np.float64)
    assert len(calls) == before + 1
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas, 'latent_decode_kernel', lambda *a: None)
        want = np.asarray(deepseek_v3._latent_attention(**call), np.float64)
    assert len(calls) == before + 1     # the einsums, not the kernel again
    return got, want


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('preset', ['tiny', 'tiny_wide_v'])
@pytest.mark.parametrize('lengths,hidden', [
    ((1,), ()),
    ((MLA_TILE - 1, MLA_TILE, MLA_TILE + 1), ()),
    ((MLA_ROWS, 5 * MLA_TILE), ()),
    ((43, 29, 64), ((0, 3), (0, 41), (1, 0), (2, 63), (2, 17)))],
    ids=['one_row', 'around_a_tile_edge', 'every_row', 'hidden_under_bound'])
def test_mla_decode_attention_agrees_with_the_einsums(
        lengths, hidden, preset, dtype, mla_interpreted):
    """The kernel, interpreted, against `_latent_attention`'s XLA path
    on the same call: a slot's row tiles up to its bound, three bf16
    passes where the einsums are exact float32 on the CPU, an online
    softmax where they take one over the whole row — float32 rounding
    of a reordered sum, and the 2^-16 the dropped lo.lo pass is worth."""
    call = _mla_call(lengths, preset, dtype, hidden)
    got, want = _mla_both(call, mla_interpreted)
    assert got.shape == want.shape
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def test_mla_decode_attention_walks_one_tile_of_a_slot_not_decoding(
        mla_interpreted):
    """An inactive slot beside long ones (`routing_scope(active)`, as
    the decode scan gives it): the kernel is handed NO seen row of it —
    its bound is nothing, one tile walked, whatever its stale position
    shows — and the decoding slots read what they read without it."""
    from paddle_tpu.nlp import deepseek_v3, generation
    call = _mla_call((57, MLA_ROWS, 40))
    active = jnp.asarray([True, False, True])
    with generation.routing_scope(active):
        got = np.asarray(deepseek_v3._latent_attention(**call))
    seen = np.asarray(mla_interpreted[0][4])
    assert seen[0].sum() == 57 and not seen[1].any() and seen[2].sum() == 40
    assert generation.active_rows() is None         # the scope is closed
    alone, want = _mla_both(call, mla_interpreted)
    assert np.isfinite(got).all()
    assert np.array_equal(got[[0, 2]], alone[[0, 2]].astype(got.dtype))
    assert np.abs(got[[0, 2]] - want[[0, 2]]).max() \
        < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize('rows', [MLA_ROWS, MLA_ROWS // 2])
def test_mla_decode_attention_reads_the_leaf_whole_under_a_shorter_mask(
        rows):
    """The half-length decode program's mask has half the columns: the
    kernel takes the leaves whole and reads no row past the mask."""
    from paddle_tpu.ops.pallas_kernels import mla_decode_attention
    call = _mla_call((rows, 9))
    seen = call['mask'][:, 0, 0, :rows]
    q_lat = jnp.einsum('bqhn,chn->bqhc', call['q_nope'],
                       call['w_kvb'][..., :call['q_nope'].shape[-1]])[:, 0]
    args = (q_lat, call['q_rope'][:, 0])
    poisoned = call['c'].at[:, rows:].set(jnp.nan)
    got = mla_decode_attention(*args, poisoned, call['r'], seen,
                               call['scale'], tile=MLA_TILE, interpret=True)
    cut = mla_decode_attention(*args, call['c'][:, :rows],
                               call['r'][:, :rows], seen, call['scale'],
                               tile=MLA_TILE, interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(cut))


@pytest.mark.parametrize('changed,match', [
    (dict(seen=jnp.zeros((2, MLA_ROWS), jnp.float32)), 'boolean mask'),
    (dict(seen=jnp.zeros((3, MLA_ROWS), bool)), 'against rows'),
    (dict(seen=jnp.zeros((2, 2 * MLA_ROWS), bool)), 'against rows'),
    (dict(r=jnp.zeros((2, MLA_ROWS, 4), jnp.bfloat16)), 'against rows'),
    (dict(q_rope=jnp.zeros((2, 4, 8), jnp.float32)), 'against rows'),
    (dict(tile=24), 'must divide'),
    (dict(tile=None), 'must divide')],
    ids=['additive_mask', 'mask_of_other_slots', 'mask_past_the_leaf',
         'leaves_of_two_dtypes', 'rope_width', 'tile_not_a_divisor',
         'no_tile_of_whole_lanes'])
def test_mla_decode_attention_refuses(changed, match):
    from paddle_tpu.ops.pallas_kernels import mla_decode_attention
    call = dict(q_lat=jnp.zeros((2, 4, 16), jnp.float32),
                q_rope=jnp.zeros((2, 4, 4), jnp.float32),
                c=jnp.zeros((2, MLA_ROWS, 16), jnp.float32),
                r=jnp.zeros((2, MLA_ROWS, 4), jnp.float32),
                seen=jnp.zeros((2, MLA_ROWS), bool), scale=0.25,
                tile=MLA_TILE)
    call.update(changed)
    with pytest.raises(ValueError, match=match):
        mla_decode_attention(**call, interpret=True)


def test_latent_decode_kernel_error_reaches_the_caller(monkeypatch):
    """The gate forced on and the kernel made to raise: a decode step
    (one query a slot against rows held, whole lanes, whole tiles) sees
    the exception, never a silent XLA stand-in; speculation's two rows
    are the einsums' by the conditions, and run."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.deepseek_v3 import (DeepseekV3Config,
                                            DeepseekV3ForCausalLM)
    from paddle_tpu.ops import pallas, pallas_kernels as pk

    def boom(*a, **k):
        raise RuntimeError('mosaic says no')
    monkeypatch.setattr(pallas, '_pallas_enabled', lambda: True)
    monkeypatch.setattr(pk, 'mla_decode_attention', boom)
    paddle.seed(0)
    model = DeepseekV3ForCausalLM(DeepseekV3Config.tiny(
        kv_lora_rank=128)).eval()
    pos = jnp.asarray([5, 9], jnp.int32)

    def step(queries):
        mask = (jnp.arange(128)[None, None, :]
                <= (pos[:, None] + jnp.arange(queries))[:, :, None])[:, None]
        return model(paddle.to_tensor(np.ones((2, queries), 'int32')),
                     cache=model.init_cache(2, 128), use_cache=True,
                     position_offset=pos, cache_offset=pos,
                     attention_mask=mask)[0]
    with pytest.raises(RuntimeError, match='mosaic says no'):
        step(1)
    assert np.isfinite(step(2).numpy()).all()


# ---------------------------------------------------------------------------
# decode attention over K and V held by head (`kv_decode_attention`, PR 40)
# ---------------------------------------------------------------------------
KV_ROWS, KV_TILE = 64, 8


def _kv_call(lengths, rep=4, hkv=2, d=64, dv=None, dtype='float32',
             window=None, hidden=(), rows=KV_ROWS, seed=0):
    """One query a slot, `hkv * rep` heads of `d`, against `KV_ROWS`
    rows of `hkv` KV heads (V `dv` wide); slot b sees rows `< lengths
    [b]`, the newest `window` of them, less `hidden` (pairs (slot,
    row)), under a mask of `rows` columns."""
    rs = np.random.RandomState(seed)
    b, dv = len(lengths), dv or d
    at = np.arange(rows)[None, :]
    seen = at < np.asarray(lengths)[:, None]
    if window:
        seen &= at >= np.asarray(lengths)[:, None] - window
    for slot, row in hidden:
        seen[slot, row] = False
    return dict(
        q=jnp.asarray(rs.randn(b, 1, hkv * rep, d), jnp.float32),
        k=jnp.asarray(rs.randn(b, KV_ROWS, hkv, d), dtype),
        v=jnp.asarray(rs.randn(b, KV_ROWS, hkv, dv), dtype),
        mask=jnp.asarray(seen)[:, None, None, :])


def _kv_both(call, tile=KV_TILE):
    """-> (the kernel, interpreted, on the leaves whole; `_attention_xla`
    on the rows the mask has columns for), float64."""
    from paddle_tpu.ops.pallas_kernels import kv_decode_attention
    q, k, v, mask = call['q'], call['k'], call['v'], call['mask']
    got = kv_decode_attention(q[:, 0], k, v, mask[:, 0, 0],
                              q.shape[-1] ** -0.5, tile=tile, interpret=True)
    n = mask.shape[-1]
    want = _attention_xla(q, k[:, :n].astype(jnp.float32),
                          v[:, :n].astype(jnp.float32), mask=mask)[:, 0]
    return np.asarray(got, np.float64), np.asarray(want, np.float64)


# one batch, a case a slot: lengths around a tile's edge, ragged ones,
# every row, windows whose first row lies past tile 0, rows hidden under
# the bound and between the bounds, nothing seen
_KV_LENGTHS = (1, KV_TILE - 1, KV_TILE, KV_TILE + 1, KV_ROWS, 43, 61, 29,
               50, 0)
_KV_WINDOWS = (None,) * 6 + (20, 20, 30, None)
_KV_HIDDEN = ((5, 3), (5, 41), (5, 0), (4, 63), (4, 17), (8, 33), (8, 21),
              (8, 49))


@pytest.mark.parametrize('rep,hkv,d,dv,dtype', [
    (1, 4, 128, 128, 'float32'), (4, 2, 64, 64, 'float32'),
    (4, 2, 64, 64, 'bfloat16'), (16, 2, 192, 128, 'float32'),
    (16, 2, 192, 128, 'bfloat16'), (8, 1, 128, 64, 'float32')],
    ids=['ungrouped', 'lfm2_4x64', 'lfm2_4x64_bf16', 'mimo_16x192_v128',
         'mimo_16x192_v128_bf16', 'one_kv_head'])
def test_kv_decode_attention_agrees_with_attention_xla(rep, hkv, d, dv,
                                                       dtype):
    """The kernel, interpreted, against `_attention_xla` on the same
    call: a slot's row tiles from its first seen row to its last, three
    bf16 passes where XLA's products are exact float32 on the CPU, an
    online softmax where it takes one over the whole row — float32
    rounding of a reordered sum, and the 2^-16 the dropped lo.lo pass
    is worth. The slot that sees nothing gets a finite answer nobody
    reads (XLA's is the mean of every row)."""
    call = _kv_call(_KV_LENGTHS, rep, hkv, d, dv, dtype, hidden=_KV_HIDDEN)
    at = np.arange(KV_ROWS)
    seen = np.array(call['mask'][:, 0, 0])
    for b, (n, w) in enumerate(zip(_KV_LENGTHS, _KV_WINDOWS)):
        if w:
            seen[b] &= at >= n - w
    call['mask'] = jnp.asarray(seen)[:, None, None, :]
    got, want = _kv_both(call)
    assert got.shape == want.shape == (len(_KV_LENGTHS), hkv * rep, dv)
    assert np.isfinite(got).all()
    assert np.abs(got[:-1] - want[:-1]).max() < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize('first,bound,tile,start,tiles', [
    (0, 0, 8, 0, 1), (0, 1, 8, 0, 1), (0, 8, 8, 0, 1), (0, 9, 8, 0, 2),
    (7, 9, 8, 0, 2), (8, 9, 8, 1, 1), (41, 61, 8, 5, 3), (0, 64, 8, 0, 8),
    (2048, 4096, 512, 4, 4), (2047, 4095, 512, 3, 5), (1, 2049, 512, 0, 5)])
def test_decode_walk_is_the_tiles_from_the_first_seen_row_to_the_last(
        first, bound, tile, start, tiles):
    from paddle_tpu.ops.pallas_kernels import decode_walk
    for kind in (np.int64, jnp.int32):
        got = decode_walk(kind(first), kind(bound), tile)
        assert tuple(map(int, got)) == (start, tiles)


def test_kv_decode_attention_walks_what_decode_walk_says(monkeypatch):
    """The grid is as long as `decode_walk`'s tiles, slot after slot,
    each slot's first tile the one its first seen row lies in: told by
    the tables the kernel is handed."""
    from paddle_tpu.ops import pallas_kernels as pk
    handed = {}
    real = pk.pl.pallas_call

    def spy(kernel, grid_spec, **kw):
        call = real(kernel, grid_spec=grid_spec, **kw)

        def run(slot, tile, edge, *rest):
            handed.update(slot=slot, tile=tile, edge=edge,
                          steps=grid_spec.grid[0])
            return call(slot, tile, edge, *rest)
        return run
    monkeypatch.setattr(pk.pl, 'pallas_call', spy)
    call = _kv_call((61, 5, 0, 33), window=20)
    _kv_both(call)
    # slot 0 sees 41..60: tiles 5-7; slot 1 0..4: tile 0; slot 2
    # nothing: tile 0; slot 3 13..32: tiles 1-4
    steps = int(handed['steps'])
    assert steps == 3 + 1 + 1 + 4
    assert np.asarray(handed['slot'])[:steps].tolist() \
        == [0, 0, 0, 1, 2, 3, 3, 3, 3]
    assert np.asarray(handed['tile'])[:steps].tolist() \
        == [5, 6, 7, 0, 0, 1, 2, 3, 4]
    assert np.asarray(handed['edge'])[:steps].tolist() \
        == [1, 0, 2, 3, 3, 1, 0, 0, 2]


def test_kv_decode_attention_walks_one_tile_of_a_slot_not_decoding(
        kv_interpreted):
    """An inactive slot beside long ones (`routing_scope(active)`, as
    the decode scan gives it): the kernel is handed NO seen row of it —
    one tile walked, whatever its stale position shows — and the
    decoding slots read what they read without it."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import generation
    call = _kv_call((57, KV_ROWS, 40))
    args = [paddle.to_tensor(call[n]) for n in ('q', 'k', 'v', 'mask')]
    with generation.routing_scope(jnp.asarray([True, False, True])), \
            jax.disable_jit():      # the spy keeps arrays, not tracers
        got = generation.bounded_decode_attention(*args).numpy()[:, 0]
    seen = np.asarray(kv_interpreted[0][3])
    assert seen[0].sum() == 57 and not seen[1].any() and seen[2].sum() == 40
    assert generation.active_rows() is None         # the scope is closed
    alone = generation.bounded_decode_attention(*args).numpy()[:, 0]
    _, want = _kv_both(call)
    assert np.isfinite(got).all()
    assert np.array_equal(got[[0, 2]], alone[[0, 2]])
    assert np.abs(got[[0, 2]] - want[[0, 2]]).max() \
        < 2e-5 * np.abs(want).max()


@pytest.mark.parametrize('rows', [KV_ROWS, KV_ROWS // 2])
def test_kv_decode_attention_reads_the_leaves_whole_under_a_shorter_mask(
        rows):
    """The half-length decode program's mask has half the columns: the
    kernel takes the leaves whole and reads no row past the mask."""
    from paddle_tpu.ops.pallas_kernels import kv_decode_attention
    call = _kv_call((rows, 9), rows=rows)
    q, seen = call['q'][:, 0], call['mask'][:, 0, 0]
    poisoned = [call[n].at[:, rows:].set(jnp.nan) for n in 'kv']
    got = kv_decode_attention(q, *poisoned, seen, 0.125, tile=KV_TILE,
                              interpret=True)
    cut = kv_decode_attention(q, call['k'][:, :rows], call['v'][:, :rows],
                              seen, 0.125, tile=KV_TILE, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(cut))


@pytest.mark.parametrize('changed,match', [
    (dict(seen=jnp.zeros((2, KV_ROWS), jnp.float32)), 'boolean mask'),
    (dict(seen=jnp.zeros((3, KV_ROWS), bool)), 'against k'),
    (dict(seen=jnp.zeros((2, 2 * KV_ROWS), bool)), 'against k'),
    (dict(v=jnp.zeros((2, KV_ROWS, 2, 16), jnp.bfloat16)), 'against k'),
    (dict(v=jnp.zeros((2, KV_ROWS, 4, 16), jnp.float32)), 'against k'),
    (dict(q=jnp.zeros((2, 8, 32), jnp.float32)), 'against k'),
    (dict(q=jnp.zeros((2, 7, 16), jnp.float32)), 'against k'),
    (dict(tile=24), 'must divide'),
    (dict(tile=None), 'must divide')],
    ids=['additive_mask', 'mask_of_other_slots', 'mask_past_the_leaves',
         'leaves_of_two_dtypes', 'v_of_other_heads', 'k_of_another_width',
         'heads_not_grouped', 'tile_not_a_divisor',
         'no_tile_of_whole_lanes'])
def test_kv_decode_attention_refuses(changed, match):
    from paddle_tpu.ops.pallas_kernels import kv_decode_attention
    call = dict(q=jnp.zeros((2, 8, 16), jnp.float32),
                k=jnp.zeros((2, KV_ROWS, 2, 16), jnp.float32),
                v=jnp.zeros((2, KV_ROWS, 2, 16), jnp.float32),
                seen=jnp.zeros((2, KV_ROWS), bool), scale=0.25,
                tile=KV_TILE)
    call.update(changed)
    with pytest.raises(ValueError, match=match):
        kv_decode_attention(**call, interpret=True)


def _kv_dispatch_call(**changed):
    spec = jax.ShapeDtypeStruct
    call = dict(q=spec((8, 1, 32, 128), jnp.float32),
                k=spec((8, 4096, 4, 128), jnp.float32),
                v=spec((8, 4096, 4, 128), jnp.float32),
                mask=spec((8, 1, 1, 4096), jnp.bool_))
    call.update(changed)
    return call


@pytest.mark.parametrize('changed,tile', [
    ({}, 512),
    (dict(mask=jax.ShapeDtypeStruct((8, 1, 1, 2048), jnp.bool_)), 512),
    (dict(mask=jax.ShapeDtypeStruct((1, 1, 1, 4096), jnp.bool_)), 512),
    (dict(mask=jax.ShapeDtypeStruct((8, 1, 1, 384), jnp.bool_)), 128),
    (dict(k=jax.ShapeDtypeStruct((8, 4096, 4, 192), jnp.float32)), 512),
    (dict(k=jax.ShapeDtypeStruct((8, 4096, 4, 128), jnp.bfloat16),
          v=jax.ShapeDtypeStruct((8, 4096, 4, 128), jnp.bfloat16)), 512),
    (dict(q=jax.ShapeDtypeStruct((8, 1, 32, 128), jnp.bfloat16)), None),
    (dict(q=jax.ShapeDtypeStruct((8, 2, 32, 128), jnp.float32),
          mask=jax.ShapeDtypeStruct((8, 1, 2, 4096), jnp.bool_)), None),
    (dict(sink=jax.ShapeDtypeStruct((32,), jnp.float32)), None),
    (dict(mask=jax.ShapeDtypeStruct((8, 1, 1, 4096), jnp.float32)), None),
    (dict(mask=jax.ShapeDtypeStruct((8, 32, 1, 4096), jnp.bool_)), None),
    (dict(mask=jax.ShapeDtypeStruct((8, 1, 1, 4000), jnp.bool_)), None),
    (dict(v=jax.ShapeDtypeStruct((8, 4096, 4, 128), jnp.bfloat16)), None),
    (dict(q=jax.ShapeDtypeStruct((8, 1, 30, 128), jnp.float32)), None),
    (dict(k=jax.ShapeDtypeStruct((8, 4096, 512), jnp.float32)), None)],
    ids=['a_decode_sub_step', 'the_half_program', 'one_mask_for_every_slot',
         'a_smaller_tile', 'k_wider_than_v', 'bf16_leaves', 'a_bf16_query',
         'two_queries_a_slot', 'a_sink', 'an_additive_mask',
         'a_per_head_mask', 'no_whole_tile', 'leaves_of_two_dtypes',
         'heads_not_grouped', 'rows_without_heads'])
def test_kv_decode_kernel_is_picked_by_the_call_alone(changed, tile):
    """The dispatch's conditions one by one, from shapes and dtypes:
    with the backend's condition lifted (`interpret=True`) each call
    gets the kernel at its tile or None; on the CPU as it is, None."""
    from paddle_tpu.ops import pallas, pallas_kernels
    call = _kv_dispatch_call(**changed)
    got = pallas.kv_decode_kernel(**call, interpret=True)
    if tile is None:
        assert got is None
    else:
        assert got.func is pallas_kernels.kv_decode_attention
        assert got.keywords == {'tile': tile, 'interpret': True}
    assert pallas.kv_decode_kernel(**call) is None


def test_kv_decode_kernel_error_reaches_the_caller(monkeypatch):
    """The gate forced on and the kernel made to raise: a decode step
    (one query a slot over K and V held, whole tiles) sees the
    exception, never a silent XLA stand-in; speculation's two rows are
    `_attention_xla`'s by the conditions, and run."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM
    from paddle_tpu.ops import pallas, pallas_kernels as pk

    def boom(*a, **k):
        raise RuntimeError('mosaic says no')
    monkeypatch.setattr(pallas, '_pallas_enabled', lambda: True)
    monkeypatch.setattr(pk, 'kv_decode_attention', boom)
    paddle.seed(0)
    model = AfmoeForCausalLM(AfmoeConfig.tiny(
        max_position_embeddings=128)).eval()
    pos = jnp.asarray([5, 9], jnp.int32)

    def step(queries):
        mask = (jnp.arange(128)[None, None, :]
                <= (pos[:, None] + jnp.arange(queries))[:, :, None])[:, None]
        return model(paddle.to_tensor(np.ones((2, queries), 'int32')),
                     cache=model.init_cache(2, 128), use_cache=True,
                     position_offset=pos, cache_offset=pos,
                     attention_mask=mask)[0]
    with pytest.raises(RuntimeError, match='mosaic says no'):
        step(1)
    assert np.isfinite(step(2).numpy()).all()
