"""Parity tests for the repo's own pallas kernels, run in interpret mode
on the CPU mesh (SURVEY.md §4). The XLA reference attention is the
ground truth for both forward values and dq/dk/dv gradients. The decode
kernels have files of their own, so that none is a worker's whole run:
`test_pallas_moe_decode_experts.py`, `test_pallas_decode_attention.py`."""
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
        # a call wider than one block is the grouped kernel's by the
        # conditions (PR 49), and its error reaches the caller as well
        wide = paddle.to_tensor(np.ones((1, BLOCK_ROWS + 1), 'int32'))
        with pytest.raises(Exception) as refused:
            model(wide)         # Mosaic, on the CPU: no stand-in steps in
        assert 'mosaic says no' not in str(refused.value)

        def boom_too(*a, **k):
            raise RuntimeError('mosaic says no to the wide call too')
        monkeypatch.setattr(pk, 'moe_grouped_experts', boom_too)
        with pytest.raises(RuntimeError, match='to the wide call too'):
            model(wide)
