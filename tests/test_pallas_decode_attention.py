"""The decode-attention kernels, interpreted on the CPU:
`mla_decode_attention` over latent rows (PR 38) and
`kv_decode_attention` over K and V held by head (PR 40), each against
the XLA path it replaces. Moved whole out of
`tests/test_pallas_kernels.py` (PR 42)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import _attention_xla


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
