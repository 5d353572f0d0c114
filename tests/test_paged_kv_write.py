"""`pa.paged_kv_write` against the row scatter it replaced.

The scatter (`pool.at[li, :, page_idx, slot, :D].set(rows)`, coordinates
from `paged_write_indices`) is the oracle: after the write each pool, K
and V, is bit-equal to its own scatter, and every page the run does not
name is bit-untouched.  The kernel runs here in interpret mode.  A span
that writes nothing repeats the page of a span that does, and the
pipeline writes a page back only when the next step names another, so
the cases lean on that: inactive slots, caps of 0, sentinels, a slot
whose last page is the pool's last page, and a call whose only live span
is on that page.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

L, HKV, P, PAGE, D = 3, 2, 12, 16, 8
W = pa.kv_pool_width(D)  # the pool's rows: whole 128-lane rows
LI = 1


def _scatter_oracle(pool, li, rows, block_tables, seq_lens, write_caps):
    b, qn = rows.shape[:2]
    page_idx, slot = pa.paged_write_indices(
        block_tables, seq_lens, write_caps, qn, pool.shape[2],
        pool.shape[3])
    return pool.at[li, :, page_idx, slot, :rows.shape[-1]].set(rows)


def _case(name):
    """(block_tables, seq_lens, write_caps, qn) of a named case; page ids
    are distinct across slots, as the allocator hands them out."""
    bt = np.array([[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11]], np.int32)
    if name == "decode":                  # one row a slot
        return bt, [5, 16, 47], [1, 1, 1], 1
    if name == "decode_inactive":         # the middle slot sits out
        return bt, [5, 0, 63], [1, 0, 1], 1
    if name == "decode_all_inactive":
        return bt, [0, 0, 0], [0, 0, 0], 1
    if name == "decode_page_start":       # first row of a fresh page
        return bt, [16, 32, 48], [1, 1, 1], 1
    if name == "chunk_mid_page_crossing":  # 16 rows from row 9: two pages
        return bt, [9, 25, 41], [16, 16, 16], 16
    if name == "chunk_aligned":
        return bt, [0, 16, 32], [16, 16, 16], 16
    if name == "chunk_caps_zero_and_partial":
        return bt, [9, 20, 30], [0, 5, 16], 16
    if name == "chunk_one_row_among_many":  # a decode row in a mixed step
        return bt, [9, 31, 15], [1, 1, 1], 16
    if name == "long_run_three_pages":    # 24 rows from row 10: 3 pages
        return bt, [10, 30, 3], [24, 24, 24], 24
    if name == "last_page_of_the_pool":   # the clamp trap: page P-1 live
        return bt, [50, 0, 60], [4, 0, 4], 16
    if name == "sentinel_block_table":    # unassigned entries read P
        bt = bt.copy()
        bt[:, 2:] = P
        return bt, [9, 20, 30], [16, 12, 2], 16
    if name == "past_the_horizon":        # capped rows beyond pages_max
        return bt, [60, 63, 56], [4, 1, 8], 16
    if name == "prefill_one_request":     # B=1 from position 0, padded
        return bt[:1], [0], [37], 64
    if name == "one_live_span_on_the_last_page":  # every other span dead
        return bt, [0, 20, 50], [0, 0, 3], 16
    if name == "verify_window":           # a few rows, not a page's worth
        return bt, [14, 0, 33], [5, 0, 3], 5
    raise KeyError(name)


CASES = ["decode", "decode_inactive", "decode_all_inactive",
         "decode_page_start", "chunk_mid_page_crossing", "chunk_aligned",
         "chunk_caps_zero_and_partial", "chunk_one_row_among_many",
         "long_run_three_pages", "last_page_of_the_pool",
         "sentinel_block_table", "past_the_horizon", "prefill_one_request",
         "one_live_span_on_the_last_page", "verify_window"]


def _operands(name, seed=0):
    """K and V pools and rows of a named case, each drawn on its own."""
    bt, lens, caps, qn = _case(name)
    rng = np.random.default_rng(seed)
    pools = rng.standard_normal((2, L, HKV, P, PAGE, W)).astype(np.float32)
    pools[..., D:] = 0.0   # lanes past D hold zeros and stay zeros
    rows = rng.standard_normal((2, len(lens), qn, HKV, D)).astype(np.float32)
    return (*map(jnp.asarray, pools), *map(jnp.asarray, rows),
            jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
            jnp.asarray(caps, jnp.int32))


@pytest.mark.parametrize("name", CASES)
def test_bit_equal_to_the_scatter(name):
    k, v, k_rows, v_rows, bt, lens, caps = _operands(name)
    got = jax.jit(pa.paged_kv_write, static_argnums=2)(
        k, v, LI, k_rows, v_rows, bt, lens, caps)
    for pool, rows, out in ((k, k_rows, got[0]), (v, v_rows, got[1])):
        want = _scatter_oracle(pool, LI, rows, bt, lens, caps)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("name", CASES)
def test_pages_not_named_are_untouched(name):
    k, v, k_rows, v_rows, bt, lens, caps = _operands(name, seed=1)
    outs = pa.paged_kv_write(k, v, LI, k_rows, v_rows, bt, lens, caps)
    named = set()
    for b, (n0, cap) in enumerate(zip(np.asarray(lens), np.asarray(caps))):
        for pos in range(int(n0), int(n0) + int(cap)):
            named.add(int(np.asarray(bt)[b, pos // PAGE]))
    others = [p for p in range(P) if p not in named]
    rest = [li for li in range(L) if li != LI]
    for pool, out in ((k, outs[0]), (v, outs[1])):
        got, before = np.asarray(out), np.asarray(pool)
        np.testing.assert_array_equal(got[:, :, others],
                                      before[:, :, others])
        # and no other layer moved at all
        np.testing.assert_array_equal(got[rest], before[rest])
        # something was written where a cap is positive
        if int(np.asarray(caps).sum()):
            assert not np.array_equal(got[LI], before[LI])


@pytest.mark.parametrize("name", ["decode", "chunk_mid_page_crossing",
                                  "one_live_span_on_the_last_page"])
def test_k_and_v_rows_land_in_their_own_pools(name):
    """One call writes both: K's pages hold K's rows and V's pages V's,
    from pools that start alike and rows that differ."""
    k, _, k_rows, _, bt, lens, caps = _operands(name, seed=2)
    v_rows = -2.0 * k_rows + 1.0
    got_k, got_v = pa.paged_kv_write(k, k, LI, k_rows, v_rows, bt, lens,
                                     caps)
    for rows, out in ((k_rows, got_k), (v_rows, got_v)):
        want = _scatter_oracle(k, LI, rows, bt, lens, caps)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert not np.array_equal(np.asarray(got_k), np.asarray(got_v))


def test_rows_are_cast_to_the_pool_dtype():
    k, v, k_rows, v_rows, bt, lens, caps = _operands(
        "chunk_mid_page_crossing")
    k16, v16 = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    got = pa.paged_kv_write(k16, v16, LI, k_rows, v_rows, bt, lens, caps)
    for pool, rows, out in ((k16, k_rows, got[0]), (v16, v_rows, got[1])):
        want = _scatter_oracle(pool, LI, rows.astype(jnp.bfloat16), bt,
                               lens, caps)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                      np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("head_dim, width",
                         [(8, 128), (64, 128), (128, 128), (160, 256)])
def test_pool_rows_are_whole_lane_rows(head_dim, width):
    assert pa.kv_pool_width(head_dim) == width
    assert pa.kv_pool_width(head_dim, jnp.bfloat16) == width
    assert pa.kv_pool_width(head_dim, jnp.int8) == head_dim
    pool = jnp.arange(2 * 3 * 4 * 16 * width, dtype=jnp.float32).reshape(
        2, 3, 4, 16, width)
    layer = pa.kv_layer(pool, 1, head_dim)
    assert layer.shape == (3, 4, 16, head_dim)
    np.testing.assert_array_equal(np.asarray(layer),
                                  np.asarray(pool)[1, ..., :head_dim])


# ---------------------------------------------------------------------------
# `pa.KVPool`: the one type that knows how the pool is stored
# ---------------------------------------------------------------------------
def _filled_pool(dtype):
    """A pool whose every element (padding lanes and scales too) differs."""
    kv = pa.KVPool.zeros(L, HKV, P, PAGE, D, dtype)
    rng = np.random.RandomState(5)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(
            rng.randint(-100, 100, a.shape).astype(a.dtype)), kv)


@pytest.mark.parametrize("dtype, leaves",
                         [(jnp.float32, 2), (jnp.bfloat16, 2), (jnp.int8, 4)])
def test_pool_flattens_to_its_arrays(dtype, leaves):
    """A float pool is exactly (k, v) — the two operands the step
    executables always took, in that order — an int8 one (k, v, k_scales,
    v_scales): a ``None`` field is no leaf."""
    kv = pa.KVPool.zeros(L, HKV, P, PAGE, D, dtype)
    flat, tree = jax.tree_util.tree_flatten(kv)
    assert len(flat) == leaves and kv.quantized == (leaves == 4)
    assert flat[0] is kv.k and flat[1] is kv.v
    assert kv.k.shape == (L, HKV, P, PAGE, pa.kv_pool_width(D, dtype))
    assert kv.dtype == dtype and tuple(kv.pages) == (kv.k, kv.v)
    if kv.quantized:
        assert flat[2] is kv.k_scales and flat[3] is kv.v_scales
        assert kv.k_scales.shape == (L, HKV, P)
    back = jax.tree_util.tree_unflatten(tree, flat)
    assert back.head_dim == D and back.quantized == kv.quantized


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_pool_pages_round_trip_through_the_host(dtype):
    """`export_pages` gives the K/V lanes of the named pages (not the
    padding lanes of a float pool's 128-lane rows) and an int8 pool's
    scales; `import_pages` puts them back bit for bit, elsewhere in
    another pool, and touches nothing else."""
    src = _filled_pool(dtype)
    ids, dst_ids = [7, 2, 11], [0, 5, 3]
    arrays = src.export_pages(ids)
    assert sorted(arrays) == (["k", "ks", "v", "vs"] if src.quantized
                              else ["k", "v"])
    assert arrays["k"].shape == (L, HKV, len(ids), PAGE, D)
    np.testing.assert_array_equal(
        arrays["v"], np.asarray(src.v)[:, :, ids, :, :D])
    dst = pa.KVPool.zeros(L, HKV, P, PAGE, D, dtype)
    assert dst.fits(arrays)
    got = dst.import_pages(dst_ids, arrays)
    for name, a in got.export_pages(dst_ids).items():
        np.testing.assert_array_equal(a, arrays[name])
    rest = [p for p in range(P) if p not in dst_ids]
    for a in jax.tree_util.tree_leaves(got):
        assert not np.asarray(a)[:, :, rest].any()
    if not src.quantized:  # the padding lanes stay zero
        assert src.k.shape[-1] > D
        assert not np.asarray(got.k)[..., D:].any()
    # what does not fit: another geometry, another storage kind
    assert not pa.KVPool.zeros(L, HKV, P, PAGE * 2, D, dtype).fits(arrays)
    other = jnp.int8 if dtype != jnp.int8 else jnp.float32
    assert not pa.KVPool.zeros(L, HKV, P, PAGE, D, other).fits(arrays)
