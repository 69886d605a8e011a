import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sntc_tpu.parallel import (
    make_tree_aggregate,
    pad_rows,
    shard_batch,
)


def test_pad_rows():
    assert pad_rows(16, 8) == 16
    assert pad_rows(17, 8) == 24
    assert pad_rows(1, 8) == 8


def test_shard_batch_pads_with_zero_weights(mesh8):
    x = np.arange(10, dtype=np.float32).reshape(10, 1)
    (xs, w) = shard_batch(mesh8, x)
    assert xs.shape == (16, 1)
    assert w.shape == (16,)
    np.testing.assert_array_equal(np.asarray(w), [1] * 10 + [0] * 6)
    # padding replicates row 0, not garbage
    assert np.asarray(xs)[10:].tolist() == [[0.0]] * 6


def test_tree_aggregate_matches_numpy(mesh8):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(100, 4)).astype(np.float32)
    y = rng.normal(size=(100,)).astype(np.float32)
    xs, ys, w = shard_batch(mesh8, x, y)

    def weighted_moments(xs, ys, w):
        return {
            "sum_x": jnp.einsum("n,nd->d", w, xs),
            "sum_xy": jnp.einsum("n,nd,n->d", w, xs, ys),
            "count": jnp.sum(w),
        }

    agg = make_tree_aggregate(weighted_moments, mesh8)
    out = agg(xs, ys, w)
    np.testing.assert_allclose(np.asarray(out["sum_x"]), x.sum(0), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out["sum_xy"]), (x * y[:, None]).sum(0), rtol=1e-4
    )
    assert float(out["count"]) == 100.0


def test_tree_aggregate_result_replicated(mesh8):
    x = np.ones((8, 2), dtype=np.float32)
    xs, w = shard_batch(mesh8, x)
    agg = make_tree_aggregate(lambda xs, w: jnp.sum(xs * w[:, None]), mesh8)
    out = agg(xs, w)
    assert float(out) == 16.0
    # replicated output: every device holds the full value
    assert out.sharding.is_fully_replicated


def _pad_bytes(where):
    from sntc_tpu.obs import registry

    return registry().get("sntc_transfer_pad_bytes_total", where=where) or 0


def _host_array(n, kind):
    """The arrays a fit places: a row-major matrix, the assembler's
    feature-major ``base.T``, a label vector, a higher-rank block; all
    over the 1 MiB at which the pad leaves the host, but ``small``."""
    rng = np.random.default_rng(n)
    if kind == "c_f32":
        return rng.normal(size=(n, 78)).astype(np.float32)
    if kind == "feature_major_f32":
        X = rng.normal(size=(78, n)).astype(np.float32).T
        assert X.flags.f_contiguous and not X.flags.c_contiguous
        return X
    if kind == "labels_i32":
        return rng.integers(0, 15, size=n).astype(np.int32)
    if kind == "rank3_f32":
        return rng.normal(size=(n, 3, 2)).astype(np.float32)
    if kind == "wide_f32":  # few rows, each a third of a MiB
        return rng.normal(size=(n, 1 << 16)).astype(np.float32)
    assert kind == "small"
    return rng.normal(size=(n, 4)).astype(np.float32)


_PLACEMENTS = [
    # rows, shards, kind
    (17, 8, "small"),
    (17, 8, "wide_f32"),
    (1, 8, "wide_f32"),          # trailing shards with no real rows
    (200_001, 8, "c_f32"),
    (200_001, 8, "feature_major_f32"),
    (300_000, 1, "c_f32"),
    (300_000, 1, "feature_major_f32"),
    (300_000, 4, "c_f32"),
    (300_000, 4, "feature_major_f32"),
    (300_000, 4, "labels_i32"),
    (300_000, 1, "labels_i32"),
    (200_001, 8, "rank3_f32"),
    (5_000, 4, "small"),
]


@pytest.mark.parametrize("n,shards,kind", _PLACEMENTS)
def test_placement_equals_the_host_pad(mesh8, n, shards, kind):
    """``shard_batch`` gives the array ``device_put`` of the host-padded
    copy gives: ``n_pad`` rows under the asked sharding, the real rows
    then row 0 replicated, bit for bit; a fit-scale array is never copied
    on the host for it (the short shard is padded on its chip), a small
    one still is; the memo on the unpadded array holds either way."""
    import gc

    from jax.sharding import NamedSharding, PartitionSpec as P

    from sntc_tpu.parallel import default_mesh
    from sntc_tpu.parallel.collectives import _DEVICE_CACHE

    mesh = mesh8 if shards == 8 else default_mesh(shards)
    arr = _host_array(n, kind)
    fit_scale = arr.nbytes >= 1 << 20
    n_pad = pad_rows(n, shards)
    assert n_pad > n and n_pad % shards == 0
    want = np.concatenate(
        [arr, np.broadcast_to(arr[:1], (n_pad - n,) + arr.shape[1:])]
    )
    host0, dev0 = _pad_bytes("host"), _pad_bytes("device")
    xs, w = shard_batch(mesh, arr)
    assert xs.shape == want.shape and xs.dtype == want.dtype
    assert xs.sharding == NamedSharding(
        mesh, P("data", *([None] * (arr.ndim - 1)))
    )
    assert xs.is_fully_addressable
    got = np.asarray(xs)
    np.testing.assert_array_equal(got, want)
    assert (got[n:] == arr[0]).all()
    # every device holds exactly its rows of the padded array
    per = n_pad // shards
    for s in xs.addressable_shards:
        assert s.data.shape[0] == per
        np.testing.assert_array_equal(np.asarray(s.data), want[s.index])
    np.testing.assert_array_equal(
        np.asarray(w), np.arange(n_pad) < n
    )
    row_bytes = arr.nbytes // n
    short_shards = -(-(n_pad - n) // per)  # shards that hold padding
    if fit_scale:
        assert _pad_bytes("host") == host0
        assert _pad_bytes("device") - dev0 == short_shards * per * row_bytes
        # the memo: same host array -> the device array it made
        assert shard_batch(mesh, arr)[0] is xs
        assert _pad_bytes("device") - dev0 == short_shards * per * row_bytes
        # ... and it dies with the host array
        key_alive = sum(e[0]() is arr for e in _DEVICE_CACHE.values())
        assert key_alive == 1
        # (XLA:CPU may take a 64-byte-aligned contiguous shard view with no
        # copy at all: the device array then keeps the host array alive,
        # as any unpadded ``device_put`` always could; no chip does that)
        lo = arr.ctypes.data
        aliased = any(
            lo <= s.data.unsafe_buffer_pointer() < lo + arr.nbytes
            for s in xs.addressable_shards
        )
        del arr, want
        gc.collect()
        shard_batch(mesh, np.zeros(3, np.float32))  # sweeps dead entries
        assert all(e[0]() is not None for e in _DEVICE_CACHE.values())
        if not aliased:
            assert not any(e[1] is xs for e in _DEVICE_CACHE.values())
    else:
        assert _pad_bytes("device") == dev0
        assert _pad_bytes("host") - host0 == arr.nbytes
        assert shard_batch(mesh, arr)[0] is not xs  # under the memo's floor


def test_placement_keeps_every_bit_pattern(mesh8):
    """Denormals, NaN payloads, signed zeros and infinities come out of the
    device pad as they went in (the program moves bytes, it computes
    nothing): compared as integers, real rows and padding rows alike."""
    bits = np.array(
        [0x00000001, 0x007FFFFF, 0x80000001, 0x7FC00001, 0x7F800001,
         0xFFC12345, 0x80000000, 0x7F800000, 0xFF800000, 0x3F800000],
        np.uint32,
    )
    n = 40_001
    X = np.resize(bits, n * 10).reshape(10, n).T.view(np.float32)
    n_pad = pad_rows(n, 8)
    want = np.concatenate([X, np.broadcast_to(X[:1], (n_pad - n, 10))])
    dev0 = _pad_bytes("device")
    with jax.debug_nans(False):  # the NaNs are the point
        xs, _ = shard_batch(mesh8, X)
        got = np.asarray(xs)
    assert _pad_bytes("device") > dev0  # the pad ran on the device
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_placement_counts_what_crosses(mesh8):
    """The ledger's upload bytes of a fit-scale placement: the unpadded
    array once, row 0 once more for the padded shard, and the weights."""
    from sntc_tpu.obs import registry

    def uploaded():
        return registry().get("sntc_transfer_upload_bytes_total") or 0

    arr = _host_array(200_001, "c_f32")
    n_pad = pad_rows(200_001, 8)
    b0 = uploaded()
    shard_batch(mesh8, arr)
    assert uploaded() - b0 == arr.nbytes + 78 * 4 + n_pad * 4


def test_device_resident_input_is_padded_where_it_lives(mesh8):
    """A ``jax.Array`` never revisits the host: no host pad, no shard
    program; the same rows come out."""
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    host0, dev0 = _pad_bytes("host"), _pad_bytes("device")
    xs, _ = shard_batch(mesh8, jnp.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(xs), np.concatenate([x, np.repeat(x[:1], 6, 0)])
    )
    assert (_pad_bytes("host"), _pad_bytes("device")) == (host0, dev0)
