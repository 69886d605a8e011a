"""Device-residency + assembly caches (the BlockManager/.cache() analog):
identity semantics, weakref lifetime, byte bounds, kill switch."""

import gc

import numpy as np
import pytest

from sntc_tpu.core.frame import Frame
from sntc_tpu.feature import VectorAssembler
from sntc_tpu.feature.vector_assembler import _ASSEMBLE_CACHE
from sntc_tpu.parallel.collectives import (
    _DEVICE_CACHE,
    pad_rows,
    shard_batch,
)


def test_pad_rows_buckets_nearby_sizes(monkeypatch):
    # fold-sized datasets land in one bucket -> one compiled program
    a, b = pad_rows(200_000, 8), pad_rows(199_000, 8)
    assert a == b
    # far-apart sizes differ
    assert pad_rows(100_000, 8) != pad_rows(200_000, 8)
    # small inputs are exact (no bucket waste)
    assert pad_rows(100, 4) == 100
    monkeypatch.setenv("SNTC_SHAPE_BUCKETS", "0")
    assert pad_rows(200_001, 8) == 200_008


def test_shard_batch_device_cache_identity(mesh8):
    X = np.random.default_rng(0).normal(size=(5000, 60)).astype(np.float32)
    xs1, _ = shard_batch(mesh8, X)
    xs2, _ = shard_batch(mesh8, X)          # same object -> same buffer
    assert xs1 is xs2
    xs3, _ = shard_batch(mesh8, X.copy())   # equal content, new object
    assert xs3 is not xs1


def test_shard_batch_cache_entry_dies_with_array(mesh8):
    X = np.random.default_rng(1).normal(size=(5000, 60)).astype(np.float32)
    shard_batch(mesh8, X)
    key_count = len(_DEVICE_CACHE)
    assert key_count >= 1
    del X
    gc.collect()
    # next call sweeps dead entries
    Y = np.random.default_rng(2).normal(size=(4000, 60)).astype(np.float32)
    shard_batch(mesh8, Y)
    assert all(e[0]() is not None for e in _DEVICE_CACHE.values())


def test_device_cache_budget_counts_bytes_a_device(mesh8, monkeypatch):
    """A row-sharded copy costs each device its shard: under a 2 MiB budget
    two 4 MiB matrices sharded over 8 devices (0.5 MiB a device each) both
    stay; on a mesh of one the second evicts the first."""
    from sntc_tpu.parallel import default_mesh

    monkeypatch.setenv("SNTC_DEVICE_CACHE_MB", "2")
    A, B = (np.full((16_384, 64), v, np.float32) for v in (1.0, 2.0))
    for mesh, kept in ((mesh8, True), (default_mesh(1), False)):
        _DEVICE_CACHE.clear()
        a1, _ = shard_batch(mesh, A)
        shard_batch(mesh, B)
        a2, _ = shard_batch(mesh, A)
        assert (a1 is a2) is kept


def test_device_cache_makes_room_before_the_new_copy_lands(monkeypatch):
    """The entry the budget evicts goes before the new array is placed,
    not after: the placement holds a short shard twice for the length of
    one program, and on the chip the evicted copy stood beside that."""
    import sntc_tpu.parallel.collectives as C
    from sntc_tpu.parallel import default_mesh

    monkeypatch.setenv("SNTC_DEVICE_CACHE_MB", "2")
    mesh1 = default_mesh(1)
    A, B = (np.full((16_385, 64), v, np.float32) for v in (1.0, 2.0))
    _DEVICE_CACHE.clear()
    a1, _ = shard_batch(mesh1, A)  # 4 MiB: over the budget, kept alone
    assert [e[1] is a1 for e in _DEVICE_CACHE.values()] == [True]
    held = []
    place = C._put_row_shards
    monkeypatch.setattr(
        C, "_put_row_shards",
        lambda *a: (held.append(len(_DEVICE_CACHE)), place(*a))[1],
    )
    b1, _ = shard_batch(mesh1, B)
    assert held == [0]  # A's copy was already out when B's went in
    assert [e[1] is b1 for e in _DEVICE_CACHE.values()] == [True]


def test_second_placement_of_a_shape_builds_no_program(mesh8):
    """The program that pads the short shard on its chip is one
    module-level ``jit``: a fit's second pass places fresh arrays of the
    same shapes and must trace and compile nothing (what keeps
    ``compiles_in_window.fit`` at 0); another row count is one more small
    program an array."""
    from sntc_tpu.parallel.collectives import _pad_shard_rows

    def place(n, fill):
        X = np.full((n, 60), fill, np.float32)   # over 1 MiB: device pad
        y = np.full(n, int(fill), np.int32)
        xs, ys, _ = shard_batch(mesh8, X, y)
        assert xs.shape[0] == ys.shape[0] == pad_rows(n, 8) > n
        return float(np.asarray(xs)[-1, 0])

    assert place(300_001, 1.0) == 1.0
    size = _pad_shard_rows._cache_size()
    assert size >= 2  # the matrix's and the label vector's
    assert place(300_001, 2.0) == 2.0
    assert _pad_shard_rows._cache_size() == size
    place(150_001, 4.0)
    assert _pad_shard_rows._cache_size() > size


def test_device_cache_kill_switch(mesh8, monkeypatch):
    monkeypatch.setenv("SNTC_DEVICE_CACHE_MB", "0")
    X = np.random.default_rng(3).normal(size=(5000, 60)).astype(np.float32)
    xs1, _ = shard_batch(mesh8, X)
    xs2, _ = shard_batch(mesh8, X)
    assert xs1 is not xs2


def test_assembler_memo_reuses_stack(monkeypatch):
    # the memo only engages for fit-scale stacks (serving micro-batches
    # skip it); drop the floor so the tiny test frames qualify
    import sntc_tpu.feature.vector_assembler as va_mod

    monkeypatch.setattr(va_mod, "_ASSEMBLE_MEMO_MIN_BYTES", 0)
    cols = {
        "a": np.arange(1000.0, dtype=np.float64),
        "b": np.arange(1000.0, dtype=np.float64) * 2,
    }
    f1 = Frame(cols)
    f2 = f1.with_column("extra", np.zeros(1000))  # shares a/b arrays
    va = VectorAssembler(inputCols=["a", "b"], outputCol="v",
                         handleInvalid="keep")
    X1 = va.transform(f1)["v"]
    X2 = va.transform(f2)["v"]
    assert X1 is X2  # identical column objects -> one stack
    monkeypatch.setenv("SNTC_DEVICE_CACHE_MB", "0")
    X3 = va.transform(f1)["v"]
    assert X3 is not X1


def test_assembler_memo_sweeps_dead_columns(monkeypatch):
    import sntc_tpu.feature.vector_assembler as va_mod

    monkeypatch.setattr(va_mod, "_ASSEMBLE_MEMO_MIN_BYTES", 0)
    big = np.random.default_rng(4).normal(size=(2000,)).astype(np.float64)
    f = Frame({"a": big, "b": big.copy()})
    va = VectorAssembler(inputCols=["a", "b"], outputCol="v",
                         handleInvalid="keep")
    va.transform(f)
    del f, big
    gc.collect()
    va2 = VectorAssembler(inputCols=["a", "b"], outputCol="v",
                          handleInvalid="keep")
    f2 = Frame({"a": np.ones(10), "b": np.ones(10)})
    va2.transform(f2)
    assert all(
        all(r() is not None for r in e[0]) for e in _ASSEMBLE_CACHE.values()
    )
