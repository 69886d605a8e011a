"""Test harness: fake 8-device CPU mesh (SURVEY.md §4.1).

Must run before jax is imported anywhere: forces the CPU platform with 8
virtual devices — the ``local[2]``/``local-cluster`` analog — so all
pmap/psum/shard_map code paths run multi-device without TPU hardware.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# no persistent compile cache in tier-1: in-process `main()` calls (and
# the CLI children tests spawn) would otherwise fill the checkout's
# .jax_cache, which the chip tool copies, and a per-session temp dir
# would only add a disk write per executable to a run that is already
# 640-770 s of its 870 s budget.  The tests of the cache itself hand
# their children an explicit directory.
os.environ["SNTC_NO_COMPILE_CACHE"] = "1"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# tests invoke bench.py helpers (smoke tests); the committed run journal
# must hold only real bench invocations
os.environ["BENCH_NO_JOURNAL"] = "1"

import jax  # noqa: E402
import pytest  # noqa: E402

# SURVEY.md §5.2: CICIDS2017's Inf/NaN values make silent NaN propagation a
# real hazard — fail tests at the op that produced the first NaN.
jax.config.update("jax_debug_nans", True)


@pytest.fixture(autouse=True, scope="module")
def _free_compiled_programs():
    """Drop every compiled executable after each test module.  Each
    XLA:CPU executable holds its own memory mappings; over the ~1000
    tests of tier-1 one process accumulates past ``vm.max_map_count``
    (65530 — measured 63 k maps at the crash) and the next large
    compile segfaults inside jaxlib (test_trees' ``_grow_fused``, at
    the seed too)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def mesh8():
    from sntc_tpu.parallel import default_mesh

    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    return default_mesh()
