import os

import numpy as np
import pytest

from sntc_tpu.parallel import global_mesh, initialize, process_info

# this container's jax build cannot run coordinated multi-process
# computations on the CPU backend — the workers die with exactly this
# message.  The two-process tests detect that SIGNATURE at runtime and
# skip (environment limitation, not a regression); on a backend that
# supports multiprocess they still run and assert in full.
_MULTIPROCESS_UNSUPPORTED = "Multiprocess computations aren't implemented"


def _require_pair_ok(procs, outs, marker):
    if any(_MULTIPROCESS_UNSUPPORTED in out for out in outs) and any(
        p.returncode != 0 for p in procs
    ):
        pytest.skip(
            "Multiprocess computations aren't implemented on the CPU "
            "backend on this jax build"
        )
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
        assert marker in out


def test_initialize_noop_single_host(monkeypatch):
    for m in (
        "JAX_COORDINATOR_ADDRESS",
        "COORDINATOR_ADDRESS",
        "MEGASCALE_COORDINATOR_ADDRESS",
    ):
        monkeypatch.delenv(m, raising=False)
    assert initialize() is False  # no multi-host markers -> no-op


def test_global_mesh_covers_all_devices(mesh8):
    m = global_mesh()
    assert m.devices.size == 8
    assert m.axis_names == ("data",)
    m2 = global_mesh(model=2)
    assert dict(m2.shape) == {"data": 4, "model": 2}
    # the mesh drives a real reduction
    import jax.numpy as jnp

    from sntc_tpu.parallel import make_tree_aggregate, shard_batch

    x = np.ones((16, 2), np.float32)
    xs, w = shard_batch(m, x)
    out = make_tree_aggregate(lambda xs, w: jnp.sum(xs * w[:, None]), m)(xs, w)
    assert float(out) == 32.0


def test_process_info_single():
    info = process_info()
    assert info["process_count"] == 1 and info["process_index"] == 0


# ---------------------------------------------------------------------------
# faked-device in-process legs (r22): the two-process legs below skip on
# this container's jax build, so tier-1 exercises the SAME estimator
# assertions over >1 device here — the faked 8-device CPU mesh and a
# 2-device subset (the smallest true multi-shard shape).  Only the
# cross-process coordination itself stays subprocess-gated.
# ---------------------------------------------------------------------------


def _planted_frame(n=2000, d=6, seed=0):
    from sntc_tpu.core.frame import Frame

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.array([1.0, -1.0, 0.5, 0.0, 0.0, 0.0])
    y = (X @ beta + 0.1 * rng.normal(size=n) > 0).astype(np.float64)
    return Frame({"features": X, "label": y}), beta


@pytest.mark.parametrize("n_devices", [2, 8])
def test_estimator_fit_over_faked_device_mesh(n_devices):
    """The _FIT_WORKER assertions, in-process: a REAL LogisticRegression
    fit SPMD over a multi-device mesh learns the planted direction, the
    tree path's histogram collective agrees, and a repeat fit is
    bit-identical (deterministic SPMD program, no device-order
    dependence)."""
    from sntc_tpu.models import DecisionTreeClassifier, LogisticRegression
    from sntc_tpu.parallel import default_mesh

    mesh = default_mesh(n_devices)
    f, beta = _planted_frame()
    m = LogisticRegression(mesh=mesh, maxIter=40).fit(f)
    coef = np.asarray(m.coefficients, np.float64)
    corr = float(
        coef[:3] @ beta[:3]
        / (np.linalg.norm(coef[:3]) * np.linalg.norm(beta[:3]))
    )
    assert corr > 0.95, corr
    y = np.asarray(f["label"])
    acc = float((np.asarray(m.transform(f)["prediction"]) == y).mean())
    assert acc > 0.9, acc
    m2 = LogisticRegression(mesh=mesh, maxIter=40).fit(f)
    np.testing.assert_array_equal(
        coef, np.asarray(m2.coefficients, np.float64)
    )

    dt = DecisionTreeClassifier(mesh=mesh, maxDepth=3).fit(f)
    dt_acc = float((np.asarray(dt.transform(f)["prediction"]) == y).mean())
    assert dt_acc > 0.8, dt_acc


_WORKER = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
from sntc_tpu.parallel.distributed import (
    global_mesh, initialize, process_info,
)

pid, port = int(sys.argv[1]), sys.argv[2]
assert initialize(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=2,
    process_id=pid,
)
info = process_info()
assert info["process_count"] == 2, info
assert info["process_index"] == pid, info
assert info["global_devices"] == 4, info
mesh = global_mesh()
assert mesh.devices.size == 4

# a REAL cross-process collective: allgather each process's scalar
from jax.experimental import multihost_utils

g = multihost_utils.process_allgather(np.array([float(pid + 1)]))
assert g.reshape(-1).tolist() == [1.0, 2.0], g
print("DIST_OK", flush=True)
"""


_FIT_WORKER = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

jax.config.update("jax_platforms", "cpu")
import numpy as np
from sntc_tpu.parallel.distributed import global_mesh, initialize

pid, port = int(sys.argv[1]), sys.argv[2]
assert initialize(
    coordinator_address=f"127.0.0.1:{port}",
    num_processes=2,
    process_id=pid,
)
mesh = global_mesh()
assert mesh.devices.size == 4

# identical data on both processes (the single-host data plane,
# replicated): a REAL LogisticRegression fit over the 2-process mesh
from sntc_tpu.core.frame import Frame
from sntc_tpu.models import LogisticRegression

rng = np.random.default_rng(0)
X = rng.normal(size=(4000, 6)).astype(np.float32)
beta = np.array([1.0, -1.0, 0.5, 0.0, 0.0, 0.0])
y = (X @ beta + 0.1 * rng.normal(size=4000) > 0).astype(np.float64)
f = Frame({"features": X, "label": y})
m = LogisticRegression(mesh=mesh, maxIter=40).fit(f)
coef = np.asarray(m.coefficients, np.float64)

# both processes must agree bit-for-bit on the result (SPMD), and the
# fit must have learned the planted direction
from jax.experimental import multihost_utils

both = multihost_utils.process_allgather(coef.astype(np.float32))
assert np.array_equal(both[0], both[1]), (both[0] - both[1])
corr = float(
    coef[:3] @ beta[:3] / (np.linalg.norm(coef[:3]) * np.linalg.norm(beta[:3]))
)
assert corr > 0.95, corr
acc = float((m.transform(f)["prediction"] == y).mean())
assert acc > 0.9, acc

# the TREE path too (a different collective shape: binned histogram
# aggregation inside the grower, psum'd across processes)
from sntc_tpu.models import DecisionTreeClassifier

dt = DecisionTreeClassifier(mesh=mesh, maxDepth=3).fit(f)
pred_col = dt.transform(f)["prediction"]
dt_acc = float((pred_col == y).mean())
assert dt_acc > 0.8, dt_acc
dt_pred = np.asarray(pred_col, np.float32)[:64]
both_dt = multihost_utils.process_allgather(dt_pred)
assert np.array_equal(both_dt[0], both_dt[1])
print("FIT_OK", round(acc, 3), round(dt_acc, 3), flush=True)
"""


def _run_pair(tmp_path, script_text, timeout=300):
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(script_text)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env, cwd=repo,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    return procs, outs


def test_two_process_estimator_fit(tmp_path):
    """A REAL estimator fit across two coordinated processes: the
    mesh-sharded LBFGS program runs SPMD over 2×2 devices with
    cross-process collectives, both processes produce bit-identical
    coefficients, and the fit learns (SURVEY.md §5.8 beyond the
    allgather smoke — shard_batch builds true global arrays via
    make_array_from_callback when the mesh spans processes)."""
    procs, outs = _run_pair(tmp_path, _FIT_WORKER)
    _require_pair_ok(procs, outs, "FIT_OK")


def test_two_process_initialize(tmp_path):
    """jax.distributed.initialize exercised for REAL: two coordinated
    processes (2 virtual CPU devices each), global mesh over all 4
    devices, one cross-process allgather (SURVEY.md §5.8)."""
    procs, outs = _run_pair(tmp_path, _WORKER)
    _require_pair_ok(procs, outs, "DIST_OK")
