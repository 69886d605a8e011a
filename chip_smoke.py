#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that ``synth → train → serve`` runs on
the chip.

    python3 chip_smoke.py                    # on a machine with a TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse-cpu   # tiny, CPU

One process, no platform override: the first act is ``jax.devices()``; if
the first device is not a TPU the script exits non-zero before any work
(there is no CPU carry-on — ``--rehearse-cpu`` is an explicit argument that
runs the same phases at a tiny size through the Pallas interpreter, prints
``"platform": "cpu"``, and can never be reached by the absence of a chip).

Everything goes in-process through ``sntc_tpu.app.main`` — the entry point
a user calls — because a chip belongs to one process at a time:

* **Phase A, flagship** (bench config 2): ``synth`` 200 k rows, ``train
  --estimator mlp --layers 78,64,15``, then ``serve --once
  --shape-buckets`` over a handful of 1–4 k-row CSV micro-batches (what a
  NetFlow collector flushes), default flags otherwise.
* **Phase B, trees** (bench config 3 shape): ``train --chisq-top 40
  --estimator rf`` (20 trees × depth 5, 50 k rows), then ``serve --once``
  under ``--row-policy salvage`` — the admission contract hands the
  predictor float32 columns, which is what routes the bucket pad through
  the ``pad_assemble`` kernel.  With the fit this puts all three
  registered Pallas kernels through Mosaic.
* **Kernel twins**: every registered kernel runs against its lowered-jnp /
  numpy twin ON THE CHIP at its ``smoke_case`` shapes and tolerance.
* **More than one device**: shards on every device, the collective mesh
  gauge equals the device count, and one extra Phase B serve pass under
  ``set_serve_mesh(default_mesh())`` checked against the single-device
  pass.  (Serving defaults to device 0 only; the summary says so.)

What counts is positive evidence read from the metrics registry, not the
absence of errors: the head dispatched on the device for every served
batch, each kernel dispatched with ``impl="pallas"``, zero poisoned
signatures, zero device faults and fallbacks, rows committed == rows fed,
served predictions agreeing with the same checkpoint evaluated on the CPU
backend, held-out macro-F1 above a floor.  Any failed check raises: the
exit code is non-zero and no result line is printed.  On success stdout
ends with two JSON lines: the detailed summary (phases, kernels, cache,
wall times, ``"claim": null``), then — last — the result line, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.
"""

from __future__ import annotations

import contextlib
import glob
import io
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke_work")

# Held-out macro-F1 floors.  Reference values are `python -m sntc_tpu train`
# on the CPU backend (jax 0.9.0) at exactly the smoke's sizes and seeds
# (my CPU run, PR 21): MLP 78-64-15, 200 k rows, seed 7, 100 LBFGS
# iterations -> 0.7746; RF 20 x depth 5 over ChiSq top-40, 50 k rows,
# seed 11 -> 0.2656 (depth 5 cannot separate 15 classes at 80 % benign;
# data/synth.py says why).  The margin covers what the chip changes
# without being wrong: f32 matmuls feed the MXU bf16 inputs under JAX's
# default precision, which moves an LBFGS trajectory, and the histogram
# kernel sums in another order.
F1_FLOOR = {"mlp": 0.7746 - 0.15, "rf": 0.2656 - 0.08}
# served label vs the same checkpoint on the CPU backend: near-tie argmaxes
# may flip under the MXU's default precision, wholesale disagreement may not
MIN_AGREEMENT = 0.98

FULL = dict(
    mlp_rows=200_000, mlp_days=4, mlp_iters=100,
    rf_rows=50_000, rf_days=2,
    mlp_batches=(1000, 1500, 2048, 3000, 4000),
    rf_batches=(700, 1500, 2048, 3000),
    bucket=1024, kernel_rows=2048,
)
TINY = dict(
    mlp_rows=4_000, mlp_days=2, mlp_iters=20,
    rf_rows=3_000, rf_days=2,
    mlp_batches=(100, 150, 256),
    rf_batches=(70, 150, 256),
    bucket=128, kernel_rows=256,
)


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def cli(argv: list) -> dict:
    """One ``python -m sntc_tpu ...`` command, in this process.  Returns
    the command's JSON result line."""
    from sntc_tpu.app import main

    buf = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    check(rc == 0, f"{argv[0]} exited {rc}")
    lines = [ln for ln in buf.getvalue().splitlines() if ln.startswith("{")]
    check(lines, f"{argv[0]} printed no JSON line")
    log(f"{' '.join(argv[:4])} ... -> {lines[-1][:300]} "
        f"({time.perf_counter() - t0:.1f} s)")
    return json.loads(lines[-1])


def metric(name: str, _snapshot=None, **labels) -> float:
    """Sum of every live series of ``name`` whose labels include
    ``labels`` (0 when the metric was never touched)."""
    from sntc_tpu.obs.metrics import registry

    m = (_snapshot or registry().snapshot()).get(name)
    if m is None:
        return 0.0
    return float(sum(
        s["value"] for s in m["series"]
        if all(s["labels"].get(k) == v for k, v in labels.items())
    ))


class Delta:
    """Counter deltas since construction (the registry is process-wide,
    and every in-process command adds to it)."""

    def __init__(self):
        from sntc_tpu.obs.metrics import registry

        self._base = registry().snapshot()

    def __call__(self, name: str, **labels) -> float:
        return metric(name, **labels) - metric(name, self._base, **labels)


def write_batches(frame, sizes, out_dir: str) -> list:
    """Feature-only CSV micro-batches (live flows carry no label); returns
    the per-file Frames for the reference pass."""
    import pyarrow.csv as pacsv

    from sntc_tpu.data import CICIDS2017_FEATURES

    os.makedirs(out_dir)
    parts, at = [], 0
    for i, n in enumerate(sizes):
        part = frame.slice(at, at + n).select(CICIDS2017_FEATURES)
        pacsv.write_csv(
            part.to_arrow(), os.path.join(out_dir, f"part_{i:03d}.csv")
        )
        parts.append(part)
        at += n
    return parts


def read_sink(out_dir: str) -> list:
    import pyarrow.csv as pacsv

    files = sorted(glob.glob(os.path.join(out_dir, "batch_*.csv")))
    return [pacsv.read_csv(p).to_pydict() for p in files]


def reference_labels(model_dir: str, parts: list, contract=None) -> list:
    """The same checkpoint, staged (unfused, kernels off), evaluated on
    the CPU backend of this process: the reference the served labels are
    held against."""
    import jax

    from sntc_tpu.app import strip_label_indexer
    from sntc_tpu.core.base import PipelineModel
    from sntc_tpu.mlio import load_model

    stages, labels = strip_label_indexer(load_model(model_dir), "label")
    model = PipelineModel(stages=stages)
    saved = os.environ.get("SNTC_SERVE_KERNELS")
    os.environ["SNTC_SERVE_KERNELS"] = "off"
    out = []
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            for part in parts:
                if contract is not None:
                    res = contract.admit(part)
                    part = res.frame.filter(res.valid)
                pred = model.transform(part)["prediction"]
                out.append([str(labels[int(p)]) for p in pred])
    finally:
        if saved is None:
            os.environ.pop("SNTC_SERVE_KERNELS", None)
        else:
            os.environ["SNTC_SERVE_KERNELS"] = saved
    return out


def agreement(served: list, ref: list, what: str) -> float:
    check(len(served) == len(ref),
          f"{what}: {len(served)} sink batches vs {len(ref)} fed")
    same = total = 0
    for i, (got, want) in enumerate(zip(served, ref)):
        check(set(got) >= {"prediction", "predictedLabel"},
              f"{what}: sink columns {sorted(got)}")
        check(len(got["predictedLabel"]) == len(want),
              f"{what} batch {i}: {len(got['predictedLabel'])} rows "
              f"served vs {len(want)} in the reference")
        check(all(p == p and p >= 0 for p in got["prediction"]),
              f"{what} batch {i}: non-finite prediction")
        same += sum(a == b for a, b in zip(got["predictedLabel"], want))
        total += len(want)
    frac = same / max(1, total)
    check(frac >= MIN_AGREEMENT,
          f"{what}: served labels agree with the CPU reference on "
          f"{frac:.4f} of {total} rows (< {MIN_AGREEMENT})")
    return round(frac, 5)


def assert_device_clean(d: Delta, what: str) -> None:
    """No path that hides the device ran during the phase."""
    check(metric("sntc_device_state") == 0, f"{what}: HOST_DEGRADED")
    for name in (
        "sntc_device_faults_total",
        "sntc_device_fallback_batches_total",
        "sntc_fuse_fallbacks_total",
        "sntc_device_oom_splits_total",
    ):
        check(d(name) == 0, f"{what}: {name} moved by {d(name)}")
    for reason in ("compile_error", "poisoned"):
        n = d("sntc_kernel_fallback_total", reason=reason)
        check(n == 0, f"{what}: {n} kernel calls fell to the twin "
              f"(reason={reason})")
    from sntc_tpu.kernels.registry import kernel_stats

    check(metric("sntc_kernel_poisoned_signatures") == 0,
          f"{what}: kernel signatures poisoned onto their twins: "
          f"{kernel_stats()['poisoned']}")
    check(metric("sntc_device_poisoned_signatures") == 0,
          f"{what}: a fused-program signature is poisoned")
    check(d("sntc_predict_head_dispatch_total", path="host") == 0,
          f"{what}: a head predicted on the host")


def check_device_line(line: dict, device: dict, what: str) -> None:
    """The command's JSON line names the device it ran on."""
    check(line["platform"] == device["platform"]
          and line["device_kind"] == device["kind"]
          and line["device_count"] == device["count"],
          f"{what} line names {line['platform']}/{line['device_kind']}"
          f" x{line['device_count']}")


def serve(model_dir, in_dir, tag, cfg, device, n_batches, n_rows,
          extra=()) -> dict:
    d = Delta()
    line = cli([
        "serve", "--model", model_dir, "--watch", in_dir,
        "--out", os.path.join(WORK, f"out_{tag}"),
        "--checkpoint", os.path.join(WORK, f"ckpt_{tag}"),
        "--once", "--shape-buckets", str(cfg["bucket"]),
        "--max-files-per-batch", "1", *extra,
    ])
    check(line["batches"] == n_batches,
          f"{tag}: served {line['batches']} batches, fed {n_batches}")
    check_device_line(line, device, f"{tag}: serve")
    check(d("sntc_batches_committed_total") == n_batches,
          f"{tag}: {d('sntc_batches_committed_total')} batches committed")
    check(d("sntc_rows_committed_total") == n_rows,
          f"{tag}: {d('sntc_rows_committed_total')} rows committed, "
          f"{n_rows} fed")
    on_device = int(d("sntc_predict_head_dispatch_total", path="device"))
    check(on_device == n_batches,
          f"{tag}: head dispatched on the device for {on_device} of "
          f"{n_batches} batches")
    assert_device_clean(d, tag)
    return {
        "served_batches": n_batches, "served_rows": n_rows,
        "head_device_dispatches": on_device,
    }


def phase_a(cfg, device) -> dict:
    """Flagship: synth -> train mlp 78-64-15 -> serve --once."""
    from sntc_tpu.data import generate_frame

    data = os.path.join(WORK, "data_mlp")
    model = os.path.join(WORK, "model_mlp")
    cli(["synth", "--out", data, "--rows", str(cfg["mlp_rows"]),
         "--days", str(cfg["mlp_days"]), "--seed", "7"])
    train = cli([
        "train", "--data", data, "--estimator", "mlp",
        "--layers", "78,64,15", "--max-iter", str(cfg["mlp_iters"]),
        "--model-out", model, "--seed", "7",
    ])
    check_device_line(train, device, "mlp: train")
    f1 = train["macroF1"]
    check(f1 == f1, "MLP macro-F1 is NaN")
    sizes = cfg["mlp_batches"]
    frame = generate_frame(sum(sizes), seed=8)
    in_dir = os.path.join(WORK, "in_mlp")
    parts = write_batches(frame, sizes, in_dir)
    served = serve(model, in_dir, "mlp", cfg, device, len(sizes), sum(sizes))
    agree = agreement(
        read_sink(os.path.join(WORK, "out_mlp")),
        reference_labels(model, parts), "phase A",
    )
    return {
        "train_rows": train["train_rows"], "macroF1": round(f1, 4),
        "fit_wall_clock_s": train["fit_wall_clock_s"],
        **served, "label_agreement_vs_cpu": agree,
    }


def phase_b(cfg, device, impl) -> dict:
    """Trees: train chisq-top-40 rf -> serve --once under salvage; with
    the fit, all three registered kernels."""
    from sntc_tpu.data import CICIDS2017_CONTRACT, generate_frame

    data = os.path.join(WORK, "data_rf")
    model = os.path.join(WORK, "model_rf")
    cli(["synth", "--out", data, "--rows", str(cfg["rf_rows"]),
         "--days", str(cfg["rf_days"]), "--seed", "11"])
    d = Delta()
    train = cli([
        "train", "--data", data, "--estimator", "rf", "--chisq-top", "40",
        "--num-trees", "20", "--max-depth", "5",
        "--model-out", model, "--seed", "11",
    ])
    check_device_line(train, device, "rf: train")
    f1 = train["macroF1"]
    check(f1 == f1, "RF macro-F1 is NaN")
    # the fit-side resolver says "pallas" on every backend (off-TPU the
    # grower runs that choice through the interpreter)
    hist = int(d("sntc_kernel_dispatch_total", kernel="tree_hist",
                 impl="pallas"))
    check(hist >= 1, "the fit never resolved tree_hist to the Pallas kernel")
    sizes = cfg["rf_batches"]
    frame = generate_frame(sum(sizes), seed=12)
    in_dir = os.path.join(WORK, "in_rf")
    parts = write_batches(frame, sizes, in_dir)
    d = Delta()
    served = serve(model, in_dir, "rf", cfg, device, len(sizes), sum(sizes),
                   extra=("--row-policy", "salvage"))
    dispatched = {"tree_hist": hist}
    for kernel in ("forest_traversal", "pad_assemble"):
        n = int(d("sntc_kernel_dispatch_total", kernel=kernel, impl=impl))
        check(n >= 1, f"phase B: {kernel} never dispatched impl={impl}")
        dispatched[kernel] = n
    contract = CICIDS2017_CONTRACT.with_mode("salvage")
    ref = reference_labels(model, parts, contract)
    sink = read_sink(os.path.join(WORK, "out_rf"))
    out = {
        "train_rows": train["train_rows"], "macroF1": round(f1, 4),
        "fit_wall_clock_s": train["fit_wall_clock_s"],
        **served, "kernel_dispatches": dispatched,
        "label_agreement_vs_cpu": agreement(sink, ref, "phase B"),
    }
    if device["count"] > 1:
        out["serve_mesh"] = serve_mesh_pass(
            model, in_dir, cfg, device, sizes, sink
        )
    return out


def serve_mesh_pass(model, in_dir, cfg, device, sizes, single):
    """The same batches once more with the serve mesh armed over every
    device, held against the single-device pass."""
    from sntc_tpu.parallel.context import (
        get_default_mesh,
        reset_serve_mesh,
        set_serve_mesh,
    )

    d = Delta()
    set_serve_mesh(get_default_mesh())
    try:
        serve(model, in_dir, "rf_mesh", cfg, device, len(sizes),
              sum(sizes), extra=("--row-policy", "salvage"))
    finally:
        reset_serve_mesh()
    # the kernel tier is single-device: a sharded dispatch must take the
    # traversal's XLA twin by the declared path, not by a poisoned compile
    on_twin = int(d("sntc_kernel_fallback_total",
                    kernel="forest_traversal", reason="mesh"))
    check(on_twin >= 1, "serve mesh: the sharded dispatch did not take "
          "the declared reason=mesh twin path")
    sharded = read_sink(os.path.join(WORK, "out_rf_mesh"))
    check(len(sharded) == len(single), "serve mesh: batch count differs")
    for i, (a, b) in enumerate(zip(sharded, single)):
        check(a["predictedLabel"] == b["predictedLabel"],
              f"serve mesh: batch {i} labels differ from the "
              "single-device pass")
    return {
        "devices": device["count"], "labels_equal_single_device": True,
        "forest_traversal": f"xla twin, reason=mesh x{on_twin} (the "
        "kernel tier is single-device)",
    }


def kernel_twins(impl: str, rows: int) -> dict:
    """Every registered kernel against its twin, both on this backend,
    at the registered smoke shapes and tolerance."""
    import numpy as np

    from sntc_tpu.kernels.registry import registered_kernels

    out = {}
    for name, spec in sorted(registered_kernels().items()):
        kernel_fn, twin_fn, args, rtol = spec.smoke_case(rows)
        got = np.asarray(kernel_fn(*args, interpret=(impl == "interpret")))
        want = np.asarray(twin_fn(*args))
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"{name}: kernel output {got.shape} / twin {want.shape}")
        if rtol:
            err = float(np.max(
                np.abs(got - want) / np.maximum(np.abs(want), 1e-6)
            ))
            check(err <= rtol,
                  f"{name}: max rel error {err:.3g} vs its twin "
                  f"(tolerance {rtol:g})")
            out[name] = {"max_rel_err": err, "rtol": rtol}
        else:
            check(np.array_equal(got, want),
                  f"{name}: not bitwise equal to its twin")
            out[name] = {"bitwise": True}
        log(f"kernel {name} vs twin: {out[name]}")
    return out


def multi_device(n_devices: int) -> dict:
    """Shards on every device, through the fit's own placement call
    over the fit's own (process-default) mesh."""
    import jax
    import numpy as np

    from sntc_tpu.parallel.collectives import shard_batch
    from sntc_tpu.parallel.context import get_default_mesh

    mesh = get_default_mesh()
    check(mesh.devices.size == n_devices,
          f"default mesh has {mesh.devices.size} of {n_devices} devices")
    n = 1000 * n_devices + 3  # ragged: exercises pad + mask
    xs, ws = shard_batch(mesh, np.ones((n, 78), np.float32))
    on = {s.device for s in xs.addressable_shards}
    check(on == set(jax.devices()),
          f"shard_batch placed shards on {len(on)} of {n_devices} devices")
    check(xs.shape[0] % n_devices == 0 and float(ws.sum()) == n,
          f"shard_batch padded {n} rows to {xs.shape[0]} with mask sum "
          f"{float(ws.sum())}")
    gauge = metric("sntc_collective_mesh_devices", axis="data")
    check(gauge == n_devices,
          f"sntc_collective_mesh_devices={gauge}, devices={n_devices}")
    moved = metric("sntc_collective_bytes_moved_total")
    check((moved > 0) == (n_devices > 1),
          f"collective wire bytes {moved} on {n_devices} device(s)")
    return {
        "shards_on_devices": len(on),
        "collective_mesh_devices": int(gauge),
        "collective_wire_bytes": int(moved),
    }


def cache_report() -> dict:
    from sntc_tpu.utils.compile_cache import resolve_cache_dir

    path = resolve_cache_dir()
    entries = (
        sum(len(files) for _p, _d, files in os.walk(path))
        if path and os.path.isdir(path) else 0
    )
    return {
        "dir": path, "entries": entries,
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    }


def run(rehearse: bool = False) -> dict:
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} rehearsal={rehearse}")
    if rehearse:
        check(device["platform"] == "cpu",
              "--rehearse-cpu is a CPU rehearsal; JAX found "
              f"{device['platform']} (set JAX_PLATFORMS=cpu yourself)")
        # the interpreter stands in for Mosaic, by name and on request
        os.environ["SNTC_SERVE_KERNELS"] = "interpret"
        os.environ["SNTC_TREE_HIST"] = "pallas"
        cfg, impl = TINY, "interpret"
    else:
        if device["platform"] != "tpu":
            raise SystemExit(
                "chip_smoke: JAX found no TPU (first device is "
                f"{device['platform']!r}); nothing was run"
            )
        for var in ("SNTC_SERVE_KERNELS", "SNTC_TREE_HIST",
                    "SNTC_SERVE_HOST_ROWS", "SNTC_SERVE_MESH_DEVICES"):
            check(var not in os.environ,
                  f"{var} is set: the smoke proves the DEFAULT path")
        cfg, impl = FULL, "pallas"

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    cache_before = cache_report()
    t0 = time.perf_counter()
    a = phase_a(cfg, device)
    t1 = time.perf_counter()
    b = phase_b(cfg, device, impl)
    t2 = time.perf_counter()
    twins = kernel_twins(impl, cfg["kernel_rows"])
    multi = multi_device(device["count"])
    if not rehearse:
        check(a["macroF1"] >= F1_FLOOR["mlp"],
              f"MLP macro-F1 {a['macroF1']} < floor {F1_FLOOR['mlp']:.4f}")
        check(b["macroF1"] >= F1_FLOOR["rf"],
              f"RF macro-F1 {b['macroF1']} < floor {F1_FLOOR['rf']:.4f}")
    cache_after = cache_report()
    shutil.rmtree(WORK, ignore_errors=True)
    return {
        "ok": True,
        "device": device,
        "rehearsal": rehearse,
        "kernel_impl": impl,
        "phase_a_mlp": a,
        "phase_b_rf": b,
        "kernel_twins": twins,
        "multi_device": multi,
        "serving_devices": (
            "device 0 only by default; the serve mesh is opt-in "
            "(set_serve_mesh / SNTC_SERVE_MESH_DEVICES)"
        ),
        "compile_cache": dict(
            cache_after,
            entries_before=cache_before["entries"],
            new_entries=cache_after["entries"] - cache_before["entries"],
        ),
        # set-up observations, not metrics: compilation included
        "wall_s": {
            "phase_a": round(t1 - t0, 1),
            "phase_b": round(t2 - t1, 1),
            "total": round(time.perf_counter() - t_start, 1),
        },
        "claim": None,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv not in ([], ["--rehearse-cpu"]):
        raise SystemExit("usage: chip_smoke.py [--rehearse-cpu]")
    summary = run(rehearse=bool(argv))
    print(json.dumps(summary), flush=True)
    # the result line: these keys and no others, last on stdout
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
