"""The benchmark's harness: one process, one cell, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell is resolved in ``BENCHMARK.json`` to its
configuration (``benchmark/configs/<config>.json``) and traffic mix
(``benchmark/traffic/<mix>.json``); the configuration's ``estimator`` names
its adapter (``benchmark/estimators/<estimator>.py``: how the program is
built for it, the work one pass needs, and the comparison with the plain
reference); in a traced run every per-layer metric of the manifest that lists
the cell is read by ``benchmark/layer_metrics/<metric>.py``.  A later PR adds
a cell, a configuration, a mix of an existing kind or a per-layer metric by
adding files and manifest entries.

Without a TPU the harness exits non-zero before any work; ``--rehearse-cpu``
is the explicit tiny CPU rehearsal (its result line says ``"platform":
"cpu"`` and is no measurement).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by its name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(manifest: dict, workload: str):
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg = load_json(ROOT, configs[cell["config"]]["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, cfg, traffic


def resolve_pair(config: str, traffic: str, chips: int = 1):
    """The same three for a configuration and a mix by their file names,
    whether or not the manifest has the cell (``readings.py`` and the tests
    read cells that were taken out of it, too)."""
    cfg = load_json(HERE, "configs", config + ".json")
    mix = load_json(HERE, "traffic", traffic + ".json")
    cell = {"name": f"{config}.{mix['kind']}", "config": config,
            "traffic": traffic, "chips": chips}
    return cell, cfg, mix


def metrics_of(manifest: dict, group: str, workload: str):
    """Manifest metrics of ``group`` that hold in ``workload``: those that
    list it, and those with no list (``setup_s``), which hold everywhere."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", (workload,))]


def judge(numbers: dict, limits: dict, sound: bool = True):
    """``(correct, checks)``: every number that has a limit has to be there
    and at or under it; ``sound`` is false for a run that produced nothing to
    compare or in which a pass failed.  The one decision of ``correct``:
    ``main``, ``readings.py`` and the tests under ``tests/`` all take it
    here."""
    checks = {name: {"value": numbers.get(name), "limit": limit}
              for name, limit in limits.items()}
    good = all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
    return bool(sound and good), checks


def model_seed(seed: int) -> int:
    """The seed the estimators and the reference draw from: any ``--seed``
    folded under 2**31 (a 32-bit PRNG key holds no more)."""
    return int(seed) % 2147483629


# --------------------------------------------------------------------------
# traffic kinds: what one pass is
# --------------------------------------------------------------------------


def fresh_frame(columns: dict):
    """A ``Frame`` over new array objects that share the generated memory:
    every pass meets a frame the program's identity-keyed memos (assembled
    matrix, device copies) have not seen, as the first fit or evaluate of a
    freshly loaded frame does in ``app.py``."""
    from sntc_tpu.core.frame import Frame

    return Frame({k: v.view() for k, v in columns.items()})


def make_fit_pass(adapter, cfg, columns, mesh, seed):
    def one_pass():
        frame = fresh_frame(columns)
        pipe = adapter.build_pipeline(cfg, mesh, seed)
        model = pipe.fit(frame)
        return {"model": model, "rows_in": frame.num_rows,
                "rows_out": frame.num_rows}

    return one_pass


def make_evaluate_pass(adapter, cfg, columns, mesh, seed):
    from sntc_tpu.evaluation import MulticlassClassificationEvaluator

    model = adapter.build_model(cfg, columns, mesh, seed)
    metric = cfg.get("evaluate_metric", "macroF1")

    def one_pass():
        frame = fresh_frame(columns)
        with span("transform"):
            out = model.transform(frame)
        with span("evaluate"):
            value = MulticlassClassificationEvaluator(
                metricName=metric, mesh=mesh
            ).evaluate(out)
        return {"out": out, "value": float(value),
                "rows_in": frame.num_rows, "rows_out": out.num_rows}

    return one_pass


KINDS = {"fit": make_fit_pass, "evaluate": make_evaluate_pass}

# how a mix's end-to-end metric is taken over the whole window
RATES = {
    "seconds_per_pass": lambda rows, n, window_s: window_s / n,
    "rows_per_second": lambda rows, n, window_s: rows * n / window_s,
}

def span(name: str):
    """A host span of the benchmark's own, written into the profiler's trace
    as ``bench:<name>`` when one is being taken (``reduce_trace`` labels the
    device's idle gaps with them)."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


def timed_pass(one_pass, name):
    t0 = time.perf_counter()
    try:
        with span(name):
            res = one_pass()
        ok = res["rows_out"] >= res["rows_in"]
    except Exception as e:  # a pass that raises is a failed operation
        log(f"pass raised: {type(e).__name__}: {e}")
        res, ok = None, False
    return res, ok, time.perf_counter() - t0


# --------------------------------------------------------------------------


def device_block(jax, n_chips: int) -> dict:
    devs = jax.devices()[:n_chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--pair", default=None,
                    help="<configuration>:<mix>, by their file names: a cell "
                         "the manifest does not hold (tests, readings)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced .xplane.pb here (inspection)")
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    if args.pair and not args.workload:
        cell, cfg, traffic = resolve_pair(*args.pair.split(":"))
    elif args.workload and not args.pair:
        cell, cfg, traffic = resolve_cell(manifest, args.workload)
    else:
        ap.error("give --workload <cell> (or --pair <configuration>:<mix>)")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)
    import jax

    n_chips = int(cell["chips"])
    platform = jax.devices()[0].platform
    if args.rehearse_cpu:
        rows = int(cfg["rehearse_rows"])
    else:
        if platform != "tpu" or len(jax.devices()) < n_chips:
            log(f"no accelerator: platform {platform!r}, "
                f"{len(jax.devices())} device(s), cell needs {n_chips} TPU chip(s)")
            return 2
        rows = int(cfg[traffic.get("rows_key", "rows")])

    from sntc_tpu.parallel.mesh import default_mesh
    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    import gen
    import work

    cache_dir = enable_persistent_cache()
    peaks = work.load_peaks(jax.devices()[0].device_kind)
    adapter = load_module("estimators", cfg["estimator"])
    mesh = default_mesh(n_chips)
    seed = model_seed(args.seed)
    log(f"cell {cell['name']}: rows {rows}, seed {args.seed} -> {seed}, "
        f"cache {cache_dir}, reached device at {time.perf_counter() - _T0:.1f}s")

    t = time.perf_counter()
    columns = gen.generate_columns(rows, args.seed)
    log(f"frame generated in {time.perf_counter() - t:.1f}s")
    kind = traffic["kind"]
    one_pass = KINDS[kind](adapter, cfg, columns, mesh, seed)
    span_name = traffic.get("span", kind)

    res, ok, dt = timed_pass(one_pass, span_name)
    log(f"warm-up pass: {dt:.2f}s ok={ok}")
    if not ok:
        log("warm-up pass failed")
        return 3
    # the warm-up's result is held until the window's first pass returns (as
    # an analyst's ``m = fit(); m = fit()`` does), so that the first pass
    # meets the same memory state as the later ones, which run beside ``last``
    held = res
    del res
    gc.collect()
    setup_s = time.perf_counter() - _T0

    # ---- the measured window -------------------------------------------
    trace_dir = None
    n_traced = int(traffic.get("trace_passes", 1)) if args.trace else 0
    if n_traced:
        trace_dir = os.path.join(ROOT, ".bench_trace", f"{cell['name']}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    passes, last, attempted, failed = [], None, 0, 0
    t_win = time.perf_counter()
    with span("window"):
        while True:
            res, ok, dt = timed_pass(one_pass, span_name)
            attempted += 1
            failed += 0 if ok else 1
            if ok:
                passes.append(dt)
                last = res
            held = None
            log(f"pass {attempted}: {dt:.3f}s ok={ok}")
            if n_traced and attempted >= n_traced:
                break
            if not n_traced and time.perf_counter() - t_win >= args.seconds:
                break
    window_s = time.perf_counter() - t_win
    if n_traced:
        jax.profiler.stop_trace()

    device = device_block(jax, n_chips)
    ctx = {
        "cell": cell, "cfg": cfg, "rows": rows, "passes": passes,
        "peaks": peaks, "adapter": adapter,
        "pass_info": adapter.pass_info(kind, last),
    }

    metrics, breakdown = {}, None
    if not args.trace:
        values = {"setup_s": setup_s}
        if passes:
            values[traffic["end_to_end"]] = RATES[traffic["rate"]](
                rows, len(passes), window_s
            )
        units = {m["name"]: m["unit"]
                 for m in metrics_of(manifest, "end_to_end", cell["name"])}
        if args.pair:  # a cell outside the manifest: the mix states the unit
            units.setdefault(traffic["end_to_end"], traffic["unit"])
        for name, value in values.items():
            if name in units:
                metrics[name] = {"value": value, "unit": units[name]}
    else:
        import reduce_trace

        trace = reduce_trace.reduce_dir(trace_dir, n_chips)
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".", exist_ok=True)
            shutil.copy(trace["path"], args.keep_trace)
        ctx["trace"] = trace
        ctx["counters"] = counters_snapshot()
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = {"device_ops": trace["device_ops"][:10],
                     "idle_gaps": trace["idle_gaps"][:10]}
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            value = load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        shutil.rmtree(os.path.join(ROOT, ".bench_trace"), ignore_errors=True)

    # ---- correct: the timed path's product against the plain reference ---
    t = time.perf_counter()
    product = adapter.extract_product(kind, last) if last is not None else None
    last = res = None
    release_program_state(jax)
    numbers = adapter.compare(kind, product, cfg, columns, seed) if product else {}
    limits = cfg["limits"][kind]
    correct, checks = judge(numbers, limits, bool(product) and failed == 0)
    log(f"reference comparison took {time.perf_counter() - t:.1f}s")

    # the contract's keys and no other: the numbers compared, each beside its
    # limit, come last; numbers read but not compared go to standard error
    result = {"correct": correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, value in numbers.items():
        if name not in limits:
            log(f"read, not compared: {name} {value}")
    for name, c in checks.items():
        log(f"check {name}: value {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0


def counters_snapshot() -> dict:
    """The program's host counters the per-layer readers may consult."""
    from sntc_tpu.obs import registry

    out = {}
    for line in registry().to_prometheus().splitlines():
        if line.startswith("sntc_kernel"):
            key, _, val = line.rpartition(" ")
            out[key] = float(val)
    return out


def release_program_state(jax) -> None:
    """Drop what the program keeps on the device (its identity-keyed device
    copies die with their frames) before the reference takes the chip."""
    from sntc_tpu.feature import vector_assembler
    from sntc_tpu.parallel import collectives

    collectives._DEVICE_CACHE.clear()
    vector_assembler._ASSEMBLE_CACHE.clear()
    gc.collect()


if __name__ == "__main__":
    sys.exit(main())
