"""Read the numbers ``correct`` compares, over many seeds in one process.

    python3 benchmark/readings.py --config <configuration> --traffic <mix> \
        --seeds 1,2,3 [--control bf16 --control-seeds 3] \
        [--faults half_batch,altered_answer] [--witness highest] \
        [--rehearse-cpu] [--out chiprun_out/readings.jsonl]

For each seed: generate the frame, run one pass of the program, compare its
product with the reference; for the first ``--control-seeds`` seeds also the
control's product (the reference in the program's place, one step of
precision down), each named fault planted under one more pass, and with
``--witness <precision>`` one more pass of the program under that
``jax_default_matmul_precision`` (a second witness where the program departs
from the precision its configuration states).  One JSON line per reading,
with ``correct`` as ``run.judge`` decides it under the configuration's
limits.  The limits are set from these readings (PERF.md gives them); the
benchmark's own runs never run this.  The configuration and the mix are
named by their files, so a cell the manifest does not hold can be read too.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--witness", default=None)
    ap.add_argument("--witness-seeds", type=int, default=2)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--rows", type=int, default=None,
                    help="another size than the cell's (the CPU witness)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    import faults
    import gen
    import run

    cell, cfg, traffic = run.resolve_pair(args.config, args.traffic)
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        run.log("no accelerator")
        return 2
    from sntc_tpu.parallel.mesh import default_mesh
    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    rows = args.rows or int(cfg["rehearse_rows"] if args.rehearse_cpu
                            else cfg[traffic.get("rows_key", "rows")])
    adapter = run.load_module("estimators", cfg["estimator"])
    mesh = default_mesh(int(cell["chips"]))
    kind = traffic["kind"]
    limits = cfg["limits"][kind]
    out_f = open(args.out, "a") if args.out else None

    def emit(seed, what, numbers, **more):
        rec = {"cell": cell["name"], "rows": rows, "seed": seed, "what": what,
               "correct": run.judge(numbers, limits)[0], "numbers": numbers}
        rec.update(more)
        line = json.dumps(rec)
        print(line, flush=True)
        if out_f:
            out_f.write(line + "\n")
            out_f.flush()

    def program_numbers(columns, seed):
        one_pass = run.KINDS[kind](adapter, cfg, columns, mesh, seed)
        t = time.perf_counter()
        res = one_pass()
        dt = time.perf_counter() - t
        product = adapter.extract_product(kind, res)
        del res, one_pass
        run.release_program_state(jax)
        t = time.perf_counter()
        numbers = adapter.compare(kind, product, cfg, columns, seed)
        return numbers, dt, time.perf_counter() - t

    fault_names = [f for f in args.faults.split(",") if f]
    for i, s in enumerate(int(v) for v in args.seeds.split(",")):
        seed = run.model_seed(s)
        columns = gen.generate_columns(rows, s)
        numbers, dt, ct = program_numbers(columns, seed)
        emit(s, "program", numbers, pass_s=dt, compare_s=ct)
        if i < args.control_seeds:
            if args.control:
                t = time.perf_counter()
                product = adapter.control_product(kind, cfg, columns, seed,
                                                  args.control)
                numbers = adapter.compare(kind, product, cfg, columns, seed)
                emit(s, "control:" + args.control, numbers,
                     control_s=time.perf_counter() - t)
                del product
            for name in fault_names:
                with faults.FAULTS[name](run, kind, cfg["estimator"]):
                    numbers, dt, _ = program_numbers(columns, seed)
                emit(s, "fault:" + name, numbers)
            if args.witness and i < args.witness_seeds:
                with jax.default_matmul_precision(args.witness):
                    numbers, dt, _ = program_numbers(columns, seed)
                emit(s, "program@" + args.witness, numbers, pass_s=dt)
        del columns
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
