"""Self-check of the yardstick (``python3 benchmark/selfcheck.py``, CPU):

1. the manifest check passes;
2. the trace reducer, run on the small recorded trace under
   ``benchmark/testdata/``, gives the busy share and the device times per
   operation and per program that were written down by hand from that trace
   (``benchmark/testdata/expected.json``), and the per-layer readers of the
   traced pair give the shares worked out by hand from those;
3. ``run.py --rehearse-cpu`` runs one cell of each traffic mix and its last
   line has the contract's keys.

It lives with the benchmark, not in ``tests/``: the tier-1 count is untouched.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

#: the result line's keys, in order: the contract's, the optional breakdown
#: of a traced run, and last the numbers compared beside their limits
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
TRACED_KEYS = KEYS[:5] + ["breakdown", "checks"]


def check_reducer() -> list:
    import reduce_trace

    errs = []
    with open(os.path.join(HERE, "testdata", "expected.json")) as f:
        want = json.load(f)
    got = reduce_trace.reduce_file(
        os.path.join(HERE, "testdata", want["file"]), 1
    )

    def close(a, b, what):
        if abs(a - b) > want["tolerance"] * max(abs(b), 1e-12):
            errs.append(f"reducer: {what} {a!r} != {b!r} (by hand)")

    close(got["busy_s"], want["busy_s"], "busy_s")
    close(got["window_s"], want["window_s"], "window_s")
    for by in ("device_seconds_by_name", "device_seconds_by_module"):
        for name, sec in want[by].items():
            close(got[by].get(name, 0.0), sec, name)

    # the per-layer readers of the traced pair, on the same trace
    import run
    import work

    cell, cfg, mix = run.resolve_pair(*want["pair"].split(":"))
    ctx = {"trace": got, "cell": cell, "cfg": cfg, "rows": cfg[mix["rows_key"]],
           "passes": [None] * want["passes"], "pass_info": {},
           "adapter": run.load_module("estimators", cfg["estimator"]),
           "peaks": work.load_peaks(want["device_kind"])}
    for name, value in want["layer_metrics"].items():
        close(run.load_module("layer_metrics", name).read(ctx), value, name)
    return errs


def check_rehearsal(workload: str, trace: int) -> list:
    env = dict(os.environ, JAX_PLATFORMS="cpu", SNTC_NO_COMPILE_CACHE="1")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", workload,
         "--seed", "2147483999", "--seconds", "1", "--trace", str(trace),
         "--rehearse-cpu"],
        capture_output=True, text=True, env=env, cwd=ROOT,
    )
    if p.returncode != 0:
        return [f"{workload}: exit {p.returncode}: {p.stderr[-400:]}"]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    errs = []
    if list(last) != (TRACED_KEYS if trace else KEYS):
        errs.append(f"{workload}: last line's keys are {list(last)}")
    if last["device"]["platform"] != "cpu":
        errs.append(f"{workload}: a rehearsal must say platform cpu")
    if last["correct"] is not True:
        errs.append(f"{workload}: rehearsal not correct: {last.get('checks')}")
    if trace and not {"busy_s", "window_s"} <= set(last["device"]):
        errs.append(f"{workload}: traced line lacks busy_s / window_s")
    return errs


def main() -> int:
    import check_manifest

    errs = []
    if check_manifest.main() != 0:
        errs.append("manifest check failed")
    errs += check_reducer()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    seen = set()
    for c in cells:  # one cell of each traffic mix
        if c["traffic"] not in seen:
            seen.add(c["traffic"])
            errs += check_rehearsal(c["name"], 0)
            errs += check_rehearsal(c["name"], 1)
    for e in errs:
        print("selfcheck:", e, file=sys.stderr)
    print("selfcheck ok" if not errs else f"selfcheck: {len(errs)} fault(s)")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
