"""Check ``BENCHMARK.json`` against the rules a driver refuses it by, before
any run: ``python3 benchmark/check_manifest.py`` exits 0 when it holds.

PR 22 was refused on a ``source`` string before any run; this runs first,
and again before every chip call.
"""

from __future__ import annotations

import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")


def _line(s, what, errs, lo=1, hi=200):
    ok = (isinstance(s, str) and lo <= len(s) <= hi and s.isascii()
          and s.isprintable() and "\t" not in s)
    if not ok:
        errs.append(f"{what}: must be {lo} to {hi} printable ASCII "
                    f"characters on one line, got {s!r}")


def check(manifest: dict, root: str = ROOT) -> list:
    errs = []
    raw = json.dumps(manifest)
    if len(raw.encode()) > 64 * 1024:
        errs.append("manifest over 64 KiB")
    if set(manifest) != TOP:
        errs.append(f"top-level keys {sorted(manifest)} != {sorted(TOP)}")
        return errs
    cmd = manifest["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32):
        errs.append("command: 1 to 32 strings")
    for w in cmd:
        _line(w, "command word", errs)
        if w.startswith("/") or ".." in w.split("/"):
            errs.append(f"command word {w!r} leaves the repo")
    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errs.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            errs.append(f"path {p!r} is not a relative path of the allowed characters")
        elif not os.path.isdir(os.path.join(root, p)):
            errs.append(f"path {p!r} is no directory")
    for w in cmd:
        if os.path.exists(os.path.join(root, w)) and not any(
            w == p or w.startswith(p.rstrip("/") + "/") for p in paths
        ):
            errs.append(f"command names {w!r}, a file outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        errs.append("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    names = set()

    def name(n, what):
        if not (isinstance(n, str) and NAME.match(n)):
            errs.append(f"{what}: bad name {n!r}")
        return n

    configs = {}
    files = set()
    if not 1 <= len(manifest["configs"]) <= 24:
        errs.append("configs: 1 to 24")
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        n = name(c["name"], "config")
        if n in configs:
            errs.append(f"config {n} twice")
        configs[n] = c
        _line(c["source"], f"config {n}: source", errs)
        _line(c["why"], f"config {n}: why", errs)
        if not PATH.match(c["file"]) or not under_paths(c["file"]):
            errs.append(f"config {n}: file {c['file']!r} not under paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            errs.append(f"config {n}: file {c['file']!r} missing")
        else:
            with open(os.path.join(root, c["file"])) as f:
                text = f.read()
            if not text.isascii():
                errs.append(f"config {n}: file is not ASCII")
            body = json.loads(text)
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                errs.append(f"config {n}: reduced differs from its file's")
            if body.get("source") != c["source"]:
                errs.append(f"config {n}: source differs from its file's")
        if c["file"] in files:
            errs.append(f"config {n}: file used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            errs.append(f"config {n}: over 16 reduced keys")
        for k in c["reduced"]:
            name(k, f"config {n}: reduced key")
            if (k.endswith("_dim") or k.endswith("_rank")
                    or k in ("layers", "features", "classes", "maxBins",
                             "numTopFeatures", "maxDepth")):
                errs.append(f"config {n}: reduced names a width/shape {k!r}")

    cells = {}
    pairs = set()
    if not 1 <= len(manifest["workloads"]) <= 24:
        errs.append("workloads: 1 to 24")
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        n = name(w["name"], "workload")
        name(w["traffic"], f"workload {n}: traffic")
        if n in cells:
            errs.append(f"workload {n} twice")
        cells[n] = w
        if w["config"] not in configs:
            errs.append(f"workload {n}: unknown config {w['config']!r}")
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"workload {n}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            errs.append(f"workload {n}: chips must be 1 or 4")
        _line(w["why"], f"workload {n}: why", errs)
        found = [e for e in TRAFFIC_EXT if os.path.isfile(
            os.path.join(root, paths[0], "traffic", w["traffic"] + e))]
        if not found:
            errs.append(f"workload {n}: no traffic file for {w['traffic']!r}")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        errs.append("over a quarter of the cells ask for 4 chips")
    for n in configs:
        if not any(w["config"] == n for w in cells.values()):
            errs.append(f"config {n}: used by no cell")

    e2e = {}
    if not 1 <= len(manifest["end_to_end"]) <= 16:
        errs.append("end_to_end: 1 to 16")
    for m in manifest["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        need = allowed - {"workloads"}
        if not (need <= set(m) <= allowed):
            errs.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
            continue
        n = name(m["name"], "end_to_end")
        if n in names:
            errs.append(f"metric {n} twice")
        names.add(n)
        e2e[n] = m
        if not UNIT.match(m["unit"]):
            errs.append(f"metric {n}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"metric {n}: better")
        if m["source"] not in ("host_clock", "device_trace"):
            errs.append(f"metric {n}: an end-to-end source is host_clock or device_trace")
        if not (isinstance(m["bound"], (int, float)) and 0.01 <= m["bound"] <= 0.1):
            errs.append(f"metric {n}: bound {m['bound']!r} outside 0.01..0.1")
        for c in m.get("workloads", []):
            if c not in cells:
                errs.append(f"metric {n}: unknown cell {c!r}")
    if "setup_s" not in e2e:
        errs.append("end_to_end lacks setup_s")
    elif "workloads" in e2e["setup_s"]:
        errs.append("setup_s must hold in every cell")

    def reports(cell, metric):
        m = e2e[metric]
        return "workloads" not in m or cell in m["workloads"]

    for c in cells:
        if sum(1 for n in e2e if n != "setup_s" and reports(c, n)) < 1:
            errs.append(f"cell {c}: no end-to-end metric besides setup_s")
        mix_file = os.path.join(root, paths[0], "traffic",
                                cells[c]["traffic"] + ".json")
        if os.path.isfile(mix_file):  # what the harness will report there
            with open(mix_file) as f:
                mix = json.load(f)
            m = e2e.get(mix.get("end_to_end"))
            if m is None or not reports(c, m["name"]):
                errs.append(f"cell {c}: its mix reports "
                            f"{mix.get('end_to_end')!r}, which the manifest "
                            "does not hold for it")
            elif m["unit"] != mix.get("unit"):
                errs.append(f"cell {c}: unit of {m['name']} differs from its mix's")

    if not 1 <= len(manifest["per_layer"]) <= 128:
        errs.append("per_layer: 1 to 128")
    covered = set()
    layer_dir = os.path.join(root, paths[0], "layer_metrics")
    for m in manifest["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        need = allowed - {"workloads"}
        if not (need <= set(m) <= allowed):
            errs.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        n = name(m["name"], "per_layer")
        if n in names:
            errs.append(f"metric {n} twice")
        names.add(n)
        if not UNIT.match(m["unit"]):
            errs.append(f"metric {n}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"metric {n}: better")
        if m["source"] not in SOURCES:
            errs.append(f"metric {n}: source {m['source']!r}")
        _line(m["layer"], f"metric {n}: layer", errs)
        if m["moves"] not in e2e:
            errs.append(f"metric {n}: moves unknown metric {m['moves']!r}")
            continue
        for c in m.get("workloads", [c for c in cells if reports(c, m["moves"])]):
            if c not in cells:
                errs.append(f"metric {n}: unknown cell {c!r}")
            elif not reports(c, m["moves"]):
                errs.append(f"metric {n}: cell {c} does not report {m['moves']}")
            covered.add(c)
        if not os.path.isfile(os.path.join(layer_dir, n + ".py")):
            errs.append(f"metric {n}: no reader {n}.py under layer_metrics/")
        if (n.endswith("_roofline") or "mfu" in n) and m["unit"] != "%":
            errs.append(f"metric {n}: a share of a roofline or a peak has unit %")
    for c in cells:
        if c not in covered:
            errs.append(f"cell {c}: no per-layer metric")

    # a full check has to fit: 2 + 14 runs a cell, with the full 24 cells
    full = (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200
    if full > 43200:
        errs.append(f"run_seconds {rs}: a full check of 24 cells takes {full}s > 43200s")
    return errs


def main() -> int:
    path = os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        text = f.read()
    errs = []
    if not text.isascii():
        errs.append("BENCHMARK.json holds non-ASCII characters")
    errs += check(json.loads(text))
    for e in errs:
        print("manifest:", e, file=sys.stderr)
    print("manifest ok" if not errs else f"manifest: {len(errs)} fault(s)")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
