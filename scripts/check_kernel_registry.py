#!/usr/bin/env python
"""Static drift check: Pallas kernels across code ⇔ registry ⇔ docs ⇔ tests.

The serving-kernel forge (r21) declares every hand-written Pallas
kernel in ``sntc_tpu.kernels.registry`` — name, owning module,
fit-guard, twin tolerance, fallback.  Five things must stay in
lockstep or the kernel tier silently rots:

1. **code → registry**: every module under ``sntc_tpu/`` containing a
   ``pl.pallas_call`` site must be the declared ``module`` of some
   registered kernel (an unregistered kernel has no guard, no poison
   ladder, no docs row, no drift protection);
2. **registry → code**: every registered kernel's declared module must
   exist and actually contain a ``pallas_call`` — a registry row whose
   kernel was deleted is dead capability documentation;
3. **registry ⇔ docs**: ``docs/PERFORMANCE.md`` carries a
   marker-delimited kernel-forge table; every registered kernel must
   have a row whose guard/tolerance/fallback match the registry, and
   every row must name a registered kernel;
4. **registry → tests**: every registered kernel name must appear in
   ``tests/test_kernels.py`` — the interpret-mode twin-equality matrix
   must exercise every kernel on every tier-1 run;
5. **registry → TPU lowering**: every registered kernel must carry a
   ``smoke_case`` and ``tests/test_kernels.py`` must hold the test
   that walks them through ``lowering_platforms=("tpu",)`` — the
   interpreter applies none of Mosaic's block-shape rules, so a kernel
   without a lowering case can pass tier-1 and still be refused (and
   silently poisoned onto its twin) on the chip.

Wired as a tier-1 test (``tests/test_kernels.py``), the same
discipline as ``check_metric_names.py`` / ``check_fault_sites.py``.

Exit 0 when consistent; exit 1 with a per-kernel report otherwise.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOC = "docs/PERFORMANCE.md"
TABLE_BEGIN = "<!-- kernel-forge:begin -->"
TABLE_END = "<!-- kernel-forge:end -->"
TESTS = "tests/test_kernels.py"
LOWERING_TEST = "def test_every_registered_kernel_lowers_for_tpu("

_CALL_RE = re.compile(r"\bpl\.pallas_call\b|\bpallas_call\(")


def _read(rel: str) -> str:
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


def _pallas_modules() -> set:
    """Repo-relative paths of every sntc_tpu module with a pallas_call
    site (the interpret shim in pallas libs themselves excluded by
    construction — we only walk sntc_tpu/)."""
    mods = set()
    for dirpath, _dirs, fnames in os.walk(os.path.join(REPO, "sntc_tpu")):
        if "__pycache__" in dirpath:
            continue
        for f in fnames:
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            with open(path) as fh:
                if _CALL_RE.search(fh.read()):
                    mods.add(os.path.relpath(path, REPO))
    return mods


def _doc_rows() -> dict:
    """name -> (guard, tolerance, fallback) from the marker table."""
    text = _read(DOC)
    if TABLE_BEGIN not in text or TABLE_END not in text:
        return {}
    table = text.split(TABLE_BEGIN, 1)[1].split(TABLE_END, 1)[0]
    rows = {}
    for line in table.splitlines():
        m = re.match(
            r"\s*\|\s*`([a-z0-9_]+)`\s*\|\s*`([a-z0-9_]+)`\s*\|"
            r"\s*([^|]+?)\s*\|\s*([^|]+?)\s*\|",
            line,
        )
        if m:
            rows[m.group(1)] = (m.group(2), m.group(3), m.group(4))
    return rows


def check() -> list:
    problems = []
    sys.path.insert(0, REPO)
    from sntc_tpu.kernels.registry import registered_kernels

    kernels = registered_kernels()
    by_module = {spec.module: name for name, spec in kernels.items()}

    code_mods = _pallas_modules()
    for mod in sorted(code_mods - set(by_module)):
        problems.append(
            f"{mod} contains a pallas_call but no registered KernelSpec "
            "declares it — register it in sntc_tpu/kernels/registry.py"
        )
    for mod in sorted(set(by_module) - code_mods):
        problems.append(
            f"registered kernel {by_module[mod]!r} declares module "
            f"{mod!r} but that module has no pallas_call (or does not "
            "exist) — dead registry row"
        )

    doc = _doc_rows()
    if not doc:
        problems.append(
            f"{DOC} is missing the marker-delimited kernel-forge table "
            f"({TABLE_BEGIN} ... {TABLE_END})"
        )
    for name, spec in sorted(kernels.items()):
        if doc and name not in doc:
            problems.append(
                f"registered kernel {name!r} missing from the {DOC} "
                "kernel-forge table"
            )
        elif doc:
            guard, tol, fb = doc[name]
            if guard != spec.guard_name:
                problems.append(
                    f"{name!r}: docs say guard {guard!r}, registry "
                    f"says {spec.guard_name!r}"
                )
            if tol != spec.tolerance:
                problems.append(
                    f"{name!r}: docs say tolerance {tol!r}, registry "
                    f"says {spec.tolerance!r}"
                )
            if fb != spec.fallback:
                problems.append(
                    f"{name!r}: docs say fallback {fb!r}, registry "
                    f"says {spec.fallback!r}"
                )
    for name in sorted(set(doc) - set(kernels)):
        problems.append(
            f"{DOC} documents kernel {name!r} but the registry does "
            "not declare it"
        )

    tests = _read(TESTS) if os.path.exists(os.path.join(REPO, TESTS)) else ""
    if not tests:
        problems.append(f"{TESTS} is missing — no interpret-mode matrix")
    for name in sorted(kernels):
        if tests and f'"{name}"' not in tests:
            problems.append(
                f"registered kernel {name!r} never named in {TESTS} — "
                "every kernel needs an interpret-mode tier-1 test"
            )
        if kernels[name].smoke_case is None:
            problems.append(
                f"registered kernel {name!r} has no smoke_case — "
                "nothing cross-lowers it for TPU in tier-1"
            )
    if tests and LOWERING_TEST not in tests:
        problems.append(
            f"{TESTS} lost the TPU cross-lowering walk "
            f"({LOWERING_TEST.strip('(')})"
        )
    return problems


def main() -> int:
    problems = check()
    if problems:
        print("kernel-registry drift detected:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    from sntc_tpu.kernels.registry import registered_kernels

    print(
        f"ok: {len(registered_kernels())} kernels consistent across "
        "code, registry, docs/PERFORMANCE.md, and tests"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
