#!/usr/bin/env python
"""Static drift check: mesh axis names in code ⇔ MESH_AXES ⇔ docs.

The r22 mesh substrate (``sntc_tpu/parallel/mesh.py``) declares the
axis vocabulary once, in ``MESH_AXES`` — every ``PartitionSpec``,
``lax.psum`` and ``axis_name=`` literal anywhere in ``sntc_tpu/`` must
resolve to one of those names, every registry key must be backed by a
``*_AXIS = "<name>"`` constant in the substrate module, and the
marker-delimited axis table in ``docs/PERFORMANCE.md`` must list
exactly the registry, both directions.  The check also enforces the
substrate boundary itself: no module outside ``parallel/mesh.py`` may
reach for ``shard_map`` or ``pmap`` directly — sharded dispatch goes
through ``map_at``/``map_reduce_at`` so placement, evidence metrics,
and elastic resize stay in one place.

Wired as a tier-1 test (``tests/test_mesh.py``) so code, registry, and
docs cannot diverge silently.  Exit 0 when consistent; exit 1 with a
per-direction report otherwise.
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SUBSTRATE = os.path.join("parallel", "mesh.py")

# axis-name string literals at sharding call sites
_AXIS_LITERAL_RES = (
    # P("data", ...) / PartitionSpec("data", ...) — any positional
    # string literal names an axis
    re.compile(r"(?:\bP|PartitionSpec)\(([^)]*)\)"),
)
_PSUM_RE = re.compile(r"""lax\.psum\([^,)]+,\s*["']([A-Za-z0-9_]+)["']""")
_KWARG_RE = re.compile(r"""axis_name\s*[:=]\s*["']([A-Za-z0-9_]+)["']""")
_MESH_TUPLE_RE = re.compile(
    r"""Mesh\([^)]*\(\s*((?:["'][A-Za-z0-9_]+["']\s*,?\s*)+)\)"""
)
_CONST_RE = re.compile(r"""^[A-Z0-9_]*_AXIS\s*=\s*["']([A-Za-z0-9_]+)["']""",
                       re.MULTILINE)
_STR_RE = re.compile(r"""["']([A-Za-z0-9_]+)["']""")

# docs table between these markers: | `axis` | carries | collectives |
_AXES_BEGIN = "<!-- mesh-axes:begin -->"
_AXES_END = "<!-- mesh-axes:end -->"
_DOC_ROW_RE = re.compile(r"^\|\s*`([A-Za-z0-9_]+)`\s*\|", re.MULTILINE)

_FORBIDDEN_RE = re.compile(r"\b(?:shard_map|pmap)\b")


def _py_files(root=None):
    root = root or os.path.join(REPO, "sntc_tpu")
    for dirpath, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def code_axis_literals() -> set:
    """Axis names used as string literals at sharding call sites
    anywhere in sntc_tpu/ (including the substrate module's own
    constants)."""
    found = set()
    for path in _py_files():
        with open(path) as f:
            text = f.read()
        for call_re in _AXIS_LITERAL_RES:
            for args in call_re.findall(text):
                found.update(_STR_RE.findall(args))
        found.update(_PSUM_RE.findall(text))
        found.update(_KWARG_RE.findall(text))
        for body in _MESH_TUPLE_RE.findall(text):
            found.update(_STR_RE.findall(body))
    return found


def substrate_constants() -> set:
    """The ``*_AXIS = "<name>"`` constants defined by the substrate."""
    with open(os.path.join(REPO, "sntc_tpu", _SUBSTRATE)) as f:
        return set(_CONST_RE.findall(f.read()))


def declared_axes() -> set:
    sys.path.insert(0, REPO)
    from sntc_tpu.parallel.mesh import MESH_AXES

    return set(MESH_AXES)


def documented_axes(doc_path=None) -> set:
    doc_path = doc_path or os.path.join(REPO, "docs", "PERFORMANCE.md")
    with open(doc_path) as f:
        text = f.read()
    if _AXES_BEGIN not in text or _AXES_END not in text:
        return set()  # reported as a drift problem by check()
    table = text.split(_AXES_BEGIN, 1)[1].split(_AXES_END, 1)[0]
    return {a for a in _DOC_ROW_RE.findall(table) if a != "axis"}


def forbidden_call_sites() -> list:
    """Modules outside the substrate that name shard_map/pmap."""
    offenders = []
    for path in _py_files():
        rel = os.path.relpath(path, os.path.join(REPO, "sntc_tpu"))
        if rel == _SUBSTRATE:
            continue
        with open(path) as f:
            text = f.read()
        if _FORBIDDEN_RE.search(text):
            offenders.append(rel)
    return sorted(offenders)


def check() -> list:
    """Returns a list of human-readable drift complaints (empty = ok)."""
    in_code = code_axis_literals()
    constants = substrate_constants()
    declared = declared_axes()
    documented = documented_axes()
    problems = []
    if not documented:
        problems.append(
            "docs/PERFORMANCE.md is missing the marker-delimited mesh-"
            f"axes table ({_AXES_BEGIN} ... {_AXES_END})"
        )
    for axis in sorted(in_code - declared):
        problems.append(
            f"axis literal {axis!r} is used at a sharding call site but "
            "is not a MESH_AXES key (sntc_tpu/parallel/mesh.py)"
        )
    for axis in sorted(declared - constants):
        problems.append(
            f"MESH_AXES declares {axis!r} but parallel/mesh.py defines "
            f"no *_AXIS = \"{axis}\" constant for call sites to import"
        )
    for axis in sorted(declared - documented) if documented else ():
        problems.append(
            f"MESH_AXES declares {axis!r} but the docs/PERFORMANCE.md "
            "axis table does not document it"
        )
    for axis in sorted(documented - declared):
        problems.append(
            f"docs/PERFORMANCE.md documents axis {axis!r} but MESH_AXES "
            "does not declare it"
        )
    for rel in forbidden_call_sites():
        problems.append(
            f"sntc_tpu/{rel} names shard_map/pmap directly — sharded "
            "dispatch must go through parallel/mesh.py (map_at / "
            "map_reduce_at / sharded_jit)"
        )
    return problems


def main() -> int:
    problems = check()
    if problems:
        print("mesh-axis drift detected:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    print(
        f"ok: {len(declared_axes())} mesh axes consistent across code "
        "literals, MESH_AXES, and docs; substrate boundary clean"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
