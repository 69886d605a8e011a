"""Faults planted under the harness for the boosted one-vs-rest kind, each
of which ``correct`` has to refuse (``faults.py``'s ``half_batch`` applies to
this kind as it is).  Each alters what the timed fit produces where it is
produced, under ``run.KINDS["fit"]``, and restores it on exit:

* ``altered_threshold``: every tree's root threshold moved by one float32;
* ``altered_leaf``: one more row counted in every tree's last leaf slot;
* ``shifted_split``: every tree's root splits at the next bin edge of its
  feature (a valid edge, a valid tree: what an off-by-one in the split search
  would give; the reading that bounds ``split_gain_gap`` from above);
* ``altered_step``: the fit boosts with a step of 0.11 and reports the
  configured weights, so its trees are not the ones its own earlier trees and
  weights lead to (the chain of margins is broken, nothing else).

    python3 benchmark/faults_gbt.py --config cicflow_gbt --traffic fit_full ...

is ``readings.py`` with these faults known to it by name.
"""

from __future__ import annotations

import contextlib
import sys

import numpy as np

import faults


@contextlib.contextmanager
def _altering(run_mod, kind, alter, prepare=None):
    old_kind = run_mod.KINDS[kind]

    def make(adapter, cfg, columns, mesh, seed):
        inner = old_kind(adapter, cfg, columns, mesh, seed)
        more = prepare(cfg, columns, seed) if prepare else ()

        def one_pass():
            res = inner()
            for model in res["model"].getStages()[-1].models:
                alter(model, cfg, *more)
            return res

        return one_pass

    run_mod.KINDS[kind] = make
    try:
        yield
    finally:
        run_mod.KINDS[kind] = old_kind


def altered_threshold(run_mod, kind, estimator):
    def alter(model, cfg):
        f = model.forest
        thr = np.array(f.threshold)
        thr[:, 0] = np.where(f.feature[:, 0] >= 0,
                             np.nextafter(thr[:, 0], np.inf), thr[:, 0])
        model.forest = f._replace(threshold=thr)

    return _altering(run_mod, kind, alter)


def altered_leaf(run_mod, kind, estimator):
    def alter(model, cfg):
        leaf = np.array(model.forest.leaf_stats)
        leaf[:, -1, 0] += 1.0
        model.forest = model.forest._replace(leaf_stats=leaf)

    return _altering(run_mod, kind, alter)


def shifted_split(run_mod, kind, estimator):
    def edges_of(cfg, columns, seed):
        import gen
        import reference as ref

        X = ref.assemble(columns, gen.load_schema()["features"])
        return (ref.quantile_edges(X, cfg["maxBins"], seed),)

    def alter(model, cfg, edges):
        f = model.forest
        thr = np.array(f.threshold)
        for t in np.flatnonzero(f.feature[:, 0] >= 0):
            row = edges[f.feature[t, 0]]
            other = row[row > thr[t, 0]]
            thr[t, 0] = other[0] if other.size else row[row < thr[t, 0]][-1]
        model.forest = f._replace(threshold=thr)

    return _altering(run_mod, kind, alter, edges_of)


@contextlib.contextmanager
def altered_step(run_mod, kind, estimator):
    from sntc_tpu.models import GBTClassifier

    def alter(model, cfg):
        w = np.full(len(model.treeWeights), cfg["stepSize"], np.float32)
        w[0] = 1.0
        model.treeWeights = w

    old = GBTClassifier.getStepSize
    GBTClassifier.getStepSize = lambda self: 1.1 * old(self)
    try:
        with _altering(run_mod, kind, alter):
            yield
    finally:
        GBTClassifier.getStepSize = old


FAULTS = {"half_batch": faults.half_batch,
          "altered_threshold": altered_threshold,
          "altered_leaf": altered_leaf, "shifted_split": shifted_split,
          "altered_step": altered_step}


if __name__ == "__main__":
    import readings

    faults.FAULTS.update(FAULTS)
    sys.exit(readings.main())
