"""Faults planted under the harness, each of which ``correct`` has to refuse.

Used by ``benchmark/tests/test_faults.py`` (CPU, test size, through
``run.main``) and by ``benchmark/readings.py`` (the chip, the cell's own
size).  Each is a context manager that breaks the timed path underneath the
harness and restores it on exit:

* ``state_unchanged``: the optimizer's step returns its state unchanged
  (L-BFGS hands back the initial weights and a flat history);
* ``half_batch``: half of the rows are left out (the fit's means are taken
  over the rest; evaluate answers for half of the rows);
* ``altered_answer``: what the pass produces is altered where it is produced
  (a fitted weight, a split threshold, one prediction in a hundred);
* ``wrong_confusion``: the evaluator's confusion matrix counts one row in a
  thousand of the largest class in a wrong cell (the predictions are sound,
  the metric is not).
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def state_unchanged(run_mod, kind, estimator):
    import jax.numpy as jnp

    from sntc_tpu.models import mlp
    from sntc_tpu.ops.lbfgs import LbfgsResult

    def frozen(value_and_grad, x0, *, max_iter=100, **kw):
        f0, _ = value_and_grad(x0)
        res = LbfgsResult(
            x=x0, loss=f0, n_iters=jnp.asarray(max_iter, jnp.int32),
            history=jnp.full((max_iter + 1,), f0, x0.dtype),
            converged=jnp.asarray(False),
        )
        return (res, None) if kw.get("return_state") else res

    old = mlp.minimize_lbfgs
    mlp.minimize_lbfgs = frozen
    mlp._mlp_optimize.clear_cache()
    try:
        yield
    finally:
        mlp.minimize_lbfgs = old
        mlp._mlp_optimize.clear_cache()


@contextlib.contextmanager
def half_batch(run_mod, kind, estimator):
    old = run_mod.fresh_frame

    def half(columns):
        n = len(next(iter(columns.values()))) // 2
        return old({k: v[:n] for k, v in columns.items()})

    run_mod.fresh_frame = half
    try:
        yield
    finally:
        run_mod.fresh_frame = old


@contextlib.contextmanager
def altered_answer(run_mod, kind, estimator):
    if kind == "evaluate":
        from sntc_tpu.models.base import ClassificationModel

        old = ClassificationModel._prob_to_prediction

        def altered(self, prob):
            pred = old(self, prob)
            pred[::100] = (pred[::100] + 1) % prob.shape[1]
            return pred

        ClassificationModel._prob_to_prediction = altered
        try:
            yield
        finally:
            ClassificationModel._prob_to_prediction = old
        return

    old_kind = run_mod.KINDS[kind]

    def make(adapter, cfg, columns, mesh, seed):
        inner = old_kind(adapter, cfg, columns, mesh, seed)

        def one_pass():
            res = inner()
            head = res["model"].getStages()[-1]
            if estimator == "mlp":
                w = np.array(head.weights)
                w[: w.size // 50] += 0.5
                head.weights = w
            else:
                f = head.forest
                thr = np.array(f.threshold)
                thr[:, 0] = np.where(f.feature[:, 0] >= 0,
                                     np.nextafter(thr[:, 0], np.inf), thr[:, 0])
                leaf = np.array(f.leaf_stats)
                leaf[:, -1, 0] += 1.0
                head.forest = f._replace(threshold=thr, leaf_stats=leaf)
            return res

        return one_pass

    run_mod.KINDS[kind] = make
    try:
        yield
    finally:
        run_mod.KINDS[kind] = old_kind


@contextlib.contextmanager
def wrong_confusion(run_mod, kind, estimator):
    from sntc_tpu.evaluation.multiclass import MulticlassMetrics

    old = MulticlassMetrics.__init__

    def wrong(self, *a, **kw):
        old(self, *a, **kw)
        moved = max(1.0, self.confusion[0, 0] // 1000)
        self.confusion[0, 0] -= moved
        self.confusion[0, 1] += moved

    MulticlassMetrics.__init__ = wrong
    try:
        yield
    finally:
        MulticlassMetrics.__init__ = old


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "altered_answer": altered_answer, "wrong_confusion": wrong_confusion}

#: the faults a cell of (traffic kind, estimator) can have
APPLICABLE = {
    ("fit", "mlp"): ("state_unchanged", "half_batch", "altered_answer"),
    ("fit", "rf"): ("half_batch", "altered_answer"),
    ("evaluate", "mlp"): ("half_batch", "altered_answer", "wrong_confusion"),
}
