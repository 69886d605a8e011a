"""Adapter of the ``mlp_gd`` estimator kind: StandardScaler + multilayer
perceptron under Spark's ``gd`` solver (full-batch steps of ``stepSize``,
stopped after ``maxIter`` or by the solution-change test at ``tol``).  The
indexer, the assembler and the scaler are the ``mlp`` adapter's, taken by
import; the plain reference is ``benchmark/reference_mlp_gd.py``."""

from __future__ import annotations

import numpy as np

import gen
import reference_mlp_gd as ref_gd
from estimators import mlp as base

#: the whole fit is one device program
PROGRAMS = {"fit": r"^jit__mlp_optimize$"}

#: widest gap, over the largest margin, that a product "at float32" may
#: leave against float64 (one bfloat16 pass leaves about 1e-3)
STATED_PRODUCT_GAP = 1e-4
_STATED = []

initial_weights = base.initial_weights
pass_info = base.pass_info
extract_product = base.extract_product


# ---- the program, as app.py builds it --------------------------------------


def require_stated_products(cfg) -> None:
    """Refuse, before anything is timed, a program whose float32 head does
    not multiply in float32: the head's margins on 256 seeded rows through
    the program's own predict path, against float64.  A program that cannot
    compute the configuration as stated cannot run the cell (the harness's
    warm-up pass fails, and the run exits non-zero)."""
    if _STATED:
        return
    from sntc_tpu.core.frame import Frame
    from sntc_tpu.models.mlp import MultilayerPerceptronClassificationModel

    layers = list(cfg["layers"])
    X = np.random.default_rng(0).standard_normal((256, layers[0]))
    X = X.astype(np.float32)
    theta = gen.glorot_weights(layers, 0, gain=4.0)
    head = MultilayerPerceptronClassificationModel(weights=theta,
                                                   layers=layers)
    got = np.asarray(head.transform(Frame({"features": X}))["rawPrediction"],
                     np.float64)
    want = X.astype(np.float64)
    for i, (W, b) in enumerate(base.ref._unpack(theta.astype(np.float64),
                                                layers)):
        want = want @ W + b
        if i < len(layers) - 2:
            want = 1.0 / (1.0 + np.exp(-want))
    gap = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    if gap > STATED_PRODUCT_GAP:
        raise RuntimeError(
            f"the head's {cfg['dtype']} products read {gap:.3g} off float64 "
            f"(at most {STATED_PRODUCT_GAP:g}): not the stated precision"
        )
    _STATED.append(gap)


def build_pipeline(cfg, mesh, seed):
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.models import MultilayerPerceptronClassifier

    require_stated_products(cfg)
    est = MultilayerPerceptronClassifier(
        mesh=mesh, initialWeights=initial_weights(cfg, seed),
        layers=list(cfg["layers"]), solver=cfg["solver"],
        maxIter=cfg["maxIter"], stepSize=cfg["stepSize"], tol=cfg["tol"],
        computeDtype=cfg["dtype"], seed=seed, featuresCol="features",
    )
    return Pipeline(stages=base._stages(cfg, mesh, gen.load_schema()) + [est])


# ---- work, from shapes -----------------------------------------------------


def work_fit(cfg, rows, info):
    """The fit as it ran: one ``value_and_grad`` (forward + backward = 3
    forward products) a step the fit reports, and one forward pass for the
    loss at the final weights; each reads the scaled matrix once."""
    steps = int(info.get("iterations", cfg["maxIter"]))
    products = base._matmul_flops(cfg["layers"], rows)
    return {"flops": products * (3.0 * steps + 1.0),
            "bytes": 4.0 * rows * cfg["layers"][0] * (steps + 1)}


# ---- the comparison --------------------------------------------------------


def _reference_fit(cfg, columns, seed, matmul):
    vocab, y, X, mean, std = base._prepared("fit", columns)
    losses, theta, steps, _, problem = ref_gd.fit(
        X, y, mean, std, cfg, initial_weights(cfg, seed), matmul
    )
    return vocab, mean, std, losses, theta, steps, problem


def control_product(kind, cfg, columns, seed, matmul):
    """The reference put in the program's place, computed with ``matmul``
    arithmetic (``"bf16"``: what the comparison must refuse)."""
    vocab, mean, std, losses, theta, steps, _ = _reference_fit(
        cfg, columns, seed, matmul
    )
    return {"labels": vocab, "mean": mean, "std": std, "weights": theta,
            "history": losses, "iterations": steps}


def compare(kind, product, cfg, columns, seed):
    """The numbers ``correct`` is decided by (each has a limit in the
    configuration's ``limits``), and further numbers read without one."""
    vocab, mean, std, losses, theta_r, steps, problem = _reference_fit(
        cfg, columns, seed, "f32"
    )
    labels = product["labels"]
    safe = np.where(std > 0, std, 1.0)
    got, n_p = product["history"], product["iterations"]
    both = min(len(got), len(losses))
    gaps = [abs(got[i] - losses[i]) / abs(losses[i]) for i in range(both)]
    final_ref, _ = problem.value_and_grad(product["weights"])
    leaf = base.leaf_change_gaps(product["weights"], theta_r,
                                 initial_weights(cfg, seed), cfg["layers"])
    out = {
        "label_mismatch": float(sum(a != b for a, b in zip(labels, vocab))
                                + abs(len(labels) - len(vocab))),
        "scaler_gap": float(max(np.max(np.abs(product["mean"] - mean) / safe),
                                np.max(np.abs(product["std"] - std) / safe))),
        "loss0_gap": gaps[0],
        "loss_followed_gap": max(gaps),
        "final_loss_gap": abs(final_ref - got[n_p]) / abs(final_ref),
        "param_change_gap": max(leaf.values()),
        "iterations_gap": float(abs(n_p - steps)),
    }
    out["loss_followed_argmax"] = float(np.argmax(gaps))
    out.update({f"loss_gap_at.{i}": gaps[i] for i in (1, 10, 50) if i < both})
    out.update({"leaf_change." + k: v for k, v in leaf.items()})
    return out
