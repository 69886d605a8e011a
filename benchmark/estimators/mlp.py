"""Adapter of the ``mlp`` estimator kind: StandardScaler + multilayer
perceptron under L-BFGS.  How the program is built for the configuration,
the work one pass needs from shapes, and the comparison of what a timed pass
produced with the plain reference (``benchmark/reference.py``)."""

from __future__ import annotations

import numpy as np

import gen
import reference as ref

FOLLOWED_ITERS = 3  # L-BFGS iterations whose losses are compared one by one

#: the layer's own device programs: the whole L-BFGS fit is one program, the
#: head's forward pass another
PROGRAMS = {"fit": r"^jit__mlp_optimize$",
            "evaluate": r"^jit__mlp_predict_fused$"}


# ---- the program, as app.py builds it --------------------------------------


def _stages(cfg, mesh, schema):
    from sntc_tpu.feature import StandardScaler, StringIndexer, VectorAssembler

    return [
        StringIndexer(inputCol=schema["label_column"], outputCol="label",
                      handleInvalid="skip"),
        VectorAssembler(inputCols=schema["features"],
                        outputCol="rawFeatures", handleInvalid="skip"),
        StandardScaler(mesh=mesh, inputCol="rawFeatures",
                       outputCol="features", withMean=True),
    ]


def initial_weights(cfg, seed):
    return gen.glorot_weights(cfg["layers"], seed)


def build_pipeline(cfg, mesh, seed):
    from sntc_tpu.core.base import Pipeline
    from sntc_tpu.models import MultilayerPerceptronClassifier

    est = MultilayerPerceptronClassifier(
        mesh=mesh, initialWeights=initial_weights(cfg, seed),
        layers=list(cfg["layers"]), maxIter=cfg["maxIter"], tol=cfg["tol"],
        solver=cfg["solver"], seed=seed, featuresCol="features",
    )
    return Pipeline(stages=_stages(cfg, mesh, gen.load_schema()) + [est])


def serving_weights(cfg, seed):
    """Weights of the evaluated model: made by the benchmark from the seed
    (Glorot times ``evaluate_weight_gain``), never by a fit of the program."""
    return gen.glorot_weights(
        cfg["layers"], seed + 1, gain=cfg.get("evaluate_weight_gain", 1.0)
    )


SCALER_SAMPLE_ROWS = 1 << 16


def served_model_inputs(columns):
    """``(labels, mean, std)`` of the evaluated model's indexer and scaler:
    inputs the benchmark makes, as it makes the weights (the schema's labels
    by descending prior, the moments of the frame's first 65,536 rows), so
    that set-up runs nothing of the reference and the reference takes nothing
    the program made.  Program and reference are both handed these."""
    schema = gen.load_schema()
    priors = schema["class_priors"]
    vocab = sorted(schema["labels"], key=lambda l: (-priors[l], l))
    head = np.stack([columns[n][:SCALER_SAMPLE_ROWS].astype(np.float64)
                     for n in schema["features"]])
    return vocab, head.mean(axis=1), head.std(axis=1, ddof=1)


def build_model(cfg, columns, mesh, seed):
    from sntc_tpu.core.base import PipelineModel
    from sntc_tpu.feature import VectorAssembler
    from sntc_tpu.feature.standard_scaler import StandardScalerModel
    from sntc_tpu.feature.string_indexer import StringIndexerModel
    from sntc_tpu.models.mlp import MultilayerPerceptronClassificationModel

    schema = gen.load_schema()
    vocab, mean, std = served_model_inputs(columns)
    indexer = StringIndexerModel(labels=vocab)
    indexer.setParams(inputCol=schema["label_column"], outputCol="label",
                      handleInvalid="skip")
    scaler = StandardScalerModel(mean=mean.astype(np.float32),
                                 std=std.astype(np.float32))
    scaler.setParams(inputCol="rawFeatures", outputCol="features",
                     withMean=True)
    head = MultilayerPerceptronClassificationModel(
        weights=serving_weights(cfg, seed), layers=list(cfg["layers"])
    )
    head.setParams(featuresCol="features")
    return PipelineModel(stages=[
        indexer,
        VectorAssembler(inputCols=schema["features"],
                        outputCol="rawFeatures", handleInvalid="skip"),
        scaler, head,
    ])


# ---- work, from shapes -----------------------------------------------------


def _matmul_flops(layers, n):
    return sum(2.0 * n * a * b for a, b in zip(layers[:-1], layers[1:]))


def work_fit(cfg, rows, info):
    """The estimator's fit: one ``value_and_grad`` (forward + backward = 3
    forward products) per L-BFGS iteration the fit reports, plus the initial
    one; each reads the scaled matrix once."""
    layers = cfg["layers"]
    evals = int(info.get("iterations", cfg["maxIter"])) + 1
    return {"flops": 3.0 * _matmul_flops(layers, rows) * evals,
            "bytes": 4.0 * rows * layers[0] * evals}


def work_evaluate(cfg, rows, info):
    """The head's forward pass: read the scaled matrix once, write raw and
    probability."""
    layers = cfg["layers"]
    return {"flops": _matmul_flops(layers, rows),
            "bytes": 4.0 * rows * (layers[0] + 2 * layers[-1])}


def pass_info(kind, last):
    if kind == "fit" and last is not None:
        head = last["model"].getStages()[-1]
        return {"iterations": int(head.summary.totalIterations)}
    return {}


# ---- what a pass produced, and its comparison ------------------------------


def extract_product(kind, last):
    if kind == "fit":
        stages = last["model"].getStages()
        head, scaler, indexer = stages[-1], stages[2], stages[0]
        return {
            "labels": list(indexer.labels),
            "mean": np.asarray(scaler.mean, np.float64),
            "std": np.asarray(scaler.std, np.float64),
            "weights": np.array(head.weights, np.float32),
            "history": [float(v) for v in head.summary.objectiveHistory],
            "iterations": int(head.summary.totalIterations),
        }
    out = last["out"]
    return {
        "rows_in": int(last["rows_in"]), "rows_out": int(last["rows_out"]),
        "probability": np.asarray(out["probability"]),
        "prediction": np.asarray(out["prediction"]),
        "value": float(last["value"]),
    }


def _prepared(kind, columns):
    """``(vocabulary, y, X, mean, std)``: a fit is judged against the
    reference's own indexing and moments; an evaluate pass against the inputs
    the evaluated model was built from."""
    schema = gen.load_schema()
    X = ref.assemble(columns, schema["features"])
    ref_vocab, ref_y = ref.index_labels(columns[schema["label_column"]])
    if kind == "fit":
        mean, std = ref.scaler_moments(columns, schema["features"])
        return ref_vocab, ref_y, X, mean, std
    vocab, mean, std = served_model_inputs(columns)
    to_served = np.array([vocab.index(l) for l in ref_vocab], np.int32)
    return vocab, to_served[ref_y], X, mean, std


def _reference_fit(cfg, columns, seed, matmul):
    """The reference's whole fit: ``(vocabulary, mean, std, problem, losses
    after 0..n iterations, fitted weights)``."""
    vocab, y, X, mean, std = _prepared("fit", columns)
    mu, f = ref.scaler_affine(mean, std)
    problem = ref.MlpProblem(X, y, mu, f, cfg["layers"], matmul)
    hist, x = ref.lbfgs_history(
        problem, initial_weights(cfg, seed), cfg["maxIter"], tol=cfg["tol"]
    )
    return vocab, mean, std, problem, hist, x


def control_product(kind, cfg, columns, seed, matmul):
    """The reference put in the program's place, computed with ``matmul``
    arithmetic: what the comparison must refuse when ``matmul`` is a step of
    precision below the configuration's."""
    if kind == "fit":
        vocab, mean, std, _, hist, x = _reference_fit(cfg, columns, seed, matmul)
        return {"labels": vocab, "mean": mean, "std": std,
                "weights": x.astype(np.float32), "history": hist,
                "iterations": len(hist) - 1}
    vocab, y, X, mean, std = _prepared(kind, columns)
    mu, f = ref.scaler_affine(mean, std)
    theta = serving_weights(cfg, seed)
    prob, pred = ref.mlp_predict(X, mu, f, theta, cfg["layers"], matmul)
    k = cfg["layers"][-1]
    conf = np.bincount(y * k + pred, minlength=k * k).reshape(k, k)
    return {"rows_in": len(y), "rows_out": len(y), "probability": prob,
            "prediction": pred.astype(np.float64),
            "value": ref.macro_f1(conf)}


def leaf_slices(layers):
    """``[(name, slice)]`` of the flat weight vector's leaves."""
    out, off = [], 0
    for i, (d_in, d_out) in enumerate(zip(layers[:-1], layers[1:]), 1):
        out.append((f"W{i}", slice(off, off + d_in * d_out)))
        off += d_in * d_out
        out.append((f"b{i}", slice(off, off + d_out)))
        off += d_out
    return out


def leaf_change_gaps(theta_p, theta_r, theta0, layers):
    """Per leaf, the gap between the norms of the program's and the
    reference's change of it over the whole fit, against the reference's norm
    of that leaf's change or of the median leaf's, whichever is larger."""
    d_p = np.asarray(theta_p, np.float64) - theta0
    d_r = np.asarray(theta_r, np.float64) - theta0
    leaves = leaf_slices(layers)
    n_r = {k: float(np.linalg.norm(d_r[sl])) for k, sl in leaves}
    med = float(np.median(list(n_r.values())))
    return {k: abs(float(np.linalg.norm(d_p[sl])) - n_r[k]) / max(n_r[k], med, 1e-30)
            for k, sl in leaves}


def compare(kind, product, cfg, columns, seed):
    """The numbers ``correct`` is decided by (each has a limit in the
    configuration's ``limits``), and further numbers that are read and
    reported without one."""
    if kind == "fit":
        vocab, mean, std, problem, hist, theta_r = _reference_fit(
            cfg, columns, seed, "f32"
        )
        labels = product["labels"]
        n_bad = sum(a != b for a, b in zip(labels, vocab)) + abs(
            len(labels) - len(vocab)
        )
        safe = np.where(std > 0, std, 1.0)
        scaler_gap = float(max(
            np.max(np.abs(product["mean"] - mean) / safe),
            np.max(np.abs(product["std"] - std) / safe),
        ))
        got = product["history"]
        k = min(FOLLOWED_ITERS, len(got) - 1, len(hist) - 1)
        gaps = [abs(got[i] - hist[i]) / abs(hist[i]) for i in range(k + 1)]
        final_ref, _ = problem.value_and_grad(product["weights"])
        final_got = got[min(product["iterations"], len(got) - 1)]
        leaf = leaf_change_gaps(product["weights"], theta_r,
                                initial_weights(cfg, seed), cfg["layers"])
        out = {
            "label_mismatch": float(n_bad),
            "scaler_gap": scaler_gap,
            "loss0_gap": gaps[0],
            "loss1_gap": gaps[1] if k >= 1 else 1.0,
            "loss_followed_gap": max(gaps[1:]) if k >= 1 else 1.0,
            "final_loss_gap": abs(final_ref - final_got) / abs(final_ref),
            "final_vs_reference_gap": abs(final_ref - hist[-1]) / abs(hist[-1]),
            "param_change_gap": max(leaf.values()),
            "iterations_gap": float(abs(product["iterations"] - (len(hist) - 1))),
        }
        out.update({"leaf_change." + k_: v for k_, v in leaf.items()})
        return out
    vocab, y, X, mean, std = _prepared(kind, columns)
    mu, f = ref.scaler_affine(mean, std)
    theta = serving_weights(cfg, seed)
    r = ref.mlp_evaluate(
        X, y, mu, f, theta, cfg["layers"],
        product["probability"], product["prediction"],
    )
    kk = cfg["layers"][-1]
    pred_p = np.clip(np.asarray(product["prediction"]).astype(np.int64), 0, kk - 1)
    n = min(len(y), len(pred_p))
    conf_p = np.bincount(y[:n] * kk + pred_p[:n], minlength=kk * kk).reshape(kk, kk)
    return {
        "rows_gap": float(abs(product["rows_in"] - product["rows_out"])
                          + abs(len(y) - product["rows_out"])),
        "prob_gap_mean": r["prob_gap_mean"],
        "pred_regret_mean": r["pred_regret_mean"],
        "metric_gap": abs(product["value"] - ref.macro_f1(conf_p)),
        "prob_gap_max": r["prob_gap_max"],
        "pred_mismatch_share": r["pred_mismatch_share"],
    }
