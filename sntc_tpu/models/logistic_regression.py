"""LogisticRegression — binomial/multinomial elastic-net logit on TPU.

Behavioral spec: SURVEY.md §2.3/§3.1 (upstream
``ml/classification/LogisticRegression.scala`` + ``LogisticAggregator`` [U]):

  * ``family`` auto/binomial/multinomial; elastic-net via ``regParam`` ×
    ``elasticNetParam`` (L1 -> OWLQN, else LBFGS), intercepts unpenalized;
  * internal feature standardization during optimization (coefficients
    returned in the original space); ``standardization=False`` keeps the
    scaled optimization but re-weights the penalty so the objective matches
    penalizing original-space coefficients, as Spark does;
  * intercept initialized to label-prior log odds;
  * ``objectiveHistory`` preserved on the training summary (SURVEY.md §5.5).

TPU design: one summarizer ``tree_aggregate`` pass (moments + class counts),
then the whole LBFGS/OWLQN loop runs as ONE jitted XLA program over
mesh-sharded data (sntc_tpu.ops.lbfgs) — Spark's per-iteration
broadcast/treeAggregate/driver-update cycle with zero host round trips.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.models.base import (
    CheckpointParams,
    ClassificationModel,
    ClassifierEstimator,
)
from sntc_tpu.mlio.optimizer_checkpoint import run_segmented
from sntc_tpu.ops.lbfgs import minimize_lbfgs
from sntc_tpu.parallel.collectives import shard_batch, shard_weights
from sntc_tpu.parallel.context import get_default_mesh


from functools import partial


def _lr_summarize_impl(xs, ys, ws, k):
    return (
        jnp.einsum("n,nd->d", ws, xs),
        jnp.einsum("n,nd->d", ws, xs * xs),
        jnp.sum(ws),
        jax.ops.segment_sum(ws, ys, num_segments=k),
    )


@partial(jax.jit, static_argnames=("k",))
def _lr_summarize(xs, ys, ws, k):
    """Moments + class counts in one pass; with mesh-sharded inputs XLA
    inserts the ICI all-reduce (the summarizer treeAggregate of §3.1)."""
    return _lr_summarize_impl(xs, ys, ws, k)


def _lr_value_and_grad(
    theta, xs, ys, ws, inv_std, l2, pen_l2, w_sum,
    *, binomial, fit_intercept, k, n_coef,
):
    """Smooth objective + gradient shared by the single and grid fits."""
    d = xs.shape[1]

    def loss_fn(theta):
        coef = theta[:n_coef]
        W = coef.reshape(d, 1) if binomial else coef.reshape(d, k)
        b = (
            theta[n_coef:]
            if fit_intercept
            else jnp.zeros((1 if binomial else k,), theta.dtype)
        )
        Wd = W * inv_std[:, None]  # fold scaling into the matmul
        margins = xs @ Wd + b[None, :]
        if binomial:
            z = margins[:, 0]
            yf = ys.astype(z.dtype)
            data = jnp.sum(ws * (jnp.logaddexp(0.0, z) - yf * z))
        else:
            logp = jax.nn.log_softmax(margins, axis=1)
            picked = jnp.take_along_axis(
                logp, ys[:, None].astype(jnp.int32), axis=1
            )[:, 0]
            data = -jnp.sum(ws * picked)
        data = data / w_sum
        penalty = 0.5 * l2 * jnp.sum(pen_l2 * theta[:n_coef] ** 2)
        return data + penalty

    return jax.value_and_grad(loss_fn)(theta)


@partial(
    jax.jit,
    static_argnames=(
        "binomial", "fit_intercept", "k", "max_iter", "tol", "use_l1",
        "resume", "use_bounds",
    ),
)
def _lr_optimize(
    xs, ys, ws, inv_std, l2, pen_l2, l1_vec, theta0, init_state, iter_limit,
    lb, ub,
    *, binomial, fit_intercept, k, max_iter, tol, use_l1, resume=False,
    use_bounds=False,
):
    """The whole LBFGS/OWLQN fit as one cached XLA program.

    Module-level jit with data as (sharded) ARGUMENTS: repeated fits on the
    same shapes reuse the compiled executable instead of re-tracing a
    closure (compile once, fit many — the Spark-analog of reusing the same
    job DAG every iteration).
    """
    d = xs.shape[1]
    n_coef = d if binomial else d * k
    w_sum = jnp.sum(ws)

    def value_and_grad(theta):
        return _lr_value_and_grad(
            theta, xs, ys, ws, inv_std, l2, pen_l2, w_sum,
            binomial=binomial, fit_intercept=fit_intercept, k=k,
            n_coef=n_coef,
        )

    return minimize_lbfgs(
        value_and_grad,
        theta0,
        max_iter=max_iter,
        tol=tol,
        l1=l1_vec if use_l1 else None,
        init_state=init_state if resume else None,
        return_state=True,
        iter_limit=iter_limit,
        bounds=(lb, ub) if use_bounds else None,
    )


@partial(
    jax.jit,
    static_argnames=(
        "binomial", "fit_intercept", "k", "max_iter", "tol", "use_l1",
    ),
)
def _lr_optimize_grid(
    xs, ys, ws, inv_std, l2_b, pen_l2_b, l1_vec_b, theta0_b,
    *, binomial, fit_intercept, k, max_iter, tol, use_l1,
):
    """G grid points fit in ONE XLA program via ``vmap`` over the
    hyperparameter axis (SURVEY.md §2.5 "task parallelism": Spark's
    CrossValidator/OneVsRest thread pools overlap independent fits; on TPU
    the same overlap is a batched axis — every LBFGS iteration's G matmuls
    fuse into one MXU-batched contraction over the SHARED sharded data).

    Lanes run until all converge (vmapped ``while_loop``); each lane's own
    ``n_iters``/``converged`` are per-lane exact.
    """
    d = xs.shape[1]
    n_coef = d if binomial else d * k
    w_sum = jnp.sum(ws)

    def one(l2, pen_l2, l1_vec, theta0):
        def value_and_grad(theta):
            return _lr_value_and_grad(
                theta, xs, ys, ws, inv_std, l2, pen_l2, w_sum,
                binomial=binomial, fit_intercept=fit_intercept, k=k,
                n_coef=n_coef,
            )

        return minimize_lbfgs(
            value_and_grad, theta0, max_iter=max_iter, tol=tol,
            l1=l1_vec if use_l1 else None,
        )

    return jax.vmap(one)(l2_b, pen_l2_b, l1_vec_b, theta0_b)


@partial(
    jax.jit,
    static_argnames=(
        "binomial", "fit_intercept", "k", "max_iter", "tol", "use_l1",
    ),
)
def _lr_optimize_lanes(
    xs, ys, ws_folds, fold_idx_b, inv_std_b, l2_b, pen_l2_b, l1_vec_b,
    theta0_b,
    *, binomial, fit_intercept, k, max_iter, tol, use_l1,
):
    """Fold×grid lanes in ONE program: like :func:`_lr_optimize_grid` but
    each lane reads its OWN row-weight vector — a CV fold is just a 0/1
    weight mask over the shared sharded data — and carries its own
    standardization, so the whole k-fold × grid sweep becomes one vmapped
    LBFGS.  Lanes index ``ws_folds[F, N]`` by ``fold_idx`` in-program:
    the masks upload once (sharded), not once per lane."""
    d = xs.shape[1]
    n_coef = d if binomial else d * k

    def one(fold_idx, inv_std, l2, pen_l2, l1_vec, theta0):
        ws = ws_folds[fold_idx]
        w_sum = jnp.sum(ws)

        def value_and_grad(theta):
            return _lr_value_and_grad(
                theta, xs, ys, ws, inv_std, l2, pen_l2, w_sum,
                binomial=binomial, fit_intercept=fit_intercept, k=k,
                n_coef=n_coef,
            )

        return minimize_lbfgs(
            value_and_grad, theta0, max_iter=max_iter, tol=tol,
            l1=l1_vec if use_l1 else None,
        )

    return jax.vmap(one)(
        fold_idx_b, inv_std_b, l2_b, pen_l2_b, l1_vec_b, theta0_b
    )


@partial(
    jax.jit,
    static_argnames=("fit_intercept", "max_iter", "tol", "use_l1"),
)
def _lr_optimize_ovr(
    xs, ys, ws, inv_std, l2, pen_l2, l1_vec, class_ids, theta0_b,
    *, fit_intercept, max_iter, tol, use_l1,
):
    """K one-vs-rest BINARY fits in ONE program: lane c relabels the
    shared sharded labels in-program (``ys == c``) — Spark's OvR
    ``parallelism`` thread pool becomes a vmapped class axis over data
    that uploads once (SURVEY.md §2.5 task parallelism)."""
    d = xs.shape[1]
    w_sum = jnp.sum(ws)

    def one(cid, theta0):
        ys_c = (ys == cid).astype(jnp.int32)

        def value_and_grad(theta):
            return _lr_value_and_grad(
                theta, xs, ys_c, ws, inv_std, l2, pen_l2, w_sum,
                binomial=True, fit_intercept=fit_intercept, k=2, n_coef=d,
            )

        return minimize_lbfgs(
            value_and_grad, theta0, max_iter=max_iter, tol=tol,
            l1=l1_vec if use_l1 else None,
        )

    return jax.vmap(one)(class_ids, theta0_b)


@partial(jax.jit, static_argnames=("k",))
def _lr_summarize_folds(xs, ys, ws_b, k):
    """Per-fold summarizer: vmapped moments + class counts over per-lane
    weight vectors (each CV fold standardizes on ITS train split, exactly
    as a sequential sub-fit would)."""
    return jax.vmap(lambda ws: _lr_summarize_impl(xs, ys, ws, k))(ws_b)


from sntc_tpu.models.summary import (
    BinaryClassificationSummary,
    BinaryClassificationTrainingSummary,
    ClassificationSummary,
    ClassificationTrainingSummary,
    TrainingSummary,
)

# Spark-parity names (upstream LogisticRegression.scala summary classes):
# multinomial fits carry per-class metrics + objectiveHistory; binomial
# fits add the threshold curves (roc/pr/fMeasureByThreshold)
LogisticRegressionTrainingSummary = ClassificationTrainingSummary
BinaryLogisticRegressionTrainingSummary = BinaryClassificationTrainingSummary
LogisticRegressionSummary = ClassificationSummary
BinaryLogisticRegressionSummary = BinaryClassificationSummary


class _LrParams:
    maxIter = Param("max LBFGS/OWLQN iterations", default=100, validator=validators.gteq(0))
    regParam = Param("regularization strength", default=0.0, validator=validators.gteq(0))
    elasticNetParam = Param(
        "elastic-net mixing: 0=L2, 1=L1", default=0.0, validator=validators.in_range(0, 1)
    )
    tol = Param("relative convergence tolerance", default=1e-6, validator=validators.gt(0))
    fitIntercept = Param("fit intercept term", default=True, validator=validators.is_bool())
    standardization = Param(
        "standardize features during optimization", default=True,
        validator=validators.is_bool(),
    )
    family = Param(
        "binomial | multinomial | auto", default="auto",
        validator=validators.one_of("auto", "binomial", "multinomial"),
    )
    lowerBoundsOnCoefficients = Param(
        "coefficient lower bounds, shape [1, D] (binomial) or [K, D]; "
        "requires elasticNetParam contributions of L1 to be zero",
        default=None,
    )
    upperBoundsOnCoefficients = Param(
        "coefficient upper bounds, same shape as the lower bounds",
        default=None,
    )
    lowerBoundsOnIntercepts = Param(
        "intercept lower bounds, length 1 (binomial) or K", default=None
    )
    upperBoundsOnIntercepts = Param(
        "intercept upper bounds, length 1 (binomial) or K", default=None
    )


_BOUND_PARAMS = (
    "lowerBoundsOnCoefficients", "upperBoundsOnCoefficients",
    "lowerBoundsOnIntercepts", "upperBoundsOnIntercepts",
)


def _bounds_digest(lb: np.ndarray, ub: np.ndarray) -> str:
    import hashlib

    h = hashlib.md5()
    h.update(np.ascontiguousarray(lb, np.float32).tobytes())
    h.update(np.ascontiguousarray(ub, np.float32).tobytes())
    return h.hexdigest()


class LogisticRegression(_LrParams, CheckpointParams, ClassifierEstimator):
    def __init__(self, mesh=None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh

    def _build_bounds(self, d, k, binomial, n_coef, n_int, std):
        """Flatten user bounds into theta-ordered (lb, ub) vectors.

        Bounds are declared on ORIGINAL-space coefficients (Spark
        ``lowerBoundsOnCoefficients`` etc.); the optimizer works in the
        scaled space ``coef_scaled = coef_orig * std``, so coefficient
        bounds scale by ``std`` per feature.  Intercepts are never scaled.
        """
        lbc = self.getLowerBoundsOnCoefficients()
        ubc = self.getUpperBoundsOnCoefficients()
        lbi = self.getLowerBoundsOnIntercepts()
        ubi = self.getUpperBoundsOnIntercepts()
        if lbc is None and ubc is None and lbi is None and ubi is None:
            z = np.zeros(n_coef + n_int, np.float32)
            return z, z, False
        if n_int == 0 and (lbi is not None or ubi is not None):
            raise ValueError(
                "intercept bounds require fitIntercept=True (the bound "
                "would otherwise silently constrain nothing)"
            )
        rows = 1 if binomial else k
        lb = np.full(n_coef + n_int, -np.inf, np.float64)
        ub = np.full(n_coef + n_int, np.inf, np.float64)

        def coef_part(mat, name):
            m = np.asarray(mat, np.float64)
            if m.shape != (rows, d):
                raise ValueError(
                    f"{name} must have shape ({rows}, {d}), got {m.shape}"
                )
            # theta coefficient layout is [D, rows] flattened; ±inf entries
            # stay infinite (inf * 0 would be NaN on std=0 features).  A
            # finite bound on a zero-variance feature collapses to 0 — its
            # original-space coefficient is identically 0 anyway (Spark
            # reports 0 for constant features too).
            with np.errstate(invalid="ignore"):  # inf * 0 in the dead branch
                scaled = np.where(np.isinf(m), m, m * std[None, :])
            return scaled.T.reshape(-1)

        if lbc is not None:
            lb[:n_coef] = coef_part(lbc, "lowerBoundsOnCoefficients")
        if ubc is not None:
            ub[:n_coef] = coef_part(ubc, "upperBoundsOnCoefficients")
        if n_int:
            def int_part(vec, name):
                v = np.asarray(vec, np.float64).reshape(-1)
                if v.shape != (rows,):
                    raise ValueError(
                        f"{name} must have length {rows}, got {v.shape}"
                    )
                return v

            if lbi is not None:
                lb[n_coef:] = int_part(lbi, "lowerBoundsOnIntercepts")
            if ubi is not None:
                ub[n_coef:] = int_part(ubi, "upperBoundsOnIntercepts")
        if not (lb <= ub).all():
            raise ValueError("lower bounds must not exceed upper bounds")
        return lb, ub, True

    def _resolve_family(self, y, n):
        """(binomial, num_classes) with Spark's auto/validation rules."""
        num_classes = int(y.max()) + 1 if n else 2
        family = self.getFamily()
        if family == "auto":
            family = "binomial" if num_classes <= 2 else "multinomial"
        if family == "binomial" and num_classes > 2:
            raise ValueError(
                f"binomial family with {num_classes} classes; use multinomial"
            )
        return family == "binomial", max(num_classes, 2)

    @staticmethod
    def _moments_to_stats(s1, s2, cnt, cc):
        """(std, inv_std, class_counts) from one summarizer pass."""
        w_sum = max(float(cnt), 1e-12)
        mean = np.asarray(s1, np.float64) / w_sum
        var = np.maximum(np.asarray(s2, np.float64) / w_sum - mean**2, 0.0)
        std = np.sqrt(var)
        inv_std = np.divide(1.0, std, out=np.zeros_like(std), where=std > 0)
        return std, inv_std, np.maximum(np.asarray(cc, np.float64), 1e-12)

    def _prep_data(self, frame: Frame, mesh) -> dict:
        """Shared per-dataset prep: shard, summarize (one treeAggregate).

        Split out so the grid-batched fit (``_fit_grid``) pays for the data
        upload and summarizer pass ONCE across all grid points."""
        X, y, w = self._extract(frame)
        n, d = X.shape
        binomial, k = self._resolve_family(y, n)

        xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
        ws = shard_weights(mesh, w, xs.shape[0])

        # ---- summarizer pass: moments + class counts (one treeAggregate) ----
        std, inv_std, class_counts = self._moments_to_stats(
            *_lr_summarize(xs, ys, ws, k)
        )
        return {
            "xs": xs, "ys": ys, "ws": ws, "n": n, "d": d, "k": k,
            "binomial": binomial, "std": std,
            "inv_std": inv_std, "class_counts": class_counts,
            # kept for the training summary (lazy predictions frame)
            "frame": frame, "mesh": mesh,
        }

    def _penalty_vectors(self, d: int, k: int, binomial: bool, inv_std):
        """Elastic-net penalty weights in the SCALED optimization space —
        the ONE encoding of Spark's standardization=True/False penalty
        semantics, shared by single fits, grid lanes, and OvR lanes."""
        reg = self.getRegParam()
        alpha = self.getElasticNetParam()
        l2 = reg * (1.0 - alpha)
        l1 = reg * alpha
        fit_intercept = self.getFitIntercept()
        standardize = self.getStandardization()
        n_coef = d if binomial else d * k
        n_int = (1 if binomial else k) if fit_intercept else 0
        pen_scale = np.ones(d) if standardize else inv_std
        pen_l2 = np.tile(pen_scale**2, 1 if binomial else k).astype(np.float32)
        l1_vec = np.concatenate(
            [l1 * np.tile(pen_scale, 1 if binomial else k), np.zeros(n_int)]
        ).astype(np.float32)
        return {
            "l2": np.float32(l2), "pen_l2": pen_l2, "l1_vec": l1_vec,
            "use_l1": l1 > 0, "n_coef": n_coef, "n_int": n_int,
        }

    def _grid_vectors(self, prep: dict) -> dict:
        """Per-grid-point optimizer inputs from shared prep (called on a
        ``copy(params)`` of the estimator for each grid point)."""
        d, k, binomial = prep["d"], prep["k"], prep["binomial"]
        vec = self._penalty_vectors(d, k, binomial, prep["inv_std"])
        n_coef, n_int = vec["n_coef"], vec["n_int"]
        class_counts = prep["class_counts"]
        theta0 = np.zeros(n_coef + n_int, dtype=np.float32)
        if self.getFitIntercept():
            # prior-log-odds intercept init (Spark parity)
            priors = class_counts / class_counts.sum()
            if binomial:
                theta0[n_coef] = np.log(priors[1] / priors[0]) if k == 2 else 0.0
            else:
                theta0[n_coef:] = np.log(priors)
        vec["theta0"] = theta0
        return vec

    def _theta_to_model(
        self, theta, prep, n_iters, history, use_bounds=False
    ) -> "LogisticRegressionModel":
        """Unscale + canonicalize a solution vector into a fitted model."""
        d, k, binomial = prep["d"], prep["k"], prep["binomial"]
        inv_std = prep["inv_std"]
        fit_intercept = self.getFitIntercept()
        reg = self.getRegParam()
        n_coef = d if binomial else d * k
        theta = np.asarray(theta, np.float64)
        W_scaled, b = (
            (theta[:n_coef].reshape(d, 1), theta[n_coef:])
            if binomial
            else (theta[:n_coef].reshape(d, k), theta[n_coef:])
        )
        coef_orig = W_scaled * inv_std[:, None]  # back to original space
        if binomial:
            coefficients = np.zeros((2, d))
            coefficients[1] = coef_orig[:, 0]
            intercepts = np.zeros(2)
            if fit_intercept:
                intercepts[1] = b[0]
            coef_matrix = coefficients
        else:
            coef_matrix = coef_orig.T  # [K, D]
            intercepts = np.asarray(
                b if fit_intercept else np.zeros(k), np.float64
            )
            # Spark canonicalization: the softmax is invariant to uniform
            # shifts; unpenalized intercepts are mean-centered, and with no
            # regularization the coefficients are too — SKIPPED under bound
            # constraints (centering could move them outside the box), as
            # Spark does
            if fit_intercept and not use_bounds:
                intercepts = intercepts - intercepts.mean()
            if reg == 0.0 and not use_bounds:
                coef_matrix = coef_matrix - coef_matrix.mean(
                    axis=0, keepdims=True
                )

        n_iters = int(n_iters)
        model = LogisticRegressionModel(
            coefficient_matrix=coef_matrix.astype(np.float32),
            intercepts=np.asarray(intercepts, np.float32),
            is_binomial=binomial,
        )
        model.setParams(
            **{
                name: val
                for name, val in self.paramValues().items()
                if model.hasParam(name)
            }
        )
        hist = np.asarray(history)[: n_iters + 1]
        if prep.get("frame") is None:
            # fold/grid lane sub-models (preps built without the source
            # frame) keep the lightweight record — per-class metrics on
            # throwaway sub-models would only pin extra frame references
            model.summary = TrainingSummary(hist, n_iters)
            return model
        summary_cls = (
            BinaryClassificationTrainingSummary
            if binomial
            else ClassificationTrainingSummary
        )
        model.summary = summary_cls(
            hist, n_iters, model, prep["frame"],
            labelCol=self.getLabelCol(), mesh=prep.get("mesh"),
        )
        return model

    # ---- grid-batched fitting (CrossValidator/TrainValidationSplit) ----

    _GRID_VARYING = frozenset(
        {"regParam", "elasticNetParam", "standardization"}
    )
    _GRID_UNIFORM = frozenset({"maxIter", "tol", "fitIntercept", "family"})

    def supports_batched_grid(self, param_maps) -> bool:
        """True if ``param_maps`` can run as ONE vmapped device program:
        every key is a hyperparameter the batched program accepts, compile-
        time (static) knobs are uniform across points, and no bound
        constraints or mid-fit checkpointing are in play."""
        if len(param_maps) < 2:
            return False
        keys = set().union(*param_maps)
        if not keys <= (self._GRID_VARYING | self._GRID_UNIFORM):
            return False
        for kk in keys & self._GRID_UNIFORM:
            vals = {m.get(kk, self.paramValues().get(kk)) for m in param_maps}
            if len(vals) > 1:
                return False
        if any(
            self.paramValues().get(p) is not None for p in _BOUND_PARAMS
        ):
            return False
        return not self._would_checkpoint()

    def _fit_grid_folds(self, frame: Frame, param_maps, fold_of, num_folds):
        """CrossValidator's ENTIRE k-fold × grid sweep in (at most two)
        device programs: a fold is a 0/1 row-weight mask over the shared
        sharded data, so (fold, grid point) lanes vmap together — data is
        uploaded once, each lane standardizes on its own fold's moments
        (matching a sequential sub-fit), and every LBFGS iteration batches
        all lanes' matmuls on the MXU.  Returns ``[num_folds][G]`` fitted
        models."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh or get_default_mesh()
        ests = [self.copy(m) for m in param_maps]
        G = len(ests)
        X, y, w = self._extract(frame)
        n, d = X.shape
        binomial, k = ests[0]._resolve_family(y, n)

        xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
        n_pad = xs.shape[0]
        fold_of = np.asarray(fold_of)
        masks = np.zeros((num_folds, n_pad), np.float32)
        for f in range(num_folds):
            masks[f, :n] = (fold_of != f) * w  # zero weight = not in fold
        axis = mesh.axis_names[0]
        ws_folds = jax.device_put(masks, NamedSharding(mesh, P(None, axis)))

        s1, s2, cnt, cc = _lr_summarize_folds(xs, ys, ws_folds, k)
        s1, s2, cnt, cc = (np.asarray(a, np.float64) for a in (s1, s2, cnt, cc))
        preps = []
        for f in range(num_folds):
            std, inv_std, class_counts = self._moments_to_stats(
                s1[f], s2[f], cnt[f], cc[f]
            )
            preps.append({
                "xs": xs, "ys": ys, "n": n, "d": d, "k": k,
                "binomial": binomial, "std": std, "inv_std": inv_std,
                "class_counts": class_counts,
            })

        vecs = [
            [ests[g]._grid_vectors(preps[f]) for g in range(G)]
            for f in range(num_folds)
        ]
        max_iter, tol = ests[0].getMaxIter(), ests[0].getTol()
        fit_intercept = ests[0].getFitIntercept()
        models = [[None] * G for _ in range(num_folds)]
        for flag in (False, True):
            lanes = [
                (f, g)
                for f in range(num_folds)
                for g in range(G)
                if bool(vecs[f][g]["use_l1"]) == flag
            ]
            if not lanes:
                continue
            res = _lr_optimize_lanes(
                xs, ys,
                ws_folds,
                jnp.asarray(
                    np.asarray([f for f, _ in lanes], np.int32)
                ),
                jnp.asarray(
                    np.stack(
                        [preps[f]["inv_std"] for f, _ in lanes]
                    ).astype(np.float32)
                ),
                jnp.asarray(np.stack([vecs[f][g]["l2"] for f, g in lanes])),
                jnp.asarray(
                    np.stack([vecs[f][g]["pen_l2"] for f, g in lanes])
                ),
                jnp.asarray(
                    np.stack([vecs[f][g]["l1_vec"] for f, g in lanes])
                ),
                jnp.asarray(
                    np.stack([vecs[f][g]["theta0"] for f, g in lanes])
                ),
                binomial=binomial,
                fit_intercept=fit_intercept,
                k=k,
                max_iter=max_iter,
                tol=tol,
                use_l1=flag,
            )
            xs_h = np.asarray(res.x)
            iters_h = np.asarray(res.n_iters)
            hist_h = np.asarray(res.history)
            for lane, (f, g) in enumerate(lanes):
                models[f][g] = ests[g]._theta_to_model(
                    xs_h[lane], preps[f], iters_h[lane], hist_h[lane]
                )
        return models

    def supports_vectorized_ovr(self) -> bool:
        """True when OneVsRest can run this classifier's K binary fits as
        one vmapped program: binomial-compatible family, no bound
        constraints, no mid-fit checkpointing."""
        if self.getFamily() == "multinomial":
            return False  # a 2-class softmax parameterization differs
        if any(
            self.paramValues().get(p) is not None for p in _BOUND_PARAMS
        ):
            return False
        return not self._would_checkpoint()

    def _would_checkpoint(self) -> bool:
        """True iff a fit would actually persist mid-fit state — the gate
        ``run_segmented`` itself uses (interval AND dir set); batched
        paths defer to the sequential fit only in that case."""
        return (
            self.getCheckpointInterval() > 0
            and bool(self.getCheckpointDir())
        )

    def _fit_ovr_lanes(self, X, y, w, k, mesh):
        """K one-vs-rest binary models fit in one device program (see
        ``_lr_optimize_ovr``): the summarizer runs once (moments are
        class-independent), per-class intercepts init to each class's
        prior log odds, and lane c's labels are relabeled in-program."""
        n, d = X.shape
        xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
        ws = shard_weights(mesh, w, xs.shape[0])
        std, inv_std, class_counts = self._moments_to_stats(
            *_lr_summarize(xs, ys, ws, k)
        )
        w_sum = float(class_counts.sum())

        fit_intercept = self.getFitIntercept()
        vec = self._penalty_vectors(d, 2, True, inv_std)
        n_int = vec["n_int"]

        theta0_b = np.zeros((k, d + n_int), np.float32)
        if fit_intercept:
            # per-class prior log odds — what each sequential relabeled
            # sub-fit's _grid_vectors init would compute
            pos = class_counts / max(w_sum, 1e-12)
            theta0_b[:, d] = np.log(
                np.maximum(pos, 1e-12) / np.maximum(1.0 - pos, 1e-12)
            )

        res = _lr_optimize_ovr(
            xs, ys, ws,
            jnp.asarray(inv_std, jnp.float32),
            jnp.asarray(vec["l2"]),
            jnp.asarray(vec["pen_l2"]),
            jnp.asarray(vec["l1_vec"]),
            jnp.arange(k, dtype=jnp.int32),
            jnp.asarray(theta0_b),
            fit_intercept=fit_intercept,
            max_iter=self.getMaxIter(),
            tol=self.getTol(),
            use_l1=bool(vec["use_l1"]),
        )
        xs_h = np.asarray(res.x)
        iters_h = np.asarray(res.n_iters)
        hist_h = np.asarray(res.history)
        prep = {
            "n": n, "d": d, "k": 2, "binomial": True,
            "std": std, "inv_std": inv_std,
        }
        return [
            self._theta_to_model(
                xs_h[c], prep, iters_h[c], hist_h[c]
            )
            for c in range(k)
        ]

    def _fit_grid(self, frame: Frame, param_maps):
        """Fit all ``param_maps`` over the SAME frame in (at most two)
        batched device programs; returns one fitted model per map, in
        order.  Data upload + summarizer run once; L1 (OWLQN) and L2-only
        (plain LBFGS) points batch separately — their update rules differ
        in-program (static ``use_l1``)."""
        mesh = self._mesh or get_default_mesh()
        ests = [self.copy(m) for m in param_maps]
        prep = ests[0]._prep_data(frame, mesh)
        vecs = [e._grid_vectors(prep) for e in ests]
        max_iter = ests[0].getMaxIter()
        tol = ests[0].getTol()
        fit_intercept = ests[0].getFitIntercept()

        models: list = [None] * len(ests)
        for flag in (False, True):
            idxs = [i for i, v in enumerate(vecs) if bool(v["use_l1"]) == flag]
            if not idxs:
                continue
            res = _lr_optimize_grid(
                prep["xs"], prep["ys"], prep["ws"],
                jnp.asarray(prep["inv_std"], jnp.float32),
                jnp.asarray(np.stack([vecs[i]["l2"] for i in idxs])),
                jnp.asarray(np.stack([vecs[i]["pen_l2"] for i in idxs])),
                jnp.asarray(np.stack([vecs[i]["l1_vec"] for i in idxs])),
                jnp.asarray(np.stack([vecs[i]["theta0"] for i in idxs])),
                binomial=prep["binomial"],
                fit_intercept=fit_intercept,
                k=prep["k"],
                max_iter=max_iter,
                tol=tol,
                use_l1=flag,
            )
            xs_h = np.asarray(res.x)
            iters_h = np.asarray(res.n_iters)
            hist_h = np.asarray(res.history)
            for lane, i in enumerate(idxs):
                models[i] = ests[i]._theta_to_model(
                    xs_h[lane], prep, iters_h[lane], hist_h[lane]
                )
        return models

    def _fit(self, frame: Frame) -> "LogisticRegressionModel":
        mesh = self._mesh or get_default_mesh()
        prep = self._prep_data(frame, mesh)
        xs, ys, ws = prep["xs"], prep["ys"], prep["ws"]
        n, d, k = prep["n"], prep["d"], prep["k"]
        binomial = prep["binomial"]
        std, inv_std = prep["std"], prep["inv_std"]

        reg = self.getRegParam()
        alpha = self.getElasticNetParam()
        fit_intercept = self.getFitIntercept()
        standardize = self.getStandardization()

        # penalty weights / init via the shared grid-vector builder
        # (standardization=True penalizes scaled coefs directly; False
        # matches original-space penalties; intercepts init to prior log
        # odds — Spark parity)
        vec = self._grid_vectors(prep)
        l2, pen_l2 = vec["l2"], vec["pen_l2"]
        l1_vec, theta0 = vec["l1_vec"], vec["theta0"]
        use_l1 = vec["use_l1"]
        n_coef, n_int = vec["n_coef"], vec["n_int"]

        # ---- bound constraints (Spark's bound-constrained variant) ----
        lb_t, ub_t, use_bounds = self._build_bounds(
            d, k, binomial, n_coef, n_int, std
        )
        if use_bounds and use_l1:
            raise ValueError(
                "bound-constrained optimization only supports none/L2 "
                "regularization (Spark parity): set elasticNetParam=0"
            )
        if use_bounds and fit_intercept:
            # the prior-log-odds init must start inside the box
            theta0[n_coef:] = np.clip(
                theta0[n_coef:], lb_t[n_coef:], ub_t[n_coef:]
            )

        def opt_call(init_state, resume, iter_limit):
            init_dev = (
                None
                if init_state is None
                else jax.tree.map(jnp.asarray, init_state)
            )
            return _lr_optimize(
                xs, ys, ws,
                jnp.asarray(inv_std, jnp.float32),
                jnp.asarray(l2, jnp.float32),
                jnp.asarray(pen_l2),
                jnp.asarray(l1_vec),
                jnp.asarray(theta0),
                init_dev,
                jnp.asarray(iter_limit, jnp.int32),
                jnp.asarray(lb_t, jnp.float32),
                jnp.asarray(ub_t, jnp.float32),
                binomial=binomial,
                fit_intercept=fit_intercept,
                k=k,
                max_iter=self.getMaxIter(),
                tol=self.getTol(),
                use_l1=use_l1,
                resume=resume,
                use_bounds=use_bounds,
            )

        fingerprint = {
            "algo": "logistic_regression",
            "n_coef": n_coef, "n_int": n_int, "num_classes": k,
            "binomial": binomial, "regParam": reg, "elasticNetParam": alpha,
            "maxIter": self.getMaxIter(), "tol": self.getTol(),
            "standardization": standardize, "n_rows": n,
            "bounds": (
                _bounds_digest(lb_t, ub_t) if use_bounds else None
            ),
        }
        res = run_segmented(
            opt_call,
            self.getMaxIter(),
            self.getCheckpointInterval(),
            self.getCheckpointDir(),
            fingerprint,
        )

        return self._theta_to_model(
            res.x, prep, res.n_iters, res.history, use_bounds=use_bounds
        )

    def partial_fit(self, frame: Frame, state=None, decay: float = 1.0,
                    n_classes: int = None):
        """One incremental update (the MLlib streaming-linear-model
        recipe): fold this mini-batch's summarizer moments into
        ``state`` and advance the solution with a warm-started run of
        the SAME jitted LBFGS program the batch fit uses; returns
        ``(model, state)``.

        The standardization moments and class counts are additive and
        accumulate EXACTLY (``decay`` < 1 down-weights history), so
        every call standardizes against all data seen — matching the
        batch fit's preprocessing on the concatenation.  The logistic
        loss has no finite sufficient statistic, so the optimization
        itself is approximate: each call minimizes the CURRENT shard's
        objective from the previous solution (the decayed-state
        gradient-step family).  The equivalence contract is therefore
        behavioral — held-out predictions agree with the batch fit on
        concatenated iid shards within the documented tolerance
        (docs/RESILIENCE.md "Model lifecycle";
        tests/test_lifecycle.py pins it).  The family/class count is
        fixed by the first call — pass ``n_classes`` there when the
        label universe is known, since a mini-batch rarely carries
        every class; bound constraints and mid-fit checkpointing are
        unsupported here."""
        from sntc_tpu.lifecycle.incremental import LRPartialFitState

        if any(
            self.paramValues().get(p) is not None for p in _BOUND_PARAMS
        ):
            raise ValueError(
                "partial_fit does not support bound constraints"
            )
        if self._would_checkpoint():
            raise ValueError(
                "partial_fit does not support mid-fit checkpointing"
            )
        mesh = self._mesh or get_default_mesh()
        X, y, w = self._extract(frame)
        n, d = X.shape
        if state is None:
            binomial, k = self._resolve_family(y, n)
            if n_classes is not None:
                if k > int(n_classes):
                    raise ValueError(
                        f"label {int(y.max())} outside the declared "
                        f"n_classes={int(n_classes)}"
                    )
                k = max(int(n_classes), 2)
                family = self.getFamily()
                binomial = k == 2 and family != "multinomial"
                if family == "binomial" and k > 2:
                    raise ValueError(
                        f"binomial family with {k} classes; use "
                        "multinomial"
                    )
            state = LRPartialFitState(d=d, k=k, binomial=binomial)
        else:
            if d != state.d:
                raise ValueError(
                    f"partial_fit feature width {d} != state's {state.d}"
                )
            if n and int(y.max()) >= state.k:
                raise ValueError(
                    f"label {int(y.max())} outside the class set fixed "
                    f"at the first partial_fit call ({state.k} classes)"
                )
        xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
        ws = shard_weights(mesh, w, xs.shape[0])
        s1, s2, cnt, cc = _lr_summarize(xs, ys, ws, state.k)
        state.update(
            np.asarray(s1, np.float64), np.asarray(s2, np.float64),
            float(cnt), np.asarray(cc, np.float64), n_rows=n,
            decay=decay,
        )
        std, inv_std, class_counts = self._moments_to_stats(
            state.s1, state.s2, state.cnt, state.class_counts
        )
        prep = {
            "xs": xs, "ys": ys, "ws": ws, "n": n, "d": d, "k": state.k,
            "binomial": state.binomial, "std": std, "inv_std": inv_std,
            "class_counts": class_counts, "frame": None,
        }
        vec = self._grid_vectors(prep)
        n_coef, n_int = vec["n_coef"], vec["n_int"]
        theta0 = vec["theta0"]
        if state.coef_orig is not None:
            # warm start: the previous ORIGINAL-space solution rescaled
            # into THIS call's standardization space (std moves as the
            # moments accumulate; original space is the invariant)
            theta0 = theta0.copy()
            theta0[:n_coef] = (
                state.coef_orig * std[:, None]
            ).reshape(-1).astype(np.float32)
            if n_int:
                theta0[n_coef:] = state.intercepts
        z = np.zeros(n_coef + n_int, np.float32)
        res, _opt_state = _lr_optimize(
            xs, ys, ws,
            jnp.asarray(inv_std, jnp.float32),
            jnp.asarray(vec["l2"], jnp.float32),
            jnp.asarray(vec["pen_l2"]),
            jnp.asarray(vec["l1_vec"]),
            jnp.asarray(theta0, jnp.float32),
            None,
            jnp.asarray(self.getMaxIter(), jnp.int32),
            jnp.asarray(z), jnp.asarray(z),
            binomial=state.binomial,
            fit_intercept=self.getFitIntercept(),
            k=state.k,
            max_iter=self.getMaxIter(),
            tol=self.getTol(),
            use_l1=bool(vec["use_l1"]),
        )
        theta = np.asarray(res.x, np.float64)
        state.coef_orig = (
            theta[:n_coef].reshape(d, state.rows) * inv_std[:, None]
        )
        state.intercepts = (
            theta[n_coef:].astype(np.float32)
            if n_int
            else np.zeros(state.rows, np.float32)
        )
        model = self._theta_to_model(
            theta, prep, res.n_iters, res.history
        )
        return model, state


@jax.jit
def _margins(X, coefT, intercepts):
    return X @ coefT + intercepts[None, :]


@partial(jax.jit, static_argnames=("binomial",))
def _predict_fused(X, coefT, intercepts, *, binomial):
    """raw margins + probabilities in ONE program (one dispatch per
    serving micro-batch [B:11]).

    Probability is softmax of the ORIGINAL margins: for binomial models
    column 0 of the coefficient matrix is identically zero, so
    softmax([0, m]) == [1-σ(m), σ(m)] — Spark's sigmoid(margin), NOT the
    sigmoid(2m) that softmax of the symmetrized rawPrediction [-m, +m]
    would give."""
    margins = X @ coefT + intercepts[None, :]
    prob = jax.nn.softmax(margins, axis=1)
    if binomial:
        m = margins[:, 1] - margins[:, 0]
        raw = jnp.stack([-m, m], axis=1)
    else:
        raw = margins
    return raw, prob


@partial(jax.jit, static_argnames=("binomial", "mode"))
def _lr_serve(X, coefT, intercepts, thr, *, binomial, mode):
    """raw + probability + prediction in ONE device program, PACKED into a
    single ``[N, 2K+1]`` output — one dispatch and one device→host
    transfer per serving micro-batch ([B:11])."""
    from sntc_tpu.models.base import pack_serve_outputs

    raw, prob = _predict_fused(X, coefT, intercepts, binomial=binomial)
    return pack_serve_outputs(raw, prob, thr, mode)


class LogisticRegressionModel(_LrParams, ClassificationModel):
    def __init__(
        self,
        coefficient_matrix: np.ndarray,  # [K, D] original space
        intercepts: np.ndarray,  # [K]
        is_binomial: bool,
        **kwargs,
    ):
        super().__init__(**kwargs)
        self.coefficientMatrix = np.array(coefficient_matrix, np.float32)
        self.interceptVector = np.array(intercepts, np.float32)
        # read-only (own copy): predict caches device copies, so silent
        # in-place mutation would serve stale weights — make it raise instead
        self.coefficientMatrix.flags.writeable = False
        self.interceptVector.flags.writeable = False
        self.is_binomial = bool(is_binomial)
        self.summary: Optional[LogisticRegressionSummary] = None
        self._dev_params = None  # lazy device-resident (coefT, intercepts)

    def _device_params(self):
        params = self._dev_params
        if params is None:
            params = (
                jnp.asarray(self.coefficientMatrix.T),
                jnp.asarray(self.interceptVector),
            )
            # never cache values created under an active trace: the
            # fusion planner jits THROUGH transform, so inside its
            # tracing these constants are tracers — caching one would
            # poison every later trace with UnexpectedTracerError
            # (bites exactly when two engines share one predictor)
            if not isinstance(params[0], jax.core.Tracer):
                self._dev_params = params
        return params

    def evaluate(self, frame: Frame):
        """Metrics summary on ``frame`` (Spark ``model.evaluate(dataset)``)
        — the training summary's surface minus objectiveHistory, lazy."""
        cls = (
            BinaryClassificationSummary
            if self.is_binomial
            else ClassificationSummary
        )
        return cls(self, frame, labelCol=self.getLabelCol())

    def _save_extra(self):
        return (
            {"is_binomial": self.is_binomial},
            {
                "coefficientMatrix": self.coefficientMatrix,
                "interceptVector": self.interceptVector,
            },
        )

    @classmethod
    def _load_from(cls, params, extra, arrays):
        m = cls(
            coefficient_matrix=arrays["coefficientMatrix"],
            intercepts=arrays["interceptVector"],
            is_binomial=extra["is_binomial"],
        )
        m.setParams(**params)
        return m

    # Spark binary-model accessors
    @property
    def coefficients(self) -> np.ndarray:
        if not self.is_binomial:
            raise AttributeError("use coefficientMatrix for multinomial models")
        return self.coefficientMatrix[1]

    @property
    def intercept(self) -> float:
        if not self.is_binomial:
            raise AttributeError("use interceptVector for multinomial models")
        return float(self.interceptVector[1])

    @property
    def num_classes(self) -> int:
        return self.coefficientMatrix.shape[0]

    def _raw_predict(self, X: np.ndarray) -> np.ndarray:
        coefT, b = self._device_params()
        raw = np.asarray(_margins(jnp.asarray(X), coefT, b))
        if self.is_binomial:
            # Spark binary rawPrediction is [-margin, +margin]
            m = raw[:, 1] - raw[:, 0]
            raw = np.stack([-m, m], axis=1)
        return raw

    def _predict_raw_prob(self, X: np.ndarray):
        coefT, b = self._device_params()
        raw, prob = _predict_fused(
            jnp.asarray(X), coefT, b, binomial=self.is_binomial
        )
        return np.asarray(raw), np.asarray(prob)

    def _predict_all_dev(self, X: np.ndarray):
        coefT, b = self._device_params()
        mode, thr = self._threshold_mode()
        return _lr_serve(
            jnp.asarray(X), coefT, b, jnp.asarray(thr),
            binomial=self.is_binomial, mode=mode,
        )

    def _predict_raw_prob_host(self, X: np.ndarray):
        """numpy predict for micro-batches at or below the host-serve
        crossover (``SNTC_SERVE_HOST_ROWS``; 0 by default — the device
        serves every batch until a crossover is measured on the chip)."""
        margins = X @ self.coefficientMatrix.T + self.interceptVector[None, :]
        if self.is_binomial:
            m = margins[:, 1] - margins[:, 0]
            raw = np.stack([-m, m], axis=1)
        else:
            raw = margins
        return raw, self._raw_to_probability(raw)

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        if self.is_binomial:
            # raw = [-m, +m]; Spark probability is sigmoid(m) — numerically
            # stable form, no exp overflow on extreme margins
            m = raw[:, 1]
            e = np.exp(-np.abs(m))
            p1 = np.where(m >= 0, 1.0, e) / (1.0 + e)
            return np.stack([1.0 - p1, p1], axis=1)
        z = raw - raw.max(axis=1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=1, keepdims=True)
