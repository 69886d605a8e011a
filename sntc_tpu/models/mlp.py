"""MultilayerPerceptronClassifier — feed-forward ANN on TPU [B:8].

Behavioral spec: SURVEY.md §2.3/§3.3 (upstream
``ml/classification/MultilayerPerceptronClassifier.scala`` + ``ml/ann/Layer``
[U]): ``layers=[in, hidden..., out]`` topology, sigmoid hidden activations,
softmax output with cross-entropy, full-batch LBFGS by default (``solver=
"l-bfgs"``, ``maxIter=100``) or gradient descent (``solver="gd"``), seeded
weight init, optional ``initialWeights`` vector.

``solver="gd"`` is Spark's ``FeedForwardTrainer.SGDOptimizer``:
``GradientDescent`` at ``miniBatchFraction`` 1.0 under ``ANNUpdater``, so a
step is ``w -= stepSize * grad`` over the whole batch (no ``1/sqrt(t)``
decay), and the fit stops after ``maxIter`` steps or once
``||w_t - w_{t-1}|| < tol * max(||w_t||, 1)``, tested from the second step
on.  One fit makes one loss-and-gradient evaluation a step and one loss
evaluation (forward only) at the final weights, so ``objectiveHistory``
holds the loss at every iterate, ``n_iters + 1`` values, as under L-BFGS.
The gradient is the mean over the rows (Spark averages the means of its
``blockSize``-row blocks; the two agree when the blocks are full).

Precision on a TPU (``computeDtype``): ``float32`` multiplies at float32
precision (``Precision.HIGHEST``: several bfloat16 passes on the MXU),
forward and backward, in fit and in predict; ``bfloat16`` rounds the
operands to bfloat16 and multiplies in one pass, accumulating in float32.
Spark computes in double.

TPU design: where Spark stacks ``blockSize`` rows per partition to call JNI
BLAS gemms (§3.3 ⟦JVM→NATIVE⟧), here the whole dataset is device-resident
and the forward/backward chain is XLA ``dot_general`` on the MXU — the
"easiest big win" of SURVEY.md §2.3.  The optimizer is the same jitted
LBFGS as LogisticRegression, data mesh-sharded, gradients all-reduced over
ICI; ``blockSize`` is accepted for API parity (batching is XLA's concern).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import Param, validators
from sntc_tpu.mlio.optimizer_checkpoint import run_segmented
from sntc_tpu.models.base import (
    CheckpointParams,
    ClassificationModel,
    ClassifierEstimator,
)
from sntc_tpu.obs import inc, module_of, span
from sntc_tpu.ops.lbfgs import minimize_lbfgs
from sntc_tpu.parallel.collectives import shard_batch, shard_weights
from sntc_tpu.parallel.context import get_default_mesh

_MODULE = module_of(__name__)


def _layer_sizes(layers: Tuple[int, ...]) -> List[Tuple[int, int]]:
    return [(layers[i], layers[i + 1]) for i in range(len(layers) - 1)]


def _n_weights(layers: Tuple[int, ...]) -> int:
    return sum(d_in * d_out + d_out for d_in, d_out in _layer_sizes(layers))


def _unpack(theta: jnp.ndarray, layers: Tuple[int, ...]):
    """Flat vector -> [(W, b), ...] (Spark keeps MLP weights as one vector)."""
    out, off = [], 0
    for d_in, d_out in _layer_sizes(layers):
        W = theta[off : off + d_in * d_out].reshape(d_in, d_out)
        off += d_in * d_out
        b = theta[off : off + d_out]
        off += d_out
        out.append((W, b))
    return out


def _forward(
    theta: jnp.ndarray,
    X: jnp.ndarray,
    layers: Tuple[int, ...],
    compute_dtype=jnp.float32,
):
    """Margins (pre-softmax) of the final layer.

    ``compute_dtype=float32`` multiplies at float32 precision (HIGHEST:
    without it a TPU rounds float32 operands to one bfloat16 pass);
    ``bfloat16`` feeds the MXU its native input width in one pass.  Both
    accumulate in f32 (``preferred_element_type``); activations and
    params stay f32 elsewhere.  Autodiff carries ``precision`` to the
    backward products."""
    precision = (
        jax.lax.Precision.HIGHEST
        if jnp.dtype(compute_dtype) == jnp.float32 else None
    )
    h = X
    wbs = _unpack(theta, layers)
    for i, (W, b) in enumerate(wbs):
        z = (
            jax.lax.dot(
                h.astype(compute_dtype),
                W.astype(compute_dtype),
                precision=precision,
                preferred_element_type=jnp.float32,
            )
            + b[None, :]
        )
        h = jax.nn.sigmoid(z) if i < len(wbs) - 1 else z
    return h


def _label_log_prob(logp: jnp.ndarray, ys: jnp.ndarray) -> jnp.ndarray:
    """Each row's entry of ``logp [N, C]`` at its label ``ys [N]``.

    A select summed over the classes, not a gather: on a TPU
    ``take_along_axis`` lowers to a serial per-row gather (172 ms a
    loss-and-gradient evaluation at 8.1 M rows), where the compare fuses
    into the softmax's pass, forward and backward.  The select adds exact
    zeros, so the sum is the gather's value bit for bit."""
    classes = jnp.arange(logp.shape[1], dtype=jnp.int32)
    mask = ys[:, None].astype(jnp.int32) == classes
    return jnp.sum(jnp.where(mask, logp, 0.0), axis=1)


@partial(
    jax.jit,
    static_argnames=(
        "layers", "max_iter", "tol", "solver", "step_size", "resume",
        "compute_dtype",
    ),
)
def _mlp_optimize(
    xs, ys, ws, theta0, init_state, iter_limit,
    *, layers, max_iter, tol, solver, step_size, resume=False,
    compute_dtype=jnp.float32,
):
    w_sum = jnp.sum(ws)

    def loss_fn(theta):
        margins = _forward(theta, xs, layers, compute_dtype)
        logp = jax.nn.log_softmax(margins, axis=1)
        return -jnp.sum(ws * _label_log_prob(logp, ys)) / w_sum

    value_and_grad = jax.value_and_grad(loss_fn)

    if solver == "l-bfgs":
        return minimize_lbfgs(
            value_and_grad, theta0, max_iter=max_iter, tol=tol,
            init_state=init_state if resume else None,
            return_state=True, iter_limit=iter_limit,
        )

    # solver == "gd": Spark's GradientDescent (miniBatchFraction 1.0) under
    # ANNUpdater: constant full-batch steps, stopped by its solution-change
    # test, which first compares the second step's weights with the first's
    def gd_step(carry):
        i, theta, hist, _ = carry
        f, g = value_and_grad(theta)
        new = theta - step_size * g
        moved = jnp.linalg.norm(new - theta)
        done = (i >= 1) & (moved < tol * jnp.maximum(jnp.linalg.norm(new), 1.0))
        return i + 1, new, hist.at[i].set(f), done

    def running(carry):
        i, _, _, done = carry
        return (i < max_iter) & ~done

    hist0 = jnp.zeros((max_iter + 1,), theta0.dtype)
    n_iters, theta, hist, converged = jax.lax.while_loop(
        running, gd_step,
        (jnp.asarray(0, jnp.int32), theta0, hist0, jnp.asarray(False)),
    )
    f_final = loss_fn(theta)  # the summary's loss at the final iterate
    hist = jnp.where(jnp.arange(max_iter + 1) >= n_iters, f_final, hist)
    from sntc_tpu.ops.lbfgs import LbfgsResult

    return (
        LbfgsResult(
            x=theta, loss=f_final, n_iters=n_iters, history=hist,
            converged=converged,
        ),
        None,  # gd has no resumable state (mid-fit checkpointing is l-bfgs)
    )


class _MlpParams:
    layers = Param(
        "layer sizes [in, hidden..., out]",
        validator=validators.list_of(lambda v: isinstance(v, (int, np.integer)) and v > 0),
    )
    maxIter = Param("max iterations", default=100, validator=validators.gteq(0))
    tol = Param("relative convergence tolerance", default=1e-6, validator=validators.gt(0))
    seed = Param("weight init seed", default=0)
    solver = Param(
        "l-bfgs | gd", default="l-bfgs", validator=validators.one_of("l-bfgs", "gd")
    )
    stepSize = Param("gd step size", default=0.03, validator=validators.gt(0))
    blockSize = Param(
        "row block size (API parity; XLA handles batching)",
        default=128,
        validator=validators.gt(0),
    )
    computeDtype = Param(
        "matmul precision of the head: float32 (float32 products, fit and "
        "predict: Precision.HIGHEST, several bfloat16 passes on a TPU) | "
        "bfloat16 (operands rounded to bfloat16, one MXU pass, f32 "
        "accumulation; fit only); Spark computes in f64",
        default="float32",
        validator=validators.one_of("float32", "bfloat16"),
    )


class MultilayerPerceptronClassifier(_MlpParams, CheckpointParams, ClassifierEstimator):
    def __init__(self, mesh=None, initialWeights: Optional[np.ndarray] = None, **kwargs):
        super().__init__(**kwargs)
        self._mesh = mesh
        self._initial_weights = initialWeights

    def _fit(self, frame: Frame) -> "MultilayerPerceptronClassificationModel":
        mesh = self._mesh or get_default_mesh()
        X, y, w = self._extract(frame)
        layers = tuple(int(v) for v in self.getLayers())
        if X.shape[1] != layers[0]:
            raise ValueError(
                f"layers[0]={layers[0]} but features have {X.shape[1]} columns"
            )
        if y.max(initial=0) >= layers[-1]:
            raise ValueError(
                f"label index {int(y.max())} >= output layer size {layers[-1]}"
            )

        xs, ys, _ = shard_batch(mesh, X, y.astype(np.int32))
        ws = shard_weights(mesh, w, xs.shape[0])

        if self._initial_weights is not None:
            theta0 = np.asarray(self._initial_weights, np.float32)
            if theta0.shape != (_n_weights(layers),):
                raise ValueError(
                    f"initialWeights must have {_n_weights(layers)} entries"
                )
        else:
            # Glorot-uniform per layer, zero biases, seeded
            rng = np.random.default_rng(self.getSeed())
            parts = []
            for d_in, d_out in _layer_sizes(layers):
                limit = np.sqrt(6.0 / (d_in + d_out))
                parts.append(
                    rng.uniform(-limit, limit, size=d_in * d_out).astype(np.float32)
                )
                parts.append(np.zeros(d_out, np.float32))
            theta0 = np.concatenate(parts)

        solver = self.getSolver()

        def opt_call(init_state, resume, iter_limit):
            init_dev = (
                None if init_state is None
                else jax.tree.map(jnp.asarray, init_state)
            )
            return _mlp_optimize(
                xs, ys, ws, jnp.asarray(theta0), init_dev,
                jnp.asarray(iter_limit, jnp.int32),
                layers=layers,
                max_iter=self.getMaxIter(),
                tol=self.getTol(),
                solver=solver,
                step_size=self.getStepSize(),
                resume=resume,
                compute_dtype=jnp.dtype(self.getComputeDtype()),
            )

        fingerprint = {
            "algo": "mlp", "layers": list(layers), "seed": self.getSeed(),
            "maxIter": self.getMaxIter(), "tol": self.getTol(),
            "solver": solver, "n_rows": int(X.shape[0]),
            "computeDtype": self.getComputeDtype(),
        }
        interval = (
            self.getCheckpointInterval()
            if solver == "l-bfgs"
            else -1  # gd state is just theta; not checkpointed
        )
        with span("mlp.optimize", solver=solver, rows=int(X.shape[0]),
                  iterations=self.getMaxIter(), module=_MODULE):
            res = run_segmented(
                opt_call, self.getMaxIter(), interval,
                self.getCheckpointDir(), fingerprint,
            )
            n_iters = int(res.n_iters)  # waits for the device
        if solver == "gd":  # one loss-and-gradient evaluation a step
            inc("sntc_mlp_grad_evals_total", n_iters)

        model = MultilayerPerceptronClassificationModel(
            weights=np.asarray(res.x), layers=list(layers)
        )
        model.setParams(
            **{k: v for k, v in self.paramValues().items() if model.hasParam(k)}
        )
        from sntc_tpu.models.summary import ClassificationTrainingSummary

        # the summary takes a copy of the model, as Spark's does
        # (``findSummaryModel``): the model itself would close a reference
        # cycle that keeps ``frame``, the scaler's device-resident features
        # among it, alive after the caller drops the model, until the
        # cyclic collector runs (2.5 GB a fit at 8.1 M rows)
        model.summary = ClassificationTrainingSummary(
            np.asarray(res.history)[: n_iters + 1], n_iters, model.copy(),
            frame, labelCol=self.getLabelCol(), mesh=mesh,
        )
        return model


@partial(jax.jit, static_argnames=("layers",))
def _mlp_margins(theta, X, layers):
    return _forward(theta, X, layers)


@partial(jax.jit, static_argnames=("layers",))
def _mlp_predict_fused(theta, X, layers):
    """Margins + softmax probabilities in one program (one dispatch per
    serving micro-batch [B:11])."""
    raw = _forward(theta, X, layers)
    return raw, jax.nn.softmax(raw, axis=1)


@partial(jax.jit, static_argnames=("layers", "mode"))
def _mlp_serve(theta, X, thr, *, layers, mode):
    """raw + probability + prediction PACKED into one ``[N, 2K+1]`` output
    — one dispatch and ONE device→host transfer per serving
    micro-batch."""
    from sntc_tpu.models.base import pack_serve_outputs

    raw = _forward(theta, X, layers)
    prob = jax.nn.softmax(raw, axis=1)
    return pack_serve_outputs(raw, prob, thr, mode)


class MultilayerPerceptronClassificationModel(_MlpParams, ClassificationModel):
    def __init__(self, weights: np.ndarray, layers: List[int], **kwargs):
        super().__init__(**kwargs)
        self.weights = np.array(weights, np.float32)
        # read-only (own copy): predict caches a device copy, so silent
        # in-place mutation would serve stale weights — make it raise instead
        self.weights.flags.writeable = False
        self.set("layers", list(layers))
        self.summary = None
        self._dev_weights = None  # lazy device-resident flat weights

    def _device_weights(self):
        w = self._dev_weights
        if w is None:
            w = jnp.asarray(self.weights)
            # never cache a value created under an active trace (the
            # fusion planner jits THROUGH transform; a cached tracer
            # poisons every later trace with UnexpectedTracerError)
            if not isinstance(w, jax.core.Tracer):
                self._dev_weights = w
        return w

    def evaluate(self, frame: Frame):
        """Metrics summary on ``frame`` (Spark ``model.evaluate(dataset)``)."""
        from sntc_tpu.models.summary import ClassificationSummary

        return ClassificationSummary(self, frame, labelCol=self.getLabelCol())

    def _save_extra(self):
        return {}, {"weights": self.weights}

    @classmethod
    def _load_from(cls, params, extra, arrays):
        layers = params.get("layers")
        m = cls(weights=arrays["weights"], layers=layers)
        m.setParams(**params)
        return m

    @property
    def num_classes(self) -> int:
        return int(self.getLayers()[-1])

    def _raw_predict(self, X: np.ndarray) -> np.ndarray:
        return np.asarray(
            _mlp_margins(
                self._device_weights(),
                jnp.asarray(X),
                tuple(int(v) for v in self.getLayers()),
            )
        )

    def _predict_raw_prob(self, X: np.ndarray):
        raw, prob = _mlp_predict_fused(
            self._device_weights(),
            jnp.asarray(X),
            tuple(int(v) for v in self.getLayers()),
        )
        return np.asarray(raw), np.asarray(prob)

    def _raw_to_probability(self, raw: np.ndarray) -> np.ndarray:
        z = raw - raw.max(axis=1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=1, keepdims=True)

    def _predict_all_dev(self, X: np.ndarray):
        mode, thr = self._threshold_mode()
        return _mlp_serve(
            self._device_weights(),
            jnp.asarray(X),
            jnp.asarray(thr),
            layers=tuple(int(v) for v in self.getLayers()),
            mode=mode,
        )

    def _predict_raw_prob_host(self, X: np.ndarray):
        """numpy forward pass for micro-batches at or below the
        host-serve crossover (``SNTC_SERVE_HOST_ROWS``; 0 by default —
        the device serves every batch until a crossover is measured on
        the chip)."""
        h = X.astype(np.float64)
        theta = self.weights.astype(np.float64)
        sizes = _layer_sizes(tuple(int(v) for v in self.getLayers()))
        off = 0
        for i, (d_in, d_out) in enumerate(sizes):
            W = theta[off : off + d_in * d_out].reshape(d_in, d_out)
            off += d_in * d_out
            b = theta[off : off + d_out]
            off += d_out
            z = h @ W + b[None, :]
            if i < len(sizes) - 1:
                # sigmoid, overflow-safe
                e = np.exp(-np.abs(z))
                h = np.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e))
            else:
                h = z
        raw = h.astype(np.float32)
        return raw, self._raw_to_probability(raw)
