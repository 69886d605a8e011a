"""Plain reference of the perceptron's fit under Spark's ``gd`` solver.

Spark's ``MultilayerPerceptronClassifier`` with ``solver="gd"`` hands its
``FeedForwardTrainer`` to ``GradientDescent`` at ``miniBatchFraction`` 1.0
under ``ANNUpdater``: every step is ``w -= stepSize * grad`` over the whole
set, with no decay of the step, and the loop ends after ``maxIter`` steps or
once ``||w_t - w_{t-1}|| < tol * max(||w_t||, 1)``, a test first made after
the second step (the first has no previous weights).  Here the loss and its
gradient are ``reference.MlpProblem``'s (float32, every product at HIGHEST,
in row blocks on the device); the step and the test are plain numpy, the
weights kept in float32.  The gradient is the mean over the rows, where
Spark averages the means of its 128-row blocks (the configuration's
``assumed`` says so).

Imports nothing from ``sntc_tpu``.
"""

from __future__ import annotations

import numpy as np

import reference as ref


def gd_fit(problem: "ref.MlpProblem", theta0, max_iter: int,
           step_size: float, tol: float):
    """``(losses, theta, n_iters, converged)``: the loss at every iterate
    (``n_iters + 1`` values, the last at the final weights), the final
    float32 weights, the steps taken and whether the tolerance stopped
    them."""
    x = np.asarray(theta0, np.float32)
    step = np.float32(step_size)
    losses, converged = [], False
    for i in range(max_iter):
        f, g = problem.value_and_grad(x)
        losses.append(f)
        new = x - step * g.astype(np.float32)
        moved = np.linalg.norm((new - x).astype(np.float64))
        x = new
        if i >= 1 and moved < tol * max(np.linalg.norm(x.astype(np.float64)), 1.0):
            converged = True
            break
    losses.append(problem.value_and_grad(x)[0])
    return losses, x, len(losses) - 1, converged


def fit(X, y, mean, std, cfg: dict, theta0, matmul: str = "f32"):
    """The configuration's whole fit on the assembled matrix, labels and
    scaler moments: ``gd_fit``'s four results and the problem, for further
    losses at other weights."""
    mu, f = ref.scaler_affine(mean, std)
    problem = ref.MlpProblem(X, y, mu, f, cfg["layers"], matmul)
    return gd_fit(problem, theta0, cfg["maxIter"], cfg["stepSize"],
                  cfg["tol"]) + (problem,)
