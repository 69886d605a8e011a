"""Plain references for the benchmark's configurations.

Straightforward numpy / ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST`` (host statistics in float64), following the published
algorithms the configurations name: frequency-ordered label indexing, unbiased
standardisation, a sigmoid/softmax perceptron under full-batch L-BFGS with an
Armijo backtracking line search, binned Pearson chi-square selection, and a
level-wise histogram CART forest with Poisson bagging.  No kernel, no cache, no
batching beyond row blocks that keep it inside the chip's memory.

Imports nothing from ``sntc_tpu`` and takes nothing the program has made
except the fitted model it is asked to judge.

``matmul`` selects the arithmetic of the matrix products and of the feature
comparisons: ``"f32"`` is the reference; ``"bf16"`` and ``"fp8"`` are the
controls (the reference put in the program's place one step of precision
down), which the comparison has to refuse.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROW_BLOCK = 1 << 16


# --------------------------------------------------------------------------
# shared feature pipeline
# --------------------------------------------------------------------------


def index_labels(labels: np.ndarray):
    """``(vocabulary, y int32)``: labels ordered by descending frequency,
    ties alphabetically (Spark ``StringIndexer`` ``frequencyDesc``)."""
    import pandas as pd

    codes, uniques = pd.factorize(labels, use_na_sentinel=False)
    counts = np.bincount(codes, minlength=len(uniques))
    names = [str(u) for u in uniques]
    order = sorted(range(len(names)), key=lambda i: (-counts[i], names[i]))
    rank = np.empty(len(names), np.int32)
    rank[order] = np.arange(len(names), dtype=np.int32)
    return [names[i] for i in order], rank[codes]


def _threaded(fn, n_items: int, threads: int = 8) -> None:
    """``fn(j)`` for every ``j`` on a few threads (numpy drops the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=threads) as pool:
        list(pool.map(fn, range(n_items)))


def assemble(columns: dict, names) -> np.ndarray:
    """``[N, F]`` float32 matrix of the named columns, in order."""
    n = len(columns[names[0]])
    X = np.empty((n, len(names)), np.float32)
    step = 1 << 18

    def fill(b):
        lo, hi = b * step, min(n, (b + 1) * step)
        for j, name in enumerate(names):
            X[lo:hi, j] = columns[name][lo:hi]

    _threaded(fill, (n + step - 1) // step)
    return X


def scaler_moments(columns: dict, names):
    """Per-feature mean and unbiased standard deviation, float64."""
    mean = np.empty(len(names), np.float64)
    std = np.empty(len(names), np.float64)

    def one(j):
        col = columns[names[j]].astype(np.float64)
        mean[j] = col.mean()
        std[j] = col.std(ddof=1)

    _threaded(one, len(names))
    return mean, std


def scaler_affine(mean, std):
    """``(mu, f)`` float32 of ``x' = (x - mu) * f``; constant features map
    to 0."""
    f = np.divide(1.0, std, out=np.zeros_like(std), where=std > 0)
    return mean.astype(np.float32), f.astype(np.float32)


def _pad_rows(a: np.ndarray, n_pad: int, fill=0):
    if a.shape[0] == n_pad:
        return a
    pad = np.full((n_pad - a.shape[0],) + a.shape[1:], fill, a.dtype)
    return np.concatenate([a, pad], axis=0)


def _blocks(n: int, block: int = ROW_BLOCK):
    return [(lo, min(n, lo + block)) for lo in range(0, n, block)]


# --------------------------------------------------------------------------
# perceptron
# --------------------------------------------------------------------------


def _q(a, matmul: str):
    """Round a matrix-product operand to the control's input type."""
    if matmul == "f32":
        return a
    if matmul == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if matmul == "fp8":
        lim = float(jnp.finfo(jnp.float8_e4m3fn).max)
        return (
            jnp.clip(a, -lim, lim).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        )
    raise ValueError(f"unknown matmul arithmetic {matmul!r}")


def _unpack(theta, layers):
    out, off = [], 0
    for d_in, d_out in zip(layers[:-1], layers[1:]):
        W = theta[off : off + d_in * d_out].reshape(d_in, d_out)
        off += d_in * d_out
        out.append((W, theta[off : off + d_out]))
        off += d_out
    return out


def _margins(theta, X, mu, f, layers, matmul):
    h = (X - mu[None, :]) * f[None, :]
    wbs = _unpack(theta, layers)
    for i, (W, b) in enumerate(wbs):
        z = jnp.dot(_q(h, matmul), _q(W, matmul), precision=HI) + b[None, :]
        h = jax.nn.sigmoid(z) if i < len(wbs) - 1 else z
    return h


@partial(jax.jit, static_argnames=("layers", "matmul"))
def _block_loss_grad(theta, X, y, w, mu, f, *, layers, matmul):
    def loss(theta):
        logp = jax.nn.log_softmax(
            _margins(theta, X, mu, f, layers, matmul), axis=1
        )
        picked = jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return -jnp.sum(w * picked)

    return jax.value_and_grad(loss)(theta)


class MlpProblem:
    """The cross-entropy objective of the perceptron over the whole set,
    held on the device in row blocks."""

    def __init__(self, X, y, mu, f, layers, matmul="f32", block=1 << 20):
        self.layers = tuple(int(v) for v in layers)
        self.matmul = matmul
        self.n = int(X.shape[0])
        self.mu = jnp.asarray(mu)
        self.f = jnp.asarray(f)
        self.parts = []
        for lo, hi in _blocks(self.n, block):
            nb = hi - lo
            n_pad = block if self.n > block else nb
            self.parts.append((
                jnp.asarray(_pad_rows(X[lo:hi], n_pad)),
                jnp.asarray(_pad_rows(y[lo:hi].astype(np.int32), n_pad)),
                jnp.asarray(_pad_rows(np.ones(nb, np.float32), n_pad)),
            ))

    def value_and_grad(self, theta):
        theta = jnp.asarray(theta, jnp.float32)
        tot, grad = 0.0, 0.0
        for Xb, yb, wb in self.parts:
            v, g = _block_loss_grad(
                theta, Xb, yb, wb, self.mu, self.f,
                layers=self.layers, matmul=self.matmul,
            )
            tot, grad = tot + v, grad + g
        return float(tot) / self.n, np.asarray(grad, np.float64) / self.n


def lbfgs_history(problem: MlpProblem, theta0, n_iters: int, *,
                  history_size=10, c1=1e-4, max_linesearch=30, tol=0.0):
    """Objective after 0..``n_iters`` L-BFGS iterations from ``theta0``:
    two-loop recursion over the last ``history_size`` curvature pairs,
    Armijo backtracking (halving) from step 1, the first step from
    ``min(1, 1/|g|_1)`` (Breeze), pairs kept only when ``s.y > 1e-10``;
    stops early when the relative improvement falls under ``tol``."""
    x = np.asarray(theta0, np.float64)
    f, g = problem.value_and_grad(x)
    hist, pairs = [f], []
    for _ in range(n_iters):
        q = g.copy()
        alphas = []
        for s, yv, rho in reversed(pairs):
            a = rho * s.dot(q)
            q -= a * yv
            alphas.append(a)
        if pairs:
            s, yv, _ = pairs[-1]
            q *= s.dot(yv) / yv.dot(yv)
        for (s, yv, rho), a in zip(pairs, reversed(alphas)):
            q += s * (a - rho * yv.dot(q))
        direction = -q
        gd = g.dot(direction)
        alpha = 1.0 if pairs else min(1.0, 1.0 / max(np.abs(g).sum(), 1e-12))
        ok = False
        for _ in range(max_linesearch):
            x_new = x + alpha * direction
            f_new, g_new = problem.value_and_grad(x_new)
            if f_new <= f + c1 * alpha * gd:
                ok = True
                break
            alpha *= 0.5
        if not ok:
            hist.append(f)
            break
        s, yv = x_new - x, g_new - g
        if s.dot(yv) > 1e-10:
            pairs = (pairs + [(s, yv, 1.0 / s.dot(yv))])[-history_size:]
        rel = abs(f_new - f) / max(abs(f_new), abs(f), 1e-12)
        x, f, g = x_new, f_new, g_new
        hist.append(f)
        if rel < tol:
            break
    return hist, x


@partial(jax.jit, static_argnames=("layers", "matmul", "n_classes"))
def _block_eval(theta, X, y, w, mu, f, prob_p, pred_p, *, layers, matmul,
                n_classes):
    prob = jax.nn.softmax(_margins(theta, X, mu, f, layers, matmul), axis=1)
    pred = jnp.argmax(prob, axis=1)
    k = n_classes
    gap = jnp.max(jnp.abs(prob - prob_p), axis=1)
    best = jnp.max(prob, axis=1)
    at_p = jnp.take_along_axis(
        prob, jnp.clip(pred_p, 0, k - 1)[:, None], axis=1
    )[:, 0]
    conf = jax.ops.segment_sum(w, y * k + pred, num_segments=k * k)
    return (
        jnp.sum(w * gap), jnp.max(w * gap), jnp.sum(w * (best - at_p)),
        jnp.sum(w * (pred != pred_p)), conf,
    )


def macro_f1(confusion: np.ndarray) -> float:
    """Unweighted mean of per-class F1 over the classes present in the true
    labels (the configuration's ``macroF1``), 0/0 counted as 0
    (``confusion[i, j]``: true i, predicted j)."""
    c = np.asarray(confusion, np.float64)
    tp = np.diag(c)
    with np.errstate(divide="ignore", invalid="ignore"):
        p = np.where(c.sum(0) > 0, tp / c.sum(0), 0.0)
        r = np.where(c.sum(1) > 0, tp / c.sum(1), 0.0)
        f = np.where(p + r > 0, 2 * p * r / (p + r), 0.0)
    present = c.sum(1) > 0
    return float(f[present].mean()) if present.any() else 0.0


def mlp_evaluate(X, y, mu, f, theta, layers, prob_p, pred_p, matmul="f32",
                 block=1 << 20):
    """Reference forward pass over every row, compared row by row with the
    probabilities and predictions ``prob_p`` / ``pred_p`` it is given.
    Returns the reference's macro-F1 and the gaps."""
    layers = tuple(int(v) for v in layers)
    k = layers[-1]
    n = int(X.shape[0])
    theta = jnp.asarray(theta, jnp.float32)
    mu, f = jnp.asarray(mu), jnp.asarray(f)
    gap_sum = regret = mism = 0.0
    gap_max = 0.0
    conf = np.zeros(k * k, np.float64)
    for lo, hi in _blocks(n, block):
        nb = hi - lo
        n_pad = block if n > block else nb
        out = _block_eval(
            theta,
            jnp.asarray(_pad_rows(X[lo:hi], n_pad)),
            jnp.asarray(_pad_rows(y[lo:hi].astype(np.int32), n_pad)),
            jnp.asarray(_pad_rows(np.ones(nb, np.float32), n_pad)),
            mu, f,
            jnp.asarray(_pad_rows(np.asarray(prob_p[lo:hi], np.float32), n_pad)),
            jnp.asarray(_pad_rows(np.asarray(pred_p[lo:hi]).astype(np.int32), n_pad)),
            layers=layers, matmul=matmul, n_classes=k,
        )
        gap_sum += float(out[0])
        gap_max = max(gap_max, float(out[1]))
        regret += float(out[2])
        mism += float(out[3])
        conf += np.asarray(out[4], np.float64)
    return {
        "macro_f1": macro_f1(conf.reshape(k, k)),
        "prob_gap_mean": gap_sum / n,
        "prob_gap_max": gap_max,
        "pred_regret_mean": regret / n,
        "pred_mismatch_share": mism / n,
    }


def mlp_predict(X, mu, f, theta, layers, matmul="f32", block=1 << 20):
    """``(probability [N, K], prediction [N])`` of the reference put in the
    program's place (the controls use it)."""
    layers = tuple(int(v) for v in layers)
    theta = jnp.asarray(theta, jnp.float32)
    mu, f = jnp.asarray(mu), jnp.asarray(f)
    fwd = jax.jit(
        lambda Xb: jax.nn.softmax(
            _margins(theta, Xb, mu, f, layers, matmul), axis=1
        )
    )
    probs = [np.asarray(fwd(jnp.asarray(X[lo:hi])))
             for lo, hi in _blocks(int(X.shape[0]), block)]
    prob = np.concatenate(probs, axis=0)
    return prob, prob.argmax(axis=1)


# --------------------------------------------------------------------------
# chi-square selection and the forest
# --------------------------------------------------------------------------


def quantile_edges(X: np.ndarray, max_bins: int, seed: int) -> np.ndarray:
    """``[F, max_bins - 1]`` float32 thresholds: linear-interpolated
    quantiles of a ``seed``-drawn sample of ``max(10000, 4 * max_bins**2)``
    rows, without replacement (Spark ``findSplits`` samples the same way)."""
    n = X.shape[0]
    sample_rows = max(10_000, 4 * max_bins * max_bins)
    if n > sample_rows:
        idx = np.random.default_rng(seed).choice(
            n, size=sample_rows, replace=False
        )
        sample = X[idx]
    else:
        sample = X
    qs = np.linspace(0.0, 1.0, max_bins + 1)[1:-1]
    return np.ascontiguousarray(
        np.quantile(sample, qs, axis=0).T.astype(np.float32)
    )


def _feat(a, matmul):
    """Features and thresholds as the control compares them."""
    return a if matmul == "f32" else a.astype(jnp.bfloat16)


@partial(jax.jit, static_argnames=("matmul",))
def _bin(X, edges, *, matmul="f32"):
    """``bin = #edges <= x`` (right-closed), int32 ``[R, F]``."""
    x, e = _feat(X, matmul), _feat(edges, matmul)
    return jnp.sum(x[:, :, None] >= e[None, :, :], axis=2).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_bins", "n_classes", "matmul"))
def _block_contingency(X, y, w, edges, *, n_bins, n_classes, matmul):
    b = jax.nn.one_hot(_bin(X, edges, matmul=matmul), n_bins,
                       dtype=jnp.float32)
    c = jax.nn.one_hot(y, n_classes, dtype=jnp.float32) * w[:, None]
    return jnp.einsum("rfb,rc->fbc", b, c, precision=HI)


def chi2_select(X, y, n_classes, *, max_bins, top, matmul="f32"):
    """Sorted indices of the ``top`` features by binned Pearson chi-square
    p-value ascending (statistic descending, index ascending on ties); the
    contingency is exact (integer counts under 2**24 in float32)."""
    from scipy.stats import chi2 as chi2_dist

    edges = jnp.asarray(quantile_edges(X, max_bins, seed=0))
    n, F = X.shape
    obs = np.zeros((F, max_bins, n_classes), np.float64)
    for lo, hi in _blocks(n):
        nb = hi - lo
        n_pad = ROW_BLOCK if n > ROW_BLOCK else nb
        obs += np.asarray(_block_contingency(
            jnp.asarray(_pad_rows(X[lo:hi], n_pad)),
            jnp.asarray(_pad_rows(y[lo:hi].astype(np.int32), n_pad)),
            jnp.asarray(_pad_rows(np.ones(nb, np.float32), n_pad)),
            edges, n_bins=max_bins, n_classes=n_classes, matmul=matmul,
        ), np.float64)
    stats = np.zeros(F)
    dofs = np.zeros(F, np.int64)
    for j in range(F):
        t = obs[j]
        t = t[t.sum(axis=1) > 0][:, t.sum(axis=0) > 0]
        if t.size == 0 or 1 in t.shape:
            continue
        expected = np.outer(t.sum(axis=1), t.sum(axis=0)) / t.sum()
        stats[j] = ((t - expected) ** 2 / expected).sum()
        dofs[j] = (t.shape[0] - 1) * (t.shape[1] - 1)
    p = np.where(dofs > 0, chi2_dist.sf(stats, np.maximum(dofs, 1)), 1.0)
    order = np.lexsort((np.arange(F), -stats, p))
    return sorted(int(i) for i in order[:top]), stats


def bucketed_rows(n: int) -> int:
    """Rows of the bagging matrix: ``n`` rounded up to 1/64 of its leading
    power of two (the deployment's row buckets; the configuration states the
    rule, because the Poisson stream is drawn row-major over it)."""
    if n <= 64:
        return n
    q = 1 << (n.bit_length() - 6)
    return ((n + q - 1) // q) * q


def bagging_weights(seed: int, n_trees: int, n: int) -> np.ndarray:
    """``[T, n]`` Poisson(1) bootstrap counts (Spark bagging with
    replacement), one seeded stream drawn tree by tree over the row buckets."""
    n_b = bucketed_rows(n)
    w = np.random.default_rng(seed).poisson(1.0, size=(n_trees, n_b))
    return w[:, :n].astype(np.float32)


def feature_masks(seed: int, depth: int, n_trees: int, n_features: int,
                  subset_k: int):
    """Per level ``[T, 2**d, F]`` boolean masks: the ``subset_k`` smallest
    of a seeded uniform draw per (tree, node); ``None`` when every feature
    is a candidate."""
    if subset_k >= n_features:
        return [None] * depth
    keys = jax.random.split(jax.random.PRNGKey(seed), depth)
    out = []
    for d in range(depth):
        r = np.asarray(
            jax.random.uniform(keys[d], (n_trees, 1 << d, n_features))
        )
        kth = np.sort(r, axis=-1)[..., subset_k - 1]
        out.append(r <= kth[..., None])
    return out


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "n_classes"))
def _block_hist(binned, key, w, *, n_nodes, n_bins, n_classes):
    """``[n_nodes * S, F * B]`` weighted class counts of one row block:
    ``key = node * S + label`` (negative: row not in the tree's level)."""
    a = jax.nn.one_hot(key, n_nodes * n_classes, dtype=jnp.float32)
    a = a * w[:, None]
    b = jax.nn.one_hot(binned, n_bins, dtype=jnp.float32)
    b = b.reshape(b.shape[0], -1)
    return jnp.dot(a.T, b, precision=HI)


@jax.jit
def _route(node, X, feat, thr, split):
    """Children of the rows' nodes: ``2 * node + (x[feature] >= threshold)``
    where the node split, else -1 (the row rests in a leaf)."""
    idx = jnp.maximum(node, 0)
    f = feat[idx]
    x = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
    child = 2 * idx + (x >= thr[idx]).astype(jnp.int32)
    return jnp.where((node >= 0) & split[idx], child, -1)


def _gini_w(stats):
    w = stats.sum(axis=-1)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(w > 0, w - (stats ** 2).sum(axis=-1) / w, 0.0)


def split_gains(hist, fmask, min_instances=1.0):
    """``[nodes, F, B-1]`` float64 gini gain per (feature, bin) split from
    ``hist [nodes, S, F, B]``; invalid splits are ``-inf``.  Also the
    nodes' class counts ``[nodes, S]``."""
    cum = np.cumsum(hist, axis=3)
    parent = cum[:, :, 0, -1]
    left = np.moveaxis(cum[:, :, :, :-1], 1, -1)  # [nodes, F, B-1, S]
    right = parent[:, None, None, :] - left
    cnt = parent.sum(axis=-1)
    gain = (
        _gini_w(parent)[:, None, None] - _gini_w(left) - _gini_w(right)
    ) / np.maximum(cnt, 1e-12)[:, None, None]
    valid = (left.sum(-1) >= min_instances) & (right.sum(-1) >= min_instances)
    if fmask is not None:
        valid &= fmask[:, :, None]
    return np.where(valid, gain, -np.inf), parent, left


class ForestData:
    """The selected feature matrix, its bins and the labels on the device,
    in row blocks."""

    def __init__(self, X, y, *, max_bins, seed, matmul="f32"):
        self.n, self.F = X.shape
        self.max_bins = max_bins
        self.edges = quantile_edges(X, max_bins, seed)
        edges_d = jnp.asarray(self.edges)
        self.parts = []
        for lo, hi in _blocks(self.n):
            nb = hi - lo
            n_pad = ROW_BLOCK if self.n > ROW_BLOCK else nb
            Xb = jnp.asarray(_pad_rows(X[lo:hi], n_pad))
            self.parts.append((
                lo, nb, Xb, _bin(Xb, edges_d, matmul=matmul),
                jnp.asarray(_pad_rows(y[lo:hi].astype(np.int32), n_pad)),
            ))


def walk_tree(data: ForestData, w_tree, masks_t, n_classes, depth, given=None):
    """Grow one tree level by level (``given is None``), or follow the
    splits of ``given = (feature [H], threshold [H], leaf_stats [H, S])``
    and judge them.  Returns ``(tree, report)``: the tree as the same three
    arrays with this walk's own class counts in its leaves, and for a
    followed tree the widest gini gain by which a split it made lies below
    the best split of that node (a leaf that should have split counts with
    its best gain), and the widest absolute difference of a leaf's counts.
    """
    S, B = n_classes, data.max_bins
    H = (1 << (depth + 1)) - 1
    feature = np.full(H, -2, np.int32)
    threshold = np.zeros(H, np.float32)
    leaf = np.zeros((H, S), np.float64)
    gain_gap, thr_bad = 0.0, 0
    nodes = [jnp.where(jnp.arange(Xb.shape[0]) < nb, 0, -1).astype(jnp.int32)
             for _, nb, Xb, _, _ in data.parts]
    w_parts = [jnp.asarray(_pad_rows(w_tree[lo:lo + nb], Xb.shape[0]))
               for lo, nb, Xb, _, _ in data.parts]
    exists = np.array([True])
    for d in range(depth):
        n_nodes, off = 1 << d, (1 << d) - 1
        hist = np.zeros((n_nodes * S, data.F * B), np.float64)
        for (_, _, _, bb, yb), nd, wb in zip(data.parts, nodes, w_parts):
            key = jnp.where(nd >= 0, nd * S + yb, -1)
            hist += np.asarray(_block_hist(
                bb, key, wb, n_nodes=n_nodes, n_bins=B, n_classes=S
            ), np.float64)
        hist = hist.reshape(n_nodes, S, data.F, B)
        gains, parent, left = split_gains(
            hist, None if masks_t[d] is None else masks_t[d]
        )
        flat = gains.reshape(n_nodes, -1)
        best = flat.argmax(axis=1)
        best_gain = flat[np.arange(n_nodes), best]
        can_split = exists & np.isfinite(best_gain) & (best_gain > 0)
        bf, bb_ = best // (B - 1), best % (B - 1)
        if given is None:
            split = can_split
        else:
            g_feat, g_thr, _ = given
            split = exists & (g_feat[off:off + n_nodes] >= 0)
            for j in np.flatnonzero(exists):
                if not split[j]:
                    if can_split[j]:
                        gain_gap = max(gain_gap, float(best_gain[j]))
                    continue
                f_j = int(g_feat[off + j])
                hits = (np.flatnonzero(data.edges[f_j] == g_thr[off + j])
                        if f_j < data.F else np.zeros(0, np.int64))
                if hits.size == 0:
                    thr_bad += 1
                    split[j] = False
                    continue
                bf[j], bb_[j] = f_j, hits[0]
                got = gains[j, f_j, hits[0]]
                ref = best_gain[j] if np.isfinite(best_gain[j]) else 0.0
                gain_gap = max(
                    gain_gap, float(ref - got) if np.isfinite(got) else 1.0
                )
        lvl = slice(off, off + n_nodes)
        feature[lvl] = np.where(split, bf, np.where(exists, -1, -2))
        threshold[lvl] = np.where(split, data.edges[bf, bb_], 0.0)
        leaf[lvl] = np.where((exists & ~split)[:, None], parent, 0.0)
        child_exists = np.repeat(split, 2)
        l_stats = left[np.arange(n_nodes), bf, bb_]
        kids = np.stack([l_stats, parent - l_stats], axis=1).reshape(-1, S)
        lvl2 = slice(off + n_nodes, off + 3 * n_nodes)
        feature[lvl2] = np.where(child_exists, -1, -2)
        leaf[lvl2] = np.where(child_exists[:, None], kids, 0.0)
        exists = child_exists
        if d < depth - 1:
            feat_d = jnp.asarray(np.where(split, bf, 0).astype(np.int32))
            thr_d = jnp.asarray(threshold[lvl])
            split_d = jnp.asarray(split)
            nodes = [_route(nd, Xb, feat_d, thr_d, split_d)
                     for nd, (_, _, Xb, _, _) in zip(nodes, data.parts)]
    report = None
    if given is not None:
        g_feat, _, g_leaf = given
        shape_bad = int(np.sum((g_feat >= 0) != (feature >= 0))
                        + np.sum((g_feat == -1) != (feature == -1)))
        is_leaf = feature == -1
        count_gap = float(np.max(
            np.abs(np.asarray(g_leaf, np.float64) - leaf)[is_leaf | (g_feat == -1)],
            initial=0.0,
        ))
        report = {
            "split_gain_gap": gain_gap,
            "leaf_count_gap": count_gap,
            "tree_shape_mismatch": shape_bad + thr_bad,
        }
    return (feature, threshold, leaf.astype(np.float32)), report


def forest_subset_k(n_features: int, n_trees: int) -> int:
    """Spark ``featureSubsetStrategy="auto"`` for classification."""
    return n_features if n_trees == 1 else int(math.ceil(math.sqrt(n_features)))
