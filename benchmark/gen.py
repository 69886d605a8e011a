"""Seeded flow-frame generator of the benchmark.

A blocked float32 copy of the law of ``sntc_tpu.data.synth.generate_frame``
(78 non-negative log-normal flow features, 15 labels at the schema's
benign-heavy priors, class-conditional axis-aligned signature plus a diffuse
displacement), without the Inf/NaN injection: no row of the benchmark's
traffic may fail.  Differences from the original, on purpose: float32 blocks
instead of float64 ``[N, 78]`` temporaries, one seeded stream per block (so
blocks are made by a few threads), a vectorised label column.  The program
receives only the resulting columns.

Imports nothing from ``sntc_tpu``.
"""

from __future__ import annotations

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))

CODE_FEATURES = (1, 16, 8, 12)
CODE_DELTA = 2.2
INT_LIKE = (0, 2, 3, 43, 44, 45, 46, 47, 48, 49, 50)
MIN_CLASS_FRACTION = 0.0005
BLOCK_ROWS = 1 << 18


def load_schema() -> dict:
    with open(os.path.join(_HERE, "schema.json")) as f:
        return json.load(f)


def class_law(seed: int, schema: dict):
    """``(priors [C], means [C, F], scale [F])`` of the mixture for ``seed``."""
    labels = schema["labels"]
    n_f = len(schema["features"])
    priors = np.maximum(
        np.array([schema["class_priors"][l] for l in labels], np.float64),
        MIN_CLASS_FRACTION,
    )
    priors /= priors.sum()
    rng = np.random.default_rng([int(seed), 1])
    means = np.zeros((len(labels), n_f), np.float64)
    rest = np.setdiff1d(np.arange(n_f), np.asarray(CODE_FEATURES))
    for c in range(1, len(labels)):
        for b, j in enumerate(CODE_FEATURES):
            means[c, j] = CODE_DELTA if (c >> b) & 1 else -CODE_DELTA
        informative = rng.choice(rest, size=12, replace=False)
        means[c, informative] = rng.normal(0.0, 2.0, size=12)
    scale = np.random.default_rng([int(seed), 2]).uniform(0.5, 4.0, size=n_f)
    scale[list(CODE_FEATURES)] = 2.0
    return priors, means, scale


def generate_columns(n_rows: int, seed: int, threads: int = 8) -> dict:
    """``{column name: ndarray}``: 78 contiguous float32 columns and the
    object-dtype string label column, the same for the same ``(n_rows, seed)``."""
    schema = load_schema()
    names = schema["features"]
    vocab = np.array(schema["labels"], dtype=object)
    priors, means, scale = class_law(seed, schema)
    half_scale = (0.5 * scale).astype(np.float32)
    means32 = (means * 0.5 * scale[None, :]).astype(np.float32)
    cdf = np.cumsum(priors)
    cdf[-1] = 1.0
    cols = [np.empty(n_rows, np.float32) for _ in names]
    y = np.empty(n_rows, np.int32)
    int_like = np.asarray(INT_LIKE)

    def block(b: int) -> None:
        lo = b * BLOCK_ROWS
        hi = min(n_rows, lo + BLOCK_ROWS)
        rng = np.random.default_rng([int(seed), 3, b])
        yb = np.searchsorted(cdf, rng.random(hi - lo), side="right").astype(
            np.int32
        )
        z = rng.standard_normal((hi - lo, len(names)), dtype=np.float32)
        z *= half_scale[None, :]
        z += means32[yb]
        np.exp(z, out=z)
        z[:, int_like] = np.floor(z[:, int_like])
        y[lo:hi] = yb
        for j, col in enumerate(cols):
            col[lo:hi] = z[:, j]

    n_blocks = (n_rows + BLOCK_ROWS - 1) // BLOCK_ROWS
    with ThreadPoolExecutor(max_workers=max(1, threads)) as pool:
        list(pool.map(block, range(n_blocks)))
    out = {name: col for name, col in zip(names, cols)}
    out[schema["label_column"]] = vocab[y]
    return out


def glorot_weights(layers, seed: int, gain: float = 1.0) -> np.ndarray:
    """Flat MLP weight vector ``[W1, b1, W2, b2, ...]`` (row-major ``W``
    of shape ``[in, out]``), Glorot-uniform times ``gain``, zero biases."""
    rng = np.random.default_rng([int(seed), 4])
    parts = []
    for d_in, d_out in zip(layers[:-1], layers[1:]):
        limit = gain * np.sqrt(6.0 / (d_in + d_out))
        parts.append(
            rng.uniform(-limit, limit, size=d_in * d_out).astype(np.float32)
        )
        parts.append(np.zeros(d_out, np.float32))
    return np.concatenate(parts)
