"""Stage-by-stage profile of bench config 1, plus the LR-FIT
decomposition: shard/upload,
summarizer pass, LBFGS optimize program (with iteration counts), and the
same numbers for sklearn measured in THIS invocation (drift-proof) —
scaler fit, lbfgs fit, n_iter_.  Per-iteration costs on both sides turn
"a bit faster" into "here is the single-fit floor".

Usage:  python scripts/profile_config1.py [--rows 250000] [--platform cpu]
Prints one JSON line per stage plus a total, then the decomposition.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=250_000)
    ap.add_argument("--platform", default=os.environ.get("BENCH_PLATFORM"))
    args = ap.parse_args()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    import jax

    from sntc_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    import numpy as np

    from bench import SEED, LR_MAX_ITER, _dataset, _feature_stages
    from sntc_tpu.models import LogisticRegression
    from sntc_tpu.parallel.context import get_default_mesh

    mesh = get_default_mesh()
    train, _ = _dataset(args.rows, binary=True)

    def run_once(record):
        stages = _feature_stages(mesh) + [
            LogisticRegression(mesh=mesh, maxIter=LR_MAX_ITER,
                               regParam=1e-4)
        ]
        frame = train
        total0 = time.perf_counter()
        for st in stages:
            name = type(st).__name__
            t0 = time.perf_counter()
            fitted = st.fit(frame) if hasattr(st, "_fit") else st
            t_fit = time.perf_counter() - t0
            t0 = time.perf_counter()
            if not isinstance(st, LogisticRegression):
                frame = fitted.transform(frame)
            t_tr = time.perf_counter() - t0
            if record is not None:
                record.append({
                    "stage": name,
                    "fit_s": round(t_fit, 4),
                    "transform_s": round(t_tr, 4),
                })
        if record is not None:
            record.append({
                "stage": "TOTAL",
                "fit_s": round(time.perf_counter() - total0, 4),
                "platform": jax.devices()[0].platform,
                "n_rows": train.num_rows,
            })

    run_once(None)  # warmup: compile + device caches
    rec: list = []
    run_once(rec)
    for row in rec:
        print(json.dumps(row), flush=True)

    # ---- LR-fit decomposition ------------------------------------------
    # Re-derive the feature frame once, then time the fit's internals:
    # extract, shard/upload, summarizer treeAggregate, LBFGS program.
    import jax.numpy as jnp

    from sntc_tpu.models.logistic_regression import (
        _lr_optimize,
        _lr_summarize,
    )
    from sntc_tpu.parallel.collectives import shard_batch, shard_weights

    stages = _feature_stages(mesh)
    frame = train
    for st in stages:
        frame = (st.fit(frame) if hasattr(st, "_fit") else st).transform(frame)

    lr = LogisticRegression(mesh=mesh, maxIter=LR_MAX_ITER, regParam=1e-4)

    def timed(fn, reps=1):
        """(result, best_s): warm best-of-reps after one untimed call."""
        fn()
        best = float("inf")
        out = None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return out, best

    X, y, w = lr._extract(frame)
    binomial, k = lr._resolve_family(y, len(y))
    y32 = y.astype(np.int32)  # hoisted: keeps identity-memoization valid

    def do_shard():
        xs, ys, _ = shard_batch(mesh, X, y32)
        jax.block_until_ready((xs, ys))
        return xs, ys

    (xs, ys), t_shard = timed(do_shard)
    ws = shard_weights(mesh, w, xs.shape[0])
    jax.block_until_ready(ws)
    # shard_batch memoizes by array identity, so the timed repeat above
    # measures the cache hit; time the true upload once with fresh copies
    Xc, yc = X.copy(), y32.copy()
    t0 = time.perf_counter()
    jax.block_until_ready(shard_batch(mesh, Xc, yc)[0])
    t_upload = time.perf_counter() - t0

    _, t_summarize = timed(
        lambda: jax.block_until_ready(_lr_summarize(xs, ys, ws, k)), reps=3
    )

    # build the prep dict from the arrays already sharded above (calling
    # _prep_data would re-extract and re-upload everything a second time)
    std, inv_std, class_counts = lr._moments_to_stats(
        *_lr_summarize(xs, ys, ws, k)
    )
    prep = {
        "xs": xs, "ys": ys, "ws": ws, "n": len(y), "d": X.shape[1],
        "k": k, "binomial": binomial, "std": std, "inv_std": inv_std,
        "class_counts": class_counts, "frame": None, "mesh": mesh,
    }
    vec = lr._grid_vectors(prep)

    def do_opt():
        res, _state = _lr_optimize(
            xs, ys, ws,
            jnp.asarray(prep["inv_std"], jnp.float32),
            jnp.asarray(vec["l2"], jnp.float32),
            jnp.asarray(vec["pen_l2"]),
            jnp.asarray(vec["l1_vec"]),
            jnp.asarray(vec["theta0"]),
            None,
            jnp.asarray(LR_MAX_ITER, jnp.int32),
            jnp.zeros_like(jnp.asarray(vec["theta0"])),
            jnp.zeros_like(jnp.asarray(vec["theta0"])),
            binomial=binomial, fit_intercept=True, k=k,
            max_iter=LR_MAX_ITER, tol=lr.getTol(), use_l1=False,
            resume=False, use_bounds=False,
        )
        jax.block_until_ready(res.x)
        return res

    res, t_opt = timed(do_opt, reps=3)
    ours_iters = int(res.n_iters)

    # ---- sklearn, SAME invocation (drift cancels) ----------------------
    from sklearn.linear_model import LogisticRegression as SkLR
    from sklearn.preprocessing import StandardScaler as SkScaler

    from bench import _proxy_xy

    Xp, yp, _ = _proxy_xy(train)
    (_, t_sk_scaler) = timed(lambda: SkScaler().fit(Xp))
    Xs = SkScaler().fit(Xp).transform(Xp)
    sk_clf, t_sk_fit = timed(
        lambda: SkLR(max_iter=LR_MAX_ITER, tol=1e-6).fit(Xs, yp)
    )
    sk_iters = int(np.max(sk_clf.n_iter_))

    decomp = {
        "stage": "LR_FIT_DECOMPOSITION",
        "upload_s": round(t_upload, 4),
        "shard_cached_s": round(t_shard, 4),
        "summarizer_pass_s": round(t_summarize, 4),
        "lbfgs_program_s": round(t_opt, 4),
        "lbfgs_iters": ours_iters,
        "per_iter_ms": round(1e3 * t_opt / max(ours_iters, 1), 3),
        "sk_scaler_fit_s": round(t_sk_scaler, 4),
        "sk_lbfgs_fit_s": round(t_sk_fit, 4),
        "sk_iters": sk_iters,
        "sk_per_iter_ms": round(1e3 * t_sk_fit / max(sk_iters, 1), 3),
        "platform": jax.devices()[0].platform,
        "n_rows": train.num_rows,
    }
    print(json.dumps(decomp), flush=True)


if __name__ == "__main__":
    main()
