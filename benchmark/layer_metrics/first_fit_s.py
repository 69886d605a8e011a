"""first_fit_s: wall seconds of the process's first ``Pipeline.fit``, the one
that traces, lowers and compiles or loads every program: the program's gauge
``sntc_pipeline_first_fit_seconds`` (``core/base.py``), set once.  The
harness's warm-up pass is this fit and, before it, the adapter's first import
of the estimators.  A program without the gauge gives no number."""

import first_call


def read(ctx):
    return first_call.gauge("sntc_pipeline_first_fit_seconds")
