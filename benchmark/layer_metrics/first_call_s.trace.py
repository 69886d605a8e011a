"""first_call_s.trace: seconds the process spent tracing functions to jaxprs
up to the end of the traced window (``sntc_xla_trace_seconds_total``, summed
over ``program``; each span's own seconds, so a ``jit`` traced inside another
is counted once).  The five largest programs go to standard error."""

import first_call


def read(ctx):
    return first_call.phase_seconds("sntc_xla_trace_seconds_total", "trace")
