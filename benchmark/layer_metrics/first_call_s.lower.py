"""first_call_s.lower: seconds the process spent lowering jaxprs to MLIR up
to the end of the traced window (``sntc_xla_lower_seconds_total``, summed over
``program``; a Pallas kernel's Mosaic lowering and the PRNG's rounds are in
here).  The five largest programs go to standard error."""

import first_call


def read(ctx):
    return first_call.phase_seconds("sntc_xla_lower_seconds_total", "lower")
