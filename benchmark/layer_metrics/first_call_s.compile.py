"""first_call_s.compile: seconds XLA spent BUILDING executables up to the end
of the traced window (``sntc_xla_compile_seconds_total{outcome="compiled"}``,
summed over ``program``).  From a warm persistent cache nothing is built and
the metric reads 0; a program without the ``outcome`` label gives no number."""

import first_call


def read(ctx):
    return first_call.compile_seconds("compiled", "compile")
