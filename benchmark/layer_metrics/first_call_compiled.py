"""first_call_compiled: of ``first_call_programs``, the executables XLA built
(``sntc_xla_compiles_total{outcome="compiled"}``): 0 from a warm cache, all of
them from an empty one.  It says which of the two ``setup_s`` regimes the run
was in."""

import first_call


def read(ctx):
    return first_call.compiles("compiled")
