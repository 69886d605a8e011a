"""first_call_programs: executables the process built or loaded up to the end
of the traced window (``sntc_xla_compiles_total``, both outcomes).  A witness
and no target: the same integer on every run of one tree, whatever the cache
held, so a change of it says that a PR added or removed a program."""

import first_call


def read(ctx):
    return first_call.compiles()
