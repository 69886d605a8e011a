"""compiles_in_window.fit: the program's ``xla.compile`` markers inside the
traced window, per fit: executables XLA built or loaded from the persistent
cache there (each a miss of a jitted function's own cache); 0 when every
shape was warmed up."""

import program_spans


def read(ctx):
    return program_spans.compiles_in_window(ctx)
