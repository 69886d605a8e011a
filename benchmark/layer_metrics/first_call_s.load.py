"""first_call_s.load: seconds spent retrieving and deserialising executables
from the persistent cache up to the end of the traced window
(``sntc_xla_compile_seconds_total{outcome="cache_loaded"}``, summed over
``program``).  From an empty cache nothing is loaded and the metric reads
0; a program without the ``outcome`` label gives no number."""

import first_call


def read(ctx):
    return first_call.compile_seconds("cache_loaded", "load")
