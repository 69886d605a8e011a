"""device_ready_s: seconds from the process's start (the kernel's, so the
interpreter's start and every import are in it) to its first mesh, by which
the runtime has reached its devices: the program's gauge
``sntc_process_device_ready_seconds`` (``parallel/mesh.py``).  The first part
of ``setup_s``.  A program without the gauge gives no number."""

import first_call


def read(ctx):
    return first_call.gauge("sntc_process_device_ready_seconds")
