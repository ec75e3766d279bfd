"""Device-busy milliseconds of one run of the jitted decode program (the
engine's ``jit_serve_decode`` on the chip's XLA Modules line): the busy
time inside each run, the mean over its runs in the traced window.  It
reads the device's events alone.  The rest of a decode-only step's busy
time (``decode_step_ms``) is sampling and copies."""
from bench import program_trace

MODULE = "serve_decode"


def read(view):
    r = program_trace.of(view)
    runs = r.module_runs(MODULE) if r is not None else []
    if not runs:
        return None
    return 1e3 * sum(r.busy_in_each(runs)) / len(runs)
