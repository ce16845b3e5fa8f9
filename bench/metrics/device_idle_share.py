"""Device: the share of the traced window in which no operation ran on
the chip (the union of the ``XLA Ops`` intervals against the window)."""
from bench import tracing


def read(run):
    dev = tracing.first_device(run.trace)
    if dev is None:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - tracing.busy_ns(run.trace, dev) / (hi - lo))
