"""Model step: device time of the refinement step programs per dispatched
refinement, over the traced window (first chip's ``XLA Modules`` line)."""
from bench import tracing

STEP_PROGRAM = "jit_step_fn"


def read(run):
    dev = tracing.first_device(run.trace)
    if dev is None or not run.probe.dispatches:
        return None
    evs = tracing.module_events(run.trace, dev, STEP_PROGRAM)
    if not evs:
        return None
    return 1e-6 * sum(e.dur_ns for e in evs) / len(run.probe.dispatches)
