"""Kernels: the fused predictor-corrector kernel
(``srds_parareal_update_residual``) as a share of its roofline over the
traced window.  Each call's operations and bytes are the benchmark's own
count (``bench.flops.corrector_cost``) from the call's array types in the
trace; the time is the sum of the kernel's device events on the first
chip."""
from bench import flops, tracing


def read(run):
    return tracing.kernel_roofline(run, "srds_parareal_update_residual",
                                   flops.corrector_cost)
