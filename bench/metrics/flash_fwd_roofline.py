"""Kernels: the flash-attention forward kernel (``srds_flash_fwd``) as a
share of its roofline over the traced window.  Each call's operations and
bytes are the benchmark's own count (``bench.flops.flash_fwd_cost``) from
the call's array types in the trace; the time is the sum of the kernel's
device events on the first chip."""
from bench import flops, tracing


def read(run):
    return tracing.kernel_roofline(run, "srds_flash_fwd",
                                   flops.flash_fwd_cost)
