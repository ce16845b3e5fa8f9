"""Operations and bytes of the served work, computed from shapes.

A matmul of (m, k) by (k, n) counts 2*m*k*n operations; elementwise work
is left out of the model count (it is a fraction of a percent at these
widths).  Kernel byte counts are the bytes each kernel must move between
HBM and the core: every operand read once, every result written once.
"""
from __future__ import annotations

import math


def dit_sample_eval_flops(cfg) -> int:
    """Matmul operations of one DiT forward pass of one sample."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    p_in = cfg["patch_size"] ** 2 * cfg["in_channels"]
    n = (cfg["image_size"] // cfg["patch_size"]) ** 2
    embed = 2 * n * p_in * d + 2 * 256 * d + 2 * d * d
    per_layer = (2 * d * 6 * d             # adaLN modulation
                 + 3 * 2 * n * d * d       # q, k, v
                 + 2 * 2 * n * n * d       # scores and weighted values
                 + 2 * n * d * d           # output projection
                 + 2 * 2 * n * d * f)      # MLP up and down
    final = 2 * d * 2 * d + 2 * n * d * p_in
    return embed + L * per_layer + final


# bytes per element of the HLO array types the kernels use
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}


def elements(shape) -> int:
    """Elements of one array type ``(dtype, dims)``."""
    return math.prod(shape[1])


def array_bytes(shape) -> int:
    """Bytes of one array type ``(dtype, dims)``; float8 types are one."""
    dtype = shape[0]
    return elements(shape) * (1 if dtype.startswith("f8")
                              else ITEMSIZE[dtype])


def call_bytes(results, operands) -> int:
    """Bytes a kernel call must move: every operand read once, every
    result written once."""
    return sum(array_bytes(a) for a in results + operands)


def flash_fwd_cost(results, operands):
    """(operations, bytes) of one non-causal flash forward call with
    operands q, k, v of shape ``(..., seq, head_dim)``: ``q @ k^T`` and the
    weighted sum of ``v``, 2 operations per product each."""
    q, k = operands[0][1], operands[1][1]
    ops = 4 * math.prod(q[:-2]) * q[-2] * k[-2] * q[-1]
    return ops, call_bytes(results, operands)


def corrector_cost(results, operands):
    """(operations, bytes) of one fused predictor-corrector call over
    operands ``y, cur, prev, old``: ``y + cur - prev`` (2 per element),
    the change against ``old`` (1), its absolute value (1) and the sum
    (1)."""
    return 5 * elements(operands[0]), call_bytes(results, operands)


def roofline_seconds(ops: float, nbytes: float, peak) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bandwidth."""
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
