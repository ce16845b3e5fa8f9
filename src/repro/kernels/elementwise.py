"""Fused elementwise Pallas kernels for the sampler's inner loop.

Between denoiser calls the sampler is elementwise-bound; fusing the DDIM
update (5 reads/1 write naive -> 2 reads/1 write fused) and the Parareal
predictor-corrector (+ residual reduction, saving a separate full pass for
the convergence norm) removes HBM round-trips on the latency-critical path.

Layout: the ops wrapper flattens/pads operands to (rows, 128) — the TPU
native lane width, and a warp-friendly lane count on the Triton lowering
— and tiles rows.  These kernels are lowering-portable as written: no
scratch is carried across grid steps (each row tile is independent, and
the reduction outputs are per-tile partials summed by the wrapper), so
the same body compiles on both the Mosaic (TPU) and Triton (GPU)
pipelines.  Tile sizes are resolved per backend by
:mod:`repro.kernels.tuning`; the constants here are the interpret-mode
anchors that seam's heuristics reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# Heuristic default row-tile size (the tuning seam's cpu/tpu anchor; GPU
# resolves a smaller tile).  The ops wrappers pad row counts to a multiple
# of the *resolved* tile size whenever per-tile reduction partials are
# consumed (a partial tile mapped past the array is an unspecified read on
# compiled backends) — resolve once, then pad and launch with the same
# value.
TILE_ROWS = 256
# Per-tile reduction partials are written as one lane-dense (8, 128) f32
# tile per grid step (the scalar broadcast across it): Mosaic stores no
# scalars to VMEM and requires the last two block dims to be (8, 128)
# multiples, so a (1, 1) partial block does not compile for the chip.  The
# entry points slice element [0, 0] of each tile back out.
PARTIAL_TILE = (8, LANES)


def _ddim_kernel(x_ref, e_ref, ab_ref, o_ref):
    a = ab_ref[0, 0]
    b = ab_ref[0, 1]
    x = x_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    x0 = (x - jnp.sqrt(1.0 - a) * e) * jax.lax.rsqrt(a)
    o_ref[...] = (jnp.sqrt(b) * x0 + jnp.sqrt(1.0 - b) * e).astype(o_ref.dtype)


def ddim_fused_pallas(x2d, eps2d, ab, *, block_rows=TILE_ROWS,
                      interpret=False):
    """x2d/eps2d: (R, 128); ab: (1, 2) [alpha_bar_from, alpha_bar_to]."""
    r = x2d.shape[0]
    br = min(block_rows, r)
    return pl.pallas_call(
        _ddim_kernel,
        grid=(pl.cdiv(r, br),),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
        name="srds_ddim_fused",
    )(x2d, eps2d, ab)


def _parareal_resid_kernel(y_ref, c_ref, p_ref, x_ref, o_ref, r_ref):
    y = y_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    xo = x_ref[...].astype(jnp.float32)
    out = y + c - p
    o_ref[...] = out.astype(o_ref.dtype)
    r_ref[...] = jnp.full(r_ref.shape, jnp.sum(jnp.abs(out - xo)), jnp.float32)


def parareal_update_residual_pallas(y2d, c2d, p2d, x2d, *,
                                    block_rows=TILE_ROWS, interpret=False):
    """Fused ``out = y + cur - prev`` with per-tile L1(out - x_old) partials.

    This is the convergence-norm feed: ``x2d`` holds the block's previous
    trajectory value, so summing the partials gives exactly the raw L1 sum
    behind the engine's ``l1_mean`` residual — the separate full-tensor
    reduction pass disappears.  Returns ``(out (R, 128),
    partials (tiles, 1) f32)``; the caller sums (or per-sample reshapes)
    the partials.  ``block_rows`` must tile the row count so partials can
    be regrouped per sample by the ops wrapper.
    """
    r = y2d.shape[0]
    br = min(block_rows, r)
    tiles = pl.cdiv(r, br)
    out, partials = pl.pallas_call(
        _parareal_resid_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1,) + PARTIAL_TILE, lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(y2d.shape, y2d.dtype),
            jax.ShapeDtypeStruct((tiles,) + PARTIAL_TILE, jnp.float32),
        ],
        interpret=interpret,
        name="srds_parareal_update_residual",
    )(y2d, c2d, p2d, x2d)
    return out, partials[:, 0, :1]


def _parareal_kernel(y_ref, c_ref, p_ref, o_ref, r_ref):
    y = y_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    o_ref[...] = (y + c - p).astype(o_ref.dtype)
    r_ref[...] = jnp.full(r_ref.shape, jnp.sum(jnp.abs(c - p)), jnp.float32)


def parareal_update_pallas(y2d, c2d, p2d, *, block_rows=TILE_ROWS,
                           interpret=False):
    """Fused out = y + cur - prev with per-tile L1(cur - prev) partials.

    Returns (out (R, 128), partials (tiles, 1) f32) — caller sums partials.
    """
    r = y2d.shape[0]
    br = min(block_rows, r)
    tiles = pl.cdiv(r, br)
    out, partials = pl.pallas_call(
        _parareal_kernel,
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1,) + PARTIAL_TILE, lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(y2d.shape, y2d.dtype),
            jax.ShapeDtypeStruct((tiles,) + PARTIAL_TILE, jnp.float32),
        ],
        interpret=interpret,
        name="srds_parareal_update",
    )(y2d, c2d, p2d)
    return out, partials[:, 0, :1]
