"""Flash attention Pallas kernels (forward + backward), TPU and GPU.

Two kernel families share the math (same ``_mask`` geometry, same
online-softmax update, same ragged-row hygiene) but differ in how the KV
reduction is structured, because the two lowerings disagree about grid
semantics:

TPU (Mosaic) family — ``_fwd_kernel`` / ``_dq_kernel`` / ``_dkv_kernel``:
  * online-softmax accumulators live in VMEM scratch and are carried across
    the *innermost sequential grid dimension* (TPU grids iterate the last
    axis sequentially per core — the idiomatic replacement for a CUDA
    thread-block loop over KV tiles);
  * tiles default to (128, 128): the MXU systolic array is 128x128; a
    head_dim below 128 runs at a fraction of the lanes (nothing pads it);
  * causal and sliding-window masking skip fully-masked KV tiles with
    ``pl.when`` (no MXU work issued for skipped tiles);
  * where the whole sequence fits one tile (``sq <= block_q`` and
    ``sk <= block_k``, a DiT's 64 tokens), ``_fwd_kernel_batched`` takes
    ``block_b`` (sample, head) rows a grid step with a single-pass softmax
    and no scratch: each grid step costs a fixed ~0.5 µs on a v5e, far
    more than one 64x64 head's math, so one head a step is all overhead.

GPU (Triton) family — ``_fwd_kernel_gpu`` / ``_dq_kernel_gpu`` /
``_dkv_kernel_gpu``:
  * Triton grid cells are concurrent CUDA blocks — nothing carries across
    grid steps, so the reduction axis moves *inside* the kernel: grid is
    (batch*heads, q-tiles) and each program walks its live KV tiles with a
    ``lax.fori_loop`` whose accumulators are loop carries (registers);
  * the reduced operand arrives as one whole (padded) ref and tiles are
    cut with ``pl.load``/``pl.dslice``; the wrappers zero-pad the walked
    axis to a tile multiple while masks keep using the true lengths;
  * tile skipping becomes loop *bounds*: the causal/window live-tile
    predicates solved for the loop variable give [lo, hi) directly, so
    masked tiles are never visited at all;
  * ``num_warps``/``num_stages`` (tuning-seam params) reach Triton via
    ``compat.gpu_compiler_params``.

Both families are exercised in ``interpret=True`` mode on CPU (the parity
suite); tile sizes come from :mod:`repro.kernels.tuning`.

GQA is handled in the BlockSpec index_map (kv head = q head // group), so
grouped KV is never materialized/repeated in HBM — both families (the
batched forward repeats a block's kv heads in VMEM).

Forward saves the per-row logsumexp where asked (``with_lse``, the
custom-vjp forward); the inference forward of the batched path writes
none.  Backward recomputes probabilities tile-by-tile (two kernels: dQ
over KV tiles; dK/dV over Q tiles).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

NEG_INF = -1e30


def _row_valid(bsz, start, limit):
    """(bsz, 1) bool mask for ragged-tile padding rows."""
    idx = start + jax.lax.broadcasted_iota(jnp.int32, (bsz, 1), 0)
    return idx < limit


def _clean(x, valid):
    """Zero padded rows with where (interpret mode poisons OOB reads with
    NaN, and NaN * 0 == NaN — multiplication cannot scrub them)."""
    return jnp.where(valid, x, 0.0)


def _mask(bq, bk, iq, ik, sq, sk, causal, window):
    """Boolean keep-mask for a (bq, bk) tile; positions right-aligned.

    Also masks ragged-tile padding rows/cols (q >= sq or k >= sk)."""
    qraw = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    qpos = qraw + (sk - sq)
    keep = jnp.logical_and(qraw < sq, kpos < sk)
    if causal:
        keep = jnp.logical_and(keep, kpos <= qpos)
    if window is not None:
        keep = jnp.logical_and(keep, kpos > qpos - window)
    return keep


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc, *,
                scale, causal, window, sq, sk, bq, bk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # tile skipping: causal / sliding-window tiles with no live entry
    q_last = iq * bq + bq - 1 + (sk - sq)
    k_first = ik * bk
    live = True
    if causal:
        live = k_first <= q_last
    if window is not None:
        q_first = iq * bq + (sk - sq)
        k_last = ik * bk + bk - 1
        live = jnp.logical_and(live, k_last > q_first - window)

    @pl.when(live)
    def _compute():
        kv_valid = _row_valid(bk, ik * bk, sk)
        q = _clean(q_ref[0].astype(jnp.float32), _row_valid(bq, iq * bq, sq))
        k = _clean(k_ref[0].astype(jnp.float32), kv_valid)
        v = _clean(v_ref[0].astype(jnp.float32), kv_valid)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = _mask(bq, bk, iq, ik, sq, sk, causal, window)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # guard fully-masked rows: m_new == NEG_INF would give exp(0) == 1
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)          # fully-masked rows -> 0
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_sc[...] + jnp.log(l_safe)


def _fwd_kernel_batched(q_ref, k_ref, v_ref, o_ref, *lse_ref, scale, causal,
                        window, sq, sk, group):
    """One grid step over a block of (sample, head) rows whose whole
    sequence fits one tile: the softmax in a single pass, so no scratch
    and no init/finalize.  Same f32 arithmetic, mask and fully-masked-row
    guard as ``_fwd_kernel`` on one tile."""
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    if group > 1:  # the block's q rows of one kv head are contiguous
        k = jnp.repeat(k, group, axis=0)
        v = jnp.repeat(v, group, axis=0)
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    keep = _mask(sq, sk, 0, 0, sq, sk, causal, window)
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.max(s, axis=2, keepdims=True)
    # guard fully-masked rows: m == NEG_INF would give exp(0) == 1
    p = jnp.where(keep, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=2, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=jnp.float32)
    l_safe = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
    o_ref[...] = (acc / l_safe).astype(o_ref.dtype)
    if lse_ref:
        lse_ref[0][...] = m + jnp.log(l_safe)


def _flash_fwd_batched(q, k, v, *, causal, window, scale, block_b, with_lse,
                       interpret):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    if bh % block_b or block_b % group:
        raise ValueError(f"block_b={block_b} must divide bh={bh} and be a "
                         f"multiple of the GQA group {group}")
    kernel = functools.partial(_fwd_kernel_batched, scale=scale,
                               causal=causal, window=window, sq=sq, sk=sk,
                               group=group)
    out_specs = [pl.BlockSpec((block_b, sq, d), lambda i: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((block_b, sq, 1), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32))
    kv_spec = pl.BlockSpec((block_b // group, sk, d), lambda i: (i, 0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(bh // block_b,),
        in_specs=[pl.BlockSpec((block_b, sq, d), lambda i: (i, 0, 0)),
                  kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="srds_flash_fwd",
    )(q, k, v)
    return outs[0], (outs[1][..., 0] if with_lse else None)


# --------------------------------------------------------------------------
# GPU (Triton) family: reduction axis inside the kernel, carries in registers
# --------------------------------------------------------------------------

def _pad_axis(x, axis, multiple):
    """Zero-pad ``x`` along ``axis`` to a multiple of ``multiple``."""
    n = x.shape[axis]
    pad = (-n) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _kv_bounds(iq, *, causal, window, sq, sk, bq, bk, nk):
    """[lo, hi) of live KV tiles for q-tile ``iq`` (loop-bound form of the
    TPU kernels' ``pl.when`` live predicates; positions right-aligned)."""
    q_last = iq * bq + bq - 1 + (sk - sq)
    hi = nk
    if causal:
        hi = jnp.clip(q_last // bk + 1, 0, nk)
    lo = 0
    if window is not None:
        q_first = iq * bq + (sk - sq)
        lo = jnp.maximum(0, (q_first - window + 1) // bk)
    return lo, hi


def _q_bounds(ik, *, causal, window, sq, sk, bq, bk, nq):
    """[lo, hi) of live Q tiles for kv-tile ``ik`` (the dK/dV loop)."""
    lo = 0
    if causal:
        lo = jnp.maximum(0, (ik * bk - (sk - sq)) // bq)
    hi = nq
    if window is not None:
        x = ik * bk + bk - 1 + window - (sk - sq)
        hi = jnp.clip((x + bq - 1) // bq, 0, nq)
    return lo, hi


def _load_tile(refp, start, size):
    """(size, D) f32 tile cut from a whole-axis 2D ref at row ``start``."""
    return pl.load(refp, (pl.dslice(start, size), slice(None))).astype(
        jnp.float32)


def _fwd_kernel_gpu(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                    scale, causal, window, sq, sk, bq, bk, nk):
    iq = pl.program_id(1)
    q = _clean(q_ref[...].astype(jnp.float32), _row_valid(bq, iq * bq, sq))
    d = q.shape[-1]
    lo, hi = _kv_bounds(iq, causal=causal, window=window, sq=sq, sk=sk,
                        bq=bq, bk=bk, nk=nk)

    def body(ik, carry):
        acc, m_prev, l_prev = carry
        kv_valid = _row_valid(bk, ik * bk, sk)
        k = _clean(_load_tile(k_ref, ik * bk, bk), kv_valid)
        v = _clean(_load_tile(v_ref, ik * bk, bk), kv_valid)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = _mask(bq, bk, iq, ik, sq, sk, causal, window)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        # guard fully-masked rows: m_new == NEG_INF would give exp(0) == 1
        p = jnp.where(keep, jnp.exp(s - m_new[:, None]), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc, m, l = jax.lax.fori_loop(
        lo, hi, body, (jnp.zeros((bq, d), jnp.float32),
                       jnp.full((bq,), NEG_INF, jnp.float32),
                       jnp.zeros((bq,), jnp.float32)))
    l_safe = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
    o_ref[...] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[...] = m + jnp.log(l_safe)


def _flash_fwd_gpu(q, k, v, *, causal, window, scale, bq, bk,
                   compiler_params, interpret):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    # the in-kernel loop cuts KV tiles with pl.dslice: pad the walked axis
    # to a tile multiple (masks keep using the true sk)
    kp = _pad_axis(k, 1, bk)
    vp = _pad_axis(v, 1, bk)
    skp = kp.shape[1]
    nk = skp // bk
    kernel = functools.partial(_fwd_kernel_gpu, scale=scale, causal=causal,
                               window=window, sq=sq, sk=sk, bq=bq, bk=bk,
                               nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(bh, pl.cdiv(sq, bq)),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, iq: (b, iq, 0)),
            pl.BlockSpec((None, skp, d), lambda b, iq, g=group: (b // g, 0, 0)),
            pl.BlockSpec((None, skp, d), lambda b, iq, g=group: (b // g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda b, iq: (b, iq, 0)),
            pl.BlockSpec((None, bq), lambda b, iq: (b, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
        name="srds_flash_fwd_gpu",
    )(q, kp, vp)


def _dq_kernel_gpu(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, window, sq, sk, bq, bk, nk):
    iq = pl.program_id(1)
    q_valid = _row_valid(bq, iq * bq, sq)
    q = _clean(q_ref[...].astype(jnp.float32), q_valid)
    do = _clean(do_ref[...].astype(jnp.float32), q_valid)
    lse = jnp.where(q_valid[:, 0], lse_ref[...], 0.0)
    delta = jnp.where(q_valid[:, 0], delta_ref[...], 0.0)
    d = q.shape[-1]
    lo, hi = _kv_bounds(iq, causal=causal, window=window, sq=sq, sk=sk,
                        bq=bq, bk=bk, nk=nk)

    def body(ik, dq_acc):
        kv_valid = _row_valid(bk, ik * bk, sk)
        k = _clean(_load_tile(k_ref, ik * bk, bk), kv_valid)
        v = _clean(_load_tile(v_ref, ik * bk, bk), kv_valid)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = _mask(bq, bk, iq, ik, sq, sk, causal, window)
        p = jnp.where(keep, jnp.exp(jnp.where(keep, s, NEG_INF)
                                    - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(keep, p * (dp - delta[:, None]) * scale, 0.0)
        return dq_acc + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _dkv_kernel_gpu(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, window, sq, sk,
                    bq, bk, nq):
    ik = pl.program_id(1)
    kv_valid = _row_valid(bk, ik * bk, sk)
    k = _clean(k_ref[...].astype(jnp.float32), kv_valid)
    v = _clean(v_ref[...].astype(jnp.float32), kv_valid)
    d = k.shape[-1]
    lo, hi = _q_bounds(ik, causal=causal, window=window, sq=sq, sk=sk,
                       bq=bq, bk=bk, nq=nq)

    def body(iq, carry):
        dk_acc, dv_acc = carry
        q_valid = _row_valid(bq, iq * bq, sq)
        q = _clean(_load_tile(q_ref, iq * bq, bq), q_valid)
        do = _clean(_load_tile(do_ref, iq * bq, bq), q_valid)
        lse = jnp.where(q_valid[:, 0],
                        pl.load(lse_ref, (pl.dslice(iq * bq, bq),)), 0.0)
        delta = jnp.where(q_valid[:, 0],
                          pl.load(delta_ref, (pl.dslice(iq * bq, bq),)), 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = _mask(bq, bk, iq, ik, sq, sk, causal, window)
        p = jnp.where(keep, jnp.exp(jnp.where(keep, s, NEG_INF)
                                    - lse[:, None]), 0.0)       # (bq, bk)
        dv_acc = dv_acc + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(keep, p * (dp - delta[:, None]) * scale, 0.0)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    dk, dv = jax.lax.fori_loop(
        lo, hi, body, (jnp.zeros((bk, d), jnp.float32),
                       jnp.zeros((bk, d), jnp.float32)))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_gpu(q, k, v, do, lse, delta, *, causal, window, scale,
                   bq, bk, compiler_params, interpret):
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    kp = _pad_axis(k, 1, bk)
    vp = _pad_axis(v, 1, bk)
    skp, nk = kp.shape[1], kp.shape[1] // bk
    kq = functools.partial(_dq_kernel_gpu, scale=scale, causal=causal,
                           window=window, sq=sq, sk=sk, bq=bq, bk=bk, nk=nk)
    dq = pl.pallas_call(
        kq,
        grid=(bh, pl.cdiv(sq, bq)),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda b, iq: (b, iq, 0)),
            pl.BlockSpec((None, skp, d), lambda b, iq, g=group: (b // g, 0, 0)),
            pl.BlockSpec((None, skp, d), lambda b, iq, g=group: (b // g, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda b, iq: (b, iq, 0)),
            pl.BlockSpec((None, bq), lambda b, iq: (b, iq)),
            pl.BlockSpec((None, bq), lambda b, iq: (b, iq)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda b, iq: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
        name="srds_flash_dq_gpu",
    )(q, kp, vp, do, lse, delta)

    # dK/dV walks Q tiles in-kernel: pad the q-side arrays instead
    qp = _pad_axis(q, 1, bq)
    dop = _pad_axis(do, 1, bq)
    lsep = _pad_axis(lse, 1, bq)
    deltap = _pad_axis(delta, 1, bq)
    sqp, nq = qp.shape[1], qp.shape[1] // bq
    kkv = functools.partial(_dkv_kernel_gpu, scale=scale, causal=causal,
                            window=window, sq=sq, sk=sk, bq=bq, bk=bk, nq=nq)
    dk, dv = pl.pallas_call(
        kkv,
        grid=(bh, pl.cdiv(sk, bk)),
        in_specs=[
            pl.BlockSpec((None, sqp, d), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((None, sqp, d), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((None, sqp), lambda b, ik: (b, 0)),
            pl.BlockSpec((None, sqp), lambda b, ik: (b, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda b, ik: (b, ik, 0)),
            pl.BlockSpec((None, bk, d), lambda b, ik: (b, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
        name="srds_flash_dkv_gpu",
    )(qp, kp, vp, dop, lsep, deltap)
    return dq, dk, dv


def _gpu_params(num_warps, num_stages):
    kw = {}
    if num_warps is not None:
        kw["num_warps"] = int(num_warps)
    if num_stages is not None:
        kw["num_stages"] = int(num_stages)
    return compat.gpu_compiler_params(**kw)


def flash_attention_fwd(q, k, v, *, causal=True, window=None, scale=None,
                        block_q=128, block_k=128, block_b=1, num_warps=None,
                        num_stages=None, plat="tpu", with_lse=True,
                        interpret=False):
    """q: (BH, Sq, D) already flattened over batch*q_heads; k/v: (BKV, Sk, D).

    ``group = BH // BKV`` kv-sharing factor (GQA) resolved via index_map.
    ``plat`` picks the kernel family ("tpu" grid-carried scratch vs "gpu"
    in-kernel loop; see module docstring) — resolved by the ops layer from
    the backend, orthogonal to ``interpret``.  ``num_warps``/``num_stages``
    only apply to the Triton family.  ``block_b`` > 1 takes that many
    (sample, head) rows a grid step where the sequence fits one tile (TPU
    family); it must divide BH and be a multiple of ``group``.  Returns
    (o (BH, Sq, D), lse (BH, Sq)); lse is None when ``with_lse`` is False.
    """
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    scale = float(scale) if scale is not None else float(d) ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if plat == "gpu":
        o, lse = _flash_fwd_gpu(q, k, v, causal=causal, window=window,
                                scale=scale, bq=bq, bk=bk,
                                compiler_params=_gpu_params(num_warps,
                                                            num_stages),
                                interpret=interpret)
        return o, (lse if with_lse else None)
    if block_b > 1 and bq == sq and bk == sk:
        return _flash_fwd_batched(q, k, v, causal=causal, window=window,
                                  scale=scale, block_b=block_b,
                                  with_lse=with_lse, interpret=interpret)
    grid = (bh, pl.cdiv(sq, bq), pl.cdiv(sk, bk))

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               window=window, sq=sq, sk=sk, bq=bq, bk=bk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, iq, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda b, iq, ik, g=group: (b // g, ik, 0)),
        ],
        # the LSE leaves as a (bh, sq, 1) column (sliced back to (bh, sq)):
        # Mosaic refuses a (1, bq) block of a (bh, sq) array
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="srds_flash_fwd",
    )(q, k, v)
    return o, (lse[..., 0] if with_lse else None)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, window, sq, sk, bq, bk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_last = iq * bq + bq - 1 + (sk - sq)
    live = (ik * bk <= q_last) if causal else True
    if window is not None:
        q_first = iq * bq + (sk - sq)
        live = jnp.logical_and(live, ik * bk + bk - 1 > q_first - window)

    @pl.when(live)
    def _compute():
        q_valid = _row_valid(bq, iq * bq, sq)
        kv_valid = _row_valid(bk, ik * bk, sk)
        q = _clean(q_ref[0].astype(jnp.float32), q_valid)
        k = _clean(k_ref[0].astype(jnp.float32), kv_valid)
        v = _clean(v_ref[0].astype(jnp.float32), kv_valid)
        do = _clean(do_ref[0].astype(jnp.float32), q_valid)
        lse = jnp.where(q_valid, lse_ref[0], 0.0)
        delta = jnp.where(q_valid, delta_ref[0], 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = _mask(bq, bk, iq, ik, sq, sk, causal, window)
        s = jnp.where(keep, s, NEG_INF)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(keep, p * (dp - delta) * scale, 0.0)
        dq_acc[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, window, sq, sk, bq, bk):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_last = iq * bq + bq - 1 + (sk - sq)
    live = (ik * bk <= q_last) if causal else True
    if window is not None:
        q_first = iq * bq + (sk - sq)
        live = jnp.logical_and(live, ik * bk + bk - 1 > q_first - window)

    @pl.when(live)
    def _compute():
        q_valid = _row_valid(bq, iq * bq, sq)
        kv_valid = _row_valid(bk, ik * bk, sk)
        q = _clean(q_ref[0].astype(jnp.float32), q_valid)
        k = _clean(k_ref[0].astype(jnp.float32), kv_valid)
        v = _clean(v_ref[0].astype(jnp.float32), kv_valid)
        do = _clean(do_ref[0].astype(jnp.float32), q_valid)
        lse = jnp.where(q_valid, lse_ref[0], 0.0)
        delta = jnp.where(q_valid, delta_ref[0], 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = _mask(bq, bk, iq, ik, sq, sk, causal, window)
        s = jnp.where(keep, s, NEG_INF)
        p = jnp.where(keep, jnp.exp(s - lse), 0.0)   # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = jnp.where(keep, p * (dp - delta) * scale, 0.0)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, window=None,
                        scale=None, block_q=128, block_k=128, num_warps=None,
                        num_stages=None, plat="tpu", interpret=False):
    """Returns (dq (BH,Sq,D), dk_g (BH,Sk,D), dv_g (BH,Sk,D)).

    dk/dv are produced per *q-head* (GQA groups not yet reduced); the ops
    wrapper sums over the group dimension — keeping the kernel free of
    cross-grid-cell reductions.  ``plat``/``num_warps``/``num_stages`` as
    in :func:`flash_attention_fwd`.
    """
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    scale = float(scale) if scale is not None else float(d) ** -0.5
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if plat == "gpu":
        return _flash_bwd_gpu(q, k, v, do, lse, delta, causal=causal,
                              window=window, scale=scale, bq=bq, bk=bk,
                              compiler_params=_gpu_params(num_warps,
                                                          num_stages),
                              interpret=interpret)
    # the TPU family keeps per-row statistics as (bq, 1) columns: a (1, bq)
    # block of a (bh, sq) array breaks Mosaic's (8, 128) block rule
    lse3, delta3 = lse[..., None], delta[..., None]

    kq = functools.partial(_dq_kernel, scale=scale, causal=causal,
                           window=window, sq=sq, sk=sk, bq=bq, bk=bk)
    dq = pl.pallas_call(
        kq,
        grid=(bh, pl.cdiv(sq, bq), pl.cdiv(sk, bk)),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, iq, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda b, iq, ik, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="srds_flash_dq",
    )(q, k, v, do, lse3, delta3)

    kkv = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                            window=window, sq=sq, sk=sk, bq=bq, bk=bk)
    dk, dv = pl.pallas_call(
        kkv,
        grid=(bh, pl.cdiv(sk, bk), pl.cdiv(sq, bq)),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ik, iq, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ik, iq, g=group: (b // g, ik, 0)),
            pl.BlockSpec((1, bq, d), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ik, iq: (b, iq, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, ik, iq: (b, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ik, iq: (b, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="srds_flash_dkv",
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv
