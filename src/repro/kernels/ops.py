"""Public jit-ready wrappers around the Pallas kernels.

Dispatch policy: kernels run *compiled* on backends with a Pallas
lowering — TPU (Mosaic) and GPU (Triton) — and in ``interpret=True`` mode
elsewhere (this container is CPU-only — interpret mode executes the kernel
body in Python, validating semantics against :mod:`repro.kernels.ref`).
The backend also picks the kernel *family* where two exist: TPU-structured
kernels carry state across the sequential innermost grid axis, GPU ones
loop in-kernel (see the flash_attention/rwkv6_scan module docstrings).
Set ``repro.kernels.ops.FORCE_REF = True`` to bypass kernels entirely (used
by models on hot training paths where the interpreted kernel would dominate
CPU test time).

Tile/block sizes are never hardcoded here: every dispatch resolves its
launch parameters through :mod:`repro.kernels.tuning` (overrides > committed
per-backend tables > backend heuristics).  Call sites outside
``repro.kernels`` must do the same — pass ``tuner=`` or explicit
``KernelTuner`` overrides, not raw integers (reprolint RL010).
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref, tuning
from .elementwise import (LANES, ddim_fused_pallas, parareal_update_pallas,
                          parareal_update_residual_pallas)
from .flash_attention import flash_attention_bwd, flash_attention_fwd
from .rwkv6_scan import rwkv6_wkv_pallas

FORCE_REF = False

# backends with a compiled Pallas lowering: Mosaic (tpu) and Triton (gpu).
# Everything else runs the kernels interpreted (semantics-validation only).
_COMPILED_BACKENDS = ("tpu", "gpu")


def _interpret() -> bool:
    return jax.default_backend() not in _COMPILED_BACKENDS


def _plat() -> str:
    """Kernel family for the current backend ("gpu" Triton structure vs
    "tpu" grid-carried structure; the latter is also the interpret-mode
    default elsewhere)."""
    return "gpu" if jax.default_backend() == "gpu" else "tpu"


def _resolve(kernel: str, tuner: Optional[tuning.KernelTuner], *,
             dtype=None, shape=None, **explicit) -> tuning.KernelConfig:
    """Resolve a kernel config, treating non-None explicit kwargs as
    overrides (an explicitly passed size always wins and marks the config
    ``source="override"``)."""
    overrides = {k: int(v) for k, v in explicit.items() if v is not None}
    t = tuner if tuner is not None else tuning.get_tuner()
    return t.resolve(kernel, dtype=dtype, shape=shape,
                     overrides=overrides or None)


# backends where the default path needs no warning: tpu/gpu run the
# compiled kernels, cpu is the known interpret-mode test/dev tier
_QUIET_BACKENDS = ("tpu", "gpu", "cpu")
_warned_degraded = False


def fused_default() -> bool:
    """Whether the fused elementwise Pallas path is on by default.

    Capability-driven: True exactly on backends with a *compiled* Pallas
    lowering (``_COMPILED_BACKENDS`` — TPU via Mosaic, GPU via Triton).
    Elsewhere the kernels only exist in ``interpret=True`` mode
    (Python-executed, for semantics validation), which would dominate the
    sampler's runtime, so e.g. CPU defaults to the pure-jnp reference
    path.  ``FORCE_REF`` force-disables the kernels regardless of backend.

    On an accelerator backend with no Pallas lowering (e.g. a plugin
    backend), the silent fallback is a real perf surprise — the deployment
    paid for an accelerator and the fused update quietly runs unfused — so
    the first call emits one structured ``UserWarning`` naming the backend
    and the knobs (``use_fused`` / ``FORCE_REF`` / the
    ``repro.kernels.tuning`` tables that would size a future lowering);
    subsequent calls stay silent.
    """
    backend = jax.default_backend()
    global _warned_degraded
    if not FORCE_REF and backend not in _QUIET_BACKENDS \
            and not _warned_degraded:
        _warned_degraded = True
        warnings.warn(
            f"repro.kernels: fused Pallas elementwise path is OFF by "
            f"default on backend={backend!r} (compiled kernels ship for "
            f"{_COMPILED_BACKENDS}; elsewhere they exist in interpret "
            f"mode, which would dominate runtime) — the pure-jnp "
            f"reference path is used instead.  Pass use_fused=True to "
            f"force the kernels, set repro.kernels.ops.FORCE_REF=True to "
            f"silence this by pinning the reference path, or — once a "
            f"lowering exists for this backend — add it to "
            f"_COMPILED_BACKENDS and commit a "
            f"repro.kernels.tuning table for it.",
            UserWarning, stacklevel=2)
    return (not FORCE_REF) and backend in _COMPILED_BACKENDS


# --------------------------------------------------------------------------
# Flash attention (custom_vjp; Pallas fwd + Pallas bwd)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, causal, window, scale, block_q, block_k, block_b,
           num_warps, num_stages, plat):
    # the primal is the inference forward: no logsumexp to write
    o, _ = flash_attention_fwd(q, k, v, causal=causal, window=window,
                               scale=scale, block_q=block_q, block_k=block_k,
                               block_b=block_b, num_warps=num_warps,
                               num_stages=num_stages, plat=plat,
                               with_lse=False, interpret=_interpret())
    return o


def _flash_fwd(q, k, v, causal, window, scale, block_q, block_k, block_b,
               num_warps, num_stages, plat):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 scale=scale, block_q=block_q, block_k=block_k,
                                 block_b=block_b, num_warps=num_warps,
                                 num_stages=num_stages, plat=plat,
                                 interpret=_interpret())
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, scale, block_q, block_k, block_b, num_warps,
               num_stages, plat, res, do):
    q, k, v, o, lse = res
    dq, dk_g, dv_g = flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, num_warps=num_warps,
        num_stages=num_stages, plat=plat, interpret=_interpret())
    group = q.shape[0] // k.shape[0]
    if group > 1:  # reduce GQA groups: (BH,...) -> (BKV,...)
        dk_g = dk_g.reshape(k.shape[0], group, *k.shape[1:]).sum(axis=1)
        dv_g = dv_g.reshape(v.shape[0], group, *v.shape[1:]).sum(axis=1)
    return dq, dk_g.astype(k.dtype), dv_g.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = True, window: Optional[int] = None,
              scale: Optional[float] = None,
              block_q: Optional[int] = None, block_k: Optional[int] = None,
              num_warps: Optional[int] = None,
              num_stages: Optional[int] = None,
              tuner: Optional[tuning.KernelTuner] = None,
              plat: Optional[str] = None,
              use_kernel: Optional[bool] = None):
    """(B, Hq, Sq, D) x (B, Hkv, Sk, D) -> (B, Hq, Sq, D). GQA via Hq%Hkv==0.

    Block sizes resolve through the tuning seam (``tuner`` or the process
    default); explicit ``block_q``/``block_k``/``num_warps``/``num_stages``
    act as overrides.  The resolved ``block_b`` caps the (sample, head)
    rows one forward grid step takes; it is cut to the largest multiple
    of the GQA group that divides ``B * Hq`` (:func:`tuning.pick_block_b`).
    ``plat`` pins the kernel family (tests exercise the Triton-structured
    kernels on CPU with ``plat="gpu"``); default follows the backend.
    """
    if use_kernel is None:
        use_kernel = not FORCE_REF
    if not use_kernel:
        return ref.attention(q, k, v, causal=causal, window=window, scale=scale)
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    scale = float(scale) if scale is not None else float(d) ** -0.5
    cfg = _resolve("flash", tuner, dtype=q.dtype, shape=(sq, sk, d),
                   block_q=block_q, block_k=block_k, num_warps=num_warps,
                   num_stages=num_stages)
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    block_b = tuning.pick_block_b(b * hq, hq // hkv, cfg.params["block_b"])
    o = _flash(qf, kf, vf, causal, window, scale,
               cfg.params["block_q"], cfg.params["block_k"], block_b,
               cfg.params.get("num_warps"), cfg.params.get("num_stages"),
               plat if plat is not None else _plat())
    return o.reshape(b, hq, sq, d)


# --------------------------------------------------------------------------
# RWKV6 WKV (kernel fwd; ref-autodiff bwd)
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _wkv(r, k, v, w, u, s0):
    out, _ = ref.rwkv6_wkv(r, k, v, w, u, s0)
    return out


def _wkv_fwd(r, k, v, w, u, s0):
    return _wkv(r, k, v, w, u, s0), (r, k, v, w, u, s0)


def _wkv_bwd(res, dout):
    r, k, v, w, u, s0 = res
    _, vjp = jax.vjp(lambda *a: ref.rwkv6_wkv(*a)[0], r, k, v, w, u, s0)
    return vjp(dout)


_wkv.defvjp(_wkv_fwd, _wkv_bwd)


def rwkv6_wkv(r, k, v, w, u, state=None, *, chunk: Optional[int] = None,
              tuner: Optional[tuning.KernelTuner] = None,
              plat: Optional[str] = None,
              use_kernel: Optional[bool] = None):
    """r,k,w: (B,H,T,Dk); v: (B,H,T,Dv); u: (H,Dk); state: (B,H,Dk,Dv).

    Returns (out (B,H,T,Dv), final_state).  Kernel forward; reference
    autodiff backward (training uses the pure-JAX chunked path in models).
    The TPU family's chunk size comes from the tuning seam
    (``chunk_target`` capped to a divisor of T); an explicit ``chunk``
    overrides.  The GPU family streams timesteps in-kernel and ignores it.
    """
    bsz, h, t, dk = r.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((bsz, h, dk, dv), jnp.float32)
    if use_kernel is None:
        use_kernel = not FORCE_REF
    if not use_kernel:
        return ref.rwkv6_wkv(r, k, v, w, u, state)
    if chunk is None:
        cfg = _resolve("rwkv6", tuner, dtype=r.dtype, shape=(t, dk))
        c = tuning.pick_chunk(t, cfg.params["chunk_target"])
    else:
        c = int(chunk)
    flat = lambda x: x.reshape(bsz * h, *x.shape[2:])
    u_t = jnp.tile(u, (bsz, 1))
    out, s_fin = rwkv6_wkv_pallas(flat(r), flat(k), flat(v), flat(w), u_t,
                                  flat(state), chunk=c,
                                  plat=plat if plat is not None else _plat(),
                                  interpret=_interpret())
    return (out.reshape(bsz, h, t, dv),
            s_fin.reshape(bsz, h, dk, dv))


# --------------------------------------------------------------------------
# Fused elementwise ops
# --------------------------------------------------------------------------

def _to_2d(x, row_multiple: int = 1):
    """Flatten/pad to (rows, 128); ``row_multiple`` additionally pads the
    row count to a multiple of the kernel's tile size (zero rows) when it
    exceeds one tile, so a fixed tile size never maps a partial tile past
    the array — compiled Pallas reads of out-of-bounds block regions are
    unspecified (interpret mode zero-fills, masking the bug on CPU), which
    matters whenever per-tile *reductions* are consumed, not just the
    masked elementwise outputs.  (At ``rows <= row_multiple`` the kernels
    shrink the tile to ``rows`` exactly — a single full tile.)"""
    n = x.size
    rows = -(-n // LANES)
    if rows > row_multiple:
        rows += (-rows) % row_multiple
    pad = rows * LANES - n
    flat = x.reshape(-1)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES), n


def _tile_rows(tuner, dtype, shape, block_rows) -> int:
    cfg = _resolve("elementwise", tuner, dtype=dtype, shape=shape,
                   tile_rows=block_rows)
    return cfg.params["tile_rows"]


def ddim_fused(x, eps, a, b, *, tuner: Optional[tuning.KernelTuner] = None,
               block_rows: Optional[int] = None,
               use_kernel: Optional[bool] = None):
    if use_kernel is None:
        use_kernel = not FORCE_REF
    if not use_kernel:
        return ref.ddim_fused(x, eps, a, b)
    tr = _tile_rows(tuner, x.dtype, x.shape, block_rows)
    x2, n = _to_2d(x)
    e2, _ = _to_2d(eps)
    ab = jnp.stack([jnp.asarray(a, jnp.float32),
                    jnp.asarray(b, jnp.float32)]).reshape(1, 2)
    o = ddim_fused_pallas(x2, e2, ab, block_rows=tr, interpret=_interpret())
    return o.reshape(-1)[:n].reshape(x.shape)


def parareal_update(y, cur, prev, *,
                    tuner: Optional[tuning.KernelTuner] = None,
                    block_rows: Optional[int] = None,
                    use_kernel: Optional[bool] = None):
    """Returns (y + cur - prev, sum|cur - prev|) fused in one pass."""
    if use_kernel is None:
        use_kernel = not FORCE_REF
    if not use_kernel:
        return ref.parareal_update(y, cur, prev)
    # pad rows to the resolved tile size: the L1 partials are consumed, so
    # the last tile must not read past the array (see _to_2d)
    tr = _tile_rows(tuner, y.dtype, y.shape, block_rows)
    y2, n = _to_2d(y, row_multiple=tr)
    c2, _ = _to_2d(cur, row_multiple=tr)
    p2, _ = _to_2d(prev, row_multiple=tr)
    o, partials = parareal_update_pallas(y2, c2, p2, block_rows=tr,
                                         interpret=_interpret())
    return o.reshape(-1)[:n].reshape(y.shape), jnp.sum(partials)


def _to_2d_per_sample(x):
    """(K, ...) -> (K * rows_per_sample, 128) with per-sample padding, so
    row tiles never straddle two samples and per-tile partials regroup into
    per-sample sums.  Returns (x2d, rows_per_sample, per_sample_size)."""
    k = x.shape[0]
    n = x.size // k
    rows = -(-n // LANES)
    pad = rows * LANES - n
    flat = x.reshape(k, n)
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(k * rows, LANES), rows, n


def parareal_update_residual(y, cur, prev, old, *, batched: bool = False,
                             batch_dims: Optional[int] = None,
                             tuner: Optional[tuning.KernelTuner] = None,
                             block_rows: Optional[int] = None,
                             use_kernel: Optional[bool] = None):
    """Fused predictor-corrector update + convergence-residual partials.

    Returns ``(y + cur - prev, sum|out - old|)`` in one pass — ``old`` is
    the block's previous trajectory value, so the second output is exactly
    the raw L1 sum behind the engine's ``l1_mean`` convergence norm (the
    kernel's per-tile partials feed it directly; no second full-tensor
    reduction).  ``batch_dims`` picks the residual's reduction shape — the
    number of leading axes preserved: 0 -> scalar, 1 -> per-sample ``(K,)``
    (legacy spelling ``batched=True``), 2 -> per-block per-sample
    ``(B, K)``, the sliding-window frontier feed (each leading-axes slice
    gets its own tile rows, so partials never straddle two slices).
    Tile rows resolve through the tuning seam; ``block_rows`` overrides
    (per-sample paths still cap it to a divisor of the sample row count).
    """
    if use_kernel is None:
        use_kernel = not FORCE_REF
    if not use_kernel:
        return ref.parareal_update_residual(y, cur, prev, old,
                                            batched=batched,
                                            batch_dims=batch_dims)
    nd = (1 if batched else 0) if batch_dims is None else int(batch_dims)
    if not 0 <= nd < y.ndim + 1:
        raise ValueError(f"batch_dims={nd} out of range for ndim={y.ndim}")
    if nd >= 2:
        # flatten the preserved leading axes into one pseudo-sample axis,
        # run the per-sample path, and restore the leading shape on the
        # partials — each (block, sample) slice keeps its own padded rows
        lead = y.shape[:nd]
        flat = lambda t: t.reshape((-1,) + t.shape[nd:])
        out, resid = parareal_update_residual(
            flat(y), flat(cur), flat(prev), flat(old), batch_dims=1,
            tuner=tuner, block_rows=block_rows, use_kernel=True)
        return out.reshape(y.shape), resid.reshape(lead)
    tr = _tile_rows(tuner, y.dtype, y.shape, block_rows)
    if nd == 0:
        # pad rows to the tile size so the consumed partials never cover
        # an out-of-bounds block region on compiled backends (zero rows
        # contribute |0 + 0 - 0 - 0| = 0 to the L1 sums)
        y2, n = _to_2d(y, row_multiple=tr)
        c2, _ = _to_2d(cur, row_multiple=tr)
        p2, _ = _to_2d(prev, row_multiple=tr)
        x2, _ = _to_2d(old, row_multiple=tr)
        o, partials = parareal_update_residual_pallas(
            y2, c2, p2, x2, block_rows=tr, interpret=_interpret())
        return o.reshape(-1)[:n].reshape(y.shape), jnp.sum(partials)
    k = y.shape[0]
    y2, rows, n = _to_2d_per_sample(y)
    c2, _, _ = _to_2d_per_sample(cur)
    p2, _, _ = _to_2d_per_sample(prev)
    x2, _, _ = _to_2d_per_sample(old)
    br = tuning.sample_tile_rows(rows, tr)
    o, partials = parareal_update_residual_pallas(
        y2, c2, p2, x2, block_rows=br, interpret=_interpret())
    resid = partials.reshape(k, rows // br).sum(axis=1)
    out = o.reshape(k, rows * LANES)[:, :n].reshape(y.shape)
    return out, resid
