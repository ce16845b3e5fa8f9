"""Distributed-optimization collectives.

``compressed_psum_mean``: int8-quantized gradient all-reduce with
error-feedback (1-bit-Adam-family trick, int8 variant).  Each shard
quantizes (grad + ef_carry) to int8 with a per-tensor scale, psums the int8
payload (exact in int32), dequantizes, and keeps the quantization residual
in the carry — so the *long-run* gradient information is lossless while the
wire format is 4x smaller than fp32 / 2x smaller than bf16.

``lse_combine``: flash-decoding reduction — combine per-shard partial
attention outputs computed over disjoint KV-sequence slices using their
logsumexps (used by the model-axis-sharded decode path in repro.serve).

Pytree plumbing goes through :data:`repro.compat.tree` (reprolint RL001).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import compat


def _quantize(x: jnp.ndarray):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return q.astype(jnp.int8), scale, x - q * scale   # payload, scale, residual


def compressed_psum_mean(grads, axis: str, ef_carry):
    """Mean-all-reduce of a gradient pytree in int8 with error feedback.

    Returns (mean_grads_f32, new_ef_carry).  Scales are psum'd alongside
    (one f32 scalar per tensor); payloads are summed exactly in int32 and
    dequantized with the *max* scale across shards (conservative, keeps the
    estimate unbiased under the shared-scale approximation; the residual
    goes back into the carry either way).
    """
    n = None

    def one(g, ef):
        nonlocal n
        gf = g.astype(jnp.float32) + ef
        q, scale, resid = _quantize(gf)
        scale_max = jax.lax.pmax(scale, axis)
        # requantize against the shared scale so the integer sum is coherent
        q = jnp.clip(jnp.round(gf / scale_max), -127, 127).astype(jnp.int8)
        resid = gf - q.astype(jnp.float32) * scale_max
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        if n is None:
            n = jax.lax.psum(jnp.ones((), jnp.int32), axis)
        mean = total.astype(jnp.float32) * scale_max / n.astype(jnp.float32)
        return mean, resid

    flat_g, tdef = compat.tree.flatten(grads)
    flat_e = compat.tree.leaves(ef_carry)
    out = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (compat.tree.unflatten(tdef, [o[0] for o in out]),
            compat.tree.unflatten(tdef, [o[1] for o in out]))


def lse_combine(o_parts, lse_parts, axis: str):
    """Combine per-shard attention partials over KV-sequence shards.

    o_parts: (..., D) partial softmax-weighted values with *local* softmax
    normalization; lse_parts: (...) local logsumexp.  Standard
    flash-decoding merge: renormalize by global lse via psum.
    """
    lse_max = jax.lax.pmax(lse_parts, axis)
    w = jnp.exp(lse_parts - lse_max)
    num = jax.lax.psum(o_parts * w[..., None], axis)
    den = jax.lax.psum(w, axis)
    return num / den[..., None]
