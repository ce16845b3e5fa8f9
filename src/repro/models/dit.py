"""DiT denoiser (adaLN-zero) — the paper-representative diffusion backbone.

Patchified image -> transformer with per-block time-conditioned modulation
-> unpatchify to an epsilon prediction.  Exposes ``make_denoiser`` returning
the weight-carrying ``Denoiser`` consumed by every sampler in repro.core.

Also provides ``TimeConditioned`` wrapping for any zoo backbone: continuous
embedding-space diffusion with the backbone as the trunk (how SRDS composes
with the assigned architectures — see DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .layers import (apply_mlp, apply_norm, attention_full, init_attention,
                     init_mlp, init_norm, sinusoidal_time_embed)


def init_dit(cfg: ArchConfig, key):
    """cfg.family == 'dit'; patch_size/in_channels set; vocab unused."""
    d = cfg.d_model
    p_in = cfg.patch_size * cfg.patch_size * cfg.in_channels
    ks = jax.random.split(key, 8)
    dtype = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    s = 1.0 / math.sqrt(d)

    def blk(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "attn": init_attention(k1, d, cfg.num_heads, cfg.num_kv_heads,
                                   cfg.resolved_head_dim, dtype=dtype),
            "mlp": init_mlp(k2, d, cfg.d_ff, "gelu", dtype),
            # adaLN-zero: 6 modulation vectors from the time embedding
            "mod": (jax.random.normal(k3, (d, 6 * d)) * 0.0).astype(dtype),
            "mod_b": jnp.zeros((6 * d,), dtype),
        }

    blocks = jax.vmap(blk)(jax.random.split(ks[0], cfg.num_layers))
    return {
        "patch_in": (jax.random.normal(ks[1], (p_in, d)) * (p_in ** -0.5)).astype(dtype),
        "pos": (jax.random.normal(ks[2], (4096, d)) * 0.02).astype(dtype),
        "t_mlp1": (jax.random.normal(ks[3], (256, d)) * (256 ** -0.5)).astype(dtype),
        "t_mlp2": (jax.random.normal(ks[4], (d, d)) * s).astype(dtype),
        "blocks": blocks,
        "ln_f": init_norm(ks[5], d),
        "mod_f": (jax.random.normal(ks[6], (d, 2 * d)) * 0.0).astype(dtype),
        "mod_fb": jnp.zeros((2 * d,), dtype),
        "patch_out": (jnp.zeros((d, p_in))).astype(dtype),  # zero-init final
    }


def _modulate(x, shift, scale):
    return x * (1 + scale[:, None]) + shift[:, None]


def dit_forward(cfg: ArchConfig, params, x_img, t, *, use_kernel=None,
                unroll: bool = False, shard_axis: Optional[str] = None):
    """x_img: (B, H, W, C); t: (B,) conditioning times. Returns eps (B,H,W,C).

    With ``shard_axis`` this is the *per-shard* body of a patch-sharded
    forward inside a ``shard_map``: ``x_img`` is the local row-shard
    (``H_total / axis_size`` rows), positions are offset by ``axis_index``
    (row-major patch order makes row-shards contiguous position ranges),
    and attention all-gathers the projected K/V over the axis so every
    local query row attends to the full sequence.  Everything else —
    patch embed, adaLN modulation, MLP, unpatchify — is per-position and
    needs no communication.
    """
    b, h, w, c = x_img.shape
    p = cfg.patch_size
    gh, gw = h // p, w // p
    dtype = params["patch_in"].dtype
    patches = x_img.reshape(b, gh, p, gw, p, c).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, gh * gw, p * p * c).astype(dtype)
    if shard_axis is None:
        pos = params["pos"][:gh * gw]
        kv_gather = None
    else:
        off = jax.lax.axis_index(shard_axis) * (gh * gw)
        pos = jax.lax.dynamic_slice_in_dim(params["pos"], off, gh * gw, 0)

        def kv_gather(a):   # (B, S_local, Hkv, D) -> (B, S_total, Hkv, D)
            return jax.lax.all_gather(a, shard_axis, axis=1, tiled=True)
    x = patches @ params["patch_in"] + pos[None]

    temb = sinusoidal_time_embed(t, 256).astype(dtype)
    temb = jax.nn.silu((temb @ params["t_mlp1"]).astype(jnp.float32)).astype(dtype)
    temb = temb @ params["t_mlp2"]                                 # (B, d)

    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim

    def body(carry, pb):
        x = carry
        mod = jax.nn.silu(temb.astype(jnp.float32)).astype(dtype) @ pb["mod"] + pb["mod_b"]
        sa, ga, sm_, gm, s2, g2 = jnp.split(mod, 6, axis=-1)
        h_in = _modulate(apply_norm({"scale": jnp.ones((cfg.d_model,))}, x), sa, ga)
        attn, _ = attention_full(pb["attn"], h_in, num_heads=hq,
                                 num_kv_heads=hkv, head_dim=hd, causal=False,
                                 theta=None, use_kernel=use_kernel,
                                 kv_gather=kv_gather)
        x = x + gm[:, None] * attn
        h2 = _modulate(apply_norm({"scale": jnp.ones((cfg.d_model,))}, x), sm_, s2)
        x = x + g2[:, None] * apply_mlp(pb["mlp"], h2, "gelu")
        return x, None

    x, _ = jax.lax.scan(body, x, params["blocks"], unroll=unroll)
    mod = jax.nn.silu(temb.astype(jnp.float32)).astype(dtype) @ params["mod_f"] + params["mod_fb"]
    sf, gf = jnp.split(mod, 2, axis=-1)
    x = _modulate(apply_norm(params["ln_f"], x), sf, gf)
    out = x @ params["patch_out"]                                  # (B, n, p*p*c)
    out = out.reshape(b, gh, gw, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(b, h, w, c).astype(x_img.dtype)


def make_denoiser(cfg: ArchConfig, params, *, use_kernel=None,
                  shard_axis: Optional[str] = None, mesh=None):
    """The DiT as a :class:`repro.core.denoiser.Denoiser` that carries
    ``params``: ``apply(params, x, t)`` takes a scalar-or-batched ``t``
    (samplers pass a scalar), and ``den(x, t)`` evaluates the carried
    weights.  A program can take the weights as an argument instead of
    baking them in as constants (the serving engine does).

    With ``shard_axis``, sample rows (the H dim of ``(K, H, W, C)``)
    patch-shard over that mesh axis (``in_spec = out_spec = P(None,
    shard_axis)``), the per-shard body is :func:`dit_forward` with its K/V
    all-gather, and ``apply`` stays the single-device global forward (the
    bit-exactness reference).  Every driver — ``srds_sample``, the
    sharded/pipelined samplers, the serving engine — consumes it through
    the seam with zero DiT-specific code.  ``mesh`` (optional) pre-binds
    the denoiser for standalone calls.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.denoiser import Denoiser

    def apply(params, x, t):
        tb = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (x.shape[0],))
        return dit_forward(cfg, params, x, tb, use_kernel=use_kernel)

    if shard_axis is None:
        return Denoiser(apply=apply, params=params)

    def shard_apply(params, x, t):
        if x.shape[1] % cfg.patch_size:
            raise ValueError(
                f"local row-shard of {x.shape[1]} rows is not divisible by "
                f"patch_size={cfg.patch_size}; pick a {shard_axis!r} axis "
                "size with (H / axis_size) % patch_size == 0")
        tb = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (x.shape[0],))
        return dit_forward(cfg, params, x, tb, use_kernel=use_kernel,
                           shard_axis=shard_axis)

    den = Denoiser(apply=apply, shard_apply=shard_apply, params=params,
                   in_spec=P(None, shard_axis), out_spec=P(None, shard_axis),
                   mesh_axes={shard_axis: 1})
    return den.bind(mesh) if mesh is not None else den


# --------------------------------------------------------------------------
# TimeConditioned wrapper: any zoo backbone as an embedding-space denoiser
# --------------------------------------------------------------------------

def init_time_conditioned(cfg: ArchConfig, key, parallel=None):
    from .transformer import LOCAL, init_params
    k1, k2, k3 = jax.random.split(key, 3)
    base = init_params(cfg, k1, parallel or LOCAL)
    d = cfg.d_model
    dtype = jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16
    base["time_in"] = (jax.random.normal(k2, (256, d)) * (256 ** -0.5)).astype(dtype)
    base["eps_out"] = (jax.random.normal(k3, (d, d)) * (d ** -0.5) * 0.02).astype(dtype)
    return base


def time_conditioned_forward(cfg: ArchConfig, params, x, t, *, parallel=None,
                             use_kernel=None):
    """x: (B, S, d_model) continuous latents; t: (B,).  eps of same shape.

    Runs the backbone's blocks bidirectionally (denoisers see the whole
    sequence) with the time embedding added to every position.
    """
    import dataclasses as dc

    from .transformer import LOCAL, _block_full, _init_state_full

    par = parallel or LOCAL
    cfg_nc = dc.replace(cfg, causal=False)
    temb = sinusoidal_time_embed(t, 256).astype(x.dtype) @ params["time_in"]
    h = x + temb[:, None, :]
    hq, hkv = cfg.padded_heads(par.model_parallel)
    state0 = _init_state_full(cfg_nc, x.shape[0],
                              jnp.float32 if cfg.dtype == "float32" else jnp.bfloat16)

    def body(carry, p_layer):
        hh, _, _ = _block_full(cfg_nc, p_layer, carry, state0, par, hq, hkv,
                               use_kernel)
        return hh, None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    h = apply_norm(params["ln_f"], h, cfg.norm)
    return (h @ params["eps_out"]).astype(x.dtype)
