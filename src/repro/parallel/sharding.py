"""Parameter / activation / cache sharding rules.

One rule table maps parameter paths to PartitionSpecs:

  * TP on ``model``: attention q-heads (padded when needed), kv-heads when
    divisible (else replicated — they are small), MLP & expert d_ff, vocab.
  * EP on ``data``: MoE expert dim (the shard_map a2a in models/moe.py
    consumes exactly these local slices).
  * FSDP on ``data``: optional second shard dim for large dense weights.
  * ZeRO-1: optimizer moments reuse the param rules with FSDP forced on.
  * Stacked layers: everything under ``blocks`` gets a leading ``None``.

Cache rules implement the flash-decoding layout: KV sequence sharded over
``model`` (batch over data/pod), combined at attention time with an LSE
merge (repro/serve).

Rules work on any mesh built by :func:`repro.compat.make_mesh` — the only
mesh attributes consumed here (``axis_names``, ``devices.shape``) are stable
across JAX versions; pytree traversal rides :data:`repro.compat.tree`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat

from repro.configs.base import ArchConfig
from repro.models.transformer import ParallelCtx


def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _param_spec(cfg: ArchConfig, path: Tuple[str, ...], shape, *,
                mp_axis: Optional[str], data_axis: Optional[str],
                fsdp: bool, kv_shardable: bool) -> P:
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = keys[-1]
    in_blocks = "blocks" in keys
    fa = data_axis if fsdp else None

    def wrap(spec: P) -> P:
        return P(None, *spec) if in_blocks else spec

    # ---- embeddings ----
    if name == "table":
        return P(mp_axis, None)
    if keys[-2:] == ["unembed", "w"] or (name == "w" and "unembed" in keys):
        return P(fa, mp_axis)
    if name in ("time_in", "eps_out"):
        return P(None, None)
    # ---- MoE ----
    if "moe" in keys:
        if name == "router":
            return wrap(P(None, None))
        if name in ("w_up", "w_gate"):
            return wrap(P(data_axis, None, mp_axis))
        if name == "w_down":
            return wrap(P(data_axis, mp_axis, None))
    # ---- RWKV (before attention: tmix reuses wk/wv names) ----
    if "tmix" in keys:
        if name in ("wr", "wk", "wv", "wg"):
            return wrap(P(fa, mp_axis))
        if name == "wo":
            return wrap(P(mp_axis, fa))
        rank = len(shape) - 1
        return wrap(P(*([None] * rank)))
    if "cmix" in keys:
        if name in ("wk_c", "wr_c"):
            return wrap(P(fa, mp_axis))
        if name == "wv_c":
            return wrap(P(mp_axis, fa))
        rank = len(shape) - 1
        return wrap(P(*([None] * rank)))
    # ---- attention ----
    if name == "wq":
        return wrap(P(fa, mp_axis))
    if name in ("wk", "wv"):
        return wrap(P(fa, mp_axis if kv_shardable else None))
    if name == "wo":
        return wrap(P(mp_axis, fa))
    if name == "bq":
        return wrap(P(mp_axis))
    if name in ("bk", "bv"):
        return wrap(P(mp_axis if kv_shardable else None))
    # ---- MLP ----
    if name in ("w_up", "w_gate"):
        return wrap(P(fa, mp_axis))
    if name == "w_down":
        return wrap(P(mp_axis, fa))
    # ---- RWKV time/channel mix ----
    if name in ("wr", "wk_", "wv_", "wg"):
        return wrap(P(fa, mp_axis))
    if name in ("wk_c", "wr_c"):
        return wrap(P(fa, mp_axis))
    if name == "wv_c":
        return wrap(P(mp_axis, fa))
    # ---- Hymba SSM ----
    if name == "w_in":
        return wrap(P(fa, mp_axis))
    if name in ("w_dt", "w_B", "w_C", "A_log"):
        return wrap(P(mp_axis, None))
    if name == "D":
        return wrap(P(mp_axis))
    if name == "w_out":
        return wrap(P(mp_axis, fa))
    # ---- DiT ----
    if name in ("patch_in", "patch_out", "t_mlp1", "t_mlp2", "pos",
                "mod", "mod_b", "mod_f", "mod_fb"):
        return wrap(P(*([None] * len(shape[1 if in_blocks else 0:]))))
    # ---- everything else (norms, loras, u, mus, ...) replicated ----
    rank = len(shape) - (1 if in_blocks else 0)
    return wrap(P(*([None] * rank)))


def param_shardings(cfg: ArchConfig, mesh, params, parallel: ParallelCtx, *,
                    fsdp: bool = False, zero1: bool = False):
    """Pytree of NamedSharding matching ``params`` (shapes or arrays)."""
    mp = parallel.model_axis
    da = parallel.data_axis
    mp_size = parallel.model_parallel
    _, hkv = cfg.padded_heads(mp_size)
    kv_shardable = mp_size > 1 and hkv % mp_size == 0

    def rule(path, leaf):
        shape = leaf.shape
        spec = _param_spec(cfg, path, shape, mp_axis=mp, data_axis=da,
                           fsdp=fsdp or zero1, kv_shardable=kv_shardable)
        # drop axes that don't divide the dim (e.g. tiny reduced configs)
        fixed = []
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
            if ax is None:
                fixed.append(None)
            else:
                sz = axis_sizes[ax] if isinstance(ax, str) else 1
                fixed.append(ax if dim % max(sz, 1) == 0 else None)
        return _ns(mesh, P(*fixed))

    return compat.tree.map_with_path(rule, params)


def opt_state_shardings(cfg, mesh, opt_state, parallel):
    """ZeRO-1: moments take the param rules with FSDP forced on."""
    m = param_shardings(cfg, mesh, opt_state["m"], parallel, zero1=True)
    v = param_shardings(cfg, mesh, opt_state["v"], parallel, zero1=True)
    return {"m": m, "v": v, "step": _ns(mesh, P())}


def _axes_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axes is None:
        return 1
    if isinstance(axes, str):
        return sizes[axes]
    out = 1
    for a in axes:
        out *= sizes[a]
    return out


def batch_shardings(mesh, batch, batch_axes):
    def rule(leaf):
        ba = batch_axes if leaf.shape[0] % _axes_size(mesh, batch_axes) == 0 \
            else None
        spec = P(ba, *([None] * (leaf.ndim - 1)))
        return _ns(mesh, spec)

    return compat.tree.map(rule, batch)


def _check_axes_bound(mesh, spec_axes) -> None:
    """Clear ``ValueError`` for axis names a mesh does not bind — XLA's own
    unbound-axis failure surfaces deep inside shard_map tracing and names
    neither the axis nor the call site."""
    if mesh is None:
        return
    known = set(mesh.axis_names)
    for ax in spec_axes:
        if ax is not None and ax not in known:
            raise ValueError(
                f"axis {ax!r} is not bound by the mesh (axes: "
                f"{tuple(mesh.axis_names)})")


def microbatch_spec(data_axis: str, *, mesh=None) -> P:
    """PartitionSpec for a serving micro-batch sharded over ``data_axis``.

    The diffusion sampling service's slot batch stacks K independent samples
    on one axis; the per-iteration fine solves see a ``(B, K, *sample)``
    block-heads tensor.  Sharding K over a data axis needs no collectives —
    every lane's refinement is independent — so the spec is just
    ``P(None, data_axis)``: block dim replicated (or handled separately by
    the block/time axis inside the shard_map body), K split, trailing sample
    dims implicitly replicated (PartitionSpec pads with None).  Callers must
    check ``K % axis_size == 0``; uneven slot batches are a config error,
    not something to pad silently.

    Pass ``mesh`` to validate that ``data_axis`` is actually bound —
    raising a clear ``ValueError`` instead of XLA's opaque unbound-axis
    failure at trace time.
    """
    _check_axes_bound(mesh, (data_axis,))
    return P(None, data_axis)


def denoiser_spec(data_axis: Optional[str], denoiser=None, *, mesh=None) -> P:
    """Block-heads spec composing the data axis with a denoiser's model axes.

    The serving engine's fine solves map a ``(B, K, *sample)`` heads
    tensor; :func:`microbatch_spec` splits K over ``data_axis``.  A
    sharding-aware :class:`repro.core.denoiser.Denoiser` additionally
    shards *sample* dims over its own mesh axes (``in_spec``, e.g. DiT
    patch rows over ``model``), so the composed spec is::

        P(None, data_axis, *in_spec[1:])
          ^B    ^K          ^sample dims, shifted past the K dim

    (the denoiser's ``in_spec`` is over the sample layout
    ``(K, *sample_shape)``; its leading K entry — replicated by
    convention — is dropped and the remaining entries shift right by one
    to land on the heads tensor's sample dims).  The engine slices each
    fine solve's heads to this spec once, runs every eval of the solve as
    the denoiser's ``shard_eval()`` — its per-shard ``shard_fn`` directly,
    no per-eval slice/gather glue — and gathers the result once.  That is
    how the block ``time`` axis, the ``data`` axis and the ``model`` axis
    compose on one (time, data, model) mesh
    (:func:`repro.launch.mesh.make_srds_mesh` builds it).

    With ``denoiser=None`` (or a plain adapted fn) this degrades to
    :func:`microbatch_spec`.  Pass ``mesh`` to validate every named axis
    is bound (clear ``ValueError`` instead of XLA's unbound-axis error).
    """
    from repro.core.denoiser import as_denoiser
    den = as_denoiser(denoiser) if denoiser is not None else None
    sample_axes = ()
    if den is not None and den.is_model_parallel:
        in_spec = tuple(den.in_spec)
        if in_spec and in_spec[0] is not None:
            raise ValueError(
                "denoiser in_spec shards the sample-batch dim "
                f"({in_spec[0]!r}); the serving engine owns that dim via "
                "data_axis")
        if mesh is not None:
            den.check_mesh(mesh)
        sample_axes = in_spec[1:]
    _check_axes_bound(mesh, (data_axis,) + tuple(sample_axes))
    return P(None, data_axis, *sample_axes)


def cache_shardings(cfg: ArchConfig, mesh, cache, parallel: ParallelCtx, *,
                    kv_seq_shard: bool = True):
    """Decode-cache layout: batch over (pod, data); KV sequence over model
    (flash-decoding) for dense caches; SSM state dims over model."""
    ba = parallel.batch_axes
    mp = parallel.model_axis
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def rule(path, leaf):
        shape = leaf.shape
        bsz = shape[1] if leaf.ndim >= 2 else 1
        b_ax = ba if bsz % _axes_size(mesh, ba) == 0 else None
        if leaf.ndim == 5:            # (L, B, S, Hkv, Dh) dense KV
            seq_ax = mp if (kv_seq_shard and shape[2] % axis_sizes.get(mp, 1) == 0) else None
            return _ns(mesh, P(None, b_ax, seq_ax, None, None))
        if leaf.ndim == 4:            # (L, B, din, n) ssm / (L,B,H?,..)
            dim_ax = mp if shape[2] % axis_sizes.get(mp, 1) == 0 else None
            return _ns(mesh, P(None, b_ax, dim_ax, None))
        if leaf.ndim == 3:            # (L, B, d)
            d_ax = mp if shape[2] % axis_sizes.get(mp, 1) == 0 else None
            return _ns(mesh, P(None, b_ax, d_ax))
        if leaf.ndim == 2:            # (L, W) ring positions
            return _ns(mesh, P(None, None))
        return _ns(mesh, P(*([None] * leaf.ndim)))

    return compat.tree.map_with_path(rule, cache)
