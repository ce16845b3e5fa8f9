"""The sharding-aware model-eval seam (`Denoiser`).

Every driver (``srds_sample``, the sharded and wavefront samplers) and the
serving engine evaluates the diffusion backbone through this one seam
instead of calling a bare ``model_fn(x, t)``.  A :class:`Denoiser` is a
callable that *also* carries its parallelism contract:

* ``in_spec`` / ``out_spec`` — :class:`~jax.sharding.PartitionSpec`s over
  the **sample** layout ``(K, *sample_shape)`` naming which dims the
  backbone shards over its own mesh axes (e.g. DiT patch-sharding rows
  over a ``model`` axis: ``P(None, "model")``);
* ``mesh_axes`` — the axes the backbone requires of whatever mesh it runs
  under, as ``{axis_name: min_size}``;
* ``fn`` — the single-device global math, the bit-exactness reference;
* ``shard_fn`` — the per-shard body: takes/returns the ``in_spec`` /
  ``out_spec`` shard and may use collectives over ``mesh_axes`` names.

Plain ``model_fn(x, t)`` callables adapt losslessly via
:func:`as_denoiser` (replicated specs, no mesh requirement), so every
existing call path is unchanged.  A model-parallel denoiser composes with
the drivers' time/data parallelism in three ways, all driver-agnostic:

1. **standalone** (``den(x, t)``): self-wraps ``shard_fn`` in a
   ``shard_map`` over the denoiser's bound ``mesh`` — what ``srds_sample``
   hits (vmap-of-shard_map over blocks);
2. **inner** (``den.inner_eval()``): for call sites already inside a
   driver ``shard_map`` whose in/out specs *replicate* over the model
   axes (the sharded/wavefront drivers, and the serving engine's
   coarse sweep under a mesh).  The mesh axes are still bound inside the
   enclosing body, so the glue slices the replicated operand per
   ``in_spec``, runs ``shard_fn``, and all-gathers per ``out_spec``;
3. **shard** (``den.shard_eval()``): for operands already sliced per
   ``in_spec`` (the serving engine's fine solves, sliced once per solve
   to ``parallel.sharding.denoiser_spec``) — ``shard_fn`` applies
   directly, no per-eval collectives beyond the backbone's own.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat

__all__ = ["Denoiser", "as_denoiser", "gather_spec", "slice_spec"]


def _spec_axes(spec):
    """(dim, axis_name) pairs for every sharded dim of a PartitionSpec."""
    out = []
    for dim, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        if not isinstance(entry, str):
            raise ValueError(
                f"Denoiser specs shard each dim over at most one axis; got "
                f"{entry!r} at dim {dim}")
        out.append((dim, entry))
    return out


def slice_spec(x, spec):
    """The local ``spec``-shard of a replicated ``x`` (inside shard_map)."""
    for dim, name in _spec_axes(spec):
        n = compat.axis_size(name)
        if x.shape[dim] % n:
            raise ValueError(
                f"dim {dim} of shape {x.shape} not divisible by axis "
                f"{name!r} (size {n})")
        chunk = x.shape[dim] // n
        x = jax.lax.dynamic_slice_in_dim(
            x, jax.lax.axis_index(name) * chunk, chunk, axis=dim)
    return x


def gather_spec(y, spec):
    """Reassemble the global array from ``spec``-shards (inside shard_map)."""
    for dim, name in _spec_axes(spec):
        y = jax.lax.all_gather(y, name, axis=dim, tiled=True)
    return y


@dataclasses.dataclass(frozen=True)
class Denoiser:
    """A model-eval callable carrying its sharding contract (see module
    docstring).  ``Denoiser(fn=f)`` with the defaults is exactly ``f`` —
    replicated specs, no mesh requirement, zero overhead."""

    fn: Callable                        # global (x, t) -> eps, the reference
    shard_fn: Optional[Callable] = None  # per-shard body (None = fn)
    in_spec: P = P()
    out_spec: P = P()
    mesh_axes: Mapping[str, int] = dataclasses.field(default_factory=dict)
    mesh: Optional[Mesh] = None          # bound mesh for standalone calls

    def __post_init__(self):
        if self.mesh_axes and self.shard_fn is None:
            raise ValueError("a Denoiser with mesh_axes needs a shard_fn")
        if self.mesh is not None:
            self.check_mesh(self.mesh)

    @property
    def is_model_parallel(self) -> bool:
        return bool(self.mesh_axes)

    def check_mesh(self, mesh: Mesh) -> None:
        """Raise a clear ValueError unless ``mesh`` binds every required
        axis at its minimum size (instead of XLA's unbound-axis error)."""
        shape = dict(mesh.shape)
        for name, min_size in self.mesh_axes.items():
            if name not in shape:
                raise ValueError(
                    f"denoiser requires mesh axis {name!r} but mesh has "
                    f"axes {tuple(shape)}")
            if shape[name] < min_size:
                raise ValueError(
                    f"denoiser requires mesh axis {name!r} of size >= "
                    f"{min_size}, got {shape[name]}")

    def bind(self, mesh: Mesh) -> "Denoiser":
        """A copy bound to ``mesh`` (validated) for standalone calls."""
        return dataclasses.replace(self, mesh=mesh)

    def __call__(self, x, t):
        """Global eval.  Model-parallel denoisers self-wrap ``shard_fn``
        in a shard_map over their bound mesh; plain ones are just ``fn``."""
        if not self.is_model_parallel:
            return self.fn(x, t)
        if self.mesh is None:
            raise ValueError(
                "model-parallel Denoiser called standalone without a bound "
                "mesh; use .bind(mesh) or eval inside a driver shard_map "
                "via .inner_eval()/.shard_eval()")
        wrapped = compat.shard_map(
            self.shard_fn, mesh=self.mesh,
            in_specs=(self.in_spec, P()), out_specs=self.out_spec,
            check_vma=False)
        return wrapped(x, t)

    def inner_eval(self) -> Callable:
        """Eval callable for *inside* an enclosing shard_map whose specs
        replicate over this denoiser's mesh axes (slice -> shard_fn ->
        all_gather; identity glue for plain denoisers)."""
        if not self.is_model_parallel:
            return self.fn
        shard_fn, in_spec, out_spec = self.shard_fn, self.in_spec, self.out_spec

        def eval_fn(x, t):
            return gather_spec(shard_fn(slice_spec(x, in_spec), t), out_spec)

        return eval_fn

    def shard_eval(self) -> Callable:
        """Eval callable for operands already sliced per ``in_spec``
        (see ``parallel.sharding.denoiser_spec``)."""
        return self.shard_fn if self.is_model_parallel else self.fn


def as_denoiser(fn) -> Denoiser:
    """Adapt a plain ``model_fn(x, t)`` callable into the seam (identity
    for values that are already :class:`Denoiser`)."""
    if isinstance(fn, Denoiser):
        return fn
    return Denoiser(fn=fn)
