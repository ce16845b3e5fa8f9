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
* ``params`` — the weights, a pytree (``()`` for a denoiser without);
* ``apply(params, x, t)`` — the single-device global math, the
  bit-exactness reference, pure in the weights;
* ``shard_apply(params, x, t)`` — the per-shard body: takes/returns the
  ``in_spec`` / ``out_spec`` shard and may use collectives over
  ``mesh_axes`` names.

``fn`` and ``shard_fn`` are ``apply`` and ``shard_apply`` closed over
``params``.  A program that takes the weights as an argument (the serving
engine's) evaluates ``den.with_params(traced)`` instead, so the weights
never become constants of its HLO.

Plain ``model_fn(x, t)`` callables adapt losslessly via
:func:`as_denoiser` (no weights, replicated specs, no mesh requirement),
so every existing call path is unchanged.  A model-parallel denoiser
composes with the drivers' time/data parallelism in three ways, all
driver-agnostic:

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
import functools
from typing import Any, Callable, Mapping, Optional

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
class _Closure:
    """The ``(params, x, t)`` form of an ``(x, t)`` closure: it carries
    its own weights, or none, and ignores ``params``."""

    fn: Callable

    def __call__(self, params, x, t):
        return self.fn(x, t)


def _close(apply: Optional[Callable], params) -> Optional[Callable]:
    """``apply`` closed over ``params``: an ``(x, t)`` callable."""
    if apply is None:
        return None
    if isinstance(apply, _Closure):
        return apply.fn
    return functools.partial(apply, params)


@dataclasses.dataclass(frozen=True, eq=False)
class Denoiser:
    """A model-eval callable carrying its weights and its sharding
    contract (see module docstring).  ``as_denoiser(f)`` of a plain
    ``f(x, t)`` is exactly ``f`` — no weights, replicated specs, no mesh
    requirement, zero overhead."""

    apply: Callable                          # (params, x, t) -> eps, global
    shard_apply: Optional[Callable] = None   # per-shard body (None = apply)
    params: Any = ()                         # the weights, a pytree
    in_spec: P = P()
    out_spec: P = P()
    mesh_axes: Mapping[str, int] = dataclasses.field(default_factory=dict)
    mesh: Optional[Mesh] = None              # bound mesh for standalone calls

    def __post_init__(self):
        if self.mesh_axes and self.shard_apply is None:
            raise ValueError("a Denoiser with mesh_axes needs a shard_fn")
        if self.mesh is not None:
            self.check_mesh(self.mesh)

    @property
    def fn(self) -> Callable:
        """The global ``(x, t)`` eval, the reference."""
        return _close(self.apply, self.params)

    @property
    def shard_fn(self) -> Optional[Callable]:
        """The per-shard ``(x, t)`` body (``None`` = ``fn``)."""
        return _close(self.shard_apply, self.params)

    def with_params(self, params) -> "Denoiser":
        """A copy evaluating ``params``: inside a program that takes the
        weights as an argument, the traced ones."""
        return dataclasses.replace(self, params=params)

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
            return self.apply(self.params, x, t)
        if self.mesh is None:
            raise ValueError(
                "model-parallel Denoiser called standalone without a bound "
                "mesh; use .bind(mesh) or eval inside a driver shard_map "
                "via .inner_eval()/.shard_eval()")
        wrapped = compat.shard_map(
            self.shard_apply, mesh=self.mesh,
            in_specs=(P(), self.in_spec, P()), out_specs=self.out_spec,
            check_vma=False)
        return wrapped(self.params, x, t)

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
    return Denoiser(apply=_Closure(fn))
