"""The one place the repo touches JAX APIs that drift between releases.

JAX's public surface moves between minor releases: ``shard_map`` moved
from ``jax.experimental.shard_map`` to ``jax.shard_map`` and renamed its
replication-check kwarg ``check_rep`` -> ``check_vma``; ``jax.make_mesh``
grew ``axis_types=``; the Pallas compiler-params dataclasses were
renamed; ``cost_analysis()`` changed its return shape.  Scattering one
release's spelling over the tree makes every such move a repo-wide edit.

Policy (see ROADMAP.md): **never call drifted JAX APIs directly — go
through ``repro.compat``** (enforced by reprolint rule RL001).  The
wrappers are written for the pinned installation (``requirements.txt``:
jax/jaxlib 0.9.0, libtpu 0.0.34) and nothing older; when the pin moves,
this module is the only file that changes.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax

__all__ = [
    "shard_map",
    "make_mesh",
    "axis_type_auto",
    "default_axis_types",
    "axis_size",
    "tpu_compiler_params",
    "gpu_compiler_params",
    "cost_analysis",
    "tree",
]


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map``; ``check_vma=None`` keeps JAX's default.  Extra
    kwargs are forwarded verbatim."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def axis_type_auto():
    """``jax.sharding.AxisType.Auto``."""
    return jax.sharding.AxisType.Auto


def default_axis_types(n_axes: int):
    """``(AxisType.Auto,) * n_axes`` — every axis auto-sharded."""
    return (axis_type_auto(),) * n_axes


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None, axis_types: Any = "auto"):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` by default.

    ``axis_types="auto"`` (the default) requests ``AxisType.Auto`` on every
    axis; pass ``None`` for JAX's own default, or an explicit tuple.
    """
    if axis_types == "auto":
        axis_types = default_axis_types(len(tuple(axis_names)))
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=axis_types, devices=devices)


def axis_size(axis_name: str) -> int:
    """Static size of a named mesh axis, inside shard_map/pmap."""
    return jax.lax.axis_size(axis_name)


def tpu_compiler_params(**kwargs):
    """``pltpu.CompilerParams(**kwargs)`` — the Mosaic options dataclass
    every TPU kernel builds through here."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(**kwargs)


def gpu_compiler_params(**kwargs):
    """``pltriton.CompilerParams(**kwargs)`` — the Triton options
    dataclass (``num_warps``, ``num_stages``)."""
    from jax.experimental.pallas import triton as pltriton
    return pltriton.CompilerParams(**kwargs)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a (possibly empty) dict: backends
    without a cost model return ``None``."""
    cost = compiled.cost_analysis()
    return dict(cost) if cost else {}


# pytree utilities: compat.tree.map / flatten / unflatten / leaves /
# structure / map_with_path are jax.tree's own functions
tree = jax.tree
