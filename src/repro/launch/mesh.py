"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — required because the
dry-run forces 512 placeholder devices via XLA_FLAGS before first init,
while tests and benches must keep seeing the single real device.

Meshes are built through :func:`repro.compat.make_mesh` (never
``jax.make_mesh`` directly), which requests ``axis_types=AxisType.Auto``
on every axis.
"""
from __future__ import annotations

from repro import compat


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI subprocess tests (8 fake devices)."""
    return compat.make_mesh(shape, axes)


def make_srds_mesh(time: int, data: int = 1, model: int = 1, *,
                   devices=None):
    """The SRDS (time, data, model) mesh: parareal blocks over ``time``,
    independent sample lanes over ``data``, and the denoiser's own
    parallelism (:class:`repro.core.denoiser.Denoiser.mesh_axes`) over
    ``model``.  Axes of size 1 are kept — specs naming them are no-ops, so
    one program covers every composition; requires time*data*model devices.
    """
    return compat.make_mesh((time, data, model), ("time", "data", "model"),
                            devices=devices)


# TPU v5e hardware constants for the roofline analysis (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
