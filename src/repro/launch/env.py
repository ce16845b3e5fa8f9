"""Runtime platform configuration: XLA flags + backend selection in one
place, applied BEFORE the JAX backend initializes.

``configure_platform`` is the launch-time front door for the knobs every
deployment (and benchmark emitter) otherwise re-derives by hand:

* ``platform`` — pin the JAX backend (``jax.config.update(
  'jax_platform_name', ...)``).  On ``"gpu"`` it also installs the XLA
  GPU performance preset (ROADMAP "GPU parity" item): triton softmax
  fusion, triton gemms, async collectives, the latency-hiding scheduler
  and the highest-priority async stream — the flag set upstream JAX
  documents for GPU serving workloads.
* ``host_device_count`` — fake N host devices via
  ``--xla_force_host_platform_device_count`` (the CPU-backed mesh trick
  the dry-run and the multi-process tests already use), so sharded
  drivers and mesh code run on a laptop.

Flags are **merged** into any existing ``XLA_FLAGS`` (ours win on
conflict, everything else is preserved) — clobbering would silently undo
a dry-run's fake-device count or a user's own tuning.

Ordering matters: XLA reads the environment once, when the backend
first initializes.  Importing JAX is fine; *running* anything is not.
``configure_platform`` raises if the backend is already up rather than
half-apply (an env var mutated after init is a silent no-op — the
worst failure mode for a performance preset).  Benchmark emitters call
it from their ``--platform`` / ``--host-devices`` CLI flags before any
device work (see docs/benchmarks.md).

``configure_compile_cache`` places JAX's persistent compilation cache:
where ``JAX_COMPILATION_CACHE_DIR`` points if it is set (JAX reads that
variable itself), else at the fixed in-checkout path :data:`CACHE_DIR`.
The path is part of the cache key, so it never moves between runs.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

__all__ = ["CACHE_DIR", "GPU_PERF_FLAGS", "GPU_RUNTIME_ENV",
           "configure_compile_cache", "configure_platform"]

# the checkout's own persistent-compilation-cache directory (gitignored)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# the XLA GPU performance preset (upstream gpu_performance_tips set):
# fusion + async collectives + latency hiding, for serving-shaped work
GPU_PERF_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)

# GPU runtime preset env vars NOT carried in XLA_FLAGS: the client
# allocator knobs (serving processes share the device with dataloaders /
# sidecars, so the 75%-grab default is the first thing every deployment
# script overrides) and runtime log verbosity.  Keys here are the
# ``configure_platform`` kwarg names; values the env vars they set.
GPU_RUNTIME_ENV = {
    "gpu_preallocate": "XLA_PYTHON_CLIENT_PREALLOCATE",
    "gpu_mem_fraction": "XLA_PYTHON_CLIENT_MEM_FRACTION",
    "gpu_allocator": "XLA_PYTHON_CLIENT_ALLOCATOR",
    "log_level": "TF_CPP_MIN_LOG_LEVEL",
}


def _merge_xla_flags(new_flags) -> str:
    """Merge ``new_flags`` into XLA_FLAGS, replacing same-name flags and
    preserving everything else (order: survivors first, ours last)."""
    names = {f.split("=", 1)[0] for f in new_flags}
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if f.split("=", 1)[0] not in names]
    merged = " ".join(kept + list(new_flags))
    os.environ["XLA_FLAGS"] = merged
    return merged


def _backend_initialized() -> bool:
    """True once the JAX backend is up (env mutations no longer apply).

    Probes private-ish state defensively across JAX versions: absent
    introspection, assume NOT initialized (the caller is about to set
    env vars, which is harmless when wrong but load-bearing when
    right)."""
    try:
        import jax._src.xla_bridge as xb
        backends = getattr(xb, "_backends", None)
        return bool(backends)
    except Exception:
        return False


def configure_platform(platform: Optional[str] = None,
                       host_device_count: Optional[int] = None, *,
                       gpu_preallocate: Optional[bool] = None,
                       gpu_mem_fraction: Optional[float] = None,
                       gpu_allocator: Optional[str] = None,
                       log_level: Optional[int] = None) -> dict:
    """Configure the JAX runtime for ``platform`` before backend init.

    Args:
      platform: ``"cpu"`` | ``"gpu"`` | ``"tpu"`` — pins
        ``jax_platform_name``.  ``"gpu"`` additionally merges
        :data:`GPU_PERF_FLAGS` into ``XLA_FLAGS``.  ``None`` leaves the
        backend choice to JAX (useful when only faking host devices).
      host_device_count: fake this many host (CPU) devices via
        ``--xla_force_host_platform_device_count`` — the local-mesh
        substrate for the sharded/wavefront drivers and the serving
        engine's ``data_axis`` on machines without real accelerators.
      gpu_preallocate: ``XLA_PYTHON_CLIENT_PREALLOCATE`` — whether the
        client grabs its memory pool up front (JAX default True/75%).
        ``False`` is the serving-friendly setting when the device is
        shared with other processes.
      gpu_mem_fraction: ``XLA_PYTHON_CLIENT_MEM_FRACTION`` — pool size as
        a fraction of device memory (only meaningful with preallocation).
      gpu_allocator: ``XLA_PYTHON_CLIENT_ALLOCATOR`` — ``"default"`` |
        ``"platform"`` (allocate/free on demand; slow but exact — the
        autotune sweep's setting so candidate configs don't fight the
        pool) | ``"bfc"`` | ``"cuda_async"``.
      log_level: ``TF_CPP_MIN_LOG_LEVEL`` — runtime log verbosity (4
        silences the C++ backend chatter in benchmark output).

    The allocator knobs are plain env vars (not XLA_FLAGS) but obey the
    same read-once-at-init rule, hence they live behind the same
    before-init guard.  They are only *applied* when explicitly passed —
    ``configure_platform("gpu")`` alone never overrides a deployment's
    externally-set allocator env.

    Returns a dict of what was applied (``platform``, ``xla_flags``,
    ``env``) — handy for benchmark metadata blocks.

    Raises ``RuntimeError`` if the JAX backend already initialized:
    XLA reads the environment exactly once, so a late call would be a
    silent no-op for the flag-carried settings.
    """
    if platform is not None and platform not in ("cpu", "gpu", "tpu"):
        raise ValueError(f"platform must be cpu|gpu|tpu, got {platform!r}")
    if gpu_allocator is not None and gpu_allocator not in (
            "default", "platform", "bfc", "cuda_async"):
        raise ValueError(f"gpu_allocator must be default|platform|bfc|"
                         f"cuda_async, got {gpu_allocator!r}")
    if _backend_initialized():
        raise RuntimeError(
            "configure_platform() after the JAX backend initialized: "
            "XLA_FLAGS are read once at backend init, so this call would "
            "silently not apply — call it before any jax computation "
            "(importing jax is fine)")
    flags = []
    if host_device_count is not None:
        flags.append("--xla_force_host_platform_device_count="
                     f"{int(host_device_count)}")
    if platform == "gpu":
        flags.extend(GPU_PERF_FLAGS)
    xla_flags = _merge_xla_flags(flags) if flags \
        else os.environ.get("XLA_FLAGS", "")
    env = {}
    if gpu_preallocate is not None:
        env[GPU_RUNTIME_ENV["gpu_preallocate"]] = \
            "true" if gpu_preallocate else "false"
    if gpu_mem_fraction is not None:
        env[GPU_RUNTIME_ENV["gpu_mem_fraction"]] = f"{gpu_mem_fraction:.2f}"
    if gpu_allocator is not None:
        env[GPU_RUNTIME_ENV["gpu_allocator"]] = gpu_allocator
    if log_level is not None:
        env[GPU_RUNTIME_ENV["log_level"]] = str(int(log_level))
    os.environ.update(env)
    if platform is not None:
        import jax
        jax.config.update("jax_platform_name", platform)
    return {"platform": platform, "xla_flags": xla_flags, "env": env}


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and this
    sets nothing.  Otherwise the cache goes to :data:`CACHE_DIR`, the same
    path inside the checkout on every run.  Call it from a script's
    ``main()`` before the first compile, never at import time.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
