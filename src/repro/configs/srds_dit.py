"""The paper's own denoiser configs: DiT backbones at the paper's benchmark
scales (CIFAR 32x32, LSUN 128x128 pixel; SD-v2-like 64x64x4 latent).

:func:`dit_denoiser` is the one-stop constructor wiring these configs into
the sharding-aware :class:`repro.core.denoiser.Denoiser` seam — the same
object drives ``srds_sample``, the sharded/pipelined drivers and the
serving engine, model-parallel or not."""
from .base import ArchConfig, get_arch, register_arch

# ~100M DiT for the end-to-end training example (CIFAR-scale)
SRDS_DIT_S = register_arch(ArchConfig(
    name="srds-dit-cifar", family="dit",
    num_layers=12, d_model=768, num_heads=12, num_kv_heads=12,
    d_ff=3072, vocab_size=0, causal=False, act="gelu", norm="layernorm",
    patch_size=4, in_channels=3,
    source="paper benchmark: 32x32 CIFAR pixel diffusion",
))

# LSUN-church/bedroom-scale pixel model (paper Table 1)
SRDS_DIT_L = register_arch(ArchConfig(
    name="srds-dit-lsun", family="dit",
    num_layers=24, d_model=1024, num_heads=16, num_kv_heads=16,
    d_ff=4096, vocab_size=0, causal=False, act="gelu", norm="layernorm",
    patch_size=8, in_channels=3,
    source="paper benchmark: 128x128 LSUN pixel diffusion",
))

# StableDiffusion-v2-like latent denoiser (paper Tables 2-4), DiT-XL-ish
SRDS_DIT_SD = register_arch(ArchConfig(
    name="srds-dit-sd2", family="dit",
    num_layers=28, d_model=1152, num_heads=16, num_kv_heads=16,
    d_ff=4608, vocab_size=0, causal=False, act="gelu", norm="layernorm",
    patch_size=2, in_channels=4,
    source="paper benchmark: SD-v2 latent diffusion (64x64x4 latents)",
))


def dit_denoiser(arch, params, *, use_kernel=None, shard_axis=None,
                 mesh=None):
    """DiT denoiser for a paper config, through the seam.

    ``arch`` is a registered config name (``srds-dit-cifar`` /
    ``srds-dit-lsun`` / ``srds-dit-sd2``) or an :class:`ArchConfig`.
    The returned :class:`repro.core.denoiser.Denoiser` carries
    ``params``, so the serving engine passes them to its programs as an
    argument.  With ``shard_axis`` it patch-shards the backbone over
    that mesh axis — typically ``"model"`` on the (time, data, model) mesh
    from :func:`repro.launch.mesh.make_srds_mesh` — and every driver runs
    a genuinely model-parallel fine solve with no driver-side changes.
    """
    from repro.models.dit import make_denoiser
    cfg = get_arch(arch) if isinstance(arch, str) else arch
    return make_denoiser(cfg, params, use_kernel=use_kernel,
                         shard_axis=shard_axis, mesh=mesh)
