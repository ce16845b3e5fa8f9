"""The program under test for the DiT family: the repository's registered
DiT config, checked against the configuration file's sizes, as a denoiser
through the program's own constructor."""
from __future__ import annotations

SIZES = ("num_layers", "d_model", "num_heads", "d_ff", "patch_size",
         "in_channels", "dtype")


def build_denoiser(cfg, params):
    from repro.configs.base import get_arch
    from repro.configs.srds_dit import dit_denoiser
    arch = get_arch(cfg["arch"])
    differ = {k: (getattr(arch, k), cfg[k]) for k in SIZES
              if getattr(arch, k) != cfg[k]}
    if differ:
        raise ValueError(f"registered {cfg['arch']!r} differs from the "
                         f"benchmark's configuration (program, file): "
                         f"{differ}")
    return dit_denoiser(arch, params)
