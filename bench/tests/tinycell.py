"""A benchmark checkout in a temporary directory, cut down for the CPU:
the committed ``BENCHMARK.json``, code and traffic mixes, with the
configuration shrunk to a two-layer, 64-wide DiT on 16x16x3 samples
(registered with the program under ``bench-tiny-dit``) and a ``cpu`` row
in its table of peaks.  Everything else, the limits included, is as
committed."""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"arch": "bench-tiny-dit", "num_layers": 2, "d_model": 64,
        "num_heads": 2, "d_ff": 128, "image_size": 16}
RATES = {"poisson80": {"rate_rps": 4.0}, "backlog": {"backlog_rps": 8.0}}


def register_tiny_arch():
    from repro.configs.base import ArchConfig, register_arch
    register_arch(ArchConfig(
        name=TINY["arch"], family="dit", num_layers=TINY["num_layers"],
        d_model=TINY["d_model"], num_heads=TINY["num_heads"],
        num_kv_heads=TINY["num_heads"], d_ff=TINY["d_ff"], vocab_size=0,
        causal=False, act="gelu", norm="layernorm", patch_size=4,
        in_channels=3))


def make_root(tmp: Path) -> Path:
    """``tmp`` as a checkout holding the tiny benchmark; returns it."""
    register_tiny_arch()
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = tmp / spec["paths"][0]
    src = REPO / spec["paths"][0]
    for d in ("references", "models", "metrics"):
        shutil.copytree(src / d, bench / d)
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    for entry in spec["configs"]:
        cfg = json.loads((REPO / entry["file"]).read_text())
        cfg.update(TINY)
        (tmp / entry["file"]).write_text(json.dumps(cfg))
    for path in (src / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        tr.update(RATES.get(path.stem, {}))
        (bench / "traffic" / path.name).write_text(json.dumps(tr))
    (bench / "peaks.json").write_text(json.dumps(
        {"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "source": "a stand-in for tests on the CPU"}}))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
