"""Unit tests for the kernel autotuning seam (repro.kernels.tuning):
bucket rounding, table resolution order, versioning, unknown-key
fallback, malformed-table loud failure, and the divisor helpers that
replaced ops._pick_chunk/_sample_tile_rows.  Pure-Python logic plus the
committed tables — no kernel launches."""
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.kernels import tuning
from repro.kernels.elementwise import TILE_ROWS


# --------------------------------------------------------------------------
# bucketing
# --------------------------------------------------------------------------

def test_bucket_rounds_to_next_pow2():
    assert tuning.next_pow2(1) == 1
    assert tuning.next_pow2(2) == 2
    assert tuning.next_pow2(3) == 4
    assert tuning.next_pow2(129) == 256
    assert tuning.bucket_for("flash", (33, 49, 16)) == (64, 64, 16)
    assert tuning.bucket_for("rwkv6", (24, 8)) == (32, 8)


def test_bucket_elementwise_flattens_to_total_size():
    """Elementwise ops flatten operands, so only total size matters — a
    (3, 129) and a (387,) operand share a bucket."""
    assert tuning.bucket_for("elementwise", (3, 129)) \
        == tuning.bucket_for("elementwise", (387,)) == (512,)
    assert tuning.bucket_for("elementwise", None) == ()


# --------------------------------------------------------------------------
# resolution order: overrides > table > heuristics
# --------------------------------------------------------------------------

def _table(backend="cpu", entries=()):
    return {"version": tuning.TABLE_SCHEMA_VERSION, "backend": backend,
            "entries": list(entries)}


def test_heuristic_defaults_match_legacy_constants():
    """With no table, the resolved defaults ARE the constants the kernels
    shipped with — the seam changes where sizes live, not their values
    (CPU bit-exactness depends on this)."""
    t = tuning.KernelTuner(table_dir="/nonexistent")
    for backend in ("cpu", "tpu"):
        el = t.resolve("elementwise", backend=backend, shape=(1000,))
        assert el.params == {"tile_rows": TILE_ROWS}
        assert el.source == "heuristic"
        fl = t.resolve("flash", backend=backend, shape=(64, 64, 32))
        assert fl.params["block_q"] == fl.params["block_k"] == 128
    rw = t.resolve("rwkv6", backend="cpu", shape=(48, 64))
    assert rw.params == {"chunk_target": 32}
    # unknown backends fall back to the default row, never error
    assert t.resolve("elementwise", backend="rocm",
                     shape=(8,)).params == {"tile_rows": TILE_ROWS}


def test_gpu_heuristics_are_triton_sized():
    t = tuning.KernelTuner(table_dir="/nonexistent")
    fl = t.resolve("flash", backend="gpu", shape=(64, 64, 32))
    assert fl.params["block_q"] == 64 and "num_warps" in fl.params
    assert t.resolve("elementwise", backend="gpu",
                     shape=(8,)).params["tile_rows"] < TILE_ROWS


def test_table_hit_and_unknown_key_fallback():
    tbl = _table(entries=[{"kernel": "flash", "dtype": "float32",
                           "bucket": [64, 64, 16],
                           "params": {"block_q": 16, "block_k": 8}}])
    t = tuning.KernelTuner(tables={"cpu": tbl})
    hit = t.resolve("flash", backend="cpu", shape=(33, 49, 16))
    assert hit.source == "table"
    assert hit.params["block_q"] == 16 and hit.params["block_k"] == 8
    # different dtype / bucket / backend -> heuristic fallback, no error
    for kwargs in ({"dtype": jnp.bfloat16, "shape": (33, 49, 16)},
                   {"shape": (128, 128, 16)},):
        miss = t.resolve("flash", backend="cpu", **kwargs)
        assert miss.source == "heuristic"
        assert miss.params["block_q"] == 128


def test_override_beats_table_and_merges():
    tbl = _table(entries=[{"kernel": "flash", "dtype": "float32",
                           "bucket": [64, 64, 16],
                           "params": {"block_q": 16, "block_k": 8}}])
    t = tuning.KernelTuner(tables={"cpu": tbl},
                           overrides={"flash": {"block_q": 4}})
    cfg = t.resolve("flash", backend="cpu", shape=(33, 49, 16))
    assert cfg.source == "override"
    assert cfg.params["block_q"] == 4      # instance override wins
    assert cfg.params["block_k"] == 8      # table value survives the merge
    call = t.resolve("flash", backend="cpu", shape=(33, 49, 16),
                     overrides={"block_q": 2})
    assert call.params["block_q"] == 2     # call-level beats instance


def test_key_records_full_lookup():
    t = tuning.KernelTuner(table_dir="/nonexistent")
    cfg = t.resolve("flash", backend="gpu", dtype=jnp.bfloat16,
                    shape=(100, 100, 64))
    assert cfg.key == ("gpu", "flash", "bfloat16", (128, 128, 64))
    with pytest.raises(ValueError, match="unknown kernel"):
        t.resolve("conv", backend="cpu")


# --------------------------------------------------------------------------
# table loading: committed tables valid; malformed tables fail LOUDLY
# --------------------------------------------------------------------------

def test_committed_tables_are_schema_valid():
    import os
    names = sorted(os.listdir(tuning.TABLE_DIR))
    assert {"cpu.json", "gpu.json", "tpu.json"} <= set(names)
    for name in names:
        if name.endswith(".json"):
            with open(os.path.join(tuning.TABLE_DIR, name)) as f:
                tuning.validate_table(json.load(f), name)


def test_missing_table_file_is_empty_not_error(tmp_path):
    t = tuning.KernelTuner(table_dir=str(tmp_path))
    assert t.resolve("flash", backend="cpu",
                     shape=(8, 8, 8)).source == "heuristic"


@pytest.mark.parametrize("mutate, msg", [
    (lambda d: d.update(version=99), "version"),
    (lambda d: d.pop("backend"), "backend"),
    (lambda d: d.update(entries={"not": "a list"}), "entries"),
    (lambda d: d["entries"].append({"kernel": "conv", "dtype": "float32",
                                    "bucket": [8], "params": {"x": 1}}),
     "unknown kernel"),
    (lambda d: d["entries"].append({"kernel": "flash", "dtype": "float32",
                                    "bucket": [8], "params": {}}),
     "params"),
    (lambda d: d["entries"].append({"kernel": "flash", "dtype": "float32",
                                    "bucket": [0], "params": {"block_q": 8}}),
     "bucket"),
    (lambda d: d["entries"].append({"kernel": "flash", "dtype": "float32",
                                    "bucket": [8],
                                    "params": {"block_q": True}}),
     "params"),
])
def test_malformed_table_fails_loudly(tmp_path, mutate, msg):
    """A broken committed table must raise TuningTableError at resolve —
    a silently ignored table would run default sizes in a deployment
    that believes itself tuned."""
    d = _table(entries=[{"kernel": "flash", "dtype": "float32",
                         "bucket": [8, 8, 8], "params": {"block_q": 8}}])
    mutate(d)
    path = tmp_path / "cpu.json"
    path.write_text(json.dumps(d))
    t = tuning.KernelTuner(table_dir=str(tmp_path))
    with pytest.raises(tuning.TuningTableError, match=msg):
        t.resolve("flash", backend="cpu", shape=(8, 8, 8))


def test_unparseable_table_fails_loudly(tmp_path):
    (tmp_path / "cpu.json").write_text("{not json")
    t = tuning.KernelTuner(table_dir=str(tmp_path))
    with pytest.raises(tuning.TuningTableError, match="JSON"):
        t.resolve("flash", backend="cpu", shape=(8, 8, 8))


# --------------------------------------------------------------------------
# divisor helpers (moved here from ops._pick_chunk/_sample_tile_rows)
# --------------------------------------------------------------------------

def test_pick_chunk_divides():
    assert tuning.pick_chunk(48, 32) == 24
    assert tuning.pick_chunk(64, 32) == 32
    assert tuning.pick_chunk(7, 32) == 7
    assert tuning.pick_chunk(13, 4) == 1


def test_sample_tile_rows_divides():
    assert tuning.sample_tile_rows(100, 256) == 100
    assert tuning.sample_tile_rows(100, 64) == 50
    assert tuning.sample_tile_rows(7, 2) == 1


def test_process_default_tuner_install_and_reset():
    custom = tuning.KernelTuner(overrides={"flash": {"block_q": 4}})
    try:
        tuning.set_tuner(custom)
        assert tuning.get_tuner() is custom
        assert tuning.resolve("flash", backend="cpu",
                              shape=(8, 8, 8)).params["block_q"] == 4
    finally:
        tuning.set_tuner(None)
    assert tuning.get_tuner() is not custom


# --------------------------------------------------------------------------
# launch/env GPU runtime knobs (the allocator preset seam)
# --------------------------------------------------------------------------

def test_gpu_runtime_env_knob_mapping_and_validation():
    from repro.launch import env as lenv
    assert set(lenv.GPU_RUNTIME_ENV) == {"gpu_preallocate",
                                         "gpu_mem_fraction",
                                         "gpu_allocator", "log_level"}
    # bad-arg validation fires before the backend-initialized guard
    with pytest.raises(ValueError, match="gpu_allocator"):
        lenv.configure_platform("gpu", gpu_allocator="arena")
    with pytest.raises(ValueError, match="platform"):
        lenv.configure_platform("cuda")
    # in a test process the backend is up: the read-once guard must trip
    import jax
    jax.devices()
    with pytest.raises(RuntimeError, match="backend initialized"):
        lenv.configure_platform("cpu", gpu_preallocate=False)


def test_compile_cache_dir_is_env_or_fixed_in_repo(monkeypatch):
    import jax

    from repro.launch import env as lenv
    old = jax.config.jax_compilation_cache_dir
    try:
        # set from outside: JAX reads the variable itself, nothing is set
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert lenv.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        # unset: the same fixed directory inside the checkout, every call
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = lenv.configure_compile_cache()
        assert first == lenv.configure_compile_cache() == \
            jax.config.jax_compilation_cache_dir
        root = Path(__file__).resolve().parents[1]
        assert Path(first) == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# --------------------------------------------------------------------------
# the autotune sweep's structural smoke (what ci.yml's bench-smoke runs)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_autotune_smoke_emits_schema_valid_table(tmp_path):
    import jax

    from benchmarks.autotune_kernels import sweep

    payload = sweep(True, cells_dir=str(tmp_path))
    tuning.validate_table(payload, "<smoke>")      # loud on any drift
    assert payload["backend"] == jax.default_backend()
    assert {e["kernel"] for e in payload["entries"]} == set(tuning.KERNELS)
    # one roofline-format cell per swept key, loadable by the harness
    cells = sorted(tmp_path.glob("*.json"))
    assert len(cells) == len(payload["entries"])
    for p in cells:
        cell = json.loads(p.read_text())
        assert {"compute_s", "memory_s", "collective_s",
                "dominant"} <= set(cell["roofline"])
