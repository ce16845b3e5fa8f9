"""``chip_smoke.py``'s phases on the CPU, on a 2-layer d=64 DiT with live
weights: the same functions its ``main()`` runs on the chip, which itself
refuses to run without a TPU."""
import dataclasses
import importlib.util
import json
import os

import jax
import pytest

from conftest import REPO, run_subprocess


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _small(cs):
    return dataclasses.replace(cs.get_arch(cs.ARCH), num_layers=2,
                               d_model=64, num_heads=4, num_kv_heads=4,
                               d_ff=256)


def test_smoke_phases_on_small_dit(cs):
    cfg = _small(cs)
    params = cs.live_params(cfg, seed=0)
    assert cs.check_live(cfg, params)["max_abs_eps"] > 0
    model_fn = cs.dit_denoiser(cfg, params, use_kernel=False)
    reqs = cs.make_requests(0)
    assert sorted({r.tol for r in reqs}) == sorted(cs.TIERS)

    eng, cold, warm, cold_s, warm_s = cs.serve(model_fn, reqs)
    assert len(warm.responses) == len(reqs) and cold_s > 0 and warm_s > 0
    iters = cs.iterations(warm, len(reqs))
    assert all(1 <= k <= 5 for k in iters)   # B = 5 blocks at 25 steps
    # the engine's per-request guarantee holds bit for bit on the CPU
    res = cs.against_srds_sample(model_fn, reqs, warm)
    assert res["bitwise"] and res["iterations_equal"], res
    assert res["equal_iterations"]["requests"] == list(range(len(reqs)))
    assert res["equal_iterations"]["bitwise"], res
    assert res["k_lane_witness"]["iterations_equal"], res
    res = cs.converged_vs_sequential(model_fn, eng, seed=0)
    assert res["iterations"] == 5 and res["rel"] <= cs.ROUNDOFF_BOUND
    res = cs.fused_vs_reference(model_fn, reqs, warm)
    assert res["bitwise"] and res["iterations_equal"], res
    res = cs.flash_vs_xla(cfg, params)
    # the kernel is interpreted here: no Mosaic call in the program
    assert res["rel"] <= cs.ATTN_BOUND and not res["tpu_custom_call"]


def test_smoke_refuses_cpu(cs, monkeypatch, capsys):
    # the cache helper would point this process's JAX at the checkout
    monkeypatch.setattr(cs, "configure_compile_cache", lambda: "unset")
    assert jax.devices()[0].platform == "cpu"
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.slow
@pytest.mark.distributed
def test_smoke_time_parallel_on_four_cpu_devices():
    r = run_subprocess(r"""
import dataclasses, json, sys
import jax
sys.path.insert(0, REPO)
import chip_smoke as cs
cfg = dataclasses.replace(cs.get_arch(cs.ARCH), num_layers=2, d_model=64,
                          num_heads=4, num_kv_heads=4, d_ff=256)
model_fn = cs.dit_denoiser(cfg, cs.live_params(cfg, 0), use_kernel=False)
res = cs.time_parallel(model_fn, cs.make_requests(0)[:4], jax.devices()[:4])
print(json.dumps(res))
""".replace("REPO", repr(REPO)), devices=4)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["trajectory_devices"] == [0, 1, 2, 3]
    assert 4 in res["collectives"]["all-gather"]
    assert res["iterations_equal"] and res["rel"] <= 2e-2
