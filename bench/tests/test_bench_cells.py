"""Whole runs of the harness on the CPU at a tiny size, past the look for
a chip: discovery of a configuration, traffic mix and metric by name; the
comparison with the plain reference passing on the program as it is, and
failing on the float8 control put in its place and on each fault planted
in the timed path."""
import json
import time

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts the repository on sys.path)
from bench import harness
from repro.serve import diffusion

SECONDS = 2.0


@pytest.fixture
def root(tmp_path):
    return tinycell.make_root(tmp_path)


def _run(root, workload="cifar.poisson80", seed=4000000007, trace=False,
         control=False):
    cell = harness.find_cell(root, workload)
    return harness.run_cell(cell, seed, SECONDS, trace, time.monotonic(),
                            control=control)


def test_config_traffic_and_metric_found_by_name(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / spec["configs"][0]["file"]).read_text())
    (root / "bench" / "configs" / "tinyconf.json").write_text(
        json.dumps(dict(cfg, name="tinyconf")))
    (root / "bench" / "traffic" / "tinymix.json").write_text(json.dumps(
        {"arrivals": "poisson", "rate_rps": 3.0, "num_steps": 25,
         "tiers": [{"tol": 0.01, "weight": 1}], "admission": "fifo",
         "stop_at_window_end": False}))
    (root / "bench" / "metrics" / "tiny_admitted.py").write_text(
        "def read(run):\n    return len(run.probe.admits)\n")
    spec["configs"].append(dict(spec["configs"][0], name="tinyconf",
                                file="bench/configs/tinyconf.json"))
    spec["workloads"].append({"name": "tiny.mix", "config": "tinyconf",
                              "traffic": "tinymix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "tiny_admitted", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "serve loop",
                              "moves": "latency_p95_s",
                              "workloads": ["tiny.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(root, "tiny.mix", trace=True)
    assert out.line["correct"] is True
    assert out.line["attempted"] == 6
    assert out.line["metrics"]["tiny_admitted"] == {"value": 6.0,
                                                     "unit": "requests"}
    assert list(out.line)[-1] == "checks"


@pytest.mark.parametrize("workload", ["cifar.poisson80", "cifar.backlog"])
def test_traced_window_keeps_the_cells_traffic(workload):
    """A traced run serves the first ``TRACE_SECONDS`` of the cell's own
    traffic: the requests due by then at the cell's rate, or the cell's
    whole backlog, stopped then."""
    cell = harness.find_cell(tinycell.REPO, workload)
    full, seconds = harness.window_traffic(cell, 7, 40.0, False)
    traced, window = harness.window_traffic(cell, 7, 40.0, True)
    assert seconds == 40.0 and window == harness.TRACE_SECONDS < 40.0
    if cell.traffic["stop_at_window_end"]:
        assert traced == full
    else:
        assert traced == [r for r in full if r["arrival"] < window]
        assert abs(len(traced) - len(full) * window / 40.0) \
            < 0.1 * len(traced)


@pytest.mark.parametrize("workload", ["cifar.poisson80", "cifar.backlog"])
def test_program_passes_and_control_fails(root, workload):
    out = _run(root, workload)
    line = out.line
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) >= {"setup_s", "hbm_peak_gib"}
    assert out.notes[1]["compiles_in_window"] == 0
    got = out.checks["sample_rel_l2_max"]
    assert got["value"] < got["limit"]
    # the control: a whole run judged with the float8 reference in the
    # program's place, on the requests it served
    ctl = _run(root, workload, control=True)
    assert ctl.notes[-1]["phase"] == "program"
    assert ctl.notes[-1]["correct"] is True
    assert ctl.line["correct"] is False
    assert list(ctl.line)[-1] == "checks"
    c = ctl.checks["sample_rel_l2_max"]
    assert c["value"] > c["limit"]
    assert c["value"] >= 3 * got["value"]


def _unchanged_state(monkeypatch):
    def refinement(G, y, x_init, x_tail, prev_coarse, *a, **kw):
        return x_tail, prev_coarse, np.zeros(x_tail.shape[1], np.float32)
    monkeypatch.setattr(diffusion, "suffix_refinement", refinement)


def _half_the_lanes(monkeypatch):
    make_fine = diffusion.DiffusionSamplingEngine._make_fine

    def broken(self, F, starts, B):
        fine = make_fine(self, F, starts, B)

        def half(x_heads):
            k = x_heads.shape[1] // 2
            return fine(x_heads).at[:, k:].set(x_heads[:, k:])
        return half
    monkeypatch.setattr(diffusion.DiffusionSamplingEngine, "_make_fine",
                        broken)


def _altered_answer(monkeypatch):
    """The first sample served in the window comes back 10% too large
    (each completed request fetches its one sample; the warm-up's come
    first)."""
    fetch = diffusion._host_fetch
    samples = []
    warm = len(harness.warm_requests(harness.find_cell(
        tinycell.REPO, "cifar.backlog"), 0))

    def alter(x):
        a = fetch(x)
        if a.ndim == 3:
            samples.append(a)
            if len(samples) == warm + 1:
                return a * 1.1
        return a
    monkeypatch.setattr(diffusion, "_host_fetch", alter)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_lanes,
                                   _altered_answer])
def test_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(root, "cifar.backlog")       # every slot in use
    assert out.line["correct"] is False
    c = out.checks["sample_rel_l2_max"]
    assert c["value"] > c["limit"]
