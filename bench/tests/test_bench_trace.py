"""The reduction from a device trace to the benchmark's numbers, on a
small hand-made trace with values worked by hand, and on a slice of a
trace recorded on a TPU v5e chip while the backlog cell ran."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops, tracing  # noqa: E402

DEV = "/device:TPU:0"
SLICE = Path(__file__).with_name("data") / "tpu_trace_slice.json"


def ev(name, start, dur):
    return tracing.Event(name, float(start), float(dur))


def hand_trace():
    """Window [0, 100]: a container op over two leaves, two overlapping
    ops, one op past the window; host spans over the two gaps."""
    ops = [ev("%fusion.1 = f32[4] fusion(...)", 0, 10),
           ev("%srds_flash_fwd.3 = (bf16[48,64,64]) custom-call(...)", 5, 15),
           ev("%while.7 = (s32[]) while(...)", 30, 30),
           ev("%srds_flash_fwd.4 = (bf16[48,64,64]) custom-call(...)", 32, 8),
           ev("%fusion.2 = f32[4] fusion(%srds_flash_fwd.4)", 45, 5),
           ev("%fusion.9 = f32[4] fusion(...)", 95, 10)]
    spans = [ev("bench.window", 0, 100), ev("bench.admit", 21, 8),
             ev("bench.step_resolve", 60, 30), ev("bench.policy", 62, 2)]
    mods = [ev("jit_step_fn(123)", 30, 30), ev("jit_init_body(9)", 0, 20)]
    return tracing.Trace(ops={DEV: ops}, modules={DEV: mods}, spans=spans)


def test_busy_idle_and_gaps_by_hand():
    t = hand_trace()
    # union of ops in [0, 100]: [0, 20], [30, 60], [95, 100]
    assert tracing.busy_ns(t, DEV) == 20 + 30 + 5
    assert tracing.idle_gaps(t, DEV) == [(20.0, 30.0), (60.0, 95.0)]
    assert tracing.host_activity(t, 60, 95) == "step_resolve"
    assert tracing.host_activity(t, 20, 30) == "admit"
    assert tracing.host_activity(t, 96, 99) == "host_other"


def test_kernels_modules_and_breakdown_by_hand():
    t = hand_trace()
    assert tracing.op_name(t.ops[DEV][1]) == "srds_flash_fwd"
    flash = tracing.kernel_events(t, DEV, "srds_flash_fwd")
    assert [e.dur_ns for e in flash] == [15.0, 8.0]   # not fusion.2
    assert [e.dur_ns for e in tracing.module_events(t, DEV, "jit_step_fn")] \
        == [30.0]
    b = tracing.breakdown(t)
    # the container (while) is left out; fusion.9 counts whole
    assert [k for k, _ in b["device_ops"]] == ["fusion", "srds_flash_fwd"]
    assert [v for _, v in b["device_ops"]] == pytest.approx([25e-9, 23e-9])
    assert [k for k, _ in b["idle_gaps"]] == ["step_resolve", "admit"]
    assert [v for _, v in b["idle_gaps"]] == pytest.approx([35e-9, 10e-9])


def test_recorded_tpu_slice():
    """40 ms of a chip trace recorded while the backlog cell ran (device
    operations, programs and harness spans inside it, the operations
    named by the head of their HLO text), against a count of busy time at
    1 us resolution and the kernel calls counted when it was recorded."""
    data = json.loads(SLICE.read_text())
    t = tracing.Trace(
        ops={DEV: [tracing.Event(*e) for e in data["ops"]]},
        modules={DEV: [tracing.Event(*e) for e in data["modules"]]},
        spans=[tracing.Event(*e) for e in data["spans"]])
    lo, hi = t.window()
    ticks = lo + (np.arange(int((hi - lo) // 1000)) + 0.5) * 1000
    covered = np.zeros(len(ticks), bool)
    for e in t.ops[DEV]:
        covered[np.searchsorted(ticks, e.start_ns):
                np.searchsorted(ticks, e.end_ns)] = True
    busy = tracing.busy_ns(t, DEV)
    assert 0 < busy < hi - lo
    assert abs(busy - covered.sum() * 1000) <= 0.001 * (hi - lo)
    assert len(tracing.kernel_events(t, DEV, "srds_flash_fwd")) \
        == data["expect"]["flash_events"] > 0
    assert len(tracing.kernel_events(
        t, DEV, "srds_parareal_update_residual")) \
        == data["expect"]["corrector_events"] > 0
    gaps = tracing.idle_gaps(t, DEV)
    assert sum(b - a for a, b in gaps) == pytest.approx(hi - lo - busy)
    b = tracing.breakdown(t)
    assert b["device_ops"][0][0] == "srds_flash_fwd"
    assert {g[0] for g in b["idle_gaps"]} <= {
        "admit", "step_dispatch", "step_resolve", "policy", "host_other"}


# the HLO text of one flash call as a TPU v5e trace names it (the backend
# config, a serialized kernel of kilobytes, cut short)
LAYOUT = "{3,2,1,0:T(8,128)(2,1)S(1)}"
FLASH_TEXT = (
    f"%srds_flash_fwd.11 = (bf16[5,48,64,64]{LAYOUT}, "
    "f32[5,48,64,1]{3,2,1,0:T(8,128)}) custom-call("
    f"bf16[5,48,64,64]{LAYOUT} %bitcast.405, "
    f"bf16[5,48,64,64]{LAYOUT} %bitcast.406, "
    f"bf16[5,48,64,64]{LAYOUT} %bitcast.407), "
    'custom_call_target="tpu_custom_call", '
    'backend_config={"custom_call_config": {"body": "TUzvUgFNTElS"}}')


def test_kernel_roofline_from_the_calls_array_types():
    """A kernel call's array types are read from its HLO text and priced
    by the benchmark's own count; an operation that is no custom call
    carries none, and a call without them silences the share."""
    raw = SimpleNamespace(name=FLASH_TEXT, start_ns=10, duration_ns=126450)
    e = tracing._op(raw)
    big = ("bf16", (5, 48, 64, 64))
    assert e.name == "%srds_flash_fwd.11"
    assert e.shapes == ((big, ("f32", (5, 48, 64, 1))), (big, big, big))
    assert tracing._op(SimpleNamespace(
        name="%fusion.2 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop",
        start_ns=0, duration_ns=1)).shapes is None
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    trace = tracing.Trace(ops={DEV: [e]}, modules={},
                          spans=[ev("bench.window", 0, 200000)])
    run = SimpleNamespace(trace=trace, peak=peak)
    # 4 * 240 batch-heads * 64 * 64 * 64 operations take 1.28 us at the
    # peak; q, k, v, o (983,040 bf16 each) and the f32 row (61,440 bytes)
    # take 9.68 us at 819 GB/s: bound by bandwidth
    want = 100 * (4 * 983040 * 2 + 61440) / 819e9 / 126450e-9
    share = tracing.kernel_roofline(run, "srds_flash_fwd",
                                    flops.flash_fwd_cost)
    assert share == pytest.approx(want) and 7.6 < share < 7.7
    trace.ops[DEV].append(ev("%srds_flash_fwd.12", 500, 10))
    assert tracing.kernel_roofline(run, "srds_flash_fwd",
                                   flops.flash_fwd_cost) is None
