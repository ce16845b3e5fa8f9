"""The program's own spans and scopes in a trace (``bench/program_trace.py``),
on small hand-made traces with values worked by hand; the scope lookup on
a hand-encoded ``XSpace``; and ``bench/trace_program.py`` end to end on
the CPU at a tiny size."""
import json
from types import SimpleNamespace

import pytest

import tinycell  # noqa: F401  (puts the repository on sys.path)
from bench import harness, program_trace as pt, tracing, trace_program

DEV = "/device:TPU:0"


def ev(name, start, dur):
    return tracing.Event(name, float(start), float(dur))


def span(name, start, dur, **args):
    return pt.Span(name, float(start), float(dur),
                   {k: float(v) for k, v in args.items()})


def op(start, dur, scope, program="jit_step_fn"):
    return pt.ScopedOp(float(start), float(dur), scope, program)


def hand_traces():
    """Window [0, 100].  Device busy [0, 10] (init), [20, 50] (a step:
    fine 12, coarse 10, correct 3, unscoped 5) and [70, 80] (a step:
    fine 6, coarse 4); idle [10, 20], [50, 70], [80, 100].  Host: an
    admission round over [8, 18] holding one admit, a harness span over
    [50, 58], the loop waiting over [82, 100], a resolve over [60, 69]
    with a fetch over [61, 68]."""
    ops = [ev("%fusion.1", 0, 10), ev("%fusion.2", 20, 30),
           ev("%fusion.3", 70, 10)]
    mods = [ev("jit_init_body(5)", 0, 10), ev("jit_step_fn(7)", 20, 30),
            ev("jit_step_fn(7)", 70, 10)]
    spans = [ev("bench.window", 0, 100), ev("bench.step_dispatch", 50, 8)]
    trace = tracing.Trace(ops={DEV: ops}, modules={DEV: mods}, spans=spans)
    program = pt.ProgramTrace(
        spans=[span("serve.admission", 8, 10, waiting=40, scanned=90,
                    admitted=2),
               span("serve.admit", 9, 2, rid=3, waited_ms=1.5),
               span("serve.admission", 30, 2, waiting=38, scanned=38,
                    admitted=0),
               span("serve.resolve", 60, 9, completed=1),
               span("serve.fetch", 61, 7),
               span("serve.wait", 82, 18, ms=0.018)],
        ops={DEV: [op(0, 10, "srds.init", "jit_init_body"),
                   op(20, 12, "srds.fine"), op(32, 10, "srds.coarse"),
                   op(42, 3, "srds.correct"), op(45, 5, None),
                   op(70, 6, "srds.fine"), op(76, 4, "srds.coarse")]})
    return trace, program


def test_scope_split_per_refinement_by_hand():
    trace, program = hand_traces()
    # two refinements dispatched: fine (12 + 6) / 2, coarse (10 + 4) / 2
    assert pt.scope_device_ms(trace, program, 2, "srds.fine") \
        == pytest.approx(9e-6)
    assert pt.scope_device_ms(trace, program, 2, "srds.coarse") \
        == pytest.approx(7e-6)
    assert pt.scope_ns(trace, program, DEV, "srds.correct") == 3
    assert pt.scope_ns(trace, program, DEV, None) == 5
    # the init program's ops are not the step's
    assert pt.scope_ns(trace, program, DEV, "srds.init") == 0
    assert pt.scope_ns(trace, program, DEV, "srds.init",
                       "jit_init_body") == 10
    assert pt.scope_device_ms(trace, program, 0, "srds.fine") is None
    bare = pt.ProgramTrace(spans=[], ops={DEV: [op(20, 30, None)]})
    assert pt.scope_device_ms(trace, bare, 2, "srds.fine") is None


def test_idle_shares_and_admission_by_hand():
    trace, program = hand_traces()
    # idle [10, 20], [50, 70], [80, 100]; host work (not the wait) covers
    # [10, 18] of the first and [60, 69] of the second: 17 of 100
    assert pt.host_bound_idle_share(trace, program) == pytest.approx(17.0)
    # with the wait, [82, 100] too: 35 of the 50 idle
    assert pt.idle_in_program_share(trace, program) == pytest.approx(70.0)
    assert pt.admission_ms(trace, program) == pytest.approx(6e-6)
    assert pt.admission_scanned(trace, program) == pytest.approx(64.0)
    empty = pt.ProgramTrace(spans=[], ops={})
    assert pt.host_bound_idle_share(trace, empty) is None
    assert pt.admission_ms(trace, empty) is None
    assert pt.admission_scanned(trace, empty) is None


def test_gaps_named_by_the_program_only_where_the_harness_is_silent():
    trace, program = hand_traces()
    # [50, 70]: the harness's dispatch span covers 8 of 20, the resolve
    # 9: neither covers half.  Over [60, 69] the resolve and the fetch
    # inside it both cover half: the fetch, innermost, names it
    assert pt.host_activity(trace, program, 50, 58) == "step_dispatch"
    assert pt.host_activity(trace, program, 50, 70) == "host_other"
    assert pt.host_activity(trace, program, 60, 69) == "serve.fetch"
    assert pt.host_activity(trace, program, 59, 62) == "serve.resolve"
    assert pt.host_activity(trace, program, 80, 100) == "serve.wait"
    assert pt.host_activity(trace, program, 10, 20) == "serve.admission"
    assert pt.idle_gaps(trace, program) == [
        ["host_other", pytest.approx(20e-9)],
        ["serve.wait", pytest.approx(20e-9)],
        ["serve.admission", pytest.approx(10e-9)]]
    # the harness's own naming of the same gaps is unchanged
    assert [g[0] for g in tracing.breakdown(trace)["idle_gaps"]] \
        == ["host_other"] * 3


def test_ops_find_their_program_and_scope():
    text = "%fusion.4 = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop"
    events = [SimpleNamespace(name=text, start_ns=21, duration_ns=3),
              SimpleNamespace(name=text, start_ns=71, duration_ns=2),
              SimpleNamespace(name="%while.2 = (s32[]) while(...)",
                              start_ns=20, duration_ns=30),
              SimpleNamespace(name=text, start_ns=60, duration_ns=1)]
    mods = [ev("jit_step_fn(7)", 20, 30), ev("jit_step_fn(8)", 70, 10)]
    ops = pt.scoped_ops(events, mods, {(7, text): "srds.coarse"})
    # the while is a container; the op at 60 ran in no program
    assert [(o.start_ns, o.scope, o.program) for o in ops] == [
        (21.0, "srds.coarse", "jit_step_fn"), (71.0, None, "jit_step_fn")]


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(key, msg):
    return _field(1, key) + _field(2, msg)


def _plane(name, ops):
    """An XPlane with stat metadata 1 = tf_op, 2 = program_id, 3 = flops,
    and one event metadata per ``(text, tf_op, program id)``."""
    stats = b"".join(_field(5, _entry(i, _field(1, i) + _field(2, n)))
                     for i, n in ((1, "tf_op"), (2, "program_id"),
                                  (3, "flops")))
    events = b"".join(_field(4, _entry(i + 1, _field(1, i + 1)
                                       + _field(2, text)
                                       + _field(5, _field(1, 3)
                                                + _field(3, 99))
                                       + _field(5, _field(1, 1)
                                                + _field(5, tf_op))
                                       + _field(5, _field(1, 2)
                                                + _field(3, pid))))
                      for i, (text, tf_op, pid) in enumerate(ops))
    lines = _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1)))
    return _field(1, _field(2, name) + lines + events + stats)


def test_op_scopes_from_an_encoded_xspace():
    big = 13602961581225950339            # above 2**63, as ids are
    step = "%fusion.9 = bf16[4]{0} fusion(...)"
    xspace = (_plane(DEV, [
        (step, "jit(step_fn)/while/body/closed_call/srds.coarse/dot:",
         big),
        (step, "jit(step_fn)/srds.fine/vmap()/while/body/add:", 5),
        ("%copy.1 = f32[2]{0} copy(...)", "jit(step_fn)/concatenate:",
         big)])
        + _plane("/host:CPU", [(step, "jit(f)/srds.init/x:", 1)]))
    assert pt.op_scopes(xspace) == {(big, step): "srds.coarse",
                                    (5, step): "srds.fine"}


def test_trace_program_on_the_cpu(tmp_path, monkeypatch, capsys):
    """The measurement script end to end at a tiny size: the harness's
    traced run, with the program's spans read from the same profile (the
    CPU trace has no device plane, so the device readers give nothing)."""
    root = tinycell.make_root(tmp_path)
    monkeypatch.setattr(harness, "missing_chips", lambda cell: None)
    monkeypatch.setattr(harness, "compile_cache", lambda bench: "")
    load, build = tracing.load, harness.build
    assert trace_program.main(["--workload", "cifar.backlog", "--seed",
                               "4000000011", "--seconds", "2"],
                              root=root) == 0
    assert (tracing.load, harness.build) == (load, build)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    prog = line["program"]
    assert prog["admission_ms"] > 0 and prog["admission_scanned"] > 0
    assert prog["spans"]["serve.admission"][0] >= 1
    assert prog["spans"]["serve.dispatch"][0] >= 1
    assert prog["samples_per_s"] > 0
    assert prog["fine_device_ms"] is None and prog["idle_gaps"] == []
