"""The served path's own tracing: the ``serve.*`` host spans the loop and
engine open, and the ``srds.*`` scopes on the device phases of the
programs.

* Under ``jax.profiler`` on the CPU, a wall-clock loop records every span
  with the documented nesting: ``serve.wait`` inside no other span, one
  ``serve.dispatch`` per dispatched refinement, ``serve.compile`` only on
  a program variant's first call.
* The admission round's ``scanned`` and ``admitted`` arguments match a
  count made by hand and a count taken at the engine's own seams.
* Every matmul, fusion and custom call of the DiT in the compiled step
  and init programs carries exactly one ``srds.*`` scope.
"""
import collections
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs.base import get_arch
from repro.configs.srds_dit import dit_denoiser
from repro.core import SolverConfig
from repro.models.dit import init_dit
from repro.serve import (AsyncServeLoop, DiffusionSamplingEngine,
                         MonotonicClock, SampleRequest, VirtualClock)

SCOPE = re.compile(r"(?:^|/)(srds\.[A-Za-z_]+)")


def _model(x, t):
    return jnp.tanh(x * 0.9) * (0.5 + 0.001 * t)


def _engine(clock, batch_size=2):
    return DiffusionSamplingEngine(_model, (8,), SolverConfig("ddim"),
                                   num_steps=16, num_blocks=4,
                                   batch_size=batch_size, clock=clock)


def _spans(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its result and the
    ``serve.*`` host spans as ``(name, start, end, args)``, by start."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append((e.name, e.start_ns, e.end_ns,
                                  {k: float(v) for k, v in e.stats}))
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _parent(spans, i):
    """The innermost other span holding span ``i``, or ``None``."""
    name, a, b, _ = spans[i]
    best = None
    for j, (n, c, d, _) in enumerate(spans):
        if j != i and c <= a and b <= d and (best is None
                                             or d - c < best[1]):
            best = (n, d - c)
    return best[0] if best else None


def _counted(engine):
    """Count dispatched refinements, admissions and the waiting entries
    the loop's eligibility scans examine, at the engine's own seams."""
    counts = collections.Counter()
    dispatch, admit, free = (engine.step_dispatch, engine.admit,
                             engine.free_slots)

    def step_dispatch(*a, **kw):
        tok = dispatch(*a, **kw)
        counts["dispatched"] += tok is not None
        return tok

    def admit_(rid, req):
        counts["admitted"] += 1
        return admit(rid, req)

    def free_slots(req):
        counts["scanned"] += 1
        return free(req)

    engine.step_dispatch, engine.admit = step_dispatch, admit_
    engine.free_slots = free_slots
    return counts


def test_spans_nest_as_documented_on_a_wall_clock(tmp_path):
    engine = _engine(MonotonicClock())
    # a burst, then a request that arrives after the loop went idle
    reqs = [SampleRequest(seed=i, tol=1e-3) for i in range(3)]
    reqs.append(SampleRequest(seed=9, tol=1e-3, arrival_time=0.5))
    loop = AsyncServeLoop(engine)
    # cold: each program variant's first call is a serve.compile span
    _, cold = _spans(tmp_path / "cold", lambda: loop.run(reqs))
    (_, step_for, _, _) = next(iter(engine._programs.values()))
    assert ("init", 0) in step_for.called
    assert len([s for s in cold if s[0] == "serve.compile"]) \
        == len(step_for.called)
    assert {_parent(cold, i) for i, s in enumerate(cold)
            if s[0] == "serve.compile"} == {"serve.dispatch"}

    counts = _counted(engine)
    rep, spans = _spans(tmp_path / "warm", lambda: loop.run(reqs))
    assert len(rep.responses) == 4
    names = collections.Counter(s[0] for s in spans)
    parents = collections.defaultdict(set)
    for i, s in enumerate(spans):
        parents[s[0]].add(_parent(spans, i))
    assert names["serve.compile"] == 0      # warm: nothing compiles
    assert names["serve.submit"] == 1
    assert spans[0][0] == "serve.submit" and spans[0][3]["requests"] == 4
    assert parents["serve.submit"] == {None}
    assert parents["serve.admission"] == {None}
    assert parents["serve.admit"] == {"serve.admission"}
    assert parents["serve.dispatch"] == {None}
    assert parents["serve.resolve"] == {None}
    assert parents["serve.fetch"] == {"serve.resolve"}
    # the idle loop's sleep stands alone: no serve.* span holds it
    assert names["serve.wait"] >= 1 and parents["serve.wait"] == {None}
    assert all(s[3]["ms"] > 0 for s in spans if s[0] == "serve.wait")
    assert names["serve.dispatch"] == counts["dispatched"] > 0
    assert names["serve.admit"] == counts["admitted"] == 4
    assert sorted(s[3]["rid"] for s in spans if s[0] == "serve.admit") \
        == [0, 1, 2, 3]
    assert sum(s[3]["completed"] for s in spans
               if s[0] == "serve.resolve") == 4
    # one fetch per refinement plus one per completed sample
    assert names["serve.fetch"] == names["serve.resolve"] + 4


def test_admission_counts_by_hand(tmp_path):
    """Two slots, five requests due at once.  The first round sees five
    waiting: its scans examine 5, then 4 (one admitted), then 3 (both
    slots full, nothing admissible): 12 scanned, 2 admitted.  Over the
    run the spans' sums equal the counts taken at the engine's seams."""
    engine = _engine(VirtualClock())
    counts = _counted(engine)
    reqs = [SampleRequest(seed=i, tol=1e-3) for i in range(5)]
    rep, spans = _spans(tmp_path, lambda: AsyncServeLoop(engine).run(reqs))
    assert len(rep.responses) == 5
    rounds = [s[3] for s in spans if s[0] == "serve.admission"]
    assert rounds[0] == {"waiting": 5, "scanned": 12, "admitted": 2}
    assert rounds[1]["waiting"] == 3
    assert sum(r["admitted"] for r in rounds) == counts["admitted"] == 5
    assert sum(r["scanned"] for r in rounds) == counts["scanned"]


def _stack_files(text):
    """``stack_frame_id -> set of file names`` on the frame's chain, from
    the tables at the head of a compiled module's text."""
    def table(title):
        body = text.split("\n" + title + "\n", 1)[1].split("\n\n", 1)[0]
        return dict(line.split(" ", 1) for line in body.splitlines())
    files = {k: v.strip('"') for k, v in table("FileNames").items()}
    locs = {k: re.search(r"file_name_id=(\d+)", v).group(1)
            for k, v in table("FileLocations").items()}
    frames = {k: (re.search(r"file_location_id=(\d+)", v).group(1),
                  re.search(r"parent_frame_id=(\d+)", v).group(1))
              for k, v in table("StackFrames").items()}

    def chain(fid):
        seen = set()
        while fid in frames and fid not in seen:
            seen.add(fid)
            loc, parent = frames[fid]
            yield files[locs[loc]]
            fid = parent
    return {fid: set(chain(fid)) for fid in frames}


def _dit_op_scopes(text):
    """``(op kind, scopes on its op_name)`` for each dot, convolution,
    fusion and custom call whose call stack passes through the DiT."""
    stacks = _stack_files(text)
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (?:\S+|\(.*?\)) "
                     r"(dot|convolution|fusion|custom-call)\(", line)
        frame = re.search(r"stack_frame_id=(\d+)", line)
        if not m or not frame or not any(
                f.endswith("repro/models/dit.py")
                for f in stacks.get(frame.group(1), ())):
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(1), SCOPE.findall(op.group(1) if op else "")))
    return out


@pytest.fixture(scope="module")
def dit_engine():
    cfg = dataclasses.replace(get_arch("srds-dit-cifar"), num_layers=2,
                              d_model=64, num_heads=4, num_kv_heads=4,
                              d_ff=256)
    params = init_dit(cfg, jax.random.PRNGKey(0))
    eng = DiffusionSamplingEngine(dit_denoiser(cfg, params), (32, 32, 3),
                                  SolverConfig("ddim"), num_steps=25,
                                  batch_size=2, num_blocks=5)
    eng.submit(SampleRequest(seed=1, tol=1e-2))
    eng.drain()
    return eng


@pytest.mark.parametrize("program,want", [
    ("init", {"srds.init"}),
    ("step", {"srds.fine", "srds.coarse"}),
])
def test_every_dit_op_carries_one_scope(dit_engine, program, want):
    (b,) = dit_engine._batches.values()
    fn = b.init_fn if program == "init" else b.step_for(0)
    text = fn.lower(b.x_init, b.x_tail, b.prev_coarse,
                    jnp.asarray(b.active)).compile().as_text()
    ops = _dit_op_scopes(text)
    kinds = collections.Counter(k for k, _ in ops)
    assert kinds["dot"] > 0 and kinds["fusion"] > 0
    assert all(len(s) == 1 for _, s in ops), \
        [o for o in ops if len(o[1]) != 1][:5]
    assert {s[0] for _, s in ops} == want
    if program == "step":
        # the corrector's update and residual carry their own scope
        assert "srds.correct" in set(SCOPE.findall(text))
