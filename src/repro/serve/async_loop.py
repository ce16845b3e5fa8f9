"""The real-time asynchronous serving loop: host scheduling overlapped
with device compute.

:func:`repro.serve.scheduler.simulate` is the *synchronous*
discrete-event driver: each refinement dispatches its step program and
immediately blocks on the ``(K,)``/``(K+B,)`` residual fetch, so the
device idles while the host runs admission, eviction and bookkeeping.
That is the right shape for bit-deterministic virtual-clock studies —
and the wrong one for wall-clock latency, where every microsecond the
device waits on the host is lost p95.

:class:`AsyncServeLoop` closes the gap with a **pipelined**
dispatch/resolve cycle over the engine's split hot loop
(:meth:`~repro.serve.diffusion.DiffusionSamplingEngine.step_dispatch` /
:meth:`~repro.serve.diffusion.DiffusionSamplingEngine.step_resolve`):

1. run the admission round (policy rejection, preemption, slot filling);
2. **dispatch** the next refinement's step program — JAX's asynchronous
   dispatch returns immediately with device futures;
3. **resolve** the *oldest* still-unresolved refinement — the host
   blocks on that one residual fetch while the device is already
   executing the step dispatched in (2).

So the fetch that used to serialize host and device now overlaps the
next refinement's compute, on a single host thread: no locks, no
executor, and the one-sync-per-refinement contract (reprolint RL003)
holds unchanged — dispatch performs zero syncs, resolve performs exactly
the one residual fetch.

The price of speculation is bounded and never observable: when a
refinement's fetch reveals a lane converged, the *next* refinement was
already dispatched with that lane still active.  That extra refinement
is wasted device work (charged physically, never effectively), but the
lane's completed sample is cut from the resolved step's own final-block
snapshot, so every response is bit-identical to what the synchronous
engine returns — on a virtual clock the async loop reproduces
``simulate()``'s samples and iteration counts exactly (asserted in
``tests/test_async_serve.py``).

The loop is clock-agnostic (:mod:`repro.serve.clock`): on the default
:class:`~repro.serve.clock.VirtualClock` it is a deterministic test
harness for the pipelined path; on a
:class:`~repro.serve.clock.MonotonicClock` it is the real-time serving
loop — arrivals become visible as wall time passes, idle waits really
sleep, latency/SLO stamps read real seconds, and wall deadlines
(``SampleRequest.deadline_wall``) drive EDF ordering, CostAware
admission rejection and mid-flight eviction through
``engine.request_deadline``.  ``benchmarks/table10_wallclock.py`` is the
wall-clock twin of ``table10_slo.py`` built on this loop.

Tracing: ``run`` opens ``serve.submit`` (``requests``) around the submit
loop, and each turn ``serve.admission`` around arrivals, the preemption
round and slot filling, with ``waiting`` (the queue the round saw),
``scanned`` (waiting entries examined by the round's eligibility scans,
summed) and ``admitted``.  The engine's spans nest inside these or stand
between them (``docs/serving.md``); an idle loop's sleep is the clock's
``serve.wait``, inside no other span.
"""
from __future__ import annotations

from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

from repro.serve.diffusion import (DiffusionSamplingEngine, SampleRequest,
                                   SampleResponse)
from repro.serve.scheduler import FIFO, Policy, SimReport, build_report

__all__ = ["AsyncServeLoop"]


class AsyncServeLoop:
    """Pipelined serving driver over one engine and one admission policy.

    The policy interface is exactly :class:`repro.serve.scheduler.
    Policy` — FIFO/EDF/CostAware (and any user policy) run unmodified in
    both the synchronous simulator and this loop; only the stepping
    discipline differs.  ``max_inflight`` bounds the dispatched-but-
    unresolved refinements per micro-batch (2 = dispatch the next step
    while the previous fetch is in flight; 1 degenerates to the
    synchronous discipline, useful for A/B-ing the overlap itself).
    """

    def __init__(self, engine: DiffusionSamplingEngine,
                 policy: Optional[Policy] = None, max_inflight: int = 2):
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self.engine = engine
        self.policy = policy if policy is not None else FIFO()
        self.max_inflight = max_inflight

    def run(self, trace: Sequence[SampleRequest]) -> SimReport:
        """Serve ``trace`` to completion; returns the same
        :class:`~repro.serve.scheduler.SimReport` shape ``simulate()``
        produces, with latencies in the engine clock's seconds (real
        ones under a wall clock).

        Requests become visible at their ``arrival_time`` on the
        engine's clock — under a wall clock that means genuinely waiting
        for them (an idle loop sleeps to the next arrival; a loaded one
        discovers them as refinements resolve).  Between refinements the
        policy may reject waiting requests (e.g. a ``deadline_wall``
        already hopeless at admission — evaluated lazily when the policy
        selects them for a free slot, see the inline note) and evict
        running ones whose wall deadline passed mid-refinement.  Engine
        metrics are reset first,
        so back-to-back runs on one warm engine are independent.
        """
        engine, policy = self.engine, self.policy
        engine.reset_metrics()

        with TraceAnnotation("serve.submit", requests=len(trace)):
            pending: List[Tuple[int, SampleRequest]] = \
                [(engine.submit(r), r)
                 for r in sorted(trace, key=lambda r: r.arrival_time)]
            submitted = [rid for rid, _ in pending]
            engine.pull_queue()   # the loop owns admission, not drain()
        first_arrival = pending[0][1].arrival_time if pending else 0.0
        engine.advance_clock(first_arrival)

        waiting: List[Tuple[int, SampleRequest]] = []
        responses: Dict[int, SampleResponse] = {}
        rejected: List[int] = []
        preempted: List[int] = []
        running: Dict[int, SampleRequest] = {}
        outstanding: Deque = deque()      # unresolved tokens, oldest first

        def arrivals(now: float) -> None:
            while pending and pending[0][1].arrival_time <= now:
                waiting.append(pending.pop(0))

        def admission(now: float) -> Dict[str, int]:
            """One turn's admission round: arrivals, preemption, slot
            filling.  Returns the counts its ``serve.admission`` span
            records."""
            arrivals(now)
            seen = len(waiting)

            # ---- preemption round (policy-driven; wall-deadline eviction
            # fires here, between refinements, even mid-pipeline: the
            # evicted lane's still-in-flight refinement resolves as
            # speculative waste) ----
            victims = policy.preempt_victims(now, sorted(running.items()),
                                             waiting, engine)
            for rid in victims:
                engine.evict(rid)
                preempted.append(rid)
                del running[rid]

            # ---- admission control + slot filling ----
            # Rejection is evaluated lazily, at selection time, rather
            # than scanning the whole waiting set every round the way
            # simulate() does.  The shedding decisions are the same ones
            # (a request is only ever served through admission, and a
            # hopeless request is at least as hopeless when its slot
            # finally opens), but the cost-model work (CostAware's
            # predict_completion per waiter) runs O(admissions) instead of
            # O(rounds x waiters) — on a wall clock that host time is real
            # and would otherwise sit on the pipelined critical path.
            scanned = admitted = 0
            while True:
                scanned += len(waiting)
                admissible = [i for i, (rid, req) in enumerate(waiting)
                              if engine.free_slots(req) > 0]
                if not admissible:
                    break
                sub = [waiting[i] for i in admissible]
                j = policy.select(now, sub, engine)
                if j is None:
                    break
                rid, req = waiting.pop(admissible[j])
                if policy.reject(now, rid, req, engine):
                    rejected.append(rid)
                    continue
                engine.admit(rid, req)
                running[rid] = req
                admitted += 1
            return {"waiting": seen, "scanned": scanned,
                    "admitted": admitted}

        while pending or waiting or engine.busy() or outstanding:
            with TraceAnnotation("serve.admission") as span:
                span.set_metadata(**admission(engine.clock))

            # ---- the overlap: dispatch the next refinement BEFORE
            # blocking on the previous one's residual fetch ----
            tok = engine.step_dispatch(max_inflight=self.max_inflight)
            if tok is not None:
                outstanding.append(tok)
            if outstanding and (tok is None or len(outstanding) > 1):
                # the device is (or just started) computing the younger
                # step(s); this fetch runs concurrently with them
                for rid, resp in engine.step_resolve(outstanding.popleft()):
                    responses[rid] = resp
                    running.pop(rid, None)
                continue
            if tok is not None:
                continue          # pipeline still filling — keep priming

            # nothing dispatched, nothing to resolve
            if waiting:
                if pending:
                    # the policy is holding back (legal — e.g. waiting to
                    # co-batch); wait for the arrival that may unblock it
                    engine.advance_clock(pending[0][1].arrival_time)
                    continue
                raise RuntimeError(
                    f"policy {policy.name!r} admitted nothing on an idle "
                    f"engine")
            if pending:
                # idle: wait (really sleep, on a wall clock) to the next
                # arrival
                engine.advance_clock(pending[0][1].arrival_time)

        return build_report(policy, responses, rejected, preempted,
                            submitted, engine, first_arrival)
