"""Batched diffusion sampling service over the batch-aware SRDS engine.

:class:`DiffusionSamplingEngine` mirrors :class:`repro.serve.engine.
ServingEngine` for diffusion workloads: callers ``submit`` sampling
requests carrying their own ``(tol, num_steps, seed)`` — and, for
SLO-aware serving, an ``arrival_time`` plus a ``deadline``/``slo_ms`` —
the engine packs *compatible* requests into fixed-size micro-batches of
``batch_size`` slots, and drives the Parareal refinement loop one
iteration at a time across each batch.

The compatibility key is ``(num_steps, solver, schedule, sample shape)``:
requests agreeing on all four share one block decomposition and one
compiled init/step program; anything else runs in its own micro-batch
group, so a mixed workload can never silently share (and retrace) a
compiled program that doesn't match its math.

Slot recycling is the throughput story: convergence is gated **per slot**
(the engine's per-sample semantics — every slot's refinement is
bit-identical to an independent :func:`repro.core.parareal.srds_sample`
call with that request's tolerance), so the moment a sample converges its
slot is freed and the next queued request is admitted into it, instead of
the whole batch idling until the slowest sample finishes.  Under lockstep
whole-batch gating a micro-batch pays ``K * max_k(iters_k)`` refinements;
with recycling it pays ``sum_k(iters_k)`` (plus a drain tail), which is
where the "effective model evals per sample" win in
``benchmarks/table9_batched.py`` comes from.

The refinement step is a **sliding-window hot loop** behind the
:class:`repro.core.window.FrontierPolicy` seam: each step program is
compiled for the group's quantized *frontier*, statically skipping the
frozen block prefix's fine solves and corrector sweep.  With the default
``ExactPrefix`` policy the frontier is the provably bitwise-frozen prefix
(every lane's first ``prefix_frontier(j)`` blocks are final after ``j``
refinements — bit-exact).  With the opt-in ``ResidualWindow`` policy the
frontier additionally advances past blocks whose per-block residual
passed ``window_tol`` (ParaDiGMS-style, *approximate* — the error knob
and guarantees live in :mod:`repro.core.window`); the ``(num_blocks,)``
per-block residual vector piggybacks on the existing per-refinement
fetch, so the host loop still performs exactly ONE device sync per
refinement (the batched ``(K,)`` residual — concatenated with the block
residuals under ``ResidualWindow``) plus one per completion (that lane's
final state only — never the ``(B, K, *shape)`` trajectory).  All device
buffers — ``x_tail``/``prev_coarse`` in both the init-sweep and the step
programs — are donated to XLA so trajectory-sized allocations are reused
in place.

Arrival-aware serving rides a pluggable **clock**
(:mod:`repro.serve.clock`).  The default :class:`~repro.serve.clock.
VirtualClock` is the deterministic discrete-event clock the engine has
always had: every engine step advances it by its *physical* model-eval
cost times ``sec_per_eval`` (the deployment's calibrated per-eval wall
time), so latency, SLO-attainment and goodput numbers are
bit-reproducible discrete-event quantities, not wall-clock noise.  A
:class:`~repro.serve.clock.MonotonicClock` engine instead stamps those
same fields from real time — the regime of the asynchronous serving
loop (:class:`repro.serve.async_loop.AsyncServeLoop`), which overlaps
host scheduling with device compute by dispatching the next
refinement's step program (:meth:`DiffusionSamplingEngine.
step_dispatch`) before blocking on the previous refinement's residual
fetch (:meth:`DiffusionSamplingEngine.step_resolve`).  The admission
*policy* (who gets a freed slot, who is rejected or preempted) lives in
:mod:`repro.serve.scheduler`; this module only exposes the mechanism:
``admit`` / ``step_once`` (= dispatch + resolve, fused) / ``evict`` /
``free_slots``.  Completion-time prediction feeds on
:class:`IterationEMA`, an online per-tier iterations estimate learned
from the engine's own completions (falling back to the caller's
``iters_hint``, then worst-case ``max_iters``).

What the engine does / does not guarantee:

* per-request exactness: each returned sample equals the single-request
  SRDS result for that ``(tol, num_steps, seed, solver, schedule)`` —
  admission order, batch-mates and preemption of *other* requests do not
  perturb it (converged/empty lanes are frozen with ``jnp.where``, never
  fed back).  *Bitwise* for elementwise-deterministic denoisers; matmul
  denoisers carry the repo's standing shape-dependent-gemm carve-out
  (roundoff-level: XLA picks gemm kernels by batch shape, and with
  ``truncate`` the group frontier sets the fine-solve width, so lane bits
  can depend on batch composition at roundoff scale — build with
  ``truncate=False`` for width-independence at full cost).  Under the
  opt-in ``ResidualWindow`` policy the guarantee weakens further: the
  group window is shared, so batch-mates influence *which* blocks freeze
  and results are approximate (bounded by ``window_tol``) and
  composition-dependent — exactness-critical workloads keep the default
  ``ExactPrefix``.  Building the engine with an accelerating ``accel``
  (:mod:`repro.core.accel`) similarly trades exactness for iterations:
  mixed iterates are tolerance-equivalent, not bitwise, and mixing is
  per-lane (vmapped), so batch-mates still cannot perturb each other
  beyond the existing window/gemm caveats;
* eval accounting is *effective* (per-active-slot): lockstep SPMD still
  computes masked lanes, so physical compute equals effective compute only
  while the queue keeps every slot busy — exactly the heavy-traffic regime
  the service targets.  ``stats()`` reports both so the gap is visible;
* no cross-key batching: requests on different grids/solvers/schedules/
  shapes run in separate micro-batch groups (one compiled program each);
* deterministic solvers only for the exactness guarantee — the frozen-noise
  ``ddpm`` solver draws noise shaped like the *batch*, so its lanes differ
  from single-request runs (same distribution, different realization).
  ``submit`` therefore **rejects** ``ddpm`` requests unless the engine was
  built with ``allow_inexact=True`` (an explicit caller opt-in).

Parallelism hooks (both ride :mod:`repro.compat` wrappers).  With a
mesh, each compiled program is one ``shard_map`` over it whose operands
and results are replicated; only the fine solves split across devices:

* ``axis`` — each device solves its slice of the *block* dim, and one
  tiled ``all_gather`` per iteration re-joins them (the
  :func:`repro.core.pipelined.srds_sharded_local` layout);
* ``data_axis`` — each device solves its slice of the *slot batch* (K):
  lanes are independent, so one ``all_gather`` re-joins them.  Both axes
  compose on a 2D mesh.

The coarse sweep and the corrector run on every device's replica, over
all K lanes, so their Pallas kernels stay per-device calls (Mosaic
kernels cannot be partitioned by XLA).

Model evals go through the :class:`repro.core.denoiser.Denoiser` seam: a
model-parallel denoiser (e.g. the patch-sharded DiT from
:func:`repro.models.dit.make_denoiser`) contributes its own ``in_spec``
sample axes to the fine solves' heads spec
(:func:`repro.parallel.sharding.denoiser_spec`): each solve's heads are
sliced to it once and every eval runs ``shard_eval`` directly, while the
coarse sweep evals in the ``inner_eval`` form — so time x data x model
all compose on one 3D mesh (:func:`repro.launch.mesh.make_srds_mesh`)
with zero engine-specific model code.

Tracing: the host calls of the hot loop open ``jax.profiler``
annotations, recorded only while a profile is being taken (about a
microsecond each otherwise): ``serve.admit`` (``rid``, ``waited_ms``),
``serve.dispatch`` (``frontier``: the window floor the step ran at),
``serve.compile`` (the first call of a program variant, inside
``serve.dispatch``; ``param_bytes``: the weights it takes as an
argument), ``serve.resolve``
(``completed``) and ``serve.fetch`` (every device-to-host transfer).
Their arguments are host numbers only, never device values.  The
programs' device phases carry the ``srds.*`` scopes of
:mod:`repro.core.engine`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.analysis.markers import hot_loop
from repro.serve.clock import Clock, VirtualClock
from repro.core.accel import resolve_accel
from repro.core.engine import (IterationCost, blockwise_norm,
                               coarse_init_sweep, convergence_norm,
                               iteration_cost, predicted_evals,
                               prefix_frontier, resolve_blocks,
                               resolve_fused, suffix_refinement,
                               truncated_evals)
from repro.core.denoiser import as_denoiser, gather_spec, slice_spec
from repro.core.schedules import DiffusionSchedule, make_schedule
from repro.core.solvers import ModelFn, SolverConfig, solve, solver_names
from repro.core.window import FixedBudget, resolve_policy
from repro.parallel.sharding import denoiser_spec

__all__ = ["SampleRequest", "SampleResponse", "CompletionRecord",
           "DiffusionSamplingEngine", "IterationEMA"]


def _host_fetch(x) -> np.ndarray:
    """The single device->host transfer point of the serving hot loop.

    ``step()`` calls it exactly once per refinement (the batched ``(K,)``
    residual vector) plus once per *completed* request (that lane's final
    state only — never the whole trajectory).  Tests monkeypatch this to
    count syncs and hold the one-sync-per-iteration contract.
    """
    with jax.profiler.TraceAnnotation("serve.fetch"):
        return np.asarray(jax.device_get(x))


class IterationEMA:
    """Online per-tier expected-iterations predictor.

    Replaces trust in the caller's static ``iters_hint`` once real
    completions exist: an exponential moving average of observed refinement
    counts, keyed per tier — ``(compat_key, tol)`` — so a mixed workload
    learns one estimate per (grid, solver, schedule, shape, tolerance)
    class.  Feeds :meth:`DiffusionSamplingEngine.predict_completion` (and
    through it the CostAware scheduler); before the first observation of a
    tier the predictor abstains and callers fall back to ``iters_hint``
    then worst-case ``max_iters``, preserving the optimistic-rejection
    soundness story.
    """

    def __init__(self, alpha: float = 0.3):
        self.alpha = alpha
        self._mean: Dict[tuple, float] = {}

    def observe(self, key: tuple, iterations: int) -> None:
        prev = self._mean.get(key)
        # incremental form: exact fixed point when observations repeat
        self._mean[key] = float(iterations) if prev is None \
            else prev + self.alpha * (float(iterations) - prev)

    def predict(self, key: tuple) -> Optional[float]:
        return self._mean.get(key)

    def reset(self) -> None:
        self._mean.clear()


@dataclasses.dataclass
class SampleRequest:
    """One sampling job: draw x_init ~ N(0, I) from ``seed`` and run SRDS
    to the requester's tolerance on a ``num_steps`` grid.

    ``arrival_time`` (seconds on the engine's clock — virtual by default,
    real under a :class:`~repro.serve.clock.MonotonicClock`) and
    ``deadline``/``deadline_wall``/``slo_ms`` make the request
    schedulable: ``deadline`` is absolute on the *virtual* clock,
    ``deadline_wall`` is absolute on a *wall* (monotonic) clock, and
    ``slo_ms`` is relative to arrival so it is meaningful on either.  An
    engine resolves whichever absolute deadline matches its own clock
    (:meth:`DiffusionSamplingEngine.request_deadline`) and falls back to
    ``slo_ms``; nothing set means "best effort" (infinite deadline).
    ``solver``/``schedule``/``shape`` override the engine defaults and
    become part of the compatibility key.  ``iters_hint`` is the caller's
    expected refinement count for cost-model admission (policies fall back
    to the worst-case ``max_iters`` when absent).
    """
    seed: int
    tol: float = 1e-3
    num_steps: Optional[int] = None      # None -> engine default grid
    arrival_time: float = 0.0            # seconds on the engine clock
    slo_ms: Optional[float] = None       # relative deadline (ms past arrival)
    deadline: Optional[float] = None     # absolute virtual-clock deadline
    deadline_wall: Optional[float] = None  # absolute wall-clock deadline
    solver: Optional[SolverConfig] = None   # None -> engine default
    schedule: Optional[str] = None       # None -> engine default
    shape: Optional[Tuple[int, ...]] = None  # None -> engine default
    iters_hint: Optional[int] = None     # expected SRDS iterations (cost model)

    def absolute_deadline(self, wall: bool = False) -> float:
        """Absolute deadline in the given clock regime: ``wall=True``
        resolves ``deadline_wall`` (ignoring the virtual ``deadline``),
        the default resolves ``deadline`` (ignoring ``deadline_wall``);
        both fall back to arrival-relative ``slo_ms``, then +inf.  Engine
        code goes through ``engine.request_deadline(req)`` so the regime
        always matches the engine's own clock."""
        absolute = self.deadline_wall if wall else self.deadline
        if absolute is not None:
            return float(absolute)
        if self.slo_ms is not None:
            return self.arrival_time + self.slo_ms / 1e3
        return math.inf


@dataclasses.dataclass
class SampleResponse:
    sample: Optional[np.ndarray]         # None only for status="preempted"
    iterations: int
    final_delta: float
    delta_history: np.ndarray            # (iterations,) — converged prefix
    model_evals: int                     # effective evals charged to this job
    status: str = "ok"                   # "ok" | "preempted"
    arrival_time: float = 0.0
    finish_time: float = 0.0             # virtual-clock completion
    latency: float = 0.0                 # finish - arrival (virtual seconds)
    deadline: float = math.inf
    slo_met: bool = True


@dataclasses.dataclass(frozen=True)
class CompletionRecord:
    """Host-side latency ledger entry (one per finished/preempted request)."""
    rid: int
    arrival_time: float
    finish_time: float
    deadline: float
    latency: float
    slo_met: bool
    status: str


def _solver_fp(solver: SolverConfig):
    """Hashable fingerprint of a SolverConfig (noise_key may be an array)."""
    nk = solver.noise_key
    nk_fp = None if nk is None else np.asarray(nk).tobytes()
    return (solver.name, solver.eta, solver.use_fused_kernel, solver.unroll,
            nk_fp)


class _Slot:
    __slots__ = ("rid", "req", "iters", "history", "evals")

    def __init__(self, rid: int, req: SampleRequest):
        self.rid = rid
        self.req = req
        self.iters = 0
        self.history: List[float] = []
        # realized per-lane eval charge (residual-window billing: the
        # executed group-window schedule, accumulated step by step)
        self.evals = 0


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unresolved refinement of a micro-batch.

    Everything the host needs to account the step *after* its residual
    fetch lands: the un-fetched device residual (``fetch`` — ``(K,)``,
    or ``(K+B,)`` with the per-block residuals under a residual-window
    policy), the post-step final-block snapshot (``snap``, ``(K,
    *shape)`` on device — a completed lane's sample is cut from here, so
    the trajectory buffers can be donated to the *next* dispatched step
    while this one is still unresolved), and the dispatch-time lane
    census (``lanes``: slot index, rid, per-lane effective-eval charge —
    a lane that completed or was evicted between dispatch and resolve is
    recognized by its rid and skipped: its refinement here was
    speculative waste, charged physically but never effectively).
    """
    batch: "_MicroBatch"
    fetch: object                        # device (K,) or (K+B,) residuals
    snap: object                         # device (K, *shape) final tails
    lanes: List[Tuple[int, int, int]]    # (slot k, rid, effective evals)
    windowed: bool                       # residual-window step?
    lo: int                              # window lower bound at dispatch
    phys: int                            # physical evals (incl. lane inits)
    init_eff: int                        # effective evals of lane inits
    epoch: int                           # batch.window_epoch at dispatch


class _MicroBatch:
    """State of one compatibility group's K-slot batch (one compiled
    init/step program).  The engine owns admission/step ordering; this
    class owns the device tensors and per-slot bookkeeping."""

    def __init__(self, engine: "DiffusionSamplingEngine", n: int,
                 schedule: str, shape: Tuple[int, ...], solver: SolverConfig):
        self.engine = engine
        self.n = n
        self.schedule = schedule
        self.shape = shape
        self.solver = solver
        (self.init_fn, self.step_for, self.B, self.S) = \
            engine._build_program(n, schedule, shape, solver)
        self.cost: IterationCost = iteration_cost(n, engine.num_blocks,
                                                  solver.evals_per_step)
        self.max_iters = engine.max_iters if engine.max_iters is not None \
            else self.B
        # truncated step programs are compiled per quantized frontier value;
        # the quantum bounds the cache at ~4 programs per group
        self.trunc_q = engine.truncate_quantum \
            if engine.truncate_quantum is not None else max(1, self.B // 4)
        self.policy = engine.window
        # residual-window group state: the dynamic window lower bound,
        # advanced from the fetched per-block residuals; reset to 0 when a
        # fresh lane is admitted (its blocks are all unconverged)
        self.lo = 0
        # dispatched-but-unresolved refinement count (async pipelining)
        # and the admission epoch guarding window re-opens across them
        self.inflight = 0
        self.window_epoch = 0
        K = engine.batch_size
        self.x_init = jnp.zeros((K,) + shape, engine.dtype)
        self.x_tail = jnp.zeros((self.B, K) + shape, engine.dtype)
        self.prev_coarse = jnp.zeros_like(self.x_tail)
        # accelerator mixing state (None under NoAccel — the step
        # programs then neither take nor return it, keeping them
        # byte-identical to the unaccelerated engine)
        self.astate = engine.accel.init_state(
            jnp.stack([self.x_tail, self.x_tail]), self.max_iters,
            batched=True) if engine.accel.accelerates else None
        self.active = np.zeros((K,), bool)
        self.slots: List[Optional[_Slot]] = [None] * K
        self.newly: List[int] = []

    # ------------------------------------------------------------- capacity

    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None)

    def busy(self) -> bool:
        return any(s is not None for s in self.slots)

    # ------------------------------------------------------------ admission

    def admit(self, rid: int, req: SampleRequest) -> int:
        """Place a request into a free slot (init happens at the next step)."""
        for k, s in enumerate(self.slots):
            if s is None:
                x0 = jax.random.normal(jax.random.PRNGKey(req.seed),
                                       self.shape, self.engine.dtype)
                self.x_init = self.x_init.at[k].set(x0)
                self.slots[k] = _Slot(rid, req)
                self.active[k] = True
                self.newly.append(k)
                # a fresh lane's blocks are all unconverged: the shared
                # residual window must re-open (existing lanes' frozen
                # blocks thaw — sound, they only refine further); the
                # epoch bump keeps an in-flight step's resolve from
                # re-advancing the freshly reset window
                self.lo = 0
                self.window_epoch += 1
                return k
        raise RuntimeError("admit() called with no free slot")

    def evict(self, rid: int) -> Tuple[SampleRequest, SampleResponse]:
        """Preempt a running request: free its slot, discard its lane.

        Frozen-lane masking means batch-mates are untouched — eviction only
        forfeits the evicted request's own (partial) refinement work.
        """
        for k, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                self.slots[k] = None
                self.active[k] = False
                uninitialized = k in self.newly
                if uninitialized:
                    self.newly.remove(k)
                return s.req, SampleResponse(
                    sample=None, iterations=s.iters,
                    final_delta=s.history[-1] if s.history else float("inf"),
                    delta_history=np.asarray(s.history, np.float32),
                    # a lane evicted before its coarse init ran did no work
                    model_evals=0 if uninitialized
                    else self._slot_evals(s),
                    status="preempted")
        raise KeyError(f"request {rid} is not running in this batch")

    # ----------------------------------------------------------------- step

    def _lane_evals(self, iters: int) -> int:
        """Per-lane eval charge for ``iters`` refinements, in the engine's
        mode: truncated frontier schedule when the step programs truncate,
        the flat untruncated rate otherwise — billing always matches what
        an ideally-packed engine of this configuration would execute."""
        return truncated_evals(self.cost, iters) if self.engine.truncate \
            else predicted_evals(self.cost, iters)

    def _slot_evals(self, s: _Slot) -> int:
        """A finished/preempted lane's eval charge.  Residual-window lanes
        bill their *realized* accumulated window schedule (tracked in
        ``_Slot.evals``); exact policies keep the per-lane ideal schedule
        of ``_lane_evals``."""
        if self.policy.needs_block_residuals:
            return s.evals
        return self._lane_evals(s.iters)

    def _refine_evals_at(self, frontier: int) -> int:
        return self.cost.refine_evals_at(frontier) if self.engine.truncate \
            else self.cost.refine_evals

    def _static_frontier(self) -> int:
        """Un-quantized provable group frontier: the min bitwise-frozen
        prefix over active lanes (each lane's frontier is its own
        completed-refinement count, lagged per ``prefix_frontier``)."""
        fr = [prefix_frontier(s.iters) for k, s in enumerate(self.slots)
              if s is not None and self.active[k]]
        return min(fr) if fr else 0

    def _frontier(self) -> int:
        """Quantized group frontier, snapped *down* to the truncation
        quantum so at most ~B/quantum step programs compile.  Snapping
        down is always sound — less truncation than provable."""
        minf = (self._static_frontier() // self.trunc_q) * self.trunc_q
        return min(minf, self.B - 1)

    def _window_frontier(self) -> Tuple[int, int]:
        """Residual-window frontiers: ``(lo, minf)`` where ``lo`` is the
        effective window lower bound (the policy's dynamic bound, floored
        at the provable group frontier and capped at B-1 — the final
        block never retires) and ``minf`` is ``lo`` snapped down to the
        quantum: the compiled suffix starts at ``minf``, blocks
        ``[minf, lo)`` are frozen by masking inside the program."""
        lo = min(max(self.lo, self._static_frontier()), self.B - 1)
        minf = min((lo // self.trunc_q) * self.trunc_q, self.B - 1)
        return lo, minf

    def step_evals(self) -> int:
        """Physical model evals of this batch's next refinement step at
        its current frontier — the unit ``predict_completion`` charges a
        waiting request per round-robin round of cross-group contention."""
        if self.policy.needs_block_residuals:
            _, minf = self._window_frontier()
        else:
            minf = self._frontier() if self.engine.truncate else 0
        return self.engine.batch_size * self._refine_evals_at(minf)

    @hot_loop
    def dispatch(self) -> _InFlight:
        """Enqueue one lockstep refinement (newly-admitted lane inits
        included) with NO device->host sync: the returned
        :class:`_InFlight` token carries the un-fetched residual and the
        post-step final-block snapshot as device values.  The async
        serving loop dispatches the *next* refinement before resolving
        this one, so the blocking fetch in :meth:`resolve` overlaps
        device compute; the synchronous path (``step()``) fuses the two
        back to back.

        A lane that — unbeknownst to the host — converged on the still
        unresolved *previous* refinement gets one speculative extra
        refinement here.  That work is physically wasted but never
        observable: the lane's completed sample is cut from the previous
        step's snapshot at resolve, so responses stay bit-identical to
        the synchronous engine's.
        """
        K = self.engine.batch_size
        params = self.engine.params
        init_eff = phys = 0
        if self.newly:
            # coarse-init the fixed batch inside one donated program (the
            # new-lane write-back included, so the trajectory-sized
            # x_tail/prev_coarse buffers are reused in place off-CPU;
            # occupied lanes keep their refined trajectories)
            m = np.zeros((K,), bool)
            m[self.newly] = True
            with self._compile_span("init", 0):
                self.x_tail, self.prev_coarse = self.init_fn(
                    params, self.x_init, self.x_tail, self.prev_coarse,
                    jnp.asarray(m))
            if self.astate is not None:
                # a recycled slot's mixing history belongs to its previous
                # tenant: zero it so old transients never mix into the
                # freshly admitted request
                self.astate = self.engine.accel.reset_lanes(
                    self.astate, jnp.asarray(m))
            init_eff = len(self.newly) * self.cost.init_evals
            phys += K * self.cost.init_evals
            for k in self.newly:
                self.slots[k].evals = self.cost.init_evals
            self.newly = []

        amask = jnp.asarray(self.active)
        args = (params, self.x_init, self.x_tail, self.prev_coarse, amask)
        windowed = self.policy.needs_block_residuals
        if windowed:
            # residual-window step: the compiled suffix starts at the
            # quantized window floor, blocks [minf, lo) freeze by masking,
            # and the (B,) group block residual rides the one fetch
            lo, minf = self._window_frontier()
            step = self.step_for.windowed(minf)
            args += (jnp.int32(lo),)
        else:
            minf = self._frontier() if self.engine.truncate else 0
            lo = minf
            step = self.step_for(minf)
        with self._compile_span("window" if windowed else "step", minf):
            if self.astate is not None:
                self.x_tail, self.prev_coarse, fetch, self.astate = step(
                    *args, self.astate)
            else:
                self.x_tail, self.prev_coarse, fetch = step(*args)
        if windowed:
            # effective = the window schedule every active lane actually
            # executes; physical = the compiled suffix width times K
            per_lane = self.cost.refine_evals_window(lo)
            lanes = [(k, s.rid, per_lane)
                     for k, s in enumerate(self.slots)
                     if s is not None and self.active[k]]
            phys += K * self.cost.refine_evals_window(minf)
        else:
            # effective = per-lane ideal (each lane truncated at its OWN
            # frontier when the engine truncates); physical = what the
            # lockstep program actually ran (K lanes at the group frontier)
            lanes = [(k, s.rid,
                      self._refine_evals_at(prefix_frontier(s.iters)))
                     for k, s in enumerate(self.slots)
                     if s is not None and self.active[k]]
            phys += K * self._refine_evals_at(minf)
        self.inflight += 1
        # the snapshot reads the REBOUND (post-step) x_tail: a device-side
        # slice enqueued before the next dispatch donates the buffer away
        return _InFlight(batch=self, fetch=fetch, snap=self.x_tail[-1],
                         lanes=lanes, windowed=windowed, lo=lo, phys=phys,
                         init_eff=init_eff, epoch=self.window_epoch)

    def _compile_span(self, program: str, frontier: int):
        """A ``serve.compile`` span around the first call of a program
        variant (``init``, or a ``step``/``window`` step at ``frontier``),
        the call that traces and compiles it; no span for later calls."""
        variant = (program, frontier)
        if variant in self.step_for.called:
            return contextlib.nullcontext()
        self.step_for.called.add(variant)
        return jax.profiler.TraceAnnotation(
            "serve.compile", frontier=frontier,
            windowed=int(program == "window"), init=int(program == "init"),
            param_bytes=self.engine.param_bytes)

    @hot_loop
    def resolve(self, tok: _InFlight):
        """Land a dispatched refinement: block on its residual fetch,
        update lane bookkeeping, finalize converged slots.  Returns
        ``(completions, effective_evals, physical_evals)`` where
        completions are ``(rid, req, response)``.

        Host traffic: exactly ONE device->host sync per refinement — the
        batched ``(K,)`` residual vector, with the ``(B,)`` per-block
        residual piggybacked onto the same fetch under a residual-window
        policy — plus one per completed request (that lane's row of the
        snapshot only, never the ``(B, K, *shape)`` trajectory).
        """
        K = self.engine.batch_size
        self.inflight -= 1
        fetched = _host_fetch(tok.fetch)     # the one per-iteration sync
        delta_np = fetched[:K]
        if tok.windowed:
            block_np = fetched[K:]
            if tok.epoch == self.window_epoch:
                # advance the shared window from the lane-max residuals;
                # never retreat below what a younger resolved step already
                # proved.  An admission since dispatch re-opened the
                # window — its reset wins (smaller window = sound).
                self.lo = max(self.lo, int(self.policy.advance(
                    tok.lo, block_np, self.B)))

        eff = tok.init_eff
        completed: List[Tuple[int, SampleRequest, SampleResponse]] = []
        for k, rid, lane_eff in tok.lanes:
            slot = self.slots[k]
            if slot is None or slot.rid != rid:
                # lane completed/was evicted between dispatch and resolve:
                # this refinement of it was speculative waste — physical,
                # never effective, and never observable
                continue
            eff += lane_eff
            if tok.windowed:
                slot.evals += lane_eff
            slot.iters += 1
            slot.history.append(float(delta_np[k]))
            # f32 compare, matching the engine's still_refining gate
            if (delta_np[k] < np.float32(slot.req.tol)
                    or slot.iters >= self.max_iters):
                completed.append((slot.rid, slot.req, SampleResponse(
                    # fetch ONLY the completed lane's final state — not the
                    # (B, K, *shape) trajectory, not even the (K, *shape)
                    # final row
                    sample=_host_fetch(tok.snap[k]),
                    iterations=slot.iters,
                    final_delta=slot.history[-1],
                    delta_history=np.asarray(slot.history, np.float32),
                    model_evals=self._slot_evals(slot))))
                self.slots[k] = None
                self.active[k] = False
        return completed, eff, tok.phys

    @hot_loop
    def step(self):
        """One synchronous refinement: dispatch + resolve back to back —
        the ``simulate()``/``drain()`` path, bit-identical to the
        pre-async fused step."""
        return self.resolve(self.dispatch())


class DiffusionSamplingEngine:
    """Micro-batching SRDS sampling service with per-slot convergence gating
    and a deterministic virtual clock for SLO-aware scheduling.

    Args:
      model_fn:     eps-predictor ``(x, t) -> eps`` (batched over leading x
                    axes).
      sample_shape: default per-sample tensor shape (no batch axis).
      solver:       default solver config (requests may override).
      schedule:     default schedule family name (``make_schedule`` key).
      num_steps:    default grid size for requests that don't pin one.
      batch_size:   K — slots per micro-batch (one compiled program per
                    compatibility group).
      num_blocks / max_iters / norm: SRDS knobs, as in ``SRDSConfig``.
      mesh / axis:  optional device mesh + *block* axis name: run each
                    refinement's fine solves block-parallel under
                    ``shard_map``.
      data_axis:    optional *data* axis name on ``mesh``: shard the K slot
                    batch itself (requires ``batch_size`` divisible by the
                    axis size).  Composes with ``axis`` on a 2D mesh.
      allow_inexact: accept stochastic (``ddpm``) solvers despite the
                    lane-exactness caveat (see module docstring).
      sec_per_eval: seconds charged per *physical* model eval on the
                    virtual clock, and the cost model's per-eval price
                    under **either** clock (calibrate it to measured
                    wall time per eval so ``predict_completion`` — and
                    through it CostAware admission — stays meaningful on
                    a wall clock).
      clock:        the engine's time source (:mod:`repro.serve.clock`).
                    ``None`` (default) -> a fresh deterministic
                    :class:`~repro.serve.clock.VirtualClock` — bit-exact
                    discrete-event time, what ``simulate()`` requires.
                    Pass a :class:`~repro.serve.clock.MonotonicClock`
                    for real-time serving under
                    :class:`repro.serve.async_loop.AsyncServeLoop`;
                    latency/SLO stamps then read real elapsed seconds
                    and wall deadlines (``deadline_wall``) apply.
      truncate:     converged-prefix truncation of the refinement step
                    (default on): each step program is compiled for the
                    group's quantized minimum frontier and statically skips
                    the provably bitwise-frozen block prefix — fewer
                    physical evals per step; bit-identical results for
                    elementwise-deterministic denoisers (matmul denoisers:
                    roundoff-level, see the guarantee block above).  Forced
                    off when ``axis`` is set (the block-parallel fine-solve
                    layout slices the full block dim).  Shorthand for
                    ``window=ExactPrefix()``.
      window:       explicit :class:`repro.core.window.FrontierPolicy`
                    (overrides ``truncate``): ``ResidualWindow(window_tol)``
                    opts into the approximate residual-driven group window
                    — fewer evals at a ``window_tol``-bounded quality cost
                    and a weakened per-request guarantee (see the module
                    docstring).  Truncating policies degrade to
                    ``FixedBudget`` when ``axis`` is set, like
                    ``truncate``.
      truncate_quantum: frontier quantization step (None -> B//4): bounds
                    the per-group compiled-step-program cache at
                    ~B/quantum variants.
      use_fused:    route the predictor-corrector + residual through the
                    fused Pallas kernel, whose per-tile L1 partials feed
                    the ``(K,)`` convergence residual directly.  ``None``
                    (default) = on where supported (TPU), off elsewhere.
      accel:        optional :class:`repro.core.accel.Accelerator` mixing
                    the refinement fixed point (fewer iterations to the
                    same tolerance, zero extra model evals per
                    iteration).  ``None`` (default) keeps the bit-exact
                    unaccelerated step programs byte-for-byte.  The
                    mixing state rides each micro-batch (reset per lane
                    on admission, so a recycled slot's history never
                    leaks into the next request) and the residual fetch
                    is untouched — still exactly one host sync per
                    refinement.  Iteration savings are priced honestly:
                    per-iteration ``IterationCost`` is unchanged (mixing
                    is eval-free) and :class:`IterationEMA` learns the
                    reduced per-tier iteration counts from completions,
                    which ``predict_completion`` then reflects.  Pairing
                    rule: a truncating frontier policy (the default
                    ``ExactPrefix``, or ``ResidualWindow``) requires a
                    ``prefix_exact`` accelerator (``TriangularAccel``);
                    ``AndersonAccel`` needs ``truncate=False`` /
                    ``window=FixedBudget()`` (see ``repro.core.accel``).
    """

    def __init__(self, model_fn: ModelFn, sample_shape: Tuple[int, ...],
                 solver: SolverConfig = SolverConfig("ddim"),
                 schedule: str = "ddpm_linear", num_steps: int = 64,
                 batch_size: int = 4, num_blocks: Optional[int] = None,
                 max_iters: Optional[int] = None, norm: str = "l1_mean",
                 mesh=None, axis: Optional[str] = None,
                 data_axis: Optional[str] = None,
                 allow_inexact: bool = False, sec_per_eval: float = 1e-6,
                 dtype=jnp.float32, truncate: bool = True,
                 truncate_quantum: Optional[int] = None,
                 use_fused: Optional[bool] = None, ema_alpha: float = 0.3,
                 window=None, clock: Optional[Clock] = None, accel=None):
        self.model_fn = model_fn
        # every model eval goes through the sharding-aware Denoiser seam;
        # plain callables adapt for free (replicated specs).  Under an
        # engine mesh a model-parallel denoiser evaluates inside the
        # programs' shard_map (see _spmd); without one it self-wraps over
        # its own bound mesh.
        den = as_denoiser(model_fn)
        if den.is_model_parallel and mesh is None and den.mesh is None:
            raise ValueError(
                "model-parallel denoiser needs a mesh: pass mesh= to "
                "the engine or bind one with Denoiser.bind(mesh)")
        self.denoiser = den
        self.sample_shape = tuple(sample_shape)
        self.solver = solver
        self.schedule = schedule
        self.num_steps = num_steps
        self.batch_size = batch_size
        self.num_blocks = num_blocks
        self.max_iters = max_iters
        self.norm = norm
        self.mesh = mesh
        self.axis = axis
        self.data_axis = data_axis
        self.allow_inexact = allow_inexact
        self.sec_per_eval = sec_per_eval
        self.dtype = dtype
        # Frontier policy seam (repro.core.window): an explicit window
        # policy wins, else `truncate` maps to ExactPrefix/FixedBudget.
        # Block-parallel fine solves slice the full (B, K, ...) head stack
        # per device, so truncating policies degrade to FixedBudget there
        # (suffix truncation would unbalance the shards).
        pol = resolve_policy(window, truncate)
        if axis is not None and pol.truncates:
            pol = FixedBudget()
        self.window = pol
        self.truncate = pol.truncates
        self.truncate_quantum = truncate_quantum
        # fixed-point acceleration seam (repro.core.accel): with NoAccel
        # (the default) the step programs are byte-identical to the
        # pre-seam engine; an accelerating Accelerator's mixing state
        # rides each micro-batch and its step programs take/return it
        self.accel = resolve_accel(accel)
        if self.accel.accelerates and pol.truncates \
                and not self.accel.prefix_exact:
            # same pairing rule as run_parareal: truncation freezes blocks
            # on the provable serial-prefix schedule, which joint mixing
            # invalidates (see repro.core.accel)
            raise ValueError(
                f"{type(self.accel).__name__} does not preserve the "
                f"serial-prefix invariant that the engine's truncating "
                f"frontier policy ({type(pol).__name__}) relies on; use "
                f"TriangularAccel, or build the engine with truncate=False "
                f"/ window=FixedBudget().")
        self.use_fused = resolve_fused(use_fused)
        # every program takes the weights as its argument 0, so its HLO
        # holds no weights and one executable serves any weights of the
        # same shapes; they are never donated.  Without a mesh they are
        # the caller's arrays as they are.  Under a mesh the programs take
        # them replicated (``_spmd``), so they are placed on every mesh
        # device once, here: jit would otherwise copy them there on every
        # call
        self.params = den.params
        if mesh is not None and compat.tree.leaves(den.params):
            self.params = jax.device_put(den.params, NamedSharding(mesh, P()))
        self.param_bytes = sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                               for leaf in compat.tree.leaves(den.params))
        # buffer donation lets XLA reuse the trajectory-sized x_tail /
        # prev_coarse allocations across refinements; the CPU backend
        # ignores donation (with a warning), so only donate off-CPU
        self._donate = (2, 3) if jax.default_backend() != "cpu" else ()
        self.iters_ema = IterationEMA(alpha=ema_alpha)
        if data_axis is not None and mesh is None:
            raise ValueError("data_axis requires a mesh")
        # the fine solves' heads layout: lanes over data_axis, sample dims
        # over the denoiser's own axes.  Raises clearly on an unbound axis
        # or a denoiser that shards the lane dim the engine owns.
        self._heads_spec = P()
        if mesh is not None and (data_axis is not None
                                 or den.is_model_parallel):
            self._heads_spec = denoiser_spec(data_axis, den, mesh=mesh)
        if data_axis is not None:
            d = mesh.shape[data_axis]
            if batch_size % d != 0:
                raise ValueError(
                    f"batch_size={batch_size} not divisible by data axis "
                    f"size {d}")
        self._queue: List[Tuple[int, SampleRequest]] = []
        self._next_rid = 0
        self._programs: Dict[tuple, Tuple[Callable, Callable, int, int]] = {}
        self._batches: Dict[tuple, _MicroBatch] = {}
        self._rr = 0                      # round-robin cursor over batches
        self._first_arrival: Optional[float] = None
        # effective (per-active-slot) vs physical (per-lane) eval accounting
        self.effective_evals = 0
        self.physical_evals = 0
        self.requests_served = 0
        # the time seam: deterministic virtual time unless the caller
        # plugs in a wall clock (repro.serve.clock)
        self._clock = clock if clock is not None else VirtualClock()
        self.records: List[CompletionRecord] = []

    # ------------------------------------------------------------------ API

    @property
    def clock(self) -> float:
        """Current engine time (seconds): the deterministic accumulator
        of a :class:`~repro.serve.clock.VirtualClock`, or real elapsed
        seconds under a :class:`~repro.serve.clock.MonotonicClock`."""
        return self._clock.now()

    def request_deadline(self, req: SampleRequest) -> float:
        """``req``'s absolute deadline in THIS engine's clock regime:
        ``deadline_wall`` under a wall clock, the virtual ``deadline``
        otherwise, ``slo_ms``-relative on either.  Policies and latency
        stamping go through here so deadlines on the wrong clock are
        never compared against the running one."""
        return req.absolute_deadline(wall=self._clock.is_wall)

    def _resolve(self, req: SampleRequest):
        """(num_steps, schedule, shape, solver) with engine defaults filled."""
        n = req.num_steps if req.num_steps is not None else self.num_steps
        schedule = req.schedule if req.schedule is not None else self.schedule
        shape = tuple(req.shape) if req.shape is not None \
            else self.sample_shape
        solver = req.solver if req.solver is not None else self.solver
        return n, schedule, shape, solver

    def compat_key(self, req: SampleRequest) -> tuple:
        """The batching compatibility key: requests agreeing on
        (num_steps, schedule, shape, solver) share one micro-batch group
        and one compiled program.  Hashable (policies may group by it)."""
        n, schedule, shape, solver = self._resolve(req)
        return (n, schedule, shape, _solver_fp(solver))

    def submit(self, req: SampleRequest) -> int:
        """Enqueue a request; returns its id (key into ``drain()``'s dict).

        Invalid requests are rejected here, so they can never poison an
        already-queued batch: unservable grids (no block decomposition),
        unknown solvers/schedules, and — unless the engine was built with
        ``allow_inexact=True`` — the stochastic ``ddpm`` solver, whose
        batch-shaped noise breaks the per-request lane-exactness guarantee
        (ROADMAP caveat: same distribution, different realization than the
        single-request run).
        """
        n, schedule, shape, solver = self._resolve(req)
        resolve_blocks(n, self.num_blocks)   # raises on an unservable grid
        if solver.name not in solver_names():
            raise ValueError(f"unknown solver {solver.name!r}; "
                             f"have {solver_names()}")
        make_schedule(schedule, n)           # raises on an unknown family
        if solver.name == "ddpm" and not self.allow_inexact:
            raise ValueError(
                "stochastic 'ddpm' solver draws batch-shaped noise, so "
                "per-request lane-exactness vs the single-request run is "
                "NOT guaranteed under micro-batching; construct the engine "
                "with allow_inexact=True to accept distribution-level "
                "(not bitwise) results.")
        rid = self._next_rid
        self._next_rid += 1
        self._first_arrival = req.arrival_time \
            if self._first_arrival is None \
            else min(self._first_arrival, req.arrival_time)
        self._queue.append((rid, req))
        return rid

    def drain(self) -> Dict[int, SampleResponse]:
        """Run every queued request to convergence; returns rid -> response.

        FIFO admission over the scheduling primitives below: requests are
        admitted into free slots of their compatibility group's micro-batch
        as slots recycle; busy batches step round-robin.  Arrival times and
        deadlines are *recorded* (the virtual clock always runs) but not
        enforced — SLO-aware admission lives in
        :func:`repro.serve.scheduler.simulate`.
        """
        results: Dict[int, SampleResponse] = {}
        queue = self.pull_queue()
        while queue or self.busy():
            remaining: List[Tuple[int, SampleRequest]] = []
            for rid, req in queue:
                # not-yet-arrived requests wait: admitting one would warp
                # the clock past co-batched requests' actual service time
                if req.arrival_time <= self.clock and self.free_slots(req) > 0:
                    self.admit(rid, req)
                else:
                    remaining.append((rid, req))
            queue = remaining
            if self.busy():
                for rid, resp in self.step_once():
                    results[rid] = resp
            elif queue:
                # idle with only future-stamped work: jump to its arrival
                self.advance_clock(min(r.arrival_time for _, r in queue))
        return results

    def stats(self) -> Dict[str, float]:
        served = max(self.requests_served, 1)
        lats = [r.latency for r in self.records if r.status == "ok"]
        with_slo = [r for r in self.records if math.isfinite(r.deadline)]
        met = sum(1 for r in self.records if r.status == "ok" and r.slo_met)
        p50, p95, p99 = (np.percentile(lats, [50, 95, 99])
                         if lats else (0.0, 0.0, 0.0))
        # goodput over the served span (first *submitted* arrival -> now),
        # matching SimReport's makespan denominator — idle time before a
        # late-starting trace must not dilute it, and a rejected first
        # arrival (which leaves no completion record) still anchors it
        start = self._first_arrival if self._first_arrival is not None \
            else min((r.arrival_time for r in self.records), default=0.0)
        span = self.clock - start
        return {
            "requests_served": self.requests_served,
            "effective_evals": self.effective_evals,
            "physical_evals": self.physical_evals,
            "effective_evals_per_sample": self.effective_evals / served,
            "physical_evals_per_sample": self.physical_evals / served,
            # clock-time latency/SLO metrics (0.0 / 1.0 when idle) —
            # deterministic under the default VirtualClock, real elapsed
            # seconds under a MonotonicClock
            "latency_p50": float(p50),
            "latency_p95": float(p95),
            "latency_p99": float(p99),
            # fraction of deadline-carrying requests that finished in time
            "slo_attainment": (sum(1 for r in with_slo
                                   if r.status == "ok" and r.slo_met)
                               / len(with_slo)) if with_slo else 1.0,
            # SLO-met completions per clock second (deadline-free requests
            # always count as met)
            "goodput_rps": met / span if span > 0 else 0.0,
            # key name kept for artifact-schema stability; reads the
            # engine clock, virtual or wall
            "virtual_time": self.clock,
        }

    def reset_metrics(self) -> None:
        """Zero the clock, eval counters and latency ledger (compiled
        programs are kept — resets are for back-to-back deterministic
        simulation runs on one warm engine)."""
        if self.busy() or self._queue:
            raise RuntimeError("reset_metrics() with requests in flight")
        self._next_rid = 0
        self._rr = 0
        self._first_arrival = None
        # drop (empty) batch state: the set of instantiated groups feeds the
        # round-robin scan order, so a warm run must rebuild it exactly as a
        # fresh run would.  Compiled programs stay cached — no recompile.
        self._batches = {}
        self.effective_evals = 0
        self.physical_evals = 0
        self.requests_served = 0
        self._clock.reset()
        self.records = []
        # the learned per-tier iteration estimates are run state too: a
        # warm re-run must make the same admission decisions as a fresh one
        self.iters_ema.reset()

    # ------------------------------------------------- scheduling primitives

    def pull_queue(self) -> List[Tuple[int, SampleRequest]]:
        """Take ownership of the submitted-but-unadmitted queue (scheduler
        policies reorder/reject it; ``drain`` serves it FIFO)."""
        q, self._queue = self._queue, []
        return q

    def _batch_for(self, req: SampleRequest) -> _MicroBatch:
        key = self.compat_key(req)
        if key not in self._batches:
            n, schedule, shape, solver = self._resolve(req)
            self._batches[key] = _MicroBatch(self, n, schedule, shape, solver)
        return self._batches[key]

    def free_slots(self, req: SampleRequest) -> int:
        """Free slots in ``req``'s compatibility group's micro-batch.

        A read-only query: a group nobody was admitted to yet is all-free
        and is NOT instantiated (no device buffers, no compile) — batches
        materialize in ``admit``.
        """
        b = self._batches.get(self.compat_key(req))
        return self.batch_size if b is None else b.free_slots()

    def admit(self, rid: int, req: SampleRequest) -> None:
        """Place a validated request into its group's batch (a free slot
        must exist — check ``free_slots`` first).  Work on a request cannot
        start before it arrives, so the clock catches up to its
        ``arrival_time`` (keeps ``drain()`` latencies non-negative)."""
        with jax.profiler.TraceAnnotation(
                "serve.admit", rid=rid,
                waited_ms=1e3 * (self.clock - req.arrival_time)):
            self.advance_clock(req.arrival_time)
            self._batch_for(req).admit(rid, req)

    def busy(self) -> bool:
        return any(b.busy() for b in self._batches.values())

    @hot_loop
    def step_once(self) -> List[Tuple[int, SampleResponse]]:
        """One synchronous lockstep refinement on the next busy
        micro-batch (round-robin): dispatch + resolve fused back to
        back, advancing the clock by the step's physical eval cost.
        Returns completions finalized by this step.  Bit-identical to
        the pre-async engine — the asynchronous loop instead interleaves
        :meth:`step_dispatch` / :meth:`step_resolve` so device compute
        overlaps the host's blocking fetch."""
        tok = self.step_dispatch()
        if tok is None:
            return []
        return self.step_resolve(tok)

    def step_dispatch(self, max_inflight: int = 2) -> Optional[_InFlight]:
        """Dispatch one refinement on the next busy micro-batch
        (round-robin) that has fewer than ``max_inflight`` unresolved
        steps; returns an opaque token for :meth:`step_resolve`, or
        ``None`` when nothing is dispatchable.  Performs NO host sync —
        the device starts computing while the host goes on scheduling.
        Tokens must be resolved in dispatch order (oldest first)."""
        batches = list(self._batches.values())
        for off in range(len(batches)):
            b = batches[(self._rr + off) % len(batches)]
            if b.busy() and b.inflight < max_inflight:
                self._rr = (self._rr + off + 1) % len(batches)
                with jax.profiler.TraceAnnotation("serve.dispatch") as span:
                    tok = b.dispatch()
                    span.set_metadata(frontier=tok.lo)
                return tok
        return None

    @hot_loop
    def step_resolve(self, tok: _InFlight) -> List[Tuple[int,
                                                         SampleResponse]]:
        """Land a dispatched refinement: block on its residual fetch
        (that refinement's ONE host sync), account effective/physical
        evals, charge the clock its physical cost, and finalize
        completions."""
        with jax.profiler.TraceAnnotation("serve.resolve") as span:
            completed, eff, phys = tok.batch.resolve(tok)
            self.effective_evals += eff
            self.physical_evals += phys
            self._clock.charge(phys * self.sec_per_eval)
            span.set_metadata(completed=len(completed))
            return [(rid, self._finalize(rid, req, resp))
                    for rid, req, resp in completed]

    def evict(self, rid: int) -> SampleResponse:
        """Preempt a running request (scheduler policy decision); its
        partial work is discarded and recorded as status="preempted"."""
        for b in self._batches.values():
            try:
                req, resp = b.evict(rid)
            except KeyError:
                continue
            return self._finalize(rid, req, resp)
        raise KeyError(f"request {rid} is not running")

    def advance_clock(self, until: float) -> None:
        """Idle the engine forward (no work to do before the next
        arrival): a virtual clock warps, a wall clock really sleeps."""
        self._clock.wait_until(until)

    def predict_iterations(self, req: SampleRequest) -> float:
        """Expected refinement count for ``req``: the *most optimistic* of
        the online per-tier EMA (:class:`IterationEMA`, fed by completed
        requests of the same ``(compat_key, tol)`` tier) and the caller's
        static ``iters_hint``; worst-case ``max_iters`` when neither
        exists.  Taking the minimum keeps CostAware's rejection on the
        optimistic side: the EMA is a *mean*, so alone it could exceed an
        easier-than-average request's true need and over-reject."""
        n, _, _, _ = self._resolve(req)
        B, _ = resolve_blocks(n, self.num_blocks)
        cap = self.max_iters if self.max_iters is not None else B
        cands = [self.iters_ema.predict((self.compat_key(req),
                                         float(req.tol)))]
        if req.iters_hint is not None:
            cands.append(float(req.iters_hint))
        cands = [c for c in cands if c is not None]
        est = min(cands) if cands else float(cap)
        return min(float(est), float(cap))

    def predict_completion(self, req: SampleRequest,
                           now: Optional[float] = None) -> float:
        """Cost-model completion estimate (virtual seconds) if ``req`` were
        admitted now: the frontier policy's own per-iteration eval pricing
        (:meth:`repro.core.window.FrontierPolicy.predict_evals` — for the
        default ``ExactPrefix``, the exact frontier schedule the step
        programs execute) times the physical K-lane width, for
        :meth:`predict_iterations` refinements — **plus cross-group device
        contention**: busy micro-batches step round-robin on the one
        device, so every *other currently-busy* group charges one step at
        its current frontier cost per refinement round this request needs.
        Within those terms the estimate stays optimistic — the frontier is
        assumed to advance every refinement, contending groups are priced
        at today's (only-shrinking) step cost and assumed not to grow, and
        the iteration estimate is the smallest available one — so
        rejection sheds requests hopeless under the *currently visible*
        load.  (Both the iteration estimate and the contention snapshot
        are estimates: a contending group can drain early, so 'never
        over-rejects' holds relative to them, not as an absolute.)"""
        now = self.clock if now is None else now
        n, _, _, solver = self._resolve(req)
        cost = iteration_cost(n, self.num_blocks, solver.evals_per_step)
        iters = self.predict_iterations(req)
        evals = self.batch_size * self.window.predict_evals(cost, iters)
        key = self.compat_key(req)
        rounds = int(math.ceil(iters))
        contention = rounds * sum(
            b.step_evals() for bkey, b in self._batches.items()
            if bkey != key and b.busy())
        return now + (evals + contention) * self.sec_per_eval

    def _finalize(self, rid: int, req: SampleRequest,
                  resp: SampleResponse) -> SampleResponse:
        """Stamp virtual-clock latency/SLO fields and ledger the outcome."""
        resp.arrival_time = req.arrival_time
        resp.finish_time = self.clock
        resp.latency = resp.finish_time - req.arrival_time
        resp.deadline = self.request_deadline(req)
        resp.slo_met = resp.status == "ok" \
            and resp.finish_time <= resp.deadline
        if resp.status == "ok":
            self.requests_served += 1
            # feed the online per-tier iterations predictor
            self.iters_ema.observe((self.compat_key(req), float(req.tol)),
                                   resp.iterations)
        self.records.append(CompletionRecord(
            rid=rid, arrival_time=resp.arrival_time,
            finish_time=resp.finish_time, deadline=resp.deadline,
            latency=resp.latency, slo_met=resp.slo_met, status=resp.status))
        return resp

    # ------------------------------------------------------- compiled cells

    def _build_program(self, n: int, schedule: str, shape: Tuple[int, ...],
                       solver: SolverConfig):
        """(init_fn, step_for, B, S) for one compatibility group (cached).

        ``step_for(minf)`` returns the jitted one-refinement program whose
        fine solves and corrector sweep are statically truncated to the
        block suffix ``[minf, B)`` (one compiled variant per quantized
        frontier value, cached).  Every program takes the weights,
        ``self.params``, as argument 0, so no weight is a constant of its
        HLO.  ``x_tail``/``prev_coarse`` are donated so
        XLA reuses the trajectory-sized buffers across refinements.
        """
        key = (n, schedule, shape, _solver_fp(solver))
        if key in self._programs:
            return self._programs[key]
        B, S = resolve_blocks(n, self.num_blocks)
        sched = make_schedule(schedule, n)
        # run the schedule in the engine's working dtype so results match a
        # standalone srds_sample on the same-dtype schedule bit for bit
        sched = DiffusionSchedule(ab=sched.ab.astype(self.dtype),
                                  t_model=sched.t_model.astype(self.dtype),
                                  kind=sched.kind)
        starts = jnp.arange(B, dtype=jnp.int32) * S
        norm = self.norm
        use_fused = self.use_fused
        accel = self.accel
        # both solves evaluate the weights each program takes as its
        # argument 0 (``params``).  Without a mesh they call the seam
        # standalone.  Under a mesh every program body runs inside one
        # replicated shard_map (``_spmd``): the coarse sweep takes the
        # seam's inner form (a model-parallel denoiser slices, runs its
        # shard_fn, gathers), and the fine solves, whose heads _make_fine
        # slices once per solve, run shard_fn directly
        den, mesh = self.denoiser, self.mesh

        def solves(params):
            """(G, fine): the coarse solve and the fine-solve hook."""
            d = den.with_params(params)
            coarse_den, fine_den = (d, d) if mesh is None \
                else (d.inner_eval(), d.shard_eval())

            def G(x, i0):
                return solve(coarse_den, sched, solver, x, i0, 1, S)

            def F(x, i0):
                return solve(fine_den, sched, solver, x, i0, S, 1)

            return G, self._make_fine(F, starts, B)

        def init_body(params, x_init, x_tail, prev_coarse, new_mask):
            # coarse initialization sweep for the whole slot batch, with
            # the new-lane write-back fused in so x_tail/prev_coarse are
            # donated (occupied lanes keep their refined trajectories —
            # the old value flows through the jnp.where)
            G, _ = solves(params)
            tail0 = coarse_init_sweep(G, x_init, starts)
            m = new_mask.reshape((1,) + new_mask.shape + (1,) * len(shape))
            return (jnp.where(m, tail0, x_tail),
                    jnp.where(m, tail0, prev_coarse))

        init_fn = jax.jit(self._spmd(init_body), donate_argnums=self._donate)

        step_cache: Dict[int, Callable] = {}
        step_win_cache: Dict[int, Callable] = {}

        def make_step(minf: int):
            if accel.accelerates:
                def step_accel(params, x_init, x_tail, prev_coarse, active,
                               astate):
                    """Accelerated refinement: the unaccelerated step's
                    math, then one :meth:`Accelerator.apply` on the joint
                    state (per-lane, live-masked to the compiled suffix).
                    The residual is recomputed post-mix — the gate must
                    see what was actually committed — and still rides the
                    program's one output fetch."""
                    heads = jnp.concatenate([x_init[None], x_tail[:-1]],
                                            axis=0)
                    if minf:
                        heads = heads[minf:]
                    G, fine = solves(params)
                    y = fine(heads)
                    new_tail, cur_all, _ = suffix_refinement(
                        G, y, x_init, x_tail, prev_coarse, starts, minf,
                        use_fused=use_fused, norm=norm, batched=True)
                    m = active.reshape((1,) + active.shape
                                       + (1,) * (x_tail.ndim - 2))
                    new_tail = jnp.where(m, new_tail, x_tail)
                    cur_all = jnp.where(m, cur_all, prev_coarse)
                    live = (jnp.arange(B, dtype=jnp.int32) >= minf) \
                        if minf else None
                    z_mix, astate = accel.apply(
                        astate, jnp.stack([x_tail, prev_coarse]),
                        jnp.stack([new_tail, cur_all]), live=live,
                        batched=True)
                    # inactive lanes are fixed points of the mix (f = 0);
                    # the re-mask makes that bitwise, not just numeric
                    new_tail = jnp.where(m, z_mix[0], x_tail)
                    cur_all = jnp.where(m, z_mix[1], prev_coarse)
                    delta = convergence_norm(new_tail[-1] - x_tail[-1],
                                             norm, batched=True)
                    delta = jnp.where(active, delta, jnp.inf)
                    return new_tail, cur_all, delta, astate

                donate = self._donate + (5,) if self._donate else ()
                return jax.jit(self._spmd(step_accel), donate_argnums=donate)

            def step_fn(params, x_init, x_tail, prev_coarse, active):
                """One Parareal refinement over all K slots, truncated to
                the suffix [minf, B) via the engine's shared
                :func:`suffix_refinement`; inactive slots (free, or
                holding a finished sample) are frozen no-ops."""
                heads = jnp.concatenate([x_init[None], x_tail[:-1]], axis=0)
                if minf:
                    heads = heads[minf:]
                G, fine = solves(params)
                y = fine(heads)
                new_tail, cur_all, delta = suffix_refinement(
                    G, y, x_init, x_tail, prev_coarse, starts, minf,
                    use_fused=use_fused, norm=norm, batched=True)
                m = active.reshape((1,) + active.shape
                                   + (1,) * (x_tail.ndim - 2))
                new_tail = jnp.where(m, new_tail, x_tail)
                cur_all = jnp.where(m, cur_all, prev_coarse)
                # inactive lanes' pre-mask residual entries are discarded
                delta = jnp.where(active, delta, jnp.inf)
                return new_tail, cur_all, delta

            return jax.jit(self._spmd(step_fn), donate_argnums=self._donate)

        def make_step_windowed(minf: int):
            if accel.accelerates:
                def step_accel(params, x_init, x_tail, prev_coarse, active,
                               lo, astate):
                    """Accelerated residual-window refinement: mixing is
                    live-masked to the dynamic window ``[lo, B)`` —
                    window-frozen blocks stay bitwise untouched — and the
                    per-block residuals are recomputed post-mix before
                    the lane-max reduction, so the window only advances
                    past blocks whose *committed* values converged."""
                    heads = jnp.concatenate([x_init[None], x_tail[:-1]],
                                            axis=0)
                    if minf:
                        heads = heads[minf:]
                    G, fine = solves(params)
                    y = fine(heads)
                    new_tail, cur_all, _, _ = suffix_refinement(
                        G, y, x_init, x_tail, prev_coarse, starts, minf,
                        use_fused=use_fused, norm=norm, batched=True,
                        window_lo=lo)
                    m = active.reshape((1,) + active.shape
                                       + (1,) * (x_tail.ndim - 2))
                    new_tail = jnp.where(m, new_tail, x_tail)
                    cur_all = jnp.where(m, cur_all, prev_coarse)
                    live = jnp.arange(B, dtype=jnp.int32) >= lo
                    z_mix, astate = accel.apply(
                        astate, jnp.stack([x_tail, prev_coarse]),
                        jnp.stack([new_tail, cur_all]), live=live,
                        batched=True)
                    new_tail = jnp.where(m, z_mix[0], x_tail)
                    cur_all = jnp.where(m, z_mix[1], prev_coarse)
                    # full-width post-mix block residuals: frozen blocks
                    # are bitwise unchanged, so their rows are exactly 0
                    br = blockwise_norm(new_tail - x_tail, norm,
                                        batched=True)
                    delta = jnp.where(active, br[-1], jnp.inf)
                    br_g = jnp.max(jnp.where(active[None, :], br, 0.0),
                                   axis=1)
                    return (new_tail, cur_all,
                            jnp.concatenate([delta, br_g]), astate)

                donate = self._donate + (6,) if self._donate else ()
                return jax.jit(self._spmd(step_accel), donate_argnums=donate)

            def step_fn(params, x_init, x_tail, prev_coarse, active, lo):
                """One residual-window refinement over all K slots: the
                compiled suffix is [minf, B), blocks [minf, lo) freeze by
                masking inside the engine's shared
                :func:`suffix_refinement`, and the (B,) lane-max per-block
                residual piggybacks on the (K,) residual so the host still
                syncs exactly once."""
                heads = jnp.concatenate([x_init[None], x_tail[:-1]], axis=0)
                if minf:
                    heads = heads[minf:]
                G, fine = solves(params)
                y = fine(heads)
                new_tail, cur_all, delta, br = suffix_refinement(
                    G, y, x_init, x_tail, prev_coarse, starts, minf,
                    use_fused=use_fused, norm=norm, batched=True,
                    window_lo=lo)
                m = active.reshape((1,) + active.shape
                                   + (1,) * (x_tail.ndim - 2))
                new_tail = jnp.where(m, new_tail, x_tail)
                cur_all = jnp.where(m, cur_all, prev_coarse)
                # inactive lanes' pre-mask residual entries are discarded
                delta = jnp.where(active, delta, jnp.inf)
                # group per-block residual: max over active lanes — the
                # shared window only advances past blocks EVERY active
                # lane passed (inactive lanes don't refine, so they never
                # hold the window back)
                br_g = jnp.max(jnp.where(active[None, :], br, 0.0), axis=1)
                if minf:
                    br_g = jnp.concatenate(
                        [jnp.zeros((minf,), br_g.dtype), br_g])
                return new_tail, cur_all, jnp.concatenate([delta, br_g])

            return jax.jit(self._spmd(step_fn), donate_argnums=self._donate)

        def step_for(minf: int) -> Callable:
            if minf not in step_cache:
                step_cache[minf] = make_step(minf)
            return step_cache[minf]

        def step_windowed(minf: int) -> Callable:
            if minf not in step_win_cache:
                step_win_cache[minf] = make_step_windowed(minf)
            return step_win_cache[minf]

        step_for.cache = step_cache     # introspectable: compiled variants
        step_for.windowed = step_windowed
        # (program, frontier) variants called at least once: the first
        # call traces and compiles (``_MicroBatch._compile_span``)
        step_for.called = set()
        step_windowed.cache = step_win_cache

        self._programs[key] = (init_fn, step_for, B, S)
        return self._programs[key]

    def _spmd(self, body: Callable) -> Callable:
        """``body`` as one program over the engine mesh: a shard_map with
        every operand and result replicated, inside which the fine-solve
        hook splits blocks, lanes and the denoiser's sample dims across
        devices and all-gathers them.
        Everything else — the coarse sweep, the corrector and residual,
        mixing — runs on each device's full replica, so Pallas kernels
        there are per-device calls (Mosaic kernels cannot be
        auto-partitioned across a mesh).  Identity without a mesh."""
        if self.mesh is None:
            return body
        return compat.shard_map(body, mesh=self.mesh, in_specs=P(),
                                out_specs=P(), check_vma=False)

    def _make_fine(self, F, starts, B: int):
        """The fine-solve hook: ``F`` vmapped over the (suffix) block stack.

        Under a mesh it runs inside :meth:`_spmd`'s replicated body: the
        heads tensor is sliced once to the engine's heads spec — blocks
        over ``axis`` (the :func:`repro.core.pipelined.srds_sharded_local`
        layout), lanes over ``data_axis`` (lanes are independent), sample
        dims over a model-parallel denoiser's own axes
        (:func:`repro.parallel.sharding.denoiser_spec`) — every eval of the
        solve is the denoiser's ``shard_eval``, and one tiled
        ``all_gather`` per sharded dim re-joins the result.  That is the
        (time, data, model) composition: one shard_map, zero
        driver-specific model code.
        """
        if self.mesh is None:
            def fine(x_heads):
                # truncated step programs pass the active suffix; recover
                # the static offset from the stack length
                f = B - x_heads.shape[0]
                with jax.named_scope("srds.fine"):
                    return jax.vmap(F)(x_heads, starts[f:] if f else starts)
            return fine

        axis = self.axis
        if axis is not None and B % self.mesh.shape[axis] != 0:
            raise ValueError(
                f"num_blocks={B} not divisible by axis size "
                f"{self.mesh.shape[axis]}")
        spec = P(axis, *tuple(self._heads_spec)[1:])

        def fine(x_heads):
            f = B - x_heads.shape[0]
            with jax.named_scope("srds.fine"):
                st = slice_spec(starts[f:] if f else starts, P(axis))
                y = jax.vmap(F)(slice_spec(x_heads, spec), st)
                return gather_spec(y, spec)
        return fine
