"""Unified Parareal engine — the single home of SRDS's refinement math.

Every SRDS sampler in this repo (sequential single-program
:func:`repro.core.parareal.srds_sample`, block-sharded
:func:`repro.core.pipelined.srds_sharded_local`, wavefront-pipelined
:func:`repro.core.pipelined.srds_pipelined_local`) consumes this module for:

  * the coarse initialization sweep (Alg 1, lines 1-4),
  * the predictor-corrector update ``y + G_cur - G_prev`` (line 11),
  * the sequential corrector sweep (lines 9-12),
  * convergence gating on the final-sample residual,
  * ``SRDSResult`` assembly.

The three samplers differ only in *where the fine solves run* (vmapped in
one program, locally per shard with an all_gather, or wavefront-staggered)
— that part is injected into :func:`run_parareal` as ``fine_fn`` — so the
algorithm itself can no longer drift between implementations.

Each device phase of a refinement runs under a ``jax.named_scope``, which
lands in the ``op_name`` metadata of every HLO op it emits (and costs
nothing at run time): ``srds.init`` (the coarse initialization sweep),
``srds.fine`` (the fine solves; the serving engine scopes its own),
``srds.coarse`` (each ``G`` of the sequential sweep) and ``srds.correct``
(the predictor-corrector update and its residual).  A profile splits a
refinement's device time by them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SRDSConfig:
    """Knobs for the SRDS sampler.

    num_blocks:   B — the coarse discretization (None -> ceil(sqrt(N)),
                  Prop 4's optimum).
    tol:          τ — convergence threshold on the mean-abs change of the
                  *final* sample between consecutive refinements.
    max_iters:    refinement-iteration cap (None -> B; Prop 1 guarantees
                  exact convergence by then).
    norm:         'l1_mean' (paper) or 'l2_mean' or 'linf'.
    use_fused_update: route the predictor-corrector update + residual
                  accumulation through the Pallas kernel.  ``None`` (the
                  default) resolves at run time to "on where supported":
                  compiled kernels on TPU/GPU, plain jnp elsewhere
                  (interpreted Pallas would dominate CPU runtime) — see
                  :func:`repro.kernels.ops.fused_default`.
    truncate:     converged-prefix truncation: refinement ``p`` runs its
                  fine solves and corrector sweep only on the active block
                  suffix ``[frontier, B)`` where ``frontier =
                  prefix_frontier(p)`` is the provably *bitwise-frozen*
                  prefix (classical Parareal exactness, lagged one
                  refinement — see :func:`prefix_frontier`), advancing by
                  one block per refinement.  The refinement loop unrolls
                  over ``p`` so
                  each iteration's suffix shape is static — strictly less
                  work per iteration, all on device.  Results are
                  bit-identical to the untruncated loop (same sample,
                  iterations, delta_history) for elementwise-deterministic
                  models; matmul denoisers match to dtype roundoff because
                  the shrinking fine-solve batch hits shape-dependent gemm
                  kernels (the same caveat as ``per_sample``).
                  Incompatible with ``block_sharding`` and straggler reuse
                  (both keep the while_loop path).  Shorthand for
                  ``window=repro.core.window.ExactPrefix()``.
    window:       a :class:`repro.core.window.FrontierPolicy` controlling
                  the active refinement window explicitly — the seam all
                  frontier rules live behind.  ``None`` resolves from
                  ``truncate``: ``ExactPrefix()`` (bit-exact, the above)
                  when True, ``FixedBudget()`` (no truncation) when
                  False.  ``ResidualWindow(window_tol=...)`` enables the
                  opt-in *approximate* residual-driven window: blocks
                  whose per-block residual passed ``window_tol`` freeze
                  even before exactness is provable (error knob and
                  guarantees in :mod:`repro.core.window`).
    per_sample:   gate convergence independently per sample over the leading
                  batch axis of ``x_init`` (shape ``(K, ...)``): the residual,
                  iteration counter and delta history become per-sample
                  ``(K,)``-shaped, converged samples freeze (their updates
                  are masked to no-ops) and the loop exits only when every
                  sample converged or ``max_iters`` hits.  Off (the default):
                  a single joint-norm residual gates the whole batch.
    accel:        a :class:`repro.core.accel.Accelerator` mixing the
                  refinement fixed point (Anderson/triangular
                  acceleration — fewer iterations to the same tolerance,
                  zero extra model evals per iteration).  ``None`` (the
                  default) resolves to ``NoAccel``: no mixing, no extra
                  loop carry, bit-identical to the pre-seam engine.
                  Accelerated modes are *approximate* in the window
                  sense: converged samples match the serial solve to
                  tolerance, with the error measured and CI-asserted
                  (see :mod:`repro.core.accel`).
    """

    num_blocks: Optional[int] = None
    tol: float = 1e-3
    max_iters: Optional[int] = None
    norm: str = "l1_mean"
    use_fused_update: Optional[bool] = None
    per_sample: bool = False
    truncate: bool = False
    # Frontier policy (repro.core.window.FrontierPolicy); None resolves
    # from `truncate`.  ResidualWindow(...) opts into the approximate
    # residual-driven sliding window.
    window: Optional[object] = None
    # Distribution hook: NamedSharding whose first axis is the parareal
    # block dim — constrains the trajectory/fine-solve tensors so GSPMD
    # maps blocks onto a mesh axis (time-parallelism on `data`).
    block_sharding: Optional[object] = None
    # Run exactly max_iters refinements under lax.scan instead of the
    # early-exit while_loop (analysis mode: cost_analysis counts while-loop
    # bodies once; also useful for fixed-budget sampling).
    fixed_iters: bool = False
    scan_unroll: bool = False
    # Fixed-point accelerator (repro.core.accel.Accelerator); None resolves
    # to NoAccel.  AndersonAccel(depth=m) / TriangularAccel() opt into
    # approximate iteration-count acceleration.
    accel: Optional[object] = None


class SRDSResult(NamedTuple):
    """Per-sample fields are scalar/(max_iters,)-shaped in joint-gating mode
    and gain a trailing batch axis of size K under per-sample gating."""
    sample: jnp.ndarray
    iterations: jnp.ndarray        # int32 () or (K,) — refinements actually run
    final_delta: jnp.ndarray       # f32 () or (K,) — last convergence residual
    delta_history: jnp.ndarray     # f32 (max_iters,) or (max_iters, K),
                                   # +inf beyond `iterations`
    trajectory: Optional[jnp.ndarray] = None  # (B+1, ...) final running traj
    window_history: Optional[jnp.ndarray] = None  # int32 (max_iters,[ K]) —
                                   # window lower bound each refinement ran
                                   # with (-1 beyond `iterations`); only
                                   # populated by residual-window policies


def _leading_axes_norm(diff: jnp.ndarray, kind: str,
                       lead: int) -> jnp.ndarray:
    """The one norm-kind dispatch: reduce every axis past the first
    ``lead``, preserving those (the ``batch_dims`` idiom the fused
    kernels use) — ``lead=0`` is a full reduction."""
    diff = diff.astype(jnp.float32)
    axes = tuple(range(lead, diff.ndim)) if lead else None
    if kind == "l1_mean":
        return jnp.mean(jnp.abs(diff), axis=axes)
    if kind == "l2_mean":
        return jnp.sqrt(jnp.mean(diff * diff, axis=axes))
    if kind == "linf":
        return jnp.max(jnp.abs(diff), axis=axes)
    raise ValueError(f"unknown norm {kind!r}")


def convergence_norm(diff: jnp.ndarray, kind: str,
                     batched: bool = False) -> jnp.ndarray:
    """Residual norm used for the paper's convergence criterion.

    With ``batched=True`` the reduction skips the leading batch axis and
    returns one residual per sample: ``(K, ...) -> (K,)``.
    """
    return _leading_axes_norm(diff, kind, 1 if batched else 0)


def blockwise_norm(diff: jnp.ndarray, kind: str,
                   batched: bool = False) -> jnp.ndarray:
    """Per-block residual norms over a block-stacked difference tensor:
    ``(B, ...) -> (B,)``, or ``(B, K, ...) -> (B, K)`` with ``batched``
    (one norm per block per sample).  Same norm kinds as
    :func:`convergence_norm` — one shared dispatch, so the convergence
    gate and the window-advance residuals can never disagree on a norm;
    residual-window policies consume these to advance the frontier past
    blocks whose residual passed tolerance.
    """
    return _leading_axes_norm(diff, kind, 2 if batched else 1)


def still_refining(delta: jnp.ndarray, tol) -> jnp.ndarray:
    """Convergence gate: keep iterating while the residual is >= τ.

    Elementwise — ``delta`` and ``tol`` may be scalars or per-sample ``(K,)``
    vectors (mixed-tolerance micro-batches pass a tol vector).
    """
    return delta >= tol


def has_converged(delta: jnp.ndarray, tol) -> jnp.ndarray:
    """The complementary gate (used by the wavefront's done-flag psum)."""
    return delta < tol


def resolve_blocks(n_steps: int, num_blocks: Optional[int]) -> Tuple[int, int]:
    """Pick (B, S): B blocks of S fine steps, B*S == N.

    Blocks are uniform — lockstep SPMD requires every block to run the same
    number of fine steps, so B must divide N exactly (the paper instead
    allows a ragged last block).  An explicit ``num_blocks`` that does not
    divide ``n_steps`` is an error.  With ``num_blocks=None``, B is
    ceil(sqrt(N)) snapped to the nearest *nontrivial* divisor of N (1 < B < N,
    preserving Prop 4's optimum for the perfect-square Ns of the paper's
    experiments); if none exists (prime N) this raises rather than silently
    degrading to the fully-serial B=1.
    """
    if num_blocks is not None:
        if not 1 <= num_blocks <= n_steps or n_steps % num_blocks != 0:
            raise ValueError(
                f"num_blocks={num_blocks} does not divide N={n_steps}: SRDS "
                f"blocks are uniform (B*S == N). Pick a divisor of N or pass "
                f"num_blocks=None to auto-select one.")
        return num_blocks, n_steps // num_blocks
    target = max(1, int(round(math.sqrt(n_steps))))
    divs = [d for d in range(2, n_steps) if n_steps % d == 0]
    if not divs:
        raise ValueError(
            f"N={n_steps} has no nontrivial divisor (prime): every block "
            f"split degenerates to the serial solve. Choose a composite "
            f"number of steps, or pass num_blocks={n_steps} or 1 explicitly "
            f"to accept a degenerate split.")
    num_blocks = min(divs, key=lambda d: abs(d - target))
    return num_blocks, n_steps // num_blocks


class IterationCost(NamedTuple):
    """Per-lane model-eval cost of one SRDS run, split by phase.

    ``init_evals`` is the sequential coarse sweep (B coarse steps);
    ``refine_evals`` is one *untruncated* Parareal refinement (B*S parallel
    fine steps + the B-step sequential corrector sweep).  All counts are in
    *model evals* — the paper's hardware-independent unit — already scaled
    by the solver's evals-per-step.  ``num_blocks``/``fine_steps``/
    ``evals_per_step`` carry the decomposition so truncated refinements
    (:meth:`refine_evals_at`) are derivable from the same record.
    """
    init_evals: int
    refine_evals: int
    num_blocks: int = 0
    fine_steps: int = 0
    evals_per_step: int = 1

    def refine_evals_window(self, lo: int, hi: Optional[int] = None) -> int:
        """Evals of one refinement restricted to the block window
        ``[lo, hi)`` (fine solves + corrector sweep on the live blocks
        only).  ``hi=None`` means ``B`` — the common suffix case; the
        final in-window block never retires, so the window floors at one
        live block.  This is the unit every windowed consumer prices
        with: billing, ``predict_completion``, the CostAware scheduler
        and the benches all derive from it."""
        if not self.num_blocks:            # legacy record: no decomposition
            return self.refine_evals
        hi = self.num_blocks if hi is None else min(int(hi), self.num_blocks)
        live = hi - min(int(lo), hi - 1)
        return live * (self.fine_steps + 1) * self.evals_per_step

    def refine_evals_at(self, frontier: int) -> int:
        """Suffix shorthand: ``refine_evals_window(frontier, B)``."""
        return self.refine_evals_window(frontier)


def iteration_cost(num_steps: int, num_blocks: Optional[int] = None,
                   evals_per_step: int = 1) -> IterationCost:
    """The engine's eval accounting, exported for cost-model consumers.

    Both the serving layer's per-request ``model_evals`` charge and the
    scheduler's completion-time predictor derive from this one function, so
    admission decisions and billing can never disagree with what the
    refinement loop actually executes.
    """
    B, S = resolve_blocks(num_steps, num_blocks)
    return IterationCost(init_evals=B * evals_per_step,
                         refine_evals=(B * S + B) * evals_per_step,
                         num_blocks=B, fine_steps=S,
                         evals_per_step=evals_per_step)


def predicted_evals(cost: IterationCost, iterations: Union[int, float]):
    """Total per-lane evals for an *untruncated* run of ``iterations``
    refinements (the pre-truncation hot loop; kept for baselines and
    ``truncate=False`` engines).  Linear, so float iteration estimates
    (the EMA's) extend continuously."""
    return cost.init_evals + iterations * cost.refine_evals


def prefix_frontier(completed: int) -> int:
    """The provably *bitwise-frozen* prefix after ``completed`` refinements.

    Classical Parareal exactness makes block ``i`` mathematically exact
    after ``i`` refinements, but bitwise stability — what truncation must
    preserve — arrives one refinement later: a block's first value mixes a
    coarse term from the *init* sweep with one from the *corrector* sweep
    (two separately compiled scans whose last bits may differ), so only
    from its second recomputation onward are both coarse terms the same
    compiled computation on identical inputs, making the update a bitwise
    fixed point.  Hence the frontier advances by exactly one block per
    refinement, one refinement behind the exactness bound.
    """
    return max(int(completed) - 1, 0)


def truncated_evals(cost: IterationCost, iterations: Union[int, float]):
    """Total per-lane evals for a prefix-truncated run: refinement ``p``
    (0-indexed) costs ``cost.refine_evals_at(prefix_frontier(p))`` because
    its fine solves and corrector sweep cover only the non-frozen suffix —
    the same frontier schedule :func:`run_parareal` executes, so billing
    and benchmarks can never disagree with the loop.  A float
    ``iterations`` (e.g. an EMA estimate) is extended continuously: the
    fractional part is charged at the next refinement's truncated rate.
    """
    k = int(iterations)
    total = cost.init_evals + sum(cost.refine_evals_at(prefix_frontier(p))
                                  for p in range(k))
    frac = float(iterations) - k
    if frac > 0.0:
        return total + frac * cost.refine_evals_at(prefix_frontier(k))
    return total


def windowed_evals(cost: IterationCost, lo_schedule):
    """Total per-lane evals for a run whose refinement ``p`` executed the
    window ``[lo_schedule[p], B)`` — the *realized* schedule of a
    residual-window run (e.g. ``SRDSResult.window_history``), as opposed
    to :func:`truncated_evals`'s provable ExactPrefix schedule.  Entries
    ``< 0`` mark refinements that never ran (the history's fill value)
    and are skipped.  A per-sample ``(max_iters, K)`` history (the
    ``per_sample`` engines') returns a ``(K,)`` array of per-sample
    totals."""
    los = np.asarray(lo_schedule)
    if los.ndim == 2:
        return np.asarray([windowed_evals(cost, los[:, s])
                           for s in range(los.shape[1])])
    total = cost.init_evals
    for lo in los:
        lo = int(lo)
        if lo >= 0:
            total += cost.refine_evals_window(lo)
    return total


def resolve_fused(flag: Optional[bool]) -> bool:
    """Resolve a ``use_fused_*`` tri-state: an explicit bool wins; ``None``
    means "on where supported" (compiled Pallas on TPU and GPU — interpreted
    Pallas elsewhere would dominate runtime, so e.g. CPU stays on the jnp
    path)."""
    if flag is None:
        from repro.kernels import ops as kops
        return kops.fused_default()
    return bool(flag)


def parareal_update(y, g_cur, g_prev, use_fused: Optional[bool] = False):
    """Predictor-corrector update (Alg 1, line 11): ``y + G_cur - G_prev``."""
    if resolve_fused(use_fused):
        from repro.kernels import ops as kops
        out, _ = kops.parareal_update(y, g_cur, g_prev)
        return out
    return y + g_cur - g_prev


def coarse_init_sweep(G, x_init: jnp.ndarray, starts: jnp.ndarray,
                      unroll: bool = False) -> jnp.ndarray:
    """Sequential coarse sweep producing the initial trajectory tail x^0.

    Returns the (B, ...) stack ``[x_1^0, ..., x_B^0]`` where
    ``x_{i+1}^0 = G(x_i^0)`` — which doubles as ``prev_coarse`` at init.
    """
    def body(x, i0):
        g = G(x, i0)
        return g, g

    with jax.named_scope("srds.init"):
        _, x_tail = jax.lax.scan(body, x_init, starts, unroll=unroll)
    return x_tail


def corrector_sweep(G, x_init: jnp.ndarray, y: jnp.ndarray,
                    prev_coarse: jnp.ndarray, starts: jnp.ndarray, *,
                    use_fused: bool = False, unroll: bool = False,
                    residual_from: Optional[jnp.ndarray] = None,
                    batched: bool = False,
                    frozen: Optional[jnp.ndarray] = None):
    """Sequential coarse sweep + predictor-corrector (Alg 1, lines 9-12).

    Returns ``(new_tail, cur_all)``: the refined trajectory tail and the
    coarse results ``G(x_i^p)`` that become next iteration's prev_coarse.

    ``residual_from`` (the previous trajectory tail, same shape as ``y``)
    switches on the in-sweep residual feed: each block's raw L1 sum
    ``sum|x_new - x_old|`` is accumulated in the same pass as the update
    (the Pallas kernel's per-tile partials when ``use_fused``, a plain
    per-block reduction otherwise) — no second full-tensor pass — and the
    sweep returns a third output, the per-block raw L1 sums ``(B,)`` (or
    ``(B, K)`` per sample with ``batched``).  Callers divide by the
    per-sample element count to obtain ``l1_mean`` residuals; the final
    entry is the convergence residual's raw sum.

    ``frozen`` (per-block bool, ``(B,)`` or ``(B, K)`` per sample with
    ``batched``; requires ``residual_from`` for the old values) is the
    residual-window mask: a frozen block's update is discarded — its
    trajectory value stays ``residual_from[i]``, its coarse result stays
    ``prev_coarse[i]``, its residual reports 0 — and, because the scan
    carry takes the frozen (old) value, downstream blocks see exactly the
    boundary a sweep that *started* past the frozen run would have seen.
    This is the masked equivalent of the serving engine's physical window
    skip, so both drivers realize the same math.
    """
    if frozen is not None and residual_from is None:
        raise ValueError("frozen blocks need residual_from (the previous "
                         "trajectory tail) to hold their old values")
    if residual_from is not None:
        if use_fused:
            from repro.kernels import ops as kops

        def sweep_r(x_cur, inp):
            y_i, prev_i, old_i, i0 = inp[:4]
            with jax.named_scope("srds.coarse"):
                cur = G(x_cur, i0)
            with jax.named_scope("srds.correct"):
                if use_fused:
                    x_next, r = kops.parareal_update_residual(
                        y_i, cur, prev_i, old_i, batched=batched)
                else:
                    x_next = y_i + cur - prev_i
                    d = (x_next - old_i).astype(jnp.float32)
                    r = jnp.sum(jnp.abs(d), axis=tuple(range(1, d.ndim))
                                if batched else None)
                if frozen is not None:
                    fz_i = inp[4]
                    m = fz_i.reshape(fz_i.shape
                                     + (1,) * (x_next.ndim - fz_i.ndim))
                    x_next = jnp.where(m, old_i, x_next)
                    cur = jnp.where(m, prev_i, cur)
                    r = jnp.where(fz_i, jnp.zeros_like(r), r)
            return x_next, (x_next, cur, r)

        xs = (y, prev_coarse, residual_from, starts)
        if frozen is not None:
            xs = xs + (frozen,)
        _, (new_tail, cur_all, r_all) = jax.lax.scan(sweep_r, x_init, xs,
                                                     unroll=unroll)
        return new_tail, cur_all, r_all

    def sweep(x_cur, inp):
        y_i, prev_i, i0 = inp
        with jax.named_scope("srds.coarse"):
            cur = G(x_cur, i0)
        with jax.named_scope("srds.correct"):
            x_next = parareal_update(y_i, cur, prev_i, use_fused)
        return x_next, (x_next, cur)

    _, (new_tail, cur_all) = jax.lax.scan(sweep, x_init,
                                          (y, prev_coarse, starts),
                                          unroll=unroll)
    return new_tail, cur_all


def suffix_refinement(G, y, x_init: jnp.ndarray, x_tail: jnp.ndarray,
                      prev_coarse: jnp.ndarray, starts: jnp.ndarray,
                      frontier: int, *, use_fused: bool = False,
                      norm: str = "l1_mean", batched: bool = False,
                      unroll: bool = False, window_lo=None,
                      block_resids: bool = False):
    """One predictor-corrector refinement truncated to ``[frontier, B)``.

    The single implementation of the sliding-window refinement body,
    shared by :func:`run_parareal`'s unrolled loop and the serving
    engine's per-frontier step programs — the frontier plumbing (suffix
    sweep resuming from the last frozen boundary, prefix re-concatenation,
    fused-vs-plain residual dispatch, residual-window freezing) can never
    drift between the two.

    ``y`` holds the fine-solve results for the suffix heads (the
    sampler-specific part stays with the caller).  Returns ``(new_tail,
    cur_all, resid)`` where ``resid`` is the final-block convergence
    residual in ``norm`` (scalar, or per-sample ``(K,)`` with
    ``batched``), computed *before* any caller-side freezing — callers
    that mask converged lanes discard those entries, and active lanes'
    values are unaffected by the mask.  With the fused path and
    ``l1_mean`` the residual comes from the update kernel's per-tile L1
    partials (no second full-tensor pass).

    ``window_lo`` (traced int, scalar or per-sample ``(K,)`` with
    ``batched``) enables the residual-window path: suffix blocks with
    absolute index ``< window_lo`` are *frozen* — their update is masked
    to a no-op inside the sweep (see :func:`corrector_sweep`), exactly
    mirroring the serving engine's physical window skip.  Implies
    ``block_resids``.  With ``block_resids`` (or ``window_lo``) the
    return grows a fourth element: the per-block residual norms of the
    suffix, ``(B - frontier,)`` or ``(B - frontier, K)``, frozen blocks
    reporting 0 — the feed for ``FrontierPolicy.advance``.
    """
    f = int(frontier)
    windowed = window_lo is not None
    block_resids = block_resids or windowed
    fused_resid = use_fused and norm == "l1_mean"
    # the sweep resumes from the last frozen boundary: the prefix's
    # recomputation is a bitwise fixed point, so skipping it changes
    # nothing downstream
    x_carry = x_init if f == 0 else x_tail[f - 1]
    old_sfx = x_tail[f:] if f else x_tail
    prev_sfx = prev_coarse[f:] if f else prev_coarse
    st = starts[f:] if f else starts
    n_per = x_init[0].size if batched else x_init.size
    n_sfx = old_sfx.shape[0]
    block_resid = None
    if windowed:
        # frozen mask per suffix block (trailing sample axis rides along
        # when window_lo is per-sample): absolute block index < lo
        idx = f + jnp.arange(n_sfx, dtype=jnp.int32)
        lo = jnp.asarray(window_lo, jnp.int32)
        fz = idx.reshape((n_sfx,) + (1,) * lo.ndim) < lo
        if norm == "l1_mean":
            # in-sweep residual feed (fused kernel partials or the plain
            # per-block reduction) — no second full-tensor pass
            new_sfx, cur_sfx, r_all = corrector_sweep(
                G, x_carry, y, prev_sfx, st, use_fused=use_fused,
                unroll=unroll, residual_from=old_sfx, batched=batched,
                frozen=fz)
            block_resid = (r_all / float(n_per)).astype(jnp.float32)
        else:
            new_sfx, cur_sfx, _ = corrector_sweep(
                G, x_carry, y, prev_sfx, st, use_fused=use_fused,
                unroll=unroll, residual_from=old_sfx, batched=batched,
                frozen=fz)
            # frozen blocks hold their old value -> their norm is 0
            with jax.named_scope("srds.correct"):
                block_resid = blockwise_norm(new_sfx - old_sfx, norm,
                                             batched=batched)
        resid = block_resid[-1]
    elif fused_resid or block_resids:
        new_sfx, cur_sfx, r_all = corrector_sweep(
            G, x_carry, y, prev_sfx, st, use_fused=use_fused, unroll=unroll,
            residual_from=old_sfx, batched=batched)
        if norm == "l1_mean":
            if block_resids:
                block_resid = (r_all / float(n_per)).astype(jnp.float32)
                resid = block_resid[-1]
            else:
                resid = (r_all[-1] / float(n_per)).astype(jnp.float32)
        else:
            with jax.named_scope("srds.correct"):
                block_resid = blockwise_norm(new_sfx - old_sfx, norm,
                                             batched=batched)
            resid = block_resid[-1]
    else:
        new_sfx, cur_sfx = corrector_sweep(G, x_carry, y, prev_sfx, st,
                                           use_fused=use_fused,
                                           unroll=unroll)
        resid = None
    if f:
        new_tail = jnp.concatenate([x_tail[:f], new_sfx], axis=0)
        cur_all = jnp.concatenate([prev_coarse[:f], cur_sfx], axis=0)
    else:
        new_tail, cur_all = new_sfx, cur_sfx
    if resid is None:
        with jax.named_scope("srds.correct"):
            resid = convergence_norm(new_tail[-1] - x_tail[-1], norm,
                                     batched=batched)
    if block_resids:
        return new_tail, cur_all, resid, block_resid
    return new_tail, cur_all, resid


class RefineState(NamedTuple):
    """Carry of the refinement loop (shared by all non-wavefront samplers).

    Under per-sample gating (``batched=True``), ``delta``/``iters``/``active``
    are ``(K,)`` vectors over the leading batch axis and ``history`` is
    ``(max_iters, K)``; otherwise they are the scalar joint-gating carries.
    """
    p: jnp.ndarray             # refinement counter (scalar int32, lockstep)
    x_tail: jnp.ndarray        # (B, ...) running trajectory x_1..x_B
    prev_coarse: jnp.ndarray   # (B, ...) G(x_i^{p-1}) for each block
    y_prev: jnp.ndarray        # (B, ...) last fine results when
                               # carry_fine_results (straggler reuse),
                               # else a scalar placeholder
    delta: jnp.ndarray         # last convergence residual, f32 () or (K,)
    history: jnp.ndarray       # residual history, f32 (max_iters,[ K])
    iters: jnp.ndarray         # refinements applied, int32 () or (K,)
    active: jnp.ndarray        # frozen-when-converged mask, bool () or (K,)
    # --- residual-window carries (None unless the frontier policy needs
    # block residuals — see repro.core.window; None is an empty pytree, so
    # exact-policy loop carries stay byte-identical to the pre-window ones)
    block_resid: Optional[jnp.ndarray] = None
                               # per-block residual norms, f32 (B,[ K])
    window_lo: Optional[jnp.ndarray] = None
                               # window lower bound, int32 () or (K,)
    lo_hist: Optional[jnp.ndarray] = None
                               # window lower bound used by refinement p,
                               # int32 (max_iters,[ K]), -1 beyond iters
    # --- fixed-point-acceleration carry (None unless the accelerator
    # mixes — see repro.core.accel; None is an empty pytree, so
    # unaccelerated loop carries stay byte-identical to the pre-seam ones)
    accel: Optional[object] = None
                               # repro.core.accel.AccelState ring buffers


FineFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


def vmap_fine_fn(F, starts: jnp.ndarray, constrain=None) -> FineFn:
    """The single-program :data:`FineFn`: fine solves batched over the
    block dim with ``vmap``, suffix-aware under truncation.

    ``F(x, i0)`` is one block's fine solve (typically ``solve(...)`` over a
    :class:`repro.core.denoiser.Denoiser`); ``starts`` the ``(B,)`` block
    start indices.  Under truncation the heads are the active suffix — the
    static offset is recovered from the stack length.  ``constrain``
    (optional) re-applies a block-dim sharding constraint around the vmap.
    Shared by ``srds_sample`` and the serve engine's meshless fine path.
    """
    B = starts.shape[0]
    cb = constrain if constrain is not None else (lambda t: t)

    def fine_fn(x_heads, p, y_prev):
        f = B - x_heads.shape[0]
        st = starts[f:] if f else starts
        return cb(jax.vmap(lambda xi, i0: F(xi, i0))(cb(x_heads), st))

    return fine_fn


def _batch_mask(mask: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Broadcast a (K,) sample mask against a (B, K, ...) trajectory tensor."""
    return mask.reshape((1,) + mask.shape + (1,) * (t.ndim - 2))


def run_parareal(G, fine_fn: FineFn, x_init: jnp.ndarray,
                 starts: jnp.ndarray, *, tol, max_iters: int,
                 norm: str = "l1_mean",
                 use_fused_update: Optional[bool] = None,
                 fixed_iters: bool = False, scan_unroll: bool = False,
                 constrain=None, carry_fine_results: bool = False,
                 batched: bool = False, truncate: bool = False,
                 window=None, accel=None) -> RefineState:
    """The complete Parareal refinement loop (Alg 1 minus the fine solves).

    ``fine_fn(x_heads, p, y_prev) -> y`` computes the fine-solve results
    for block heads ``x_heads`` at refinement ``p`` — this is the only
    sampler-specific part (vmap in one program; local vmap + all_gather +
    straggler masking under shard_map).  Untruncated, ``x_heads`` is the
    full ``(B, ...)`` stack ``[x_0, ..., x_{B-1}]``; under ``truncate`` it
    is the active suffix ``[x_frontier, ..., x_{B-1}]`` — samplers recover
    the static offset as ``B - x_heads.shape[0]``.
    ``tol`` may be a python float, a traced scalar, or — with ``batched`` —
    a per-sample ``(K,)`` vector (mixed-tolerance micro-batches).
    ``constrain`` (optional) re-applies a block-dim sharding constraint to
    the trajectory tensors each iteration (GSPMD time-parallel path).
    ``carry_fine_results`` keeps the previous iteration's (B, ...) fine
    results in the loop carry, handed to ``fine_fn`` as ``y_prev`` (needed
    for straggler reuse); off by default so samplers that never read it
    don't pay an extra trajectory-sized buffer of loop state.
    ``batched`` treats the leading axis of ``x_init`` as a batch of K
    independent samples and gates convergence per sample: each sample's
    residual/iteration-count/history evolves on its own, converged samples
    freeze (their updates become no-ops via ``jnp.where``, so the result is
    bit-identical to K independent runs), and the loop exits when every
    sample converged or at ``max_iters``.  Under ``fixed_iters`` no freezing
    happens (all samples run the full budget, matching K independent
    fixed-budget runs) but the carries stay per-sample.

    ``truncate`` switches the loop to converged-prefix truncation (see
    :class:`SRDSConfig`): the loop unrolls over ``p`` so refinement ``p``
    statically restricts its fine solves and corrector sweep to the suffix
    ``[prefix_frontier(p), B)`` — the frozen prefix's recomputation is a
    bitwise fixed point (see :func:`prefix_frontier`), so skipping it is a
    no-op.
    Early exit is preserved via ``lax.cond`` per unrolled step (the skipped
    branch is genuinely not executed), so ``iterations``/``delta_history``
    match the while_loop bit for bit.  Incompatible with ``constrain`` and
    ``carry_fine_results``.

    ``window`` is the generalization: a
    :class:`repro.core.window.FrontierPolicy` controlling the active
    refinement window (``truncate`` is shorthand for ``ExactPrefix``; see
    :func:`repro.core.window.resolve_policy`).  A residual-driven policy
    (``ResidualWindow``) keeps the unrolled static suffix of the provable
    frontier and *additionally* freezes blocks the policy advanced past,
    by masking inside the sweep — the carried per-block residuals, window
    bound and per-refinement window history live in the returned state's
    ``block_resid`` / ``window_lo`` / ``lo_hist`` fields (None for
    non-residual policies).

    ``accel`` is a :class:`repro.core.accel.Accelerator` mixing the
    refinement fixed point: after each refinement's corrector sweep (and
    convergence-gate masking) the joint iterate ``(x_tail, prev_coarse)``
    is extrapolated over the accelerator's ring-buffer history — fewer
    iterations to tolerance, zero extra model evals.  The convergence
    residual is recomputed from the *mixed* state (the gate must see what
    is committed) and the live-window mask keeps frozen blocks bitwise
    untouched.  ``None`` resolves to ``NoAccel`` (no mixing, no extra
    carry — bit-identical).  Incompatible with ``carry_fine_results``
    (stale fine results are not iterates of the mixed sequence) and —
    unless the accelerator is ``prefix_exact`` (``TriangularAccel``) —
    with truncating frontier policies, whose provable-prefix schedule is
    a theorem about the plain iteration only.
    """
    from .accel import resolve_accel
    from .window import resolve_policy
    policy = resolve_policy(window, truncate)
    acc = resolve_accel(accel)
    accel_on = acc.accelerates
    if accel_on and carry_fine_results:
        raise ValueError("an accelerating Accelerator is incompatible with "
                         "straggler reuse (carry_fine_results): stale fine "
                         "results are not iterates of the mixed sequence.")
    if accel_on and policy.truncates and not acc.prefix_exact:
        # truncating policies freeze blocks on the provable serial-prefix
        # schedule ("block i is exact after i+1 refinements") — a theorem
        # about the PLAIN iteration that joint mixing invalidates, so the
        # frozen prefix would pin not-yet-converged mixed values and the
        # committed trajectory diverges.  TriangularAccel restores the
        # invariant by construction.
        raise ValueError(
            f"{type(acc).__name__} does not preserve the serial-prefix "
            f"invariant that truncating frontier policies "
            f"({type(policy).__name__}) rely on; use TriangularAccel "
            f"(prefix-exact mixing), or disable truncation "
            f"(truncate=False / window=FixedBudget()).")
    truncate = policy.truncates
    windowed = policy.needs_block_residuals
    if truncate and constrain is not None:
        raise ValueError("truncate is incompatible with a block-sharding "
                         "constraint (the GSPMD path keeps full-width "
                         "trajectory tensors); drop one of the two.")
    if truncate and carry_fine_results:
        raise ValueError("truncate is incompatible with straggler reuse "
                         "(carry_fine_results): stale fine results are "
                         "indexed on the full block axis.")
    cb = constrain if constrain is not None else (lambda t: t)
    use_fused = resolve_fused(use_fused_update)
    B = starts.shape[0]
    # Early-exit per-sample mode freezes converged samples; fixed-iters mode
    # never gates updates (scan runs the full budget for every sample).
    gate = batched and not fixed_iters

    x_tail = coarse_init_sweep(G, x_init, starts, unroll=scan_unroll)
    # prev_coarse_i == G(x_i^0) == x_{i+1}^0 at init; y_prev's init value is
    # never read (straggler substitution is gated on p > 0).
    y_prev0 = x_tail if carry_fine_results else jnp.zeros((), x_tail.dtype)
    if batched:
        k = x_init.shape[0]
        delta0 = jnp.full((k,), jnp.inf, jnp.float32)
        hist0 = jnp.full((max_iters, k), jnp.inf, jnp.float32)
        iters0 = jnp.zeros((k,), jnp.int32)
        active0 = jnp.ones((k,), bool)
    else:
        delta0 = jnp.float32(jnp.inf)
        hist0 = jnp.full((max_iters,), jnp.inf, jnp.float32)
        iters0 = jnp.int32(0)
        active0 = jnp.asarray(True)
    if windowed:
        kd = (x_init.shape[0],) if batched else ()
        br0 = jnp.full((B,) + kd, jnp.inf, jnp.float32)
        lo0 = jnp.zeros(kd, jnp.int32)
        loh0 = jnp.full((max_iters,) + kd, -1, jnp.int32)
    else:
        br0 = lo0 = loh0 = None
    astate0 = acc.init_state(jnp.stack([x_tail, x_tail]), max_iters,
                             batched=batched) if accel_on else None
    init = RefineState(jnp.int32(0), x_tail, x_tail, y_prev0,
                       delta0, hist0, iters0, active0, br0, lo0, loh0,
                       astate0)

    def cond(c: RefineState):
        return jnp.logical_and(c.p < max_iters, jnp.any(c.active))

    def body(c: RefineState, f: int = 0) -> RefineState:
        """One refinement; ``f`` is the static frontier (0 = untruncated)."""
        heads = jnp.concatenate([x_init[None], c.x_tail[:-1]], axis=0)
        if f:
            heads = heads[f:]
        # ---- fine solves (Alg 1, lines 7-8) — sampler-specific ----
        with jax.named_scope("srds.fine"):
            y = fine_fn(heads, c.p, c.y_prev)
        # ---- sequential coarse sweep + predictor-corrector (lines 9-12),
        # truncated to the suffix — the one shared implementation ----
        new_tail, cur_all, resid = suffix_refinement(
            G, y, x_init, c.x_tail, c.prev_coarse, starts, f,
            use_fused=use_fused, norm=norm, batched=batched,
            unroll=scan_unroll)
        new_tail = cb(new_tail)
        cur_all = cb(cur_all)
        if gate:
            # converged samples' fine solves are no-ops: freeze their
            # trajectory and coarse state so they stay bit-identical to an
            # independent run that exited at their convergence iteration
            # (their pre-mask resid entries are discarded just below)
            m = _batch_mask(c.active, new_tail)
            new_tail = jnp.where(m, new_tail, c.x_tail)
            cur_all = jnp.where(m, cur_all, c.prev_coarse)
        if accel_on:
            # mix the joint fixed-point iterate AFTER gate masking (frozen
            # lanes are fixed points of the mix) with the live-window mask
            # (the truncated prefix must stay bitwise untouched); the
            # convergence residual is recomputed from the committed state
            live = jnp.arange(B, dtype=jnp.int32) >= f if f else None
            z_mix, astate = acc.apply(
                c.accel, jnp.stack([c.x_tail, c.prev_coarse]),
                jnp.stack([new_tail, cur_all]), live=live, batched=batched)
            new_tail, cur_all = cb(z_mix[0]), cb(z_mix[1])
            if gate:
                new_tail = jnp.where(m, new_tail, c.x_tail)
                cur_all = jnp.where(m, cur_all, c.prev_coarse)
            resid = convergence_norm(new_tail[-1] - c.x_tail[-1], norm,
                                     batched=batched)
        else:
            astate = c.accel

        if gate:
            delta = jnp.where(c.active, resid, c.delta)
            history = c.history.at[c.p].set(
                jnp.where(c.active, resid, c.history[c.p]))
            iters = c.iters + c.active.astype(jnp.int32)
        else:
            delta = resid
            history = c.history.at[c.p].set(resid)
            iters = c.iters + 1
        active = jnp.logical_and(c.active, still_refining(delta, tol))
        if carry_fine_results:
            y_keep = jnp.where(_batch_mask(c.active, y), y, c.y_prev) \
                if gate else y
        else:
            y_keep = c.y_prev
        return RefineState(c.p + 1, new_tail, cur_all, y_keep, delta, history,
                           iters, active, c.block_resid, c.window_lo,
                           c.lo_hist, astate)

    def body_windowed(c: RefineState, f: int) -> RefineState:
        """One refinement under a residual-driven window policy: the
        compiled suffix is the static provable frontier ``f`` (same
        shapes as the exact policy), and blocks ``[f, lo)`` the policy
        advanced past are additionally frozen by masking inside the
        sweep — the approximate part, bounded by the policy's
        ``window_tol`` knob."""
        lo_eff = jnp.maximum(c.window_lo, jnp.int32(f))
        heads = jnp.concatenate([x_init[None], c.x_tail[:-1]], axis=0)
        if f:
            heads = heads[f:]
        y = fine_fn(heads, c.p, c.y_prev)
        new_tail, cur_all, resid, br_sfx = suffix_refinement(
            G, y, x_init, c.x_tail, c.prev_coarse, starts, f,
            use_fused=use_fused, norm=norm, batched=batched,
            unroll=scan_unroll, window_lo=lo_eff)
        if gate:
            m = _batch_mask(c.active, new_tail)
            new_tail = jnp.where(m, new_tail, c.x_tail)
            cur_all = jnp.where(m, cur_all, c.prev_coarse)
        if accel_on:
            # mix with the dynamic window's live mask (blocks below lo_eff
            # stay bitwise frozen through mixing), then recompute the
            # full-width per-block residuals and the convergence residual
            # from the committed (mixed) state — frozen blocks are bitwise
            # unchanged, so their recomputed residual is exactly 0
            idx = jnp.arange(B, dtype=jnp.int32)
            live = idx.reshape((B,) + (1,) * lo_eff.ndim) >= lo_eff
            z_mix, astate = acc.apply(
                c.accel, jnp.stack([c.x_tail, c.prev_coarse]),
                jnp.stack([new_tail, cur_all]), live=live, batched=batched)
            new_tail, cur_all = z_mix[0], z_mix[1]
            if gate:
                new_tail = jnp.where(m, new_tail, c.x_tail)
                cur_all = jnp.where(m, cur_all, c.prev_coarse)
            br = blockwise_norm(new_tail - c.x_tail, norm, batched=batched)
            resid = br[-1]
        else:
            astate = c.accel
            # full-width per-block residuals: the statically-skipped prefix
            # is bitwise frozen, i.e. residual 0
            if f:
                br = jnp.concatenate(
                    [jnp.zeros((f,) + br_sfx.shape[1:], br_sfx.dtype),
                     br_sfx], axis=0)
            else:
                br = br_sfx
        if gate:
            delta = jnp.where(c.active, resid, c.delta)
            history = c.history.at[c.p].set(
                jnp.where(c.active, resid, c.history[c.p]))
            iters = c.iters + c.active.astype(jnp.int32)
        else:
            delta = resid
            history = c.history.at[c.p].set(resid)
            iters = c.iters + 1
        active = jnp.logical_and(c.active, still_refining(delta, tol))
        new_lo = policy.advance(lo_eff, br, B)
        if gate:
            # converged samples' window state freezes with them
            br = jnp.where(c.active[None], br, c.block_resid)
            new_lo = jnp.where(c.active, new_lo, c.window_lo)
            lo_hist = c.lo_hist.at[c.p].set(
                jnp.where(c.active, lo_eff, c.lo_hist[c.p]))
        else:
            lo_hist = c.lo_hist.at[c.p].set(lo_eff)
        return RefineState(c.p + 1, new_tail, cur_all, c.y_prev, delta,
                           history, iters, active, br, new_lo, lo_hist,
                           astate)

    if truncate:
        # Unrolled: refinement p's suffix shape is static, so the fine
        # solves and corrector sweep genuinely shrink each iteration; the
        # cond's skipped branch is never executed, preserving the early
        # exit physically as well as in the reported iteration counts.
        state = init
        loop_body = body_windowed if windowed else body
        for p in range(max_iters):
            # the policy's static frontier (for ExactPrefix: the
            # bitwise-frozen prefix, lagging exactness by one refinement —
            # see prefix_frontier; the final block never retires)
            f = policy.static_frontier(p, B)
            step = lambda c, _f=f: loop_body(c, _f)
            if fixed_iters:
                state = step(state)
            else:
                state = jax.lax.cond(jnp.any(state.active), step,
                                     lambda c: c, state)
        return state
    if fixed_iters:
        out, _ = jax.lax.scan(lambda c, _: (body(c), None), init, None,
                              length=max_iters, unroll=scan_unroll)
        return out
    return jax.lax.while_loop(cond, body, init)


def assemble_result(sample: jnp.ndarray, iterations: jnp.ndarray,
                    final_delta: jnp.ndarray, delta_history: jnp.ndarray,
                    trajectory: Optional[jnp.ndarray] = None,
                    window_history: Optional[jnp.ndarray] = None
                    ) -> SRDSResult:
    """The one place an ``SRDSResult`` is put together from loop outputs."""
    return SRDSResult(sample=sample, iterations=iterations,
                      final_delta=final_delta, delta_history=delta_history,
                      trajectory=trajectory, window_history=window_history)


def result_from_state(state: RefineState,
                      trajectory: Optional[jnp.ndarray] = None) -> SRDSResult:
    return assemble_result(state.x_tail[-1], state.iters, state.delta,
                           state.history, trajectory,
                           window_history=state.lo_hist)
