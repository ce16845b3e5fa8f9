"""Distributed SRDS: shard_map block-parallel and wavefront-pipelined samplers.

Two TPU-native implementations of the paper's parallelism:

``srds_sharded_local``
    Algorithmically identical to :func:`repro.core.parareal.srds_sample` —
    both drive the *same* refinement loop in :mod:`repro.core.engine` — but
    the parareal blocks live on a mesh axis: each device(-group) runs the
    fine solves for its own blocks; boundary values are exchanged with one
    ``all_gather`` per refinement and the (cheap) coarse sweep is computed
    redundantly on every device.  Supports >1 block per device and the
    SRDS-native straggler-mitigation mask (stale fine results are accepted
    for straggling blocks; correctness is preserved because convergence is
    still gated on the final-sample residual and exactness re-enters as soon
    as the block computes again).

``srds_pipelined_local``
    The paper's wavefront pipeline (Fig. 4) at *model-eval granularity*:
    one block per device; at superstep ``s`` device ``i`` performs fine
    sub-step ``j=(s-i) mod S`` of refinement ``p=(s-i)//S + 1``; the coarse
    eval is **batched into the same model call** as the fine eval (paper
    §3.4: "the coarse solver is simply a DDIM-step with a larger time-step,
    so it can be batched with fine solves").  Boundary values ride a ring
    ``lax.ppermute`` — this replaces the paper's torch.multiprocessing
    coordinator (their footnote 4) with the ICI-native pattern.  Effective
    serial evals ≈ k·S + B - 1, reproducing the paper's Table 3 pipelining
    gain (e.g. N=25: 9 supersteps for k=1).

Both functions are written against a *local* (per-shard) view and must be
called inside ``shard_map``; ``make_*_sampler`` wrappers build the jitted
SPMD program for a given mesh via :func:`repro.compat.shard_map` (the
one module that names drifting JAX APIs, so no call site here names a
``jax.*`` spelling directly).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat

from .accel import resolve_accel
from .denoiser import as_denoiser
from .engine import (SRDSConfig, assemble_result, convergence_norm,
                     has_converged, parareal_update, resolve_blocks,
                     run_parareal)
from .schedules import DiffusionSchedule
from .solvers import ModelFn, SolverConfig, solve, solver_step
from .window import ExactPrefix, resolve_policy


# --------------------------------------------------------------------------
# Block-parallel (non-wavefront) distributed SRDS
# --------------------------------------------------------------------------

def srds_sharded_local(model_fn: ModelFn, sched: DiffusionSchedule,
                       solver: SolverConfig, x_init: jnp.ndarray,
                       axis: str, cfg: SRDSConfig,
                       straggler_fn: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
                       tol=None):
    """Per-shard body. x_init is replicated; returns replicated outputs.

    ``straggler_fn(p) -> (B,) bool`` marks blocks whose fine solve is treated
    as dropped at refinement ``p`` (stale result substituted).
    ``tol`` overrides ``cfg.tol`` and may be a traced scalar or — with
    ``cfg.per_sample`` — a per-sample ``(K,)`` vector over the leading batch
    axis of ``x_init`` (mixed-tolerance micro-batches).

    ``model_fn`` may be a :class:`repro.core.denoiser.Denoiser`: the specs
    of the enclosing shard_map replicate over the denoiser's mesh axes, so
    its ``inner_eval`` glue (slice per ``in_spec`` -> shard body ->
    all_gather per ``out_spec``) runs the backbone model-parallel on the
    same mesh — the block ``axis`` and the model axes compose without any
    driver-specific code.
    """
    n = sched.num_steps
    d = compat.axis_size(axis)
    me = jax.lax.axis_index(axis)
    eval_fn = as_denoiser(model_fn).inner_eval()
    b_total, s_steps = resolve_blocks(n, cfg.num_blocks)
    if b_total % d != 0:
        raise ValueError(f"num_blocks={b_total} not divisible by axis size {d}")
    if resolve_policy(cfg.window, cfg.truncate).truncates \
            and straggler_fn is not None:
        raise ValueError("truncate is incompatible with straggler_fn (stale "
                         "fine results are indexed on the full block axis)")
    b_local = b_total // d
    max_iters = cfg.max_iters if cfg.max_iters is not None else b_total

    my_starts = (me * b_local + jnp.arange(b_local, dtype=jnp.int32)) * s_steps
    all_starts = jnp.arange(b_total, dtype=jnp.int32) * s_steps

    def G(x, i0):
        return solve(eval_fn, sched, solver, x, i0, 1, s_steps)

    def F(x, i0):
        return solve(eval_fn, sched, solver, x, i0, s_steps, 1)

    def fine_fn(x_heads, p, y_prev):
        live = x_heads.shape[0]
        if live == b_total:
            # ---- full-width local fine solves (the parallel part) ----
            my_heads = jax.lax.dynamic_slice_in_dim(x_heads, me * b_local,
                                                    b_local)
            y_local = jax.vmap(F)(my_heads, my_starts)             # (B_local, ...)
            y = jax.lax.all_gather(y_local, axis, tiled=True)      # (B, ...)
            if straggler_fn is not None:
                mask = straggler_fn(p).reshape((-1,) + (1,) * (y.ndim - 1))
                y = jnp.where(jnp.logical_and(mask, p > 0), y_prev, y)
            return y
        # ---- truncated suffix: redistribute the live blocks over the axis
        # so retired prefix blocks free whole devices.  Every device takes a
        # ceil(live/d) chunk of the suffix (padded with copies of the last
        # head so the lockstep shapes stay static); devices whose chunk
        # starts past the suffix skip their fine solves entirely — real
        # per-device retirement, not masking.  Block->device placement
        # shifts as the frontier advances, which is fine: results are
        # re-joined by one all_gather either way.
        f = b_total - live
        m = -(-live // d)
        pad = d * m - live
        heads = x_heads
        st = all_starts[f:]
        if pad:
            heads = jnp.concatenate(
                [heads, jnp.broadcast_to(heads[-1:],
                                         (pad,) + heads.shape[1:])], axis=0)
            st = jnp.concatenate([st, jnp.broadcast_to(st[-1:], (pad,))])
        start = me * m
        my_heads = jax.lax.dynamic_slice_in_dim(heads, start, m)
        my_st = jax.lax.dynamic_slice_in_dim(st, start, m)
        y_local = jax.lax.cond(
            start < live,
            lambda: jax.vmap(F)(my_heads, my_st),
            lambda: jnp.zeros((m,) + x_heads.shape[1:], x_heads.dtype))
        return jax.lax.all_gather(y_local, axis, tiled=True)[:live]

    # The coarse sweep / predictor-corrector / convergence gating all come
    # from the shared engine; the coarse sweep is computed redundantly on
    # every device (cheap: B coarse evals).
    out = run_parareal(G, fine_fn, x_init, all_starts,
                       tol=cfg.tol if tol is None else tol,
                       max_iters=max_iters, norm=cfg.norm,
                       use_fused_update=cfg.use_fused_update,
                       fixed_iters=cfg.fixed_iters,
                       scan_unroll=cfg.scan_unroll,
                       carry_fine_results=straggler_fn is not None,
                       batched=cfg.per_sample, truncate=cfg.truncate,
                       window=cfg.window, accel=cfg.accel)
    return out.x_tail[-1], out.iters, out.delta, out.history


def make_sharded_sampler(mesh, axis: str, model_fn: ModelFn,
                         sched: DiffusionSchedule, solver: SolverConfig,
                         cfg: SRDSConfig, straggler_fn=None,
                         data_axis: str = None):
    """jit-compiled SPMD sampler: x_init (replicated) -> SRDSResult.

    The returned callable takes an optional runtime ``tol`` (scalar, or a
    per-sample ``(K,)`` vector with ``cfg.per_sample``) so a serving layer
    can pack requests with different tolerances into one micro-batch without
    recompiling; ``tol=None`` uses ``cfg.tol``.

    ``data_axis`` (optional) shards the leading K sample batch of
    ``x_init`` — and the runtime ``tol`` vector with it — over a second
    mesh axis: lanes are independent, so the split needs no collectives and
    composes with the block ``axis`` on a 2D mesh.  Requires
    ``cfg.per_sample`` (joint-norm gating couples lanes: each data shard
    would gate on its local residual and iteration counts would diverge)
    and a ``K`` divisible by the axis size.

    ``model_fn`` may be a sharding-aware
    :class:`repro.core.denoiser.Denoiser` whose ``mesh_axes`` name further
    axes of the same ``mesh`` (e.g. ``model``) — a (time, data, model)
    mesh then runs time-, data- and model-parallel fine solves through the
    one seam.  The mesh is validated against the denoiser's requirement
    up front (clear ``ValueError`` instead of XLA's unbound-axis error).
    """
    as_denoiser(model_fn).check_mesh(mesh)
    if data_axis is not None and not cfg.per_sample:
        raise ValueError("data_axis shards the sample batch, which is only "
                         "exact under per-sample gating — set "
                         "SRDSConfig.per_sample=True")
    d_data = mesh.shape[data_axis] if data_axis is not None else 1

    def local(x_init, tol):
        s, it, d, h = srds_sharded_local(model_fn, sched, solver, x_init, axis,
                                         cfg, straggler_fn, tol=tol)
        return s, it, d, h

    if data_axis is not None:
        in_specs = (P(data_axis), P(data_axis))
        out_specs = (P(data_axis), P(data_axis), P(data_axis),
                     P(None, data_axis))
    else:
        in_specs = (P(), P())
        out_specs = (P(), P(), P(), P())
    fn = compat.shard_map(local, mesh=mesh,
                          in_specs=in_specs, out_specs=out_specs,
                          check_vma=False)

    @jax.jit
    def _sample(x_init, tol):
        s, it, d, h = fn(x_init, tol)
        return assemble_result(s, it, d, h)

    def sample(x_init, tol=None):
        tolv = jnp.asarray(cfg.tol if tol is None else tol, jnp.float32)
        if data_axis is not None:
            k = x_init.shape[0]
            if k % d_data != 0:
                raise ValueError(f"sample batch K={k} not divisible by "
                                 f"data axis size {d_data}")
            if tolv.ndim == 0:
                tolv = jnp.broadcast_to(tolv, (k,))
        return _sample(x_init, tolv)

    return sample


# --------------------------------------------------------------------------
# Wavefront-pipelined SRDS (paper Fig. 4, eval-granular)
# --------------------------------------------------------------------------

class _WaveCarry(NamedTuple):
    s: jnp.ndarray             # superstep counter
    z: jnp.ndarray             # running fine-solve state
    x_new: jnp.ndarray         # latest left-boundary value x_i^p
    prev_coarse: jnp.ndarray   # G(x_i^{p-1})
    out_last: jnp.ndarray      # device's last completed block output
    delta: jnp.ndarray         # last residual, f32 () or (K,) per sample —
                               # live on device B-1, psum-broadcast on exit
    history: jnp.ndarray       # per-refinement residuals, (max_iters,[ K]) —
                               # live on device B-1, psum-broadcast on exit
    p_done: jnp.ndarray        # completed refinements (device-local),
                               # int32 () or per-sample (K,)
    conv: jnp.ndarray          # per-sample converged mask on device B-1,
                               # bool () or (K,) (always False elsewhere)
    done: jnp.ndarray          # all-samples-converged flag (replicated)
    my_evals: jnp.ndarray      # int32 model evals this device actually ran
                               # (retired/ramp supersteps skip the eval)


def srds_pipelined_local(model_fn: ModelFn, sched: DiffusionSchedule,
                         solver: SolverConfig, x_init: jnp.ndarray,
                         axis: str, cfg: SRDSConfig):
    """Per-shard wavefront body; one parareal block per device along ``axis``.

    Every *working* superstep performs exactly ONE model call on a 2-sample
    batch (fine slot + coarse slot) per device — the paper's unit of
    "effective serial evals".  The coarse slot is live only on
    block-boundary and init supersteps; it rides the same call (cost: a 2x
    smaller micro-batch would not be faster on the MXU anyway).  Devices
    whose block is past the converged-prefix frontier are *retired* — block
    ``i`` is provably exact after ``i`` refinements, so device ``i-1``
    skips its model call entirely from then on (``lax.cond``; the ring
    exchange still runs every superstep) — and devices ahead of the ramp
    skip theirs too.  The returned ``evals`` counts the model evals that
    actually ran.

    The wavefront restructures *scheduling*, not math: the corrector update
    and convergence gate below are :func:`repro.core.engine.parareal_update`
    and :func:`repro.core.engine.convergence_norm` — the same code the
    sequential and block-sharded samplers run.

    With ``cfg.per_sample`` the leading axis of ``x_init`` is a batch of K
    samples gated independently: the tail device carries a per-sample
    residual/convergence mask, freezes converged samples' outputs, and the
    psum'd done-flag fires only once *every* sample has converged.
    """
    n = sched.num_steps
    d = compat.axis_size(axis)
    me = jax.lax.axis_index(axis)
    # model evals go through the sharding-aware seam: a model-parallel
    # Denoiser's inner_eval composes its mesh axes with the ring axis
    eval_fn = as_denoiser(model_fn).inner_eval()
    if n % d != 0:
        raise ValueError(f"N={n} must be divisible by device count {d}")
    if resolve_accel(cfg.accel).accelerates:
        # one block per device, no central iterate history: the joint-state
        # mixing the Accelerator seam defines has nowhere to live on the
        # ring — refuse loudly rather than silently not accelerating
        raise ValueError("the wavefront pipeline does not support "
                         "accelerating Accelerators (per-block state is "
                         "distributed with no central iterate history); "
                         "use srds_sample or the sharded driver, or pass "
                         "accel=None")
    s_steps = n // d                       # fine steps per block
    evals_per_step = solver.evals_per_step
    max_iters = cfg.max_iters if cfg.max_iters is not None else d
    # Frontier policy behind per-device retirement.  Retirement is exact
    # and free on the wavefront (see the retire_at note below), so the
    # default is ExactPrefix regardless of cfg.truncate; an explicit
    # cfg.window (e.g. FixedBudget to disable retirement for analysis)
    # overrides it.  ResidualWindow falls back to the provable rule here —
    # per-block residuals live on no single device of the ring.
    policy = cfg.window if cfg.window is not None else ExactPrefix()
    max_supersteps = max_iters * s_steps + d + 2
    right = [(i, (i + 1) % d) for i in range(d)]
    per = cfg.per_sample

    def lane_mask(mask, t):
        # broadcast a per-sample mask against a (K, ...) state tensor
        return mask.reshape(mask.shape + (1,) * (t.ndim - mask.ndim)) \
            if per else mask

    block_i0 = me * s_steps                # my block's first grid index

    def batched_eval(z, j, x_coarse):
        """One lockstep model call advancing fine slot and coarse slot."""
        fine_i0 = block_i0 + j
        # Stack the two slots on a fresh leading axis; solver_step below
        # will broadcast its per-slot grid indices.
        stacked = jnp.stack([z, x_coarse], axis=0)
        i0 = jnp.stack([fine_i0, block_i0])
        i1 = jnp.stack([fine_i0 + 1, block_i0 + s_steps])

        def one(slot, a, b):
            return solver_step(eval_fn, sched, solver, slot, a, b)

        out = jax.vmap(one)(stacked, i0, i1)
        return out[0], out[1]              # fine-advanced z, coarse result

    def body(c: _WaveCarry) -> _WaveCarry:
        rel = c.s - me
        active = rel >= 0
        j = jnp.where(active, rel % s_steps, 0)
        p = jnp.where(active, rel // s_steps + 1, 0)
        is_first = jnp.logical_and(active, j == 0)
        is_last = jnp.logical_and(active, j == s_steps - 1)
        is_init = jnp.logical_and(is_first, p == 1)

        # --- per-device retirement (the wavefront's converged-prefix
        # truncation): block me+1 is provably exact after me+1 refinements
        # (classical Parareal), so once this device has completed
        # min(me+1, max_iters) refinements every further eval would
        # reproduce its boundary bit for bit.  Note the frontier does NOT
        # need the engine's one-refinement lag (prefix_frontier): that lag
        # exists because the engine's init sweep and corrector sweep are
        # two separately compiled scans whose coarse values can differ in
        # the last bits — here BOTH coarse terms of every update come from
        # the same batched_eval call site in this one loop body, so equal
        # inputs give bitwise-equal terms already at the first
        # recomputation (inductively: block i's boundary is a bitwise
        # fixed point from refinement i).  Retired (and not-yet-ramped)
        # devices genuinely skip the model call via lax.cond — the
        # predicate is device-local and the branch holds no collectives, so
        # SPMD stays sound; the ring exchange below still runs every
        # superstep on every device.
        completed = jnp.where(active, rel // s_steps, 0)
        # the tail device keeps computing until `over` freezes it: its
        # residuals feed delta/history, and with max_iters > d a retired
        # tail would report a pinned 0.0 in place of a computed residual
        # (identical by the fixed-point argument, but never synthesize a
        # number that gates convergence) — the policy's retire_at encodes
        # both the per-block rule and the tail exemption
        retire_at = policy.retire_at(me, d, max_iters)
        retired = jnp.logical_and(active, completed >= retire_at)
        do_eval = jnp.logical_and(active, jnp.logical_not(retired))

        # fine input: at j==0 restart from the boundary value x_i^{p-1}
        z_in = jnp.where(is_first, c.x_new, c.z)
        z_out, coarse_out = jax.lax.cond(
            do_eval,
            lambda: batched_eval(z_in, j, c.x_new),
            lambda: (c.z, c.prev_coarse))
        my_evals = c.my_evals + jnp.where(do_eval, 2 * evals_per_step, 0)

        # --- init superstep: coarse_out = G(x_i^0): seed prev_coarse, send
        # --- last superstep:  coarse_out = G(x_i^p): predictor-corrector
        prev_eff = jnp.where(is_init, coarse_out, c.prev_coarse)
        out_block = parareal_update(z_out, coarse_out, prev_eff,
                                    cfg.use_fused_update)
        # a retired device's boundary is already final: pin out_block to it
        # so every downstream consumer (send, residual, out_last) sees the
        # stable value instead of the skipped eval's placeholders
        out_block = jnp.where(retired, c.out_last, out_block)
        send_val = jnp.where(is_last, out_block,
                             jnp.where(is_init, coarse_out, c.out_last))
        send_flag = jnp.logical_or(is_init, is_last)

        new_prev_coarse = jnp.where(jnp.logical_or(is_init, is_last),
                                    coarse_out, c.prev_coarse)
        # out_last tracks x_{i+1}^p (x_{i+1}^0 after the init eval), so the
        # tail device's p=1 residual compares against x_B^0 per Alg. 1.
        # Samples already converged on the tail device stay frozen — their
        # reported output is the value at their convergence refinement, the
        # same contract as the engine's per-sample gating (c.conv is always
        # False off the tail device, so this is a no-op elsewhere).  The
        # superstep budget has a few supersteps of ramp slack past
        # refinement max_iters (for s_steps <= 3 a block can complete an
        # extra refinement inside it) — `over` freezes those too, so
        # iterations/delta/history never report past the budget.
        over = p > max_iters
        frozen = lane_mask(jnp.logical_or(c.conv, over), out_block)
        new_out_last = jnp.where(is_last,
                                 jnp.where(frozen, c.out_last, out_block),
                                 jnp.where(is_init, coarse_out, c.out_last))
        new_p_done = jnp.where(
            jnp.logical_and(
                jnp.logical_and(is_last, jnp.logical_not(retired)),
                jnp.logical_not(jnp.logical_or(c.conv, over))),
            p, c.p_done)

        # convergence residual on the final block (per sample when gated)
        is_tail = me == d - 1
        resid = convergence_norm(out_block - c.out_last, cfg.norm, batched=per)
        upd = jnp.logical_and(is_tail,
                              jnp.logical_and(is_last, jnp.logical_not(over)))
        live = jnp.logical_and(upd, jnp.logical_not(c.conv))
        delta = jnp.where(live, resid, c.delta)
        # record the refinement's residual for still-refining samples (the
        # +inf tail past a sample's convergence matches the engine contract)
        idx = jnp.clip(p - 1, 0, max_iters - 1)
        history = c.history.at[idx].set(
            jnp.where(live, resid, c.history[idx]))
        conv = jnp.where(upd, has_converged(delta, cfg.tol), c.conv)
        local_conv = jnp.where(
            upd, jnp.all(conv).astype(jnp.float32), 0.0)
        done = jax.lax.psum(local_conv, axis) > 0.0

        # ring exchange of boundary values (one sample per neighbor pair)
        recv_val = jax.lax.ppermute(send_val, axis, right)
        recv_flag = jax.lax.ppermute(send_flag.astype(jnp.float32), axis, right)
        take = jnp.logical_and(recv_flag > 0, me > 0)
        x_new = jnp.where(take, recv_val, c.x_new)
        x_new = jnp.where(me == 0, x_init, x_new)   # x_0 is the fixed IC

        return _WaveCarry(c.s + 1, jnp.where(active, z_out, c.z), x_new,
                          jnp.where(active, new_prev_coarse, c.prev_coarse),
                          jnp.where(active, new_out_last, c.out_last),
                          delta, history,
                          jnp.where(active, new_p_done, c.p_done), conv, done,
                          my_evals)

    def cond(c: _WaveCarry):
        return jnp.logical_and(c.s < max_supersteps, jnp.logical_not(c.done))

    if per:
        k = x_init.shape[0]
        delta0 = jnp.full((k,), jnp.inf, jnp.float32)
        hist0 = jnp.full((max_iters, k), jnp.inf, jnp.float32)
        p_done0 = jnp.zeros((k,), jnp.int32)
        conv0 = jnp.zeros((k,), bool)
    else:
        delta0 = jnp.float32(jnp.inf)
        hist0 = jnp.full((max_iters,), jnp.inf, jnp.float32)
        p_done0 = jnp.int32(0)
        conv0 = jnp.asarray(False)
    init = _WaveCarry(s=jnp.int32(0), z=x_init, x_new=x_init,
                      prev_coarse=jnp.zeros_like(x_init),
                      out_last=jnp.zeros_like(x_init),
                      delta=delta0, history=hist0, p_done=p_done0,
                      conv=conv0, done=jnp.asarray(False),
                      my_evals=jnp.int32(0))
    c = jax.lax.while_loop(cond, body, init)

    # broadcast the tail device's answers to every shard
    def from_tail(v):
        return jax.lax.psum(jnp.where(me == d - 1, v, jnp.zeros_like(v)),
                            axis)

    sample = from_tail(c.out_last)
    iters = from_tail(c.p_done)
    delta = from_tail(c.delta)
    history = from_tail(c.history)
    supersteps = c.s
    # physical model evals actually run across the ring (retired and
    # not-yet-ramped devices skipped theirs)
    evals = jax.lax.psum(c.my_evals, axis)
    return sample, iters, delta, history, supersteps, evals


def make_pipelined_sampler(mesh, axis: str, model_fn: ModelFn,
                           sched: DiffusionSchedule, solver: SolverConfig,
                           cfg: SRDSConfig):
    as_denoiser(model_fn).check_mesh(mesh)

    def local(x_init):
        return srds_pipelined_local(model_fn, sched, solver, x_init, axis, cfg)

    fn = compat.shard_map(local, mesh=mesh, in_specs=P(),
                          out_specs=(P(), P(), P(), P(), P(), P()),
                          check_vma=False)

    @jax.jit
    def sample(x_init):
        s, p, dlt, hist, steps, evals = fn(x_init)
        return assemble_result(s, p, dlt, hist), steps, evals

    return sample
