"""TRON: trust-region Newton with truncated conjugate gradient.

TPU-native counterpart of the reference's LIBLINEAR port
(ml/optimization/TRON.scala:153-340): an outer trust-region loop whose inner
CG performs one Hessian-vector product per iteration. In the reference each
Hv product is a distributed treeAggregate; here it is a jvp-of-grad through
the fused GLM objective — under data sharding XLA turns the contraction into
an ICI all-reduce, and under ``vmap`` the whole solver batches over entities.

Trust-region update rules follow LIBLINEAR (sigma1/sigma2/sigma3,
eta0/eta1/eta2); the improvement-failure budget mirrors
TRON.scala's maxNumImprovementFailures=5 (ml/optimization/TRON.scala:258-264).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu import telemetry
from photon_ml_tpu.optimization.convergence import (
    ConvergenceReason,
    OptimizerResult,
    check_solver_finite,
)
from photon_ml_tpu.optimization.lbfgs import _project

Array = jax.Array

# Shared per-outer-iteration telemetry with the streaming L-BFGS
# (optimization/glm_lbfgs.py) — one histogram, one schema.
_H_ITERATION = telemetry.histogram("training.iteration_seconds")
_M_ITERATIONS = telemetry.counter("training.solver_iterations")
# Batched λ-grid: grid rows still iterating (same gauge object as the
# streaming L-BFGS — the registry is get-or-create).
_G_GRID_ACTIVE = telemetry.gauge("training.grid.active_points")

_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0
_CG_XI = 0.1  # inner CG stops at ||r|| <= xi ||g||


def _truncated_cg(hvp, g, delta, max_cg, dtype):
    """Steihaug-Toint truncated CG: approximately solve H s = -g, ||s||<=delta.

    Returns (s, r, k) with r the final residual -g - H s (needed for the
    predicted-reduction formula) and k the CG steps taken. One hvp per
    step — the hot loop (reference: TRON.scala:280-340). Where ``hvp``
    also returns its direction's margins, ``d -> (H d, X d)``, the CG sums
    them as it sums the step, and ``s`` is the pair ``(s, X s)``.
    """
    d0 = -g
    s0 = jnp.zeros_like(g)
    r0 = -g
    rtr0 = jnp.vdot(r0, r0)
    stop_norm = _CG_XI * jnp.linalg.norm(g)
    out = jax.eval_shape(hvp, g)
    xs0 = (jnp.zeros(out[1].shape, out[1].dtype)
           if isinstance(out, tuple) else None)

    class CGState(NamedTuple):
        s: Array
        xs: Optional[Array]  # X s, where the product reports X d
        r: Array
        d: Array
        rtr: Array
        k: Array
        done: Array

    init = CGState(s0, xs0, r0, d0, rtr0, jnp.zeros((), jnp.int32),
                   jnp.linalg.norm(r0) <= stop_norm)

    def cond(st: CGState):
        return jnp.logical_and(~st.done, st.k < max_cg)

    def body(st: CGState):
        hd, xd = hvp(st.d) if st.xs is not None else (hvp(st.d), None)
        dhd = jnp.vdot(st.d, hd)
        # Guard: non-positive curvature direction -> march to the boundary.
        alpha = st.rtr / jnp.where(dhd > 0, dhd, jnp.asarray(1.0, dtype))
        s_try = st.s + alpha * st.d

        crossed = jnp.logical_or(jnp.linalg.norm(s_try) > delta, dhd <= 0)

        # Boundary intersection: tau >= 0 with ||s + tau d|| = delta.
        std = jnp.vdot(st.s, st.d)
        dd = jnp.vdot(st.d, st.d)
        ss = jnp.vdot(st.s, st.s)
        gap = jnp.maximum(delta * delta - ss, 0.0)
        rad = jnp.sqrt(jnp.maximum(std * std + dd * gap, 0.0))
        safe_dd = jnp.maximum(dd, 1e-30)
        tau = jnp.where(
            std >= 0, gap / jnp.maximum(std + rad, 1e-30), (rad - std) / safe_dd
        )

        step = jnp.where(crossed, tau, alpha)
        s_new = st.s + step * st.d
        xs_new = None if xd is None else st.xs + step * xd
        r_new = st.r - step * hd

        rtr_new = jnp.vdot(r_new, r_new)
        beta = rtr_new / jnp.maximum(st.rtr, 1e-30)
        d_new = r_new + beta * st.d

        done_new = jnp.logical_or(
            crossed, jnp.sqrt(rtr_new) <= stop_norm
        )
        new = CGState(s_new, xs_new, r_new, d_new, rtr_new, st.k + 1,
                      done_new)
        return jax.tree.map(lambda a, b: jnp.where(st.done, a, b), st, new)

    final = lax.while_loop(cond, body, init)
    s = final.s if final.xs is None else (final.s, final.xs)
    return s, final.r, final.k


class _TronState(NamedTuple):
    x: Array
    z: Optional[Array]  # the margins at x, where the loop carries them
    f: Array
    g: Array
    delta: Array
    it: Array  # accepted iterations
    attempted: Array  # outer iterations run, accepted or rejected
    cg: Array  # CG steps (Hessian-vector products) over all of them
    cg_x: Array  # the last outer iteration's CG: the point it ran at,
    cg_s: Array  # the step it returned
    cg_r: Array  # and the residual it carried, -g - H s
    fails: Array  # consecutive improvement failures
    reason: Array
    value_hist: Array
    gnorm_hist: Array
    first: Array  # bool: before first step (delta clamp rule)
    coef_hist: Optional[Array]  # [max_iter+1, d] when tracking, else None


@functools.partial(
    jax.jit,
    static_argnames=("fun", "max_iter", "tol", "max_cg",
                     "max_improvement_failures", "has_bounds",
                     "track_coefficients", "make_hvp",
                     "margins_value_and_grad"),
)
def _minimize_tron_impl(
    fun, x0, args, lower, upper, *, max_iter, tol, max_cg,
    max_improvement_failures, has_bounds, track_coefficients=False,
    make_hvp=None, margins_value_and_grad=None,
) -> OptimizerResult:
    vg = jax.value_and_grad(fun)
    carried = margins_value_and_grad is not None
    dtype = x0.dtype
    lo = lower if has_bounds else None
    hi = upper if has_bounds else None

    def proj_grad_norm(x, g):
        # Norm of the projected gradient: ||x - P(x - g)||. Equals ||g|| in
        # the unconstrained case; the right stationarity measure with bounds.
        if not has_bounds:
            return jnp.linalg.norm(g)
        return jnp.linalg.norm(x - _project(x - g, lo, hi))

    x0 = _project(x0, lo, hi)
    if carried:
        z0, f0, g0 = margins_value_and_grad(x0, *args)
    else:
        z0 = None
        f0, g0 = vg(x0, *args)
    gnorm0 = proj_grad_norm(x0, g0)
    f0_scale = jnp.maximum(jnp.abs(f0), jnp.asarray(1e-30, dtype))

    value_hist = jnp.full((max_iter + 1,), jnp.nan, dtype).at[0].set(f0)
    gnorm_hist = jnp.full((max_iter + 1,), jnp.nan, dtype).at[0].set(gnorm0)
    coef_hist = (jnp.full((max_iter + 1, x0.shape[-1]), jnp.nan,
                          dtype).at[0].set(x0)
                 if track_coefficients else None)

    init = _TronState(
        x=x0, z=z0, f=f0, g=g0, delta=gnorm0,
        it=jnp.zeros((), jnp.int32), attempted=jnp.zeros((), jnp.int32),
        cg=jnp.zeros((), jnp.int32), cg_x=x0, cg_s=jnp.zeros_like(x0),
        cg_r=jnp.zeros_like(x0), fails=jnp.zeros((), jnp.int32),
        reason=jnp.where(
            gnorm0 <= 0.0, int(ConvergenceReason.GRADIENT_CONVERGED),
            int(ConvergenceReason.NOT_CONVERGED)).astype(jnp.int32),
        value_hist=value_hist, gnorm_hist=gnorm_hist,
        first=jnp.ones((), bool), coef_hist=coef_hist,
    )

    def cond(st: _TronState):
        return st.reason == int(ConvergenceReason.NOT_CONVERGED)

    def body(st: _TronState):
        if carried:
            # The GLM's product from the margins the loop carries: the
            # curvature weights with no pass over X, and each CG step's
            # X d returned beside H d.
            hvp = make_hvp(st.z, *args)
        elif make_hvp is not None:
            # Caller-specialized product (GLM: margin-cached, exactly one
            # matvec+rmatvec per CG step; curvature weights computed once
            # per outer iteration and hoisted out of the CG loop).
            hvp = make_hvp(st.x, *args)
        else:
            def hvp(v):
                grad_fn = lambda xx: vg(xx, *args)[1]
                return jax.jvp(grad_fn, (st.x,), (v,))[1]

        if has_bounds:
            # Active-set reduction: coordinates pinned at a bound with the
            # gradient pushing outward are frozen; CG runs in the free
            # subspace so the Newton model isn't polluted by directions the
            # projection will clip anyway.
            eps = jnp.asarray(1e-12, dtype)
            active = jnp.logical_or(
                jnp.logical_and(st.x <= lo + eps, st.g > 0),
                jnp.logical_and(st.x >= hi - eps, st.g < 0),
            )
            free = (~active).astype(dtype)
            g_cg = st.g * free
            hvp_cg = lambda v: free * hvp(free * v)
        else:
            g_cg, hvp_cg = st.g, hvp

        s, r, cg_steps = _truncated_cg(hvp_cg, g_cg, st.delta, max_cg, dtype)
        if carried:
            # margins are affine in x: the trial's are z + X s, and its
            # value and gradient cost one rmatvec
            s, xs = s
            z_try = st.z + xs

        x_try = _project(st.x + s, lo, hi)
        s_real = x_try - st.x
        if carried:
            _, f_new, g_new = margins_value_and_grad(x_try, *args, z=z_try)
        else:
            f_new, g_new = vg(x_try, *args)

        gs = jnp.vdot(st.g, s_real)
        if has_bounds:
            # Projection changed the step; evaluate the quadratic model on the
            # realized step for a consistent predicted reduction.
            prered = -(gs + 0.5 * jnp.vdot(s_real, hvp(s_real)))
        else:
            prered = -0.5 * (gs - jnp.vdot(s_real, r))
        actred = st.f - f_new
        snorm = jnp.linalg.norm(s_real)

        delta = jnp.where(st.first, jnp.minimum(st.delta, snorm), st.delta)

        # LIBLINEAR step-size interpolation for the radius update.
        denom = f_new - st.f - gs
        alpha = jnp.where(
            denom <= 0, _SIGMA3,
            jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.maximum(denom, 1e-30))),
        )
        alpha_s = alpha * snorm
        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta,
                            jnp.minimum(alpha_s, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta,
                                jnp.minimum(alpha_s, _SIGMA3 * delta)),
                    jnp.maximum(delta, jnp.minimum(alpha_s, _SIGMA3 * delta)),
                ),
            ),
        )

        accept = jnp.logical_and(actred > _ETA0 * prered, jnp.isfinite(f_new))
        it_new = st.it + jnp.where(accept, 1, 0).astype(jnp.int32)
        fails_new = jnp.where(accept, 0, st.fails + 1).astype(jnp.int32)

        x_acc = jnp.where(accept, x_try, st.x)
        f_acc = jnp.where(accept, f_new, st.f)
        g_acc = jnp.where(accept, g_new, st.g)
        gnorm_acc = proj_grad_norm(x_acc, g_acc)
        f_delta = jnp.abs(st.f - f_acc)

        reason = jnp.where(
            fails_new > max_improvement_failures,
            int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING),
            jnp.where(
                jnp.logical_and(accept, gnorm_acc <= tol * gnorm0),
                int(ConvergenceReason.GRADIENT_CONVERGED),
                jnp.where(
                    jnp.logical_and(accept, f_delta <= tol * f0_scale),
                    int(ConvergenceReason.FUNCTION_VALUES_CONVERGED),
                    jnp.where(
                        it_new >= max_iter,
                        int(ConvergenceReason.MAX_ITERATIONS),
                        int(ConvergenceReason.NOT_CONVERGED)))),
        ).astype(jnp.int32)

        new = _TronState(
            x=x_acc, z=jnp.where(accept, z_try, st.z) if carried else None,
            f=f_acc, g=g_acc, delta=delta, it=it_new,
            attempted=st.attempted + 1, cg=st.cg + cg_steps,
            cg_x=st.x, cg_s=s, cg_r=r,
            fails=fails_new, reason=reason,
            value_hist=jnp.where(
                accept, st.value_hist.at[it_new].set(f_acc), st.value_hist),
            gnorm_hist=jnp.where(
                accept, st.gnorm_hist.at[it_new].set(gnorm_acc),
                st.gnorm_hist),
            first=jnp.zeros((), bool),
            coef_hist=(None if st.coef_hist is None
                       else jnp.where(
                           accept, st.coef_hist.at[it_new].set(x_acc),
                           st.coef_hist)),
        )
        done = ~cond(st)
        return jax.tree.map(lambda a, b: jnp.where(done, a, b), st, new)

    final = lax.while_loop(cond, body, init)
    # Passes over X: the first value and gradient (2), an outer step's own
    # (the trial's gradient where the margins are carried; else the
    # margins for the curvature weights and the trial's value and
    # gradient, and with bounds the realized step's product), two a CG
    # step. Unknown for the jvp-of-grad product.
    per_step = 1 if carried else 5 if has_bounds else 3
    passes = (None if make_hvp is None
              else 2 + per_step * final.attempted + 2 * final.cg)
    return OptimizerResult(
        x=final.x, value=final.f, grad_norm=jnp.linalg.norm(final.g),
        iterations=final.it, reason=final.reason,
        value_history=final.value_hist, grad_norm_history=final.gnorm_hist,
        coef_history=final.coef_hist,
        cg_iterations=final.cg, attempted_iterations=final.attempted,
        cg_point=final.cg_x, cg_step=final.cg_s, cg_residual=final.cg_r,
        feature_passes=passes,
    )


@jax.jit
def _stream_cg_step(s, r, d_vec, rtr, hd, delta, stop_norm):
    """One Steihaug-Toint CG step given the (streamed) Hessian product —
    the body of `_truncated_cg` verbatim, as a single [d]-space dispatch;
    the streaming driver makes the loop decisions on host."""
    dtype = s.dtype
    dhd = jnp.vdot(d_vec, hd)
    alpha = rtr / jnp.where(dhd > 0, dhd, jnp.asarray(1.0, dtype))
    s_try = s + alpha * d_vec
    crossed = jnp.logical_or(jnp.linalg.norm(s_try) > delta, dhd <= 0)

    std = jnp.vdot(s, d_vec)
    dd = jnp.vdot(d_vec, d_vec)
    ss = jnp.vdot(s, s)
    gap = jnp.maximum(delta * delta - ss, 0.0)
    rad = jnp.sqrt(jnp.maximum(std * std + dd * gap, 0.0))
    safe_dd = jnp.maximum(dd, 1e-30)
    tau = jnp.where(std >= 0, gap / jnp.maximum(std + rad, 1e-30),
                    (rad - std) / safe_dd)

    step = jnp.where(crossed, tau, alpha)
    s_new = s + step * d_vec
    r_new = r - step * hd
    rtr_new = jnp.vdot(r_new, r_new)
    beta = rtr_new / jnp.maximum(rtr, 1e-30)
    d_new = r_new + beta * d_vec
    done = jnp.logical_or(crossed, jnp.sqrt(rtr_new) <= stop_norm)
    return s_new, r_new, d_new, rtr_new, done


@jax.jit
def _stream_tr_update(f, f_new, g, s, r, delta, first):
    """Trust-region bookkeeping for one outer step — the LIBLINEAR radius
    interpolation of `_minimize_tron_impl` (unbounded branch), verbatim."""
    gs = jnp.vdot(g, s)
    prered = -0.5 * (gs - jnp.vdot(s, r))
    actred = f - f_new
    snorm = jnp.linalg.norm(s)
    delta = jnp.where(first, jnp.minimum(delta, snorm), delta)

    denom = f_new - f - gs
    alpha = jnp.where(
        denom <= 0, _SIGMA3,
        jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.maximum(denom, 1e-30))))
    alpha_s = alpha * snorm
    delta = jnp.where(
        actred < _ETA0 * prered,
        jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
        jnp.where(
            actred < _ETA1 * prered,
            jnp.maximum(_SIGMA1 * delta,
                        jnp.minimum(alpha_s, _SIGMA2 * delta)),
            jnp.where(
                actred < _ETA2 * prered,
                jnp.maximum(_SIGMA1 * delta,
                            jnp.minimum(alpha_s, _SIGMA3 * delta)),
                jnp.maximum(delta, jnp.minimum(alpha_s, _SIGMA3 * delta)),
            ),
        ),
    )
    accept = jnp.logical_and(actred > _ETA0 * prered, jnp.isfinite(f_new))
    return delta, accept


def minimize_tron_streaming(
    sharded_objective,
    x0: Array,
    l2_weight,
    *,
    max_iter: int = 15,
    tol: float = 1e-5,
    max_cg: int = 20,
    max_improvement_failures: int = 5,
    track_coefficients: bool = False,
    trace_ctx=None,
    convergence_ring=None,
    margins_out=None,
) -> OptimizerResult:
    """Out-of-core TRON: the outer trust-region loop runs on the host;
    each value/gradient evaluation and each inner-CG Hessian-vector
    product is a streaming pass over the shard cache
    (ops/sharded_objective.py — margins + curvature computed once per
    outer iteration, exactly like `GLMObjective.make_tron_hvp`; each CG
    product costs one matvec + one rmatvec per shard). Unsupported here:
    box constraints (use the resident path). Accumulation order is the
    fixed shard order — deterministic, residency-independent, and (via
    the objective's mesh) device-count-independent: per-shard curvature
    stays resident on each shard's mesh device, each CG step broadcasts
    the direction and folds the Hvp partials in fixed shard order, while
    the [d]-space trust-region algebra here runs on the fold device.
    On a 2-D (data x model) mesh the CG direction broadcasts as
    per-column-block SLICES and Hvp partials re-assemble through the
    objective's deterministic model-axis concat; the trust-region state
    here (coefficients, gradient, CG iterates) stays FULL-WIDTH on the
    host/default device — the documented state decision shared with
    `minimize_lbfgs_glm_streaming` — so mesh shapes {1x1, 2x1, 1x2,
    2x2} solve bit-identically with no TRON-side mesh code.

    Spill-tier interaction: margins and curvature (the per-outer-
    iteration row-space state) are never evicted, so the compressed
    (``spill_dtype="bf16"``) and out-of-core (``spill_source=
    "redecode"``) tiers only affect the FEATURE passes — each CG Hvp
    walks `cache.blocks()` and pays the miss path (re-upload + decode,
    or Avro re-decode) per evicted block, so an outer iteration with k
    CG steps costs (k + 2) restore epochs; the trust-region
    accept/reject arithmetic itself touches no features at all.

    Divergence watchdog + ``trace_ctx``: same contract as
    `minimize_lbfgs_glm_streaming` — loss/grad-norm checked for NaN/Inf
    each outer iteration on already-host scalars (typed
    ``SolverDivergedError``, trace-tagged), one ``solver_step`` trace
    event per accepted or rejected outer step. An unaccepted trial with
    non-finite value is NOT a divergence — the trust region shrinks and
    retries, exactly like the fused impl — so only the accepted state
    is checked.

    ``convergence_ring`` / ``margins_out`` — same distribution-
    observability hooks as ``minimize_lbfgs_glm_streaming``: one ring
    entry per ACCEPTED outer iteration (step = ||s||, the trust-region
    step actually taken; all scalars already host-side), and the final
    per-shard margin list for zero-pass training-score sketching."""
    import numpy as np

    sobj = sharded_objective
    x = jnp.asarray(x0)
    dtype = x.dtype
    np_dtype = np.dtype(dtype)
    l2 = jnp.asarray(l2_weight, dtype)

    def host(v):
        return np.asarray(v)[()]

    tol_s = np_dtype.type(tol)
    z_list, f, g = sobj.margins_value_grad(x, l2)
    f_h = host(f)
    gnorm = host(jnp.linalg.norm(g))
    check_solver_finite("streaming-tron", 0, f_h, gnorm, trace_ctx)
    if convergence_ring is not None:
        convergence_ring.append(0, f_h, gnorm, None)
    gnorm0 = gnorm
    f0_scale = np.maximum(np.abs(f_h), np_dtype.type(1e-30))
    delta = jnp.asarray(gnorm0, dtype)

    value_hist = np.full(max_iter + 1, np.nan, np_dtype)
    gnorm_hist = np.full(max_iter + 1, np.nan, np_dtype)
    value_hist[0], gnorm_hist[0] = f_h, gnorm
    coef_hist = (np.full((max_iter + 1, x.shape[-1]), np.nan, np_dtype)
                 if track_coefficients else None)
    if coef_hist is not None:
        coef_hist[0] = np.asarray(x)

    reason = (ConvergenceReason.GRADIENT_CONVERGED if gnorm0 <= 0.0
              else ConvergenceReason.NOT_CONVERGED)
    it = 0
    fails = 0
    first = True
    while reason == ConvergenceReason.NOT_CONVERGED:
        # ``solver_step`` = one trust-region outer iteration (curvature +
        # inner CG + trial evaluation) — same per-iteration telemetry
        # schema as the streaming L-BFGS.
        with telemetry.timed_span("solver_step", histogram=_H_ITERATION,
                                  counter=_M_ITERATIONS):
            if trace_ctx is not None:
                trace_ctx.event("solver_step")
            d2_list = sobj.curvature_list(z_list)

            # -- truncated CG (streamed Hv per step) ----------------------
            s = jnp.zeros_like(g)
            r = -g
            d_vec = -g
            rtr = jnp.vdot(r, r)
            stop_norm = _CG_XI * jnp.linalg.norm(g)
            cg_done = bool(host(jnp.linalg.norm(r) <= stop_norm))
            k = 0
            while not cg_done and k < max_cg:
                hd = sobj.hessian_vector(d_vec, d2_list, l2)
                s, r, d_vec, rtr, done_dev = _stream_cg_step(
                    s, r, d_vec, rtr, hd, delta, stop_norm)
                cg_done = bool(host(done_dev))
                k += 1

            x_try = x + s
            z_try, f_new, g_new = sobj.margins_value_grad(x_try, l2)
            delta, accept_dev = _stream_tr_update(
                f, f_new, g, s, r, delta, jnp.asarray(first))
            first = False
            accept = bool(host(accept_dev))

            if accept:
                it += 1
                fails = 0
                x, z_list, g = x_try, z_try, g_new
                f_new_h = host(f_new)
                f_delta = np.abs(f_h - f_new_h)
                f, f_h = f_new, f_new_h
                gnorm = host(jnp.linalg.norm(g))
                # Watchdog on the ACCEPTED state (host scalars already
                # in hand — no added sync); a rejected non-finite trial
                # is normal trust-region behavior, not divergence.
                check_solver_finite("streaming-tron", it, f_h, gnorm,
                                    trace_ctx)
                value_hist[it], gnorm_hist[it] = f_h, gnorm
                if coef_hist is not None:
                    coef_hist[it] = np.asarray(x)
                if convergence_ring is not None:
                    convergence_ring.append(
                        it, f_h, gnorm, host(jnp.linalg.norm(s)))
                if gnorm <= tol_s * gnorm0:
                    reason = ConvergenceReason.GRADIENT_CONVERGED
                elif f_delta <= tol_s * f0_scale:
                    reason = ConvergenceReason.FUNCTION_VALUES_CONVERGED
                elif it >= max_iter:
                    reason = ConvergenceReason.MAX_ITERATIONS
            else:
                fails += 1
                if fails > max_improvement_failures:
                    reason = ConvergenceReason.OBJECTIVE_NOT_IMPROVING

    if margins_out is not None:
        margins_out[:] = z_list
    return OptimizerResult(
        x=x, value=f, grad_norm=jnp.asarray(gnorm, dtype),
        iterations=jnp.asarray(it, jnp.int32),
        reason=jnp.asarray(int(reason), jnp.int32),
        value_history=jnp.asarray(value_hist),
        grad_norm_history=jnp.asarray(gnorm_hist),
        coef_history=(None if coef_hist is None
                      else jnp.asarray(coef_hist)),
    )


@jax.jit
def _grid_cg_step(s, r, d_vec, rtr, hd, delta, stop_norm):
    """Per-row Steihaug-Toint CG step: `_stream_cg_step` vmapped over
    the grid axis (every array gains a leading [G])."""
    return jax.vmap(_stream_cg_step)(s, r, d_vec, rtr, hd, delta,
                                     stop_norm)


@jax.jit
def _grid_tr_update(f, f_new, g, s, r, delta, first):
    """Per-row LIBLINEAR trust-region update: `_stream_tr_update`
    vmapped over the grid axis (``first`` broadcast — all rows share
    the before-first-step clamp)."""
    return jax.vmap(_stream_tr_update,
                    in_axes=(0, 0, 0, 0, 0, 0, None))(
        f, f_new, g, s, r, delta, first)


def minimize_tron_grid_streaming(
    sharded_objective,
    x0s: Array,
    l2_weights,
    *,
    max_iter: int = 15,
    tol: float = 1e-5,
    max_cg: int = 20,
    max_improvement_failures: int = 5,
    track_coefficients: bool = False,
    trace_ctxs=None,
    convergence_rings=None,
    margins_out=None,
):
    """Batched λ-grid streaming TRON: one curvature pass, one shared CG
    (each Hvp feature pass serves EVERY grid row's iterate), and one
    trial evaluation pass advance all G trust-region solves per outer
    iteration. Coefficients ``[G, d]``, margins/curvature ``[G, rows]``
    per shard, λ row ``[G]``. Returns a list of G
    :class:`OptimizerResult`, row-aligned with the inputs.

    **Masked convergence.** Per-row CG done-masks freeze a row's
    (s, r, d, rtr) once it hits its own Steihaug-Toint stop; the inner
    loop runs until every ACTIVE row is done or ``max_cg`` — so a
    sweep's Hvp pass count is the slowest row's CG depth, not the sum.
    Outer accept/reject, improvement-failure budgets and convergence
    reasons are per row (host numpy masks); finished rows take step 0
    and keep their state bit-identical through `jnp.where` row selects.

    **Bit discipline / observability / divergence** follow
    :func:`~photon_ml_tpu.optimization.glm_lbfgs.minimize_lbfgs_glm_grid_streaming`:
    G=1 delegates to :func:`minimize_tron_streaming` (bitwise gate);
    ``trace_ctxs``/``convergence_rings`` are row-aligned; only ACCEPTED
    states are watchdog-checked, and a non-finite accepted row raises
    :class:`SolverDivergedError` with that row's λ and ``grid_row``.
    """
    import numpy as np

    from photon_ml_tpu.optimization.glm_lbfgs import _grid_select_rows

    sobj = sharded_objective
    x = jnp.asarray(x0s)
    if x.ndim != 2:
        raise ValueError(
            f"x0s must be [G, d] (one coefficient row per grid point), "
            f"got shape {x.shape}")
    G, d = x.shape
    dtype = x.dtype
    np_dtype = np.dtype(dtype)
    l2s = jnp.asarray(l2_weights, dtype)
    if l2s.shape != (G,):
        raise ValueError(
            f"l2_weights must be [G]={G} (one λ per grid row), got "
            f"shape {l2s.shape}")
    ctxs = list(trace_ctxs) if trace_ctxs is not None else [None] * G
    rings = (list(convergence_rings) if convergence_rings is not None
             else [None] * G)
    if len(ctxs) != G or len(rings) != G:
        raise ValueError(
            f"trace_ctxs/convergence_rings must be row-aligned with the "
            f"grid (G={G}), got {len(ctxs)}/{len(rings)}")

    if G == 1:
        # Bitwise gate: the 1-row grid IS the scalar streamed solver.
        holder = [] if margins_out is not None else None
        res = minimize_tron_streaming(
            sobj, x[0], l2s[0], max_iter=max_iter, tol=tol,
            max_cg=max_cg,
            max_improvement_failures=max_improvement_failures,
            track_coefficients=track_coefficients, trace_ctx=ctxs[0],
            convergence_ring=rings[0], margins_out=holder)
        if margins_out is not None:
            margins_out[:] = [z[None] for z in holder]
        return [res]

    tol_s = np_dtype.type(tol)
    l2_h = np.asarray(l2s)
    z_list, f, g = sobj.grid_margins_value_grad(x, l2s)
    f_h = np.asarray(f)
    gnorm = np.asarray(jnp.linalg.norm(g, axis=-1))
    for gi in range(G):
        check_solver_finite("streaming-tron-grid", 0, f_h[gi],
                            gnorm[gi], ctxs[gi], lam=l2_h[gi],
                            grid_row=gi)
        if rings[gi] is not None:
            rings[gi].append(0, f_h[gi], gnorm[gi], None)
    gnorm0 = gnorm.copy()
    f0_scale = np.maximum(np.abs(f_h), np_dtype.type(1e-30))
    delta = jnp.asarray(gnorm0)

    value_hist = np.full((G, max_iter + 1), np.nan, np_dtype)
    gnorm_hist = np.full((G, max_iter + 1), np.nan, np_dtype)
    value_hist[:, 0], gnorm_hist[:, 0] = f_h, gnorm
    coef_hist = (np.full((G, max_iter + 1, d), np.nan, np_dtype)
                 if track_coefficients else None)
    if coef_hist is not None:
        coef_hist[:, 0] = np.asarray(x)

    reasons = [ConvergenceReason.GRADIENT_CONVERGED if gnorm0[gi] <= 0.0
               else ConvergenceReason.NOT_CONVERGED for gi in range(G)]
    active = np.array(
        [r == ConvergenceReason.NOT_CONVERGED for r in reasons])
    its = np.zeros(G, np.int64)
    fails = np.zeros(G, np.int64)
    first = True

    while active.any():
        with telemetry.timed_span("solver_step", histogram=_H_ITERATION,
                                  counter=_M_ITERATIONS):
            _G_GRID_ACTIVE.set(int(active.sum()))
            for gi in np.flatnonzero(active):
                if ctxs[gi] is not None:
                    ctxs[gi].event("solver_step")
            d2_list = sobj.grid_curvature_list(z_list)

            # -- per-row truncated CG: one shared Hvp feature pass per
            # step; rows past their own stop are frozen by row masks,
            # and the loop runs to the slowest ACTIVE row's depth.
            s = jnp.zeros_like(g)
            r = -g
            d_vec = -g
            rtr = jnp.sum(r * r, axis=-1)
            stop_norm = _CG_XI * jnp.linalg.norm(g, axis=-1)
            cg_done = (np.asarray(
                jnp.linalg.norm(r, axis=-1) <= stop_norm) | ~active)
            k = 0
            while not cg_done.all() and k < max_cg:
                hd = sobj.grid_hessian_vector(d_vec, d2_list, l2s)
                s2, r2, d2v, rtr2, done_dev = _grid_cg_step(
                    s, r, d_vec, rtr, hd, delta, stop_norm)
                run = jnp.asarray(~cg_done)
                s = _grid_select_rows(run, s2, s)
                r = _grid_select_rows(run, r2, r)
                d_vec = _grid_select_rows(run, d2v, d_vec)
                rtr = jnp.where(run, rtr2, rtr)
                cg_done |= (~cg_done) & np.asarray(done_dev)
                k += 1

            active_dev = jnp.asarray(active)
            x_try = _grid_select_rows(active_dev, x + s, x)
            z_try, f_new, g_new = sobj.grid_margins_value_grad(
                x_try, l2s)
            delta_new, accept_dev = _grid_tr_update(
                jnp.asarray(f_h), f_new, g, s, r, delta,
                jnp.asarray(first))
            first = False
            delta = jnp.where(active_dev, delta_new, delta)
            accept = np.asarray(accept_dev) & active

            if accept.any():
                acc_dev = jnp.asarray(accept)
                x = _grid_select_rows(acc_dev, x_try, x)
                g = _grid_select_rows(acc_dev, g_new, g)
                z_list = [jnp.where(acc_dev[:, None], zt, z)
                          for zt, z in zip(z_try, z_list)]
                snorm = np.asarray(jnp.linalg.norm(s, axis=-1))
                f_new_h = np.asarray(f_new)
                gnorm_new = np.asarray(jnp.linalg.norm(g, axis=-1))
                f_delta = np.abs(f_h - f_new_h)
                f_h = np.where(accept, f_new_h, f_h)
                gnorm = np.where(accept, gnorm_new, gnorm)
                its[accept] += 1
                fails[accept] = 0
                for gi in np.flatnonzero(accept):
                    # Watchdog on ACCEPTED rows only — a rejected
                    # non-finite trial is normal trust-region behavior.
                    check_solver_finite(
                        "streaming-tron-grid", int(its[gi]), f_h[gi],
                        gnorm[gi], ctxs[gi], lam=l2_h[gi], grid_row=gi)
                    value_hist[gi, its[gi]] = f_h[gi]
                    gnorm_hist[gi, its[gi]] = gnorm[gi]
                    if coef_hist is not None:
                        coef_hist[gi, its[gi]] = np.asarray(x[gi])
                    if rings[gi] is not None:
                        rings[gi].append(int(its[gi]), f_h[gi],
                                         gnorm[gi], float(snorm[gi]))
                    if gnorm[gi] <= tol_s * gnorm0[gi]:
                        reasons[gi] = ConvergenceReason.GRADIENT_CONVERGED
                    elif f_delta[gi] <= tol_s * f0_scale[gi]:
                        reasons[gi] = (
                            ConvergenceReason.FUNCTION_VALUES_CONVERGED)
                    elif its[gi] >= max_iter:
                        reasons[gi] = ConvergenceReason.MAX_ITERATIONS
                    if reasons[gi] != ConvergenceReason.NOT_CONVERGED:
                        active[gi] = False

            rejected = active & ~accept
            fails[rejected] += 1
            for gi in np.flatnonzero(rejected):
                if fails[gi] > max_improvement_failures:
                    reasons[gi] = (
                        ConvergenceReason.OBJECTIVE_NOT_IMPROVING)
                    active[gi] = False
    _G_GRID_ACTIVE.set(0)

    if margins_out is not None:
        margins_out[:] = z_list
    x_np = np.asarray(x)
    return [
        OptimizerResult(
            x=jnp.asarray(x_np[gi]),
            value=jnp.asarray(f_h[gi]),
            grad_norm=jnp.asarray(gnorm[gi]),
            iterations=jnp.asarray(int(its[gi]), jnp.int32),
            reason=jnp.asarray(int(reasons[gi]), jnp.int32),
            value_history=jnp.asarray(value_hist[gi]),
            grad_norm_history=jnp.asarray(gnorm_hist[gi]),
            coef_history=(None if coef_hist is None
                          else jnp.asarray(coef_hist[gi])),
        )
        for gi in range(G)
    ]


def minimize_tron(
    fun: Callable[..., Array],
    x0: Array,
    args: Tuple[Any, ...] = (),
    *,
    max_iter: int = 15,
    tol: float = 1e-5,
    max_cg: int = 20,
    max_improvement_failures: int = 5,
    lower_bounds: Optional[Array] = None,
    upper_bounds: Optional[Array] = None,
    track_coefficients: bool = False,
    make_hvp: Optional[Callable] = None,
    margins_value_and_grad: Optional[Callable] = None,
) -> OptimizerResult:
    """Minimize twice-differentiable ``fun(x, *args)`` from ``x0``.

    Defaults mirror the reference (maxIter=15, tol=1e-5, <=20 CG iterations,
    <=5 improvement failures; ml/optimization/TRON.scala:258-264).

    The result counts the work besides the accepted iterations:
    ``cg_iterations``, the CG steps (one Hessian-vector product each) of
    every outer iteration, and ``attempted_iterations``, the outer
    iterations run, accepted or rejected (each evaluates one trial point),
    and, with the GLM's product, ``feature_passes``: the reads of the
    matrix, ``2 + attempted + 2 * cg`` where the margins are carried (the
    first margins and gradient; a trial's gradient an outer iteration; a
    matvec and an rmatvec a CG step), ``2 + 3 * attempted + 2 * cg`` with
    ``make_hvp`` alone (a margin pass and the trial's value and gradient
    an outer iteration) and two more an outer iteration with bounds (the
    realized step's product); None without ``make_hvp``. It also keeps the
    last outer iteration's CG: ``cg_point`` (where it ran), ``cg_step`` (the
    step ``s`` it returned) and ``cg_residual`` (the ``r`` it carried, which
    without bounds is ``-g - H s`` by the products it ran), so that the
    product the solve spent its time in can be checked after the fact.

    ``make_hvp(x, *args) -> (v -> H v)``: optional specialized
    Hessian-vector factory, called once per outer iteration (its
    closed-over precomputations hoist out of the inner CG loop). Defaults
    to jvp-of-grad. Must be a STABLE callable (hashed as a static jit
    argument).

    ``margins_value_and_grad(x, *args, z=None) -> (z, f, g)``: optional,
    for a GLM without bounds (``GLMObjective.margins_value_and_grad``): the
    margins at ``x`` (affine in ``x``; made where ``z`` is not given) and
    the value and gradient from them. With it the loop carries the margins
    at its point: ``make_hvp`` is then called with them, ``make_hvp(z,
    *args)`` (``GLMObjective.make_tron_hvp_at_margins``), its product
    returns the direction's margins too, ``v -> (H v, X v)``, and a trial's
    margins are ``z + X s`` from the CG's own products. Projection onto
    bounds is not affine, so bounds take the point's ``make_hvp``.
    """
    x0 = jnp.asarray(x0)
    dtype = x0.dtype
    has_bounds = lower_bounds is not None or upper_bounds is not None
    if margins_value_and_grad is not None and (has_bounds
                                               or make_hvp is None):
        raise ValueError(
            "carried margins need the product from them (make_hvp) and no "
            "bounds: a projected step's margins are not z + X s")
    d = x0.shape[-1]
    lo = (jnp.full((d,), -jnp.inf, dtype) if lower_bounds is None
          else jnp.asarray(lower_bounds, dtype))
    hi = (jnp.full((d,), jnp.inf, dtype) if upper_bounds is None
          else jnp.asarray(upper_bounds, dtype))
    return _minimize_tron_impl(
        fun, x0, args, lo, hi, max_iter=max_iter, tol=tol, max_cg=max_cg,
        max_improvement_failures=max_improvement_failures,
        has_bounds=has_bounds, track_coefficients=track_coefficients,
        make_hvp=make_hvp, margins_value_and_grad=margins_value_and_grad,
    )
