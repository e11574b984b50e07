"""Pallas TPU kernel: the whole per-entity GLM L-BFGS solve fused into one
kernel, entities vectorized along lanes.

The random-effect coordinate solves thousands of tiny independent GLMs
(reference: one Breeze L-BFGS per entity inside a shuffled executor task,
ml/algorithm/RandomEffectCoordinate.scala:104-113). The jnp path runs them
as ONE vmapped masked `lax.while_loop` — correct and portable, but every
XLA op in the loop body is a separate HBM-roundtrip launch: ~50 tiny ops
per L-BFGS iteration, each streaming [E, d]-shaped intermediates to HBM
and back. At bucket sizes the solve is pure launch/bandwidth overhead
(measured: the 100k-entity sweep spent ~185 ms on ~0.1 ms of FLOPs).

This kernel runs the ENTIRE solve — margins, batched-Armijo line search,
two-loop direction, cautious history updates, convergence bookkeeping —
for 128 entities per grid step, with all state resident in VMEM/registers.
The only HBM traffic is one read of the entity block and one write of the
results. Grid steps pipeline across entity tiles. Every loop over the
bucket's r rows is rolled (_row_loop: steps of ROWS_PER_STEP rows), so
the traced body is the same size at any r of two steps or more.

Layout: entities along the 128-lane axis; every array the kernel touches
is 2-D [sublanes, 128] (Mosaic's native vreg shape — 3-D contractions do
not lower). Per grid step the kernel sees
  x rows x_ref[i] [d, 128] (i < r), labels/offsets/weights [r, 128],
  coef0 [d, 128]
and carries state c/g [d, 128], z [r, 128], and the (s, y) history as m
static pairs of [d, 128] arrays. Every reduction is over sublanes (r or
d); nothing crosses lanes, so 128 solves proceed in lockstep with
per-lane `done` masking — the same semantics as the vmapped host solver
(identical convergence reasons and tolerances; all line-search candidates
are priced as one [T, 128] block per row, and the accepted step is the
FIRST Armijo-passing candidate, like optimization/glm_lbfgs.py's batched
search with its tail folded in).

Routing: algorithm/coordinates.py uses this kernel for random-effect
bucket solves on TPU — L-BFGS with L2 (box constraints via projected
trials), OWL-QN for L1/elastic-net, or TRON (trust-region Newton-CG,
twice-differentiable losses, box constraints via projected trust-region
trials + active-set-reduced CG). Per-entity feature normalization folds
into all three modes as a one-time x' = (x - shift).*factor transform
in VMEM. Remaining fallbacks to the vmapped jnp path: oversize-VMEM
buckets and non-TPU backends only. Set PHOTON_ML_TPU_NO_PALLAS=1 to
disable.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optimization.convergence import (
    ConvergenceReason,
    OptimizerResult,
)
from photon_ml_tpu.optimization.owlqn import pseudo_gradient
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.utils.compile_cache import note_kernel_body

Array = jax.Array

LANES = 128
_CAUTIOUS_EPS = 1e-10

# Kernel hyperparameter defaults — shared with the routing guard in
# algorithm/coordinates.py via entity_solver_vmem_bytes so the VMEM
# eligibility estimate can never drift from the kernel's actual working
# set (the dispatch and the guard both read these constants).
DEFAULT_M = 10
DEFAULT_MAX_LINE_SEARCH = 30

# The routing guard (algorithm/coordinates.py) sends a bucket to the
# kernel only while entity_solver_vmem_bytes stays under this.
VMEM_GUARD_BYTES = 10 << 20

# Scoped-VMEM limit handed to Mosaic. Its default on v5e is 16 MiB, and
# the kernel's real working set — the window buffers
# entity_solver_vmem_bytes estimates, plus Mosaic's own stack — ran 2-4x
# that estimate while the row loops were unrolled: at the default the v5e
# compiler refused buckets the routing guard admits (r=256 d=32, r=512
# d=8, r=4 d=512). Half of v5e's 128 MiB VMEM compiles everything
# VMEM_GUARD_BYTES lets through; tests/test_mosaic_aot.py pins that
# boundary with the real compiler and reads the rolled kernel's need.
VMEM_LIMIT_BYTES = 64 << 20

# Rows a chunk of the kernels' row loops handles: one sublane tile of an
# [r, L] block.
ROW_CHUNK = 8
# Rows a step of the rolled row loops covers: ROWS_PER_STEP / ROW_CHUNK
# chunks unrolled inside one step. A step of 8 rows costs the L-BFGS
# kernel ~25% of its time on a v5e (each step pays its reductions'
# latency); at 64 the compiler's schedule keeps L-BFGS and OWL-QN within
# 3% of the unrolled body's bundles (TRON at width 8: +10-15%), and a
# bucket of fewer than 128 rows stays a static unroll.
ROWS_PER_STEP = 64
# [r, L] VMEM scratch blocks a kernel gets: the blocks its row loops
# build or read by chunk of rows. Every kernel's _row_sweeps takes one;
# the L-BFGS/OWL-QN line search's price reads the margins and the
# direction's products from two more, TRON's product its curvature
# weights from one.
_SCRATCH_ROWS = {"lbfgs": 3, "owlqn": 3, "tron": 2}


def entity_solver_vmem_bytes(
    r: int, d: int, itemsize: int, *, m: int = DEFAULT_M,
    max_line_search: int = DEFAULT_MAX_LINE_SEARCH,
    normalized: bool = False, bounded: bool = False,
) -> int:
    """VMEM working-set estimate per 128-entity grid step: the
    double-buffered x tile, 2m history buffers + c/g/direction and
    friends, the [T, 128] line-search block, and the [r, 128] vectors.
    Normalization adds double-buffered factor/shift tiles; bounds add
    lower/upper tiles. Keep callers' eligibility checks on THIS function
    so the guard and the kernel cannot disagree about the working set.
    What Mosaic really allocates is 1.25-1.75x this at the guard's
    extremes (tests/test_mosaic_aot.py; see VMEM_LIMIT_BYTES)."""
    units = 2 * r * d + 2 * m * d + 8 * d + 8 * r + 2 * (max_line_search + 1)
    units += 2  # scalars / slack
    if normalized:
        units += 4 * d
    if bounded:
        units += 4 * d
    return units * LANES * itemsize


class _KState(NamedTuple):
    c: Array  # [d, L]
    z: Array  # [r, L]
    f: Array  # [1, L]
    g: Array  # [d, L]
    s_hist: Tuple[Array, ...]  # m x [d, L], oldest first
    y_hist: Tuple[Array, ...]  # m x [d, L]
    rho: Array  # [m, L]
    count: Array  # [1, L] i32
    it: Array  # [1, L] i32
    reason: Array  # [1, L] i32
    gnorm: Array  # [1, L]
    k: Array  # scalar i32 loop counter


def _count_eqns(jaxpr) -> int:
    """Equations of a jaxpr, those of the jaxprs inside its equations
    (loop bodies, branches) included."""
    return sum(1 + sum(_count_eqns(sub)
                       for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


def _rsum(a):
    """Sublane reduction -> [1, L]."""
    return jnp.sum(a, axis=0, keepdims=True)


def _two_loop(g, s_hist, y_hist, rho, count):
    """Two-loop recursion vectorized over lanes; reductions over sublanes.
    Inside a fused kernel the 4m-deep chain is register work, so the
    compact representation's op-count advantage (lbfgs.py) is moot and
    the recursion's lower arithmetic count wins."""
    m = len(s_hist)
    q = g
    alphas = []
    for j in reversed(range(m)):
        alpha = rho[j:j + 1] * _rsum(s_hist[j] * q)  # [1, L]
        q = q - alpha * y_hist[j]
        alphas.append(alpha)
    alphas.reverse()

    yy = _rsum(y_hist[-1] * y_hist[-1])
    sy = _rsum(s_hist[-1] * y_hist[-1])
    gamma = jnp.where(count > 0, sy / jnp.maximum(yy, _CAUTIOUS_EPS), 1.0)
    rr = gamma * q
    for j in range(m):
        beta = rho[j:j + 1] * _rsum(y_hist[j] * rr)
        rr = rr + (alphas[j] - beta) * s_hist[j]
    return -rr



def _tiered_sweep(sweep, active, init_carry, t1, n_trials):
    """Tier-1 line-search sweep always; the rare tail as a 0/1-trip
    while_loop. Mosaic legalizes neither a vector-valued scf.if
    (lax.cond) nor vector<i1> loop carries (KERNEL.md constraint #6),
    so carry[0] is the found flag as a FLOAT 0/1 mask and the tail
    trigger is a scalar bool. One shared implementation — the pattern
    is subtle enough that its copies drifted once already."""
    carry = sweep(0, t1, init_carry)
    if n_trials > t1:
        need_tail = jnp.any(jnp.logical_and(active, carry[0] <= 0))
        carry = lax.while_loop(
            lambda c: c[0],
            lambda c: (jnp.zeros((), bool),) + sweep(t1, n_trials, c[1:]),
            (need_tail,) + carry)[1:]
    return carry


def _sel(mask, a, b):
    """where(mask, a, b) for a [1, L] bool mask against [k, L] data —
    Mosaic cannot relayout a sublane-replicated select, so use the
    arithmetic form (both branches are finite everywhere this is used)."""
    if a.shape == mask.shape and a.dtype == jnp.int32:
        return jnp.where(mask, a, b)
    m = mask.astype(a.dtype)
    return b + m * (a - b)


def _row_loop(r, chunk, carry):
    """``carry = chunk(start, n, carry)`` over the rows 0..r-1 in order, n
    rows a call, n = ROW_CHUNK but for the last: ``lax.fori_loop`` over
    steps of ROWS_PER_STEP rows (``start`` traced, a multiple of
    ROW_CHUNK, so every [n, L] access is whole sublane tiles), then the
    rows left after the last whole step, static. A traced body holds one
    step's rows whatever r is; a bucket of fewer than two steps' rows is
    a static unroll of its rows."""

    def chunks(start, rows, carry):
        for k in range(0, rows, ROW_CHUNK):
            at = (start + k if isinstance(start, int)
                  else pl.multiple_of(start + k, ROW_CHUNK))
            carry = chunk(at, min(ROW_CHUNK, rows - k), carry)
        return carry

    steps = r // ROWS_PER_STEP
    if steps < 2:
        return chunks(0, r, carry)
    carry = lax.fori_loop(
        0, steps, lambda s, c: chunks(s * ROWS_PER_STEP, ROWS_PER_STEP, c),
        carry)
    return chunks(steps * ROWS_PER_STEP, r - steps * ROWS_PER_STEP, carry)


def _rows_of(ref, start, n):
    """Rows start..start+n-1 of an [r, L] ref."""
    return ref[pl.ds(start, n), :]


def _normalize_rows(r, x_ref, fac, shf):
    """x' = (x - shift) .* factor over the block's r rows, in place in
    ``x_ref`` (this grid step's window of x, which nothing reads again),
    once before any sweep reads a row."""

    def chunk(start, n, carry):
        for j in range(n):
            x_ref[start + j] = (x_ref[start + j] - shf) * fac
        return carry

    _row_loop(r, chunk, ())


def _row_sweeps(r, x_ref, rows_ref):
    """The kernels' two sweeps over the bucket's rows, read from
    ``x_ref`` chunk by chunk. ``products(c)`` is the [r, L] block of
    <x_i, c>, built in ``rows_ref``; ``accumulate(g, u_ref)`` is
    g + sum_i x_i u_i with the rows added in order 0..r-1."""

    def products(c):
        def chunk(start, n, carry):
            rows_ref[pl.ds(start, n), :] = jnp.concatenate(
                [_rsum(x_ref[start + j] * c) for j in range(n)], axis=0)
            return carry

        _row_loop(r, chunk, ())
        return rows_ref[:]

    def accumulate(g, u_ref):
        def chunk(start, n, g):
            u = _rows_of(u_ref, start, n)
            for j in range(n):
                g = g + x_ref[start + j] * u[j:j + 1]
            return g

        return _row_loop(r, chunk, g)

    return products, accumulate


def _make_kernel(loss: PointwiseLoss, *, r: int, max_iter: int, tol: float,
                 m: int, c1: float, max_line_search: int,
                 owlqn: bool = False, normalized: bool = False,
                 bounded: bool = False):
    not_conv = np.int32(int(ConvergenceReason.NOT_CONVERGED))
    shrink = 0.5
    n_trials = max_line_search + 1
    if bounded and owlqn:
        raise ValueError("box constraints with L1 are not supported "
                         "(matching solve_glm)")

    def kernel(l2_ref, l1_ref, x_ref, y_ref, off_ref, w_ref, c0_ref,
               *refs):
        # Optional inputs trail the fixed seven, in declaration order:
        # [factor, shift] when normalized, [lower, upper] when bounded;
        # then the five outputs and the [r, L] scratch (_SCRATCH_ROWS).
        i = 0
        if normalized:
            factor_ref, shift_ref = refs[i], refs[i + 1]
            i += 2
        if bounded:
            lb_ref, ub_ref = refs[i], refs[i + 1]
            i += 2
        (out_c_ref, out_f_ref, out_gnorm_ref, out_it_ref,
         out_reason_ref, rows_ref, z_ref, zp_ref) = refs[i:]

        yv = y_ref[:]  # [r, L]
        off = off_ref[:]
        w = w_ref[:]
        l2 = l2_ref[0]
        l1 = l1_ref[0]
        if normalized:
            # Normalization folds in as a one-time transform of the x
            # block resident in VMEM: x' = (x - shift) .* factor
            # (data/normalization.py's algebra, NormalizationContext.
            # scala:38-83). Everything downstream — margins, gradients,
            # curvature, the line search — is the plain un-normalized
            # kernel on x'. Solve-space coefficients; the coordinate
            # back-transforms outside.
            _normalize_rows(r, x_ref, factor_ref[:], shift_ref[:])
        products, accumulate = _row_sweeps(r, x_ref, rows_ref)
        if bounded:
            lb = lb_ref[:]  # [d, L]
            ub = ub_ref[:]

            def project(c):
                return jnp.minimum(jnp.maximum(c, lb), ub)

        def margins(c):
            return products(c) + off

        def value_from(z, csq):
            return _rsum(w * loss.loss(z, yv)) + 0.5 * l2 * csq

        def grad_from(c, z):
            rows_ref[:] = w * loss.d1(z, yv)  # [r, L]
            return accumulate(l2 * c, rows_ref)

        def pseudo_grad(c, g):
            # optimization/owlqn.py's pseudo_gradient is pure elementwise
            # jnp — the single shared implementation works inside the
            # kernel unchanged (l1 broadcasts from the SMEM scalar).
            return pseudo_gradient(c, g, l1)

        c0 = c0_ref[:]
        if bounded:
            c0 = project(c0)  # host path projects x0 before evaluating
        z0 = margins(c0)
        f0 = value_from(z0, _rsum(c0 * c0))
        if owlqn:
            f0 = f0 + l1 * _rsum(jnp.abs(c0))
        g0 = grad_from(c0, z0)
        conv_g0 = pseudo_grad(c0, g0) if owlqn else g0
        gnorm0 = jnp.sqrt(_rsum(conv_g0 * conv_g0))
        f0_scale = jnp.maximum(jnp.abs(f0), 1e-30)

        # History buffers are initialized as 0*data rather than zeros:
        # a constant-zero carry gets a sublane-REPLICATED Mosaic layout,
        # and the loop body's shift-update (non-replicated) then needs an
        # invalid relayout of a non-singleton dimension.
        state = _KState(
            c=c0, z=z0, f=f0, g=g0,
            s_hist=tuple(c0 * 0.0 for _ in range(m)),
            y_hist=tuple(c0 * 0.0 for _ in range(m)),
            rho=jnp.concatenate([f0 * 0.0 for _ in range(m)], axis=0),
            count=jnp.zeros((1, c0.shape[1]), jnp.int32),
            it=jnp.zeros((1, c0.shape[1]), jnp.int32),
            reason=jnp.where(
                gnorm0 <= 0.0, int(ConvergenceReason.GRADIENT_CONVERGED),
                int(ConvergenceReason.NOT_CONVERGED)).astype(jnp.int32),
            gnorm=gnorm0,
            k=jnp.zeros((), jnp.int32),
        )

        def finish(st, active, ok, c_new, z_new, f_new, g_new,
                   gnorm_new):
            """Shared tail: cautious history update, convergence reasons,
            failed-line-search and frozen-lane masking."""
            s_vec = c_new - st.c
            y_vec = g_new - st.g
            sy = _rsum(s_vec * y_vec)
            s_n = jnp.sqrt(_rsum(s_vec * s_vec))
            y_n = jnp.sqrt(_rsum(y_vec * y_vec))
            store = jnp.logical_and(ok, sy > _CAUTIOUS_EPS * s_n * y_n)
            s_hist = tuple(
                _sel(store, nxt, old) for nxt, old in
                zip(st.s_hist[1:] + (s_vec,), st.s_hist))
            y_hist = tuple(
                _sel(store, nxt, old) for nxt, old in
                zip(st.y_hist[1:] + (y_vec,), st.y_hist))
            rho_shift = jnp.concatenate(
                [st.rho[1:], jnp.where(sy != 0, 1.0 / sy, 0.0)], axis=0)
            rho = _sel(store, rho_shift, st.rho)
            count = jnp.where(store,
                              jnp.minimum(st.count + 1, m), st.count)

            it_new = st.it + 1
            f_delta = jnp.abs(st.f - f_new)
            reason = jnp.where(
                ~ok, int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING),
                jnp.where(
                    gnorm_new <= tol * gnorm0,
                    int(ConvergenceReason.GRADIENT_CONVERGED),
                    jnp.where(
                        f_delta <= tol * f0_scale,
                        int(ConvergenceReason.FUNCTION_VALUES_CONVERGED),
                        jnp.where(it_new >= max_iter,
                                  int(ConvergenceReason.MAX_ITERATIONS),
                                  not_conv)))).astype(jnp.int32)

            # Failed line search must not move the iterate.
            c_new = _sel(ok, c_new, st.c)
            z_new = _sel(ok, z_new, st.z)
            f_new = jnp.where(ok, f_new, st.f)
            g_new = _sel(ok, g_new, st.g)
            gnorm_new = jnp.where(ok, gnorm_new, st.gnorm)

            # Frozen (converged) lanes keep their previous state.
            msk = lambda a, b: (jnp.where(active, a, b)
                                if a.shape == active.shape
                                else _sel(active, a, b))
            return _KState(
                c=msk(c_new, st.c), z=msk(z_new, st.z),
                f=msk(f_new, st.f), g=msk(g_new, st.g),
                s_hist=tuple(msk(a, b)
                             for a, b in zip(s_hist, st.s_hist)),
                y_hist=tuple(msk(a, b)
                             for a, b in zip(y_hist, st.y_hist)),
                rho=msk(rho, st.rho),
                count=msk(count, st.count),
                it=msk(it_new, st.it),
                reason=msk(reason, st.reason),
                gnorm=msk(gnorm_new, st.gnorm),
                k=st.k + 1)

        def body_owlqn(st: _KState) -> _KState:
            """OWL-QN iteration (optimization/owlqn.py semantics):
            pseudo-gradient direction with sign projection, trials
            projected onto the current orthant (margins are NOT affine in
            the step, so every trial re-computes margins — still register
            work), curvature pairs from the smooth gradient only."""
            active = st.reason == not_conv
            pg = pseudo_grad(st.c, st.g)
            direction = _two_loop(pg, st.s_hist, st.y_hist, st.rho,
                                  st.count)
            direction = jnp.where(direction * pg < 0, direction, 0.0)
            degenerate = _rsum(direction * pg) >= 0
            direction = _sel(degenerate, -pg, direction)

            orthant = jnp.where(st.c != 0, jnp.sign(st.c), jnp.sign(-pg))
            first = st.count == 0
            dnorm = jnp.sqrt(_rsum(direction * direction))
            init_step = jnp.where(first,
                                  1.0 / jnp.maximum(dnorm, 1.0), 1.0)

            def trial(t):
                x_t = st.c + t * direction
                x_t = jnp.where(jnp.sign(x_t) == orthant, x_t, 0.0)
                z_t = margins(x_t)
                f_t = (value_from(z_t, _rsum(x_t * x_t))
                       + l1 * _rsum(jnp.abs(x_t)))
                armijo = jnp.logical_and(
                    f_t <= st.f + c1 * _rsum(pg * (x_t - st.c)),
                    jnp.isfinite(f_t))
                return armijo, x_t, z_t, f_t

            def sweep(k_lo, k_hi, carry):
                # The found flag is carried as a FLOAT 0/1 mask, not
                # bool: Mosaic cannot legalize vector<i1> values carried
                # through scf.while/scf.if (KERNEL.md constraint #6 —
                # transient bool masks are fine, loop carries are not).
                foundf, x_acc, z_acc, f_acc = carry
                for k in range(k_lo, k_hi):
                    t = init_step * (shrink ** k)
                    a, x_t, z_t, f_t = trial(t)
                    take = jnp.logical_and(a, foundf <= 0)
                    # 0*inf is NaN in _sel's arithmetic select — an
                    # overflowed (rejected) trial's margins must not
                    # poison the carried accumulator.
                    z_t = jnp.where(jnp.isfinite(z_t), z_t, 0.0)
                    x_acc = _sel(take, x_t, x_acc)
                    z_acc = _sel(take, z_t, z_acc)
                    f_acc = jnp.where(take, f_t, f_acc)
                    foundf = jnp.maximum(foundf,
                                         a.astype(foundf.dtype))
                return foundf, x_acc, z_acc, f_acc

            # zeros_like, NOT st.f * 0.0: an overflowed lane (f = inf)
            # would seed the found-mask with NaN and disable its line
            # search forever. The constant-zero-carry layout hazard
            # (constraint #2) does not apply — the mask reaches the
            # tail while_loop only after tier 1's data-derived updates.
            okf, c_new, z_new, f_new = _tiered_sweep(
                sweep, active, (jnp.zeros_like(st.f), st.c, st.z, st.f),
                min(n_trials, 8), n_trials)
            ok = okf > 0

            g_new = grad_from(c_new, z_new)
            pg_new = pseudo_grad(c_new, g_new)
            gnorm_new = jnp.sqrt(_rsum(pg_new * pg_new))
            return finish(st, active, ok, c_new, z_new, f_new, g_new,
                          gnorm_new)

        def body_bounded(st: _KState) -> _KState:
            """Projected L-BFGS iteration, exactly the host semantics
            (optimization/lbfgs.py:173-229 + OptimizationUtils.scala:53):
            each trial point is clamped onto [lower, upper], Armijo is
            evaluated on the realized (projected) displacement
            <g, x_t - x>, convergence uses the raw gradient norm, and
            curvature pairs come from the projected accepted step.
            Clamping breaks the affine-margin identity, so every trial
            re-computes margins (register work, like OWL-QN's orthant
            projection)."""
            active = st.reason == not_conv
            direction = _two_loop(st.g, st.s_hist, st.y_hist, st.rho,
                                  st.count)
            dg = _rsum(direction * st.g)
            direction = _sel(dg >= 0, -st.g, direction)

            first = st.count == 0
            dnorm = jnp.sqrt(_rsum(direction * direction))
            init_step = jnp.where(first,
                                  1.0 / jnp.maximum(dnorm, 1.0), 1.0)

            def trial(t):
                x_t = project(st.c + t * direction)
                z_t = margins(x_t)
                f_t = value_from(z_t, _rsum(x_t * x_t))
                armijo = jnp.logical_and(
                    f_t <= st.f + c1 * _rsum(st.g * (x_t - st.c)),
                    jnp.isfinite(f_t))
                return armijo, x_t, z_t, f_t

            def sweep(k_lo, k_hi, carry):
                # Float 0/1 found-mask carry — see body_owlqn's sweep
                # (Mosaic cannot carry vector<i1> through scf loops).
                foundf, x_acc, z_acc, f_acc = carry
                for k in range(k_lo, k_hi):
                    t = init_step * (shrink ** k)
                    a, x_t, z_t, f_t = trial(t)
                    take = jnp.logical_and(a, foundf <= 0)
                    z_t = jnp.where(jnp.isfinite(z_t), z_t, 0.0)
                    x_acc = _sel(take, x_t, x_acc)
                    z_acc = _sel(take, z_t, z_acc)
                    f_acc = jnp.where(take, f_t, f_acc)
                    foundf = jnp.maximum(foundf,
                                         a.astype(foundf.dtype))
                return foundf, x_acc, z_acc, f_acc

            # zeros_like init, shared tail — see body_owlqn.
            okf, c_new, z_new, f_new = _tiered_sweep(
                sweep, active, (jnp.zeros_like(st.f), st.c, st.z, st.f),
                min(n_trials, 8), n_trials)
            ok = okf > 0

            g_new = grad_from(c_new, z_new)
            gnorm_new = jnp.sqrt(_rsum(g_new * g_new))
            return finish(st, active, ok, c_new, z_new, f_new, g_new,
                          gnorm_new)

        def body(st: _KState) -> _KState:
            active = st.reason == not_conv  # [1, L]
            direction = _two_loop(st.g, st.s_hist, st.y_hist, st.rho,
                                  st.count)
            dg = _rsum(direction * st.g)
            direction = _sel(dg >= 0, -st.g, direction)

            zp = margins(direction) - off  # [r, L]
            # price() reads both by chunk of rows
            z_ref[:] = st.z
            zp_ref[:] = zp
            xx = _rsum(st.c * st.c)
            xp = _rsum(st.c * direction)
            pp = _rsum(direction * direction)
            gp = _rsum(st.g * direction)

            first = st.count == 0
            init_step = jnp.where(first,
                                  1.0 / jnp.maximum(jnp.sqrt(pp), 1.0), 1.0)

            # Armijo candidates priced as [T, L] blocks, data term
            # accumulated row by row; the accepted step is the FIRST
            # passing candidate — identical to sequential backtracking.
            # TIERED: almost every iteration accepts within the first 8
            # halvings, so the [T1, L] block is computed always and the
            # [T-T1, L] tail only when some active lane failed all of
            # tier 1 (lax.cond — the tail's r-row sweep is the single
            # most expensive block in the kernel).
            def price(ts):
                def chunk(start, n, data_t):
                    zc, zpc = _rows_of(z_ref, start, n), _rows_of(
                        zp_ref, start, n)
                    wc, yc = _rows_of(w_ref, start, n), _rows_of(
                        y_ref, start, n)
                    for j in range(n):
                        z_ti = zc[j:j + 1] + ts * zpc[j:j + 1]  # [T, L]
                        data_t = data_t + wc[j:j + 1] * loss.loss(
                            z_ti, yc[j:j + 1])
                    return data_t

                data_t = _row_loop(r, chunk, jnp.zeros_like(ts))
                csq_t = xx + 2.0 * ts * xp + ts * ts * pp
                f_t = data_t + 0.5 * l2 * csq_t
                armijo = jnp.logical_and(f_t <= st.f + c1 * ts * gp,
                                         jnp.isfinite(f_t))
                # First passing candidate per lane: candidates strictly
                # decrease (ts[0] > ts[1] > ... > 0), so "first" = the
                # MAX passing step — a plain reduction, no scan.
                t_acc = jnp.max(jnp.where(armijo, ts, 0.0), axis=0,
                                keepdims=True)
                hit = jnp.logical_and(armijo, ts == t_acc)
                # Tie-safe: if step underflow ever makes two candidates
                # equal, their f_t are identical too — average instead of
                # summing so the degenerate tie cannot double-count.
                nhit = jnp.maximum(
                    jnp.sum(hit.astype(f_t.dtype), axis=0, keepdims=True),
                    1.0)
                f_acc = jnp.sum(jnp.where(hit, f_t, 0.0), axis=0,
                                keepdims=True) / nhit
                return jnp.any(armijo, axis=0, keepdims=True), t_acc, f_acc

            t1 = min(n_trials, 8)
            shr = jnp.asarray(shrink, st.f.dtype)

            def steps(lo, hi):
                ks = lax.broadcasted_iota(jnp.int32, (hi - lo, 1), 0
                                          ).astype(st.f.dtype)
                # `lo` is a python int (tier boundary): adding it to the
                # float iota keeps st.f's dtype without a host conversion.
                return init_step * jnp.power(shr, ks + lo)

            ok, t_acc, f_new = price(steps(0, t1))
            if n_trials > t1:
                # 0/1-trip while_loop, not lax.cond, and the ok flag
                # rides as a FLOAT 0/1 mask: Mosaic legalizes neither a
                # vector-valued scf.if nor vector<i1> loop carries
                # (KERNEL.md constraint #6).
                need_tail = jnp.any(jnp.logical_and(active, ~ok))

                def with_tail(c):
                    _, okf0, t0, f0 = c
                    ok0 = okf0 > 0
                    ok2, t2, f2 = price(steps(t1, n_trials))
                    okf2 = jnp.maximum(okf0, ok2.astype(okf0.dtype))
                    return (jnp.zeros((), bool), okf2,
                            jnp.where(ok0, t0, t2),
                            jnp.where(ok0, f0, f2))

                _, okf, t_acc, f_new = lax.while_loop(
                    lambda c: c[0], with_tail,
                    (need_tail, ok.astype(st.f.dtype), t_acc, f_new))
                ok = okf > 0

            c_new = st.c + t_acc * direction
            z_new = st.z + t_acc * zp
            g_new = grad_from(c_new, z_new)
            gnorm_new = jnp.sqrt(_rsum(g_new * g_new))
            return finish(st, active, ok, c_new, z_new, f_new, g_new,
                          gnorm_new)

        def cond(st: _KState):
            return jnp.logical_and(st.k < max_iter,
                                   jnp.any(st.reason == not_conv))

        step = (body_owlqn if owlqn
                else body_bounded if bounded else body)
        final = lax.while_loop(cond, step, state)

        out_c_ref[:] = final.c
        out_f_ref[:] = final.f
        out_gnorm_ref[:] = final.gnorm
        out_it_ref[:] = final.it
        out_reason_ref[:] = final.reason

    return kernel



def _make_tron_kernel(loss: PointwiseLoss, *, r: int, max_iter: int,
                      tol: float, max_cg: int = 20,
                      max_improvement_failures: int = 5,
                      normalized: bool = False, bounded: bool = False):
    """TRON (trust-region Newton-CG) per-entity kernel — the same
    LIBLINEAR rules as optimization/tron.py (sigma/eta constants, radius
    interpolation, improvement-failure budget), vectorized over lanes
    with a nested masked CG while-loop. The Gauss-Newton product uses
    margin-cached curvature weights computed once per outer iteration:
    Hv = X^T (d2w * (X v)) + l2 v — one r-row sweep per CG step.
    Normalization folds in as the same one-time x' = (x - shift).*factor
    transform as the L-BFGS kernel (margins, gradients and Hv all see
    x'). Box constraints mirror optimization/tron.py's projected variant
    (and the reference's per-step hypercube projection, TRON.scala:228):
    the trial point is clamped onto [lower, upper], CG runs in the
    active-set-reduced free subspace, predicted reduction is the
    quadratic model on the REALIZED (projected) step, and stationarity
    is the projected-gradient norm ||x - P(x - g)||."""
    not_conv = np.int32(int(ConvergenceReason.NOT_CONVERGED))
    ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
    SIG1, SIG2, SIG3 = 0.25, 0.5, 4.0
    CG_XI = 0.1

    def kernel(l2_ref, l1_ref, x_ref, y_ref, off_ref, w_ref, c0_ref,
               *refs):
        del l1_ref  # TRON is L2-only (solve_glm rejects L1+TRON)
        i = 0
        if normalized:
            factor_ref, shift_ref = refs[i], refs[i + 1]
            i += 2
        if bounded:
            lb_ref, ub_ref = refs[i], refs[i + 1]
            i += 2
        (out_c_ref, out_f_ref, out_gnorm_ref, out_it_ref,
         out_reason_ref, rows_ref, d2w_ref) = refs[i:]
        yv = y_ref[:]
        off = off_ref[:]
        w = w_ref[:]
        l2 = l2_ref[0]
        if normalized:
            _normalize_rows(r, x_ref, factor_ref[:], shift_ref[:])
        products, accumulate = _row_sweeps(r, x_ref, rows_ref)
        if bounded:
            lb = lb_ref[:]  # [d, L]
            ub = ub_ref[:]

            def project(c):
                return jnp.minimum(jnp.maximum(c, lb), ub)

        def margins(c):
            return products(c) + off

        def value_from(z, csq):
            return _rsum(w * loss.loss(z, yv)) + 0.5 * l2 * csq

        def grad_from(c, z):
            rows_ref[:] = w * loss.d1(z, yv)
            return accumulate(l2 * c, rows_ref)

        def stat_norm(c, g):
            # Stationarity: raw gradient norm unconstrained, projected-
            # gradient norm ||c - P(c - g)|| with bounds (tron.py's
            # proj_grad_norm).
            if not bounded:
                return jnp.sqrt(_rsum(g * g))
            pg = c - project(c - g)
            return jnp.sqrt(_rsum(pg * pg))

        c0 = c0_ref[:]
        if bounded:
            c0 = project(c0)  # host path projects x0 before evaluating
        z0 = margins(c0)
        f0 = value_from(z0, _rsum(c0 * c0))
        g0 = grad_from(c0, z0)
        gnorm0 = stat_norm(c0, g0)
        f0_scale = jnp.maximum(jnp.abs(f0), 1e-30)

        # (c, z, f, g, delta, it, fails, reason, gnorm, first, k)
        state = (c0, z0, f0, g0, gnorm0,
                 jnp.zeros((1, c0.shape[1]), jnp.int32),
                 jnp.zeros((1, c0.shape[1]), jnp.int32),
                 jnp.where(gnorm0 <= 0.0,
                           int(ConvergenceReason.GRADIENT_CONVERGED),
                           not_conv).astype(jnp.int32),
                 gnorm0,
                 jnp.ones((1, c0.shape[1]), jnp.int32),
                 jnp.zeros((), jnp.int32))

        def body(st):
            (c, z, f, g, delta, it, fails, reason, gnorm, first, k) = st
            active = reason == not_conv

            # Curvature weights once per outer iteration (margin-cached).
            d2w_ref[:] = w * loss.d2(z, yv)  # [r, L]

            def hvp(v):
                # One sweep: a chunk's products <x_i, v>, weighted, then
                # added back row by row (the same operations, in the same
                # order, as a sweep of products and then one of sums).
                def chunk(start, n, hv):
                    x = [x_ref[start + j] for j in range(n)]
                    u = _rows_of(d2w_ref, start, n) * jnp.concatenate(
                        [_rsum(xj * v) for xj in x], axis=0)
                    for j in range(n):
                        hv = hv + x[j] * u[j:j + 1]
                    return hv

                return _row_loop(r, chunk, l2 * v)

            if bounded:
                # Active-set reduction (tron.py:174-188): coordinates
                # pinned at a bound with the gradient pushing outward are
                # frozen; CG runs in the free subspace so the Newton
                # model isn't polluted by directions the projection will
                # clip anyway. [d, L] elementwise mask — no cross-lane
                # or relayout traffic.
                eps = 1e-12
                pinned = jnp.logical_or(
                    jnp.logical_and(c <= lb + eps, g > 0),
                    jnp.logical_and(c >= ub - eps, g < 0))
                free = 1.0 - pinned.astype(c.dtype)
                g_cg = g * free

                def hvp_cg(v):
                    return free * hvp(free * v)
            else:
                g_cg = g
                hvp_cg = hvp

            # Steihaug-Toint truncated CG, per-lane masked (mirrors
            # _truncated_cg in optimization/tron.py).
            stop_norm = CG_XI * jnp.sqrt(_rsum(g_cg * g_cg))

            def cg_body(cg):
                # The done flag rides as a FLOAT 0/1 mask — Mosaic
                # cannot legalize vector<i1> loop carries (KERNEL.md
                # constraint #6); bools stay transient inside the body.
                s, rres, dvec, rtr, kk, donef = cg
                done = donef > 0
                hd = hvp_cg(dvec)
                dhd = _rsum(dvec * hd)
                alpha = rtr / jnp.where(dhd > 0, dhd, 1.0)
                s_try = s + alpha * dvec
                crossed = jnp.logical_or(
                    _rsum(s_try * s_try) > delta * delta, dhd <= 0)
                std = _rsum(s * dvec)
                dd = _rsum(dvec * dvec)
                ss = _rsum(s * s)
                gap = jnp.maximum(delta * delta - ss, 0.0)
                rad = jnp.sqrt(jnp.maximum(std * std + dd * gap, 0.0))
                tau = jnp.where(std >= 0,
                                gap / jnp.maximum(std + rad, 1e-30),
                                (rad - std) / jnp.maximum(dd, 1e-30))
                step = jnp.where(crossed, tau, alpha)
                s_new = s + step * dvec
                r_new = rres - step * hd
                rtr_new = _rsum(r_new * r_new)
                beta = rtr_new / jnp.maximum(rtr, 1e-30)
                d_new = r_new + beta * dvec
                done_new = jnp.logical_or(
                    crossed, jnp.sqrt(rtr_new) <= stop_norm)
                sel2 = lambda a, b: _sel(done, b, a)  # frozen lanes keep b
                return (sel2(s_new, s), sel2(r_new, rres),
                        sel2(d_new, dvec), jnp.where(done, rtr, rtr_new),
                        kk + 1,
                        jnp.maximum(donef,
                                    done_new.astype(donef.dtype)))

            def cg_cond(cg):
                return jnp.logical_and(cg[4] < max_cg,
                                       jnp.any(cg[5] <= 0))

            # Frozen (converged) lanes start CG done — their results are
            # discarded by the outer mask, so running their Hv sweeps
            # would only stretch the lockstep loop for the whole group.
            cg0 = (g_cg * 0.0, -g_cg, -g_cg, _rsum(g_cg * g_cg),
                   jnp.zeros((), jnp.int32),
                   jnp.logical_or(~active,
                                  jnp.sqrt(_rsum(g_cg * g_cg))
                                  <= stop_norm).astype(g.dtype))
            s, rres, *_ = lax.while_loop(cg_cond, cg_body, cg0)

            if bounded:
                # Clamp the trial and evaluate the quadratic model on
                # the REALIZED step (tron.py:192-202): the projection
                # changed the step, so the CG residual identity no
                # longer prices it — one extra Hv on s_real instead.
                c_try = project(c + s)
                s_real = c_try - c
            else:
                c_try = c + s
                s_real = s
            z_try = margins(c_try)
            f_new = value_from(z_try, _rsum(c_try * c_try))
            g_new = grad_from(c_try, z_try)

            gs = _rsum(g * s_real)
            if bounded:
                prered = -(gs + 0.5 * _rsum(s_real * hvp(s_real)))
            else:
                prered = -0.5 * (gs - _rsum(s * rres))
            actred = f - f_new
            snorm = jnp.sqrt(_rsum(s_real * s_real))

            delta_n = jnp.where(first > 0, jnp.minimum(delta, snorm), delta)
            denom = f_new - f - gs
            alpha_i = jnp.where(
                denom <= 0, SIG3,
                jnp.maximum(SIG1, -0.5 * (gs / jnp.maximum(denom, 1e-30))))
            alpha_s = alpha_i * snorm
            delta_n = jnp.where(
                actred < ETA0 * prered,
                jnp.minimum(jnp.maximum(alpha_i, SIG1) * snorm,
                            SIG2 * delta_n),
                jnp.where(
                    actred < ETA1 * prered,
                    jnp.maximum(SIG1 * delta_n,
                                jnp.minimum(alpha_s, SIG2 * delta_n)),
                    jnp.where(
                        actred < ETA2 * prered,
                        jnp.maximum(SIG1 * delta_n,
                                    jnp.minimum(alpha_s, SIG3 * delta_n)),
                        jnp.maximum(delta_n,
                                    jnp.minimum(alpha_s, SIG3 * delta_n)))))

            accept = jnp.logical_and(actred > ETA0 * prered,
                                     jnp.isfinite(f_new))
            it_n = it + jnp.where(accept, 1, 0).astype(jnp.int32)
            fails_n = jnp.where(accept, 0, fails + 1).astype(jnp.int32)

            # Sanitize non-finite trial values before the arithmetic
            # keep-old selects: _sel computes b + m*(a-b), and 0*inf is
            # NaN — an overflowed rejected trial must not poison the
            # retained iterate (the vmapped path's jnp.where is immune;
            # a rejected lane never accepts these zeros).
            z_try = jnp.where(jnp.isfinite(z_try), z_try, 0.0)
            g_new = jnp.where(jnp.isfinite(g_new), g_new, 0.0)
            f_new = jnp.where(jnp.isfinite(f_new), f_new, 0.0)

            c_acc = _sel(accept, c_try, c)
            z_acc = _sel(accept, z_try, z)
            f_acc = jnp.where(accept, f_new, f)
            g_acc = _sel(accept, g_new, g)
            gnorm_acc = stat_norm(c_acc, g_acc)
            f_delta = jnp.abs(f - f_acc)

            reason_n = jnp.where(
                fails_n > max_improvement_failures,
                int(ConvergenceReason.OBJECTIVE_NOT_IMPROVING),
                jnp.where(
                    jnp.logical_and(accept, gnorm_acc <= tol * gnorm0),
                    int(ConvergenceReason.GRADIENT_CONVERGED),
                    jnp.where(
                        jnp.logical_and(accept, f_delta <= tol * f0_scale),
                        int(ConvergenceReason.FUNCTION_VALUES_CONVERGED),
                        jnp.where(it_n >= max_iter,
                                  int(ConvergenceReason.MAX_ITERATIONS),
                                  not_conv)))).astype(jnp.int32)

            msk = lambda a, b: (jnp.where(active, a, b)
                                if a.shape == active.shape
                                else _sel(active, a, b))
            return (msk(c_acc, c), msk(z_acc, z), msk(f_acc, f),
                    msk(g_acc, g), msk(delta_n, delta), msk(it_n, it),
                    msk(fails_n, fails), msk(reason_n, reason),
                    msk(gnorm_acc, gnorm),
                    msk(jnp.zeros_like(first), first), k + 1)

        def cond(st):
            # Outer trip bound: every non-accepted iteration burns one of
            # max_improvement_failures+1 budget, so the host's unbounded
            # while terminates within this many trips.
            trips = max_iter * (max_improvement_failures + 2)
            return jnp.logical_and(st[10] < trips,
                                   jnp.any(st[7] == not_conv))

        final = lax.while_loop(cond, body, state)
        out_c_ref[:] = final[0]
        out_f_ref[:] = final[2]
        out_gnorm_ref[:] = final[8]
        out_it_ref[:] = final[5]
        out_reason_ref[:] = final[7]

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("loss", "max_iter", "tol", "m", "c1",
                     "max_line_search", "mode", "interpret"))
def pallas_entity_lbfgs(
    loss: PointwiseLoss,
    x: Array,  # [E, r, d]
    labels: Array,  # [E, r]
    offsets: Array,  # [E, r]
    weights: Array,  # [E, r]
    coef0: Array,  # [E, d]
    l2_weight,
    l1_weight=0.0,
    factors: Optional[Array] = None,  # [E, d] normalization factors
    shifts: Optional[Array] = None,   # [E, d] normalization shifts
    lower: Optional[Array] = None,    # [E, d] box lower bounds
    upper: Optional[Array] = None,    # [E, d] box upper bounds
    *,
    max_iter: int = 100,
    tol: float = 1e-7,
    m: int = DEFAULT_M,
    c1: float = 1e-4,
    max_line_search: int = DEFAULT_MAX_LINE_SEARCH,
    mode: str = "lbfgs",
    interpret: bool = False,
) -> OptimizerResult:
    """Batched per-entity GLM solve via the fused Pallas kernel.
    ``mode``: "lbfgs" (L2), "owlqn" (elastic net — l1_weight applies),
    or "tron" (trust-region Newton-CG, L2, reference defaults for the
    CG budget).

    ``factors``/``shifts`` fold per-entity feature normalization into
    the kernel (x' = (x - shift) .* factor computed once in VMEM;
    NormalizationContext.scala:38-83 semantics). Coefficients in and out
    are in the SOLVE (normalized) space — callers own the model-space
    transforms. ``lower``/``upper`` activate projected L-BFGS or
    projected TRON ("lbfgs"/"tron" modes; rejected with OWL-QN like
    solve_glm) and clamp the solve-space iterate directly — the
    reference's exact constraint semantics (its projected Breeze iterate
    is the normalized-space vector, LBFGS.scala:77; TRON projects each
    trust-region trial onto the hypercube, TRON.scala:228) and the same
    trial projection as optimization/{lbfgs,tron}.py. Returns an
    OptimizerResult with [E]-leading leaves (value / gradient-norm
    histories are not tracked on this path — None)."""
    e, r, d = x.shape
    dtype = x.dtype
    ep = -(-e // LANES) * LANES
    pad = ep - e

    normalized = factors is not None or shifts is not None
    bounded = lower is not None or upper is not None
    if bounded and mode == "owlqn":
        raise ValueError(
            "box constraints with L1 are not supported (matching solve_glm)")

    def to_lanes(a, trail):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return jnp.moveaxis(a, 0, -1).reshape(trail + (ep,))

    x_l = to_lanes(x, (r, d))
    y_l = to_lanes(labels.astype(dtype), (r,))
    off_l = to_lanes(offsets.astype(dtype), (r,))
    w_l = to_lanes(weights.astype(dtype), (r,))  # pad weights are 0
    c0_l = to_lanes(coef0.astype(dtype), (d,))
    extra_inputs = []
    if normalized:
        fac = (jnp.ones((e, d), dtype) if factors is None
               else factors.astype(dtype))
        shf = (jnp.zeros((e, d), dtype) if shifts is None
               else shifts.astype(dtype))
        # Padding lanes: factor 1 keeps x' = x = 0 there (jnp.pad default
        # 0 for the shift, but the factor tile must pad with 1s so no
        # 0*inf appears if bounds are infinite).
        extra_inputs += [
            jnp.pad(jnp.moveaxis(fac, 0, -1), ((0, 0), (0, pad)),
                    constant_values=1.0),
            jnp.pad(jnp.moveaxis(shf, 0, -1), ((0, 0), (0, pad))),
        ]
    if bounded:
        lo = (jnp.full((e, d), -jnp.inf, dtype) if lower is None
              else lower.astype(dtype))
        hi = (jnp.full((e, d), jnp.inf, dtype) if upper is None
              else upper.astype(dtype))
        extra_inputs += [
            jnp.pad(jnp.moveaxis(lo, 0, -1), ((0, 0), (0, pad)),
                    constant_values=-jnp.inf),
            jnp.pad(jnp.moveaxis(hi, 0, -1), ((0, 0), (0, pad)),
                    constant_values=jnp.inf),
        ]

    if mode == "tron":
        kernel = _make_tron_kernel(loss, r=r, max_iter=max_iter, tol=tol,
                                   normalized=normalized, bounded=bounded)
    elif mode in ("lbfgs", "owlqn"):
        kernel = _make_kernel(loss, r=r, max_iter=max_iter, tol=tol, m=m,
                              c1=c1, max_line_search=max_line_search,
                              owlqn=mode == "owlqn", normalized=normalized,
                              bounded=bounded)
    else:
        raise ValueError(f"unknown mode {mode!r}: "
                         "expected lbfgs | owlqn | tron")
    grid = (ep // LANES,)

    def bspec(*trail):
        return pl.BlockSpec(trail + (LANES,),
                            lambda i: (0,) * len(trail) + (i,),
                            memory_space=pltpu.VMEM)

    name = scopes.KERNEL if mode == "lbfgs" else f"{scopes.KERNEL}_{mode}"
    out_shapes = (
        jax.ShapeDtypeStruct((d, ep), dtype),   # coef
        jax.ShapeDtypeStruct((1, ep), dtype),   # value
        jax.ShapeDtypeStruct((1, ep), dtype),   # grad norm
        jax.ShapeDtypeStruct((1, ep), jnp.int32),  # iterations
        jax.ShapeDtypeStruct((1, ep), jnp.int32),  # reason
    )
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # l2 scalar
            pl.BlockSpec(memory_space=pltpu.SMEM),  # l1 scalar
            bspec(r, d), bspec(r), bspec(r), bspec(r), bspec(d),
        ] + [bspec(d) for _ in extra_inputs],
        out_specs=(bspec(d), bspec(1), bspec(1), bspec(1), bspec(1)),
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM((r, LANES), dtype)] * _SCRATCH_ROWS[mode],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        # The device trace's event for this kernel: the benchmark sums
        # the operations whose name starts with scopes.KERNEL (a mode
        # suffix may follow it, another prefix may not).
        name=name,
    )
    args = (jnp.asarray(l2_weight, dtype).reshape(1),
            jnp.asarray(l1_weight, dtype).reshape(1),
            x_l, y_l, off_l, w_l, c0_l, *extra_inputs)
    # The kernel body is traced once, here; the call is then bound from
    # that trace, and the compile ledger reads the body's size off it.
    # Every driver's metrics.json carries that ledger (``compile``), so a
    # set-up that grows can be told from a body that grew with its rows
    # again: a body's trace time follows its equations.
    traced = jax.make_jaxpr(call)(*args)
    (body,) = [eqn.params["jaxpr"] for eqn in traced.jaxpr.eqns
               if eqn.primitive.name == "pallas_call"]
    note_kernel_body(name, variant=mode + "+norm" * normalized
                     + "+bounds" * bounded, rows=r, width=d,
                     eqns=_count_eqns(body))
    c_l, f_l, gn_l, it_l, reason_l = jax.core.eval_jaxpr(
        traced.jaxpr, traced.consts, *args)

    return OptimizerResult(
        x=jnp.moveaxis(c_l, -1, 0)[:e],
        value=f_l[0, :e],
        grad_norm=gn_l[0, :e],
        iterations=it_l[0, :e],
        reason=reason_l[0, :e],
        value_history=None,
        grad_norm_history=None,
        coef_history=None,
    )
