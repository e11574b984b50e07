"""Convergence reasons and optimizer results.

Semantics mirror the reference's Optimizer template
(ml/optimization/Optimizer.scala:156-170, ml/util/ConvergenceReason.scala):
an optimizer stops when
  - iteration count hits max_iter                        -> MAX_ITERATIONS
  - |f_k - f_{k-1}| <= tol * |f_0|                       -> FUNCTION_VALUES_CONVERGED
  - ||g_k|| <= tol * ||g_0||                             -> GRADIENT_CONVERGED
  - the line search / trust region cannot improve        -> OBJECTIVE_NOT_IMPROVING

Reasons are small ints so they live inside jitted state and vmap lanes.
"""

from __future__ import annotations

import dataclasses
import enum
import math
import threading
from collections import deque
from typing import Optional

import jax

Array = jax.Array


class ConvergenceRing:
    """Bounded per-outer-iteration solver history ring (loss, gradient
    norm, accepted step size) — the live-observable complement of
    :class:`OptimizerResult`'s padded history arrays.

    The host-driven streaming solvers (optimization/glm_lbfgs.py /
    tron.py ``convergence_ring=``) append one entry per outer iteration
    as it happens, so a multi-hour ``--stream-train --distmon`` run's
    /distz shows each λ-grid point's convergence tail LIVE; the fused
    ``lax.while_loop`` solvers cannot (no host callbacks mid-solve) and
    get their rings populated post-hoc from the result histories
    (data/distmon.py ``ring_from_history`` — ``step`` is None there).
    Bounded: only the newest ``capacity`` entries are retained
    (``recorded`` counts all appends). Lock-guarded: the solver thread
    appends while scrape threads snapshot."""

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.recorded = 0
        self._entries: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()

    def append(self, iteration: int, value, grad_norm,
               step=None) -> None:
        entry = {
            "iteration": int(iteration),
            "value": float(value),
            "grad_norm": float(grad_norm),
            "step": None if step is None else float(step),
        }
        with self._lock:
            self.recorded += 1
            self._entries.append(entry)

    def snapshot(self) -> dict:
        with self._lock:
            return {"capacity": self.capacity,
                    "recorded": self.recorded,
                    "tail": [dict(e) for e in self._entries]}


def check_solver_finite(solver: str, iteration: int, value, grad_norm,
                        trace_ctx=None, *, lam=None,
                        grid_row=None) -> None:
    """Divergence watchdog for the host-driven streaming solvers: raise
    :class:`SolverDivergedError` when loss or gradient norm went
    non-finite. ``value``/``grad_norm`` must already be HOST scalars
    (the streamed outer loops hold them for convergence compares, so
    the check adds no device sync). ``trace_ctx`` — the solve's trace
    context, finished as ``diverged`` (tail-kept) and its id attached
    to the fault so the flight dump is tagged with it. The batched
    λ-grid solvers pass ``lam``/``grid_row`` so the fault names the ONE
    grid row that went non-finite (row-isolated divergence — the other
    rows' masks are untouched when the caller handles the fault)."""
    v, g = float(value), float(grad_norm)
    if math.isfinite(v) and math.isfinite(g):
        return
    trace_id = None
    if trace_ctx is not None:
        trace_id = trace_ctx.trace_id
        trace_ctx.annotate(solver=solver, iteration=int(iteration),
                           value=v, grad_norm=g)
        if lam is not None:
            trace_ctx.annotate(reg_weight=float(lam))
        if grid_row is not None:
            trace_ctx.annotate(grid_row=int(grid_row))
        trace_ctx.finish("diverged")
    raise SolverDivergedError(solver, iteration, v, g, trace_id=trace_id,
                              lam=lam, grid_row=grid_row)


class SolverDivergedError(RuntimeError):
    """A host-driven streaming solver observed a non-finite loss or
    gradient norm — the divergence watchdog's typed fault.

    The fused ``lax.while_loop`` solvers cannot raise mid-solve (a NaN
    silently rides the history arrays to a convergence-failure reason);
    the streamed L-BFGS/TRON outer loops run on the HOST, so they check
    every outer iteration and fail fast with the evidence attached:
    which solver, which iteration, the offending value/grad-norm, and
    the solve's trace_id (telemetry/tracectx.py) so the driver's flight
    dump — which this fault triggers like any other unhandled driver
    exception — is tagged with a resolvable timeline."""

    def __init__(self, solver: str, iteration: int, value, grad_norm,
                 trace_id: Optional[str] = None, lam=None, grid_row=None):
        where = ""
        if grid_row is not None:
            where = f" [grid row {int(grid_row)}"
            if lam is not None:
                where += f", l2={float(lam)!r}"
            where += "]"
        elif lam is not None:
            where = f" [l2={float(lam)!r}]"
        super().__init__(
            f"{solver} diverged at outer iteration {iteration}{where}: "
            f"value={value!r}, grad_norm={grad_norm!r} (non-finite). "
            "Typical causes: learning-rate/regularization far off scale, "
            "corrupt feature values, or an overflowing loss; see the "
            "flight dump for the solve's last stages"
            + (f" (trace {trace_id})" if trace_id else ""))
        self.solver = solver
        self.iteration = int(iteration)
        self.value = value
        self.grad_norm = grad_norm
        self.trace_id = trace_id
        # Batched λ-grid provenance: the ONE row that diverged (other
        # rows' masks are not poisoned — the caller may drop the row and
        # continue, or fail the sweep with this evidence attached).
        self.lam = None if lam is None else float(lam)
        self.grid_row = None if grid_row is None else int(grid_row)


class ConvergenceReason(enum.IntEnum):
    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_CONVERGED = 2
    GRADIENT_CONVERGED = 3
    OBJECTIVE_NOT_IMPROVING = 4

    @property
    def summary(self) -> str:
        return {
            ConvergenceReason.NOT_CONVERGED: "not converged",
            ConvergenceReason.MAX_ITERATIONS: "max iterations reached",
            ConvergenceReason.FUNCTION_VALUES_CONVERGED:
                "objective function values converged",
            ConvergenceReason.GRADIENT_CONVERGED: "gradient converged",
            ConvergenceReason.OBJECTIVE_NOT_IMPROVING:
                "objective is not improving",
        }[self]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class OptimizerResult:
    """Solution + telemetry. Fully array-valued, so it vmaps/shards cleanly.

    The per-iteration ``value_history``/``grad_norm_history`` arrays (padded
    to max_iter+1, valid up to ``iterations``) are the TPU replacement for the
    reference's OptimizationStatesTracker ring
    (ml/optimization/OptimizationStatesTracker.scala).
    """

    x: Array
    value: Array
    grad_norm: Array
    iterations: Array  # i32
    reason: Array  # i32, a ConvergenceReason value
    value_history: Array
    grad_norm_history: Array
    # Per-iteration coefficient snapshots [max_iter+1, d], recorded only when
    # the solver was asked to track them (the reference's ModelTracker state,
    # ml/supervised/model/ModelTracker.scala). None otherwise.
    coef_history: Optional[Array] = None
    # What a trust-region solve did besides its accepted iterations
    # (optimization/tron.py): the inner CG steps summed over its outer
    # iterations, and the outer iterations it ran, accepted or rejected.
    # None from the solvers that do not count them (L-BFGS, OWL-QN, the
    # streamed and the batched-grid TRON).
    cg_iterations: Optional[Array] = None  # i32
    attempted_iterations: Optional[Array] = None  # i32
    # The fused TRON's last outer iteration's CG: the point it ran at, the
    # step s it returned and the residual r it carried (-g - H s by the
    # Hessian-vector products it ran; over the free coordinates with
    # bounds). None from the other solvers.
    cg_point: Optional[Array] = None
    cg_step: Optional[Array] = None
    cg_residual: Optional[Array] = None
    # The fused TRON's reads of the feature matrix, with the GLM's product:
    # 2 + attempted + 2 * cg where it carries the margins (optimization/
    # tron.py). None from the other solvers and the jvp-of-grad product.
    feature_passes: Optional[Array] = None  # i32

    @property
    def converged(self) -> Array:
        return self.reason != int(ConvergenceReason.NOT_CONVERGED)

    def reason_enum(self) -> ConvergenceReason:
        return ConvergenceReason(int(self.reason))

    def tree_flatten(self):
        return (
            self.x, self.value, self.grad_norm, self.iterations, self.reason,
            self.value_history, self.grad_norm_history, self.coef_history,
            self.cg_iterations, self.attempted_iterations,
            self.cg_point, self.cg_step, self.cg_residual,
            self.feature_passes,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)
