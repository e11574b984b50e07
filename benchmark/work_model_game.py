"""Operations and bytes a full GAME fit needs, from shapes and from the
iteration counts the program reports (``jobs/cd_fit_game.py``'s counters).

As ``work_model.py``: the work a solve NEEDS at the true sizes (true rows,
true widths d and k), not what the program moves; padding, re-layouts and
recomputation count against the program. One value-and-gradient a solver
iteration, as ``fit_mfu`` counts.
"""

from __future__ import annotations

from benchmark.work_model import ITEM, value_and_grad_flops


def projection_flops(rows: float, d: int, k: int) -> float:
    """``x B^T`` for every true row: the latent features of one
    alternation."""
    return 2.0 * rows * d * k


def refit_value_and_grad_flops(rows: float, d: int, k: int) -> float:
    """One value-and-gradient of the refit of B over rows whose features
    are ``gamma (x) x``: the margins ``(x B^T) . gamma`` (2 r d k) and the
    gradient ``sum u gamma x^T`` (2 r d k)."""
    return 4.0 * rows * d * k


def refit_iteration_bytes(rows: float, d: int, k: int) -> float:
    """One solver iteration of the refit reads every row's features and
    factors twice: once for the direction's margins, once for the
    gradient (``work_model.fe_iteration_bytes``'s reasoning)."""
    return 2.0 * rows * (d + k) * ITEM


def job_flops(counters: dict) -> float:
    """FLOPs of one job, every coordinate's value-and-gradients at the
    iterations its trackers report."""
    n = counters["n_rows"]
    total = value_and_grad_flops(n, counters["d_fixed"]) * counters[
        "fe_iterations"]
    for re in counters["re"].values():
        d = counters["groups"][re["group"]]["d"]
        total += value_and_grad_flops(re["row_iterations"], d)
    for mf in counters["mf"].values():
        d, k = counters["groups"][mf["group"]]["d"], mf["factors"]
        total += mf["alternations"] * projection_flops(n, d, k)
        total += sum(value_and_grad_flops(ri, k)
                     for ri in mf["latent_row_iterations"])
        total += sum(refit_value_and_grad_flops(n, d, k) * it
                     for it in mf["refit_iterations"])
    return total
