"""Operations and bytes the algorithm needs, from shapes; and the peaks.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick. The model is the work a pass NEEDS at the true sizes, not
what the program happens to move: padding, re-layouts and recomputation
count against the program.
"""

from __future__ import annotations

ITEM = 4  # float32


def peaks_of(ctx: dict) -> dict:
    """The chip's peaks by ``device_kind``; an unknown kind is an error."""
    kind = ctx["device"]["kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json: "
                       "add the chip with its source, do not default")
    return ctx["peaks"][kind]


def uses_tron(coordinate: dict) -> bool:
    """Whether a coordinate's optimizer string names TRON, whose CG steps
    the program does not report: its passes cannot be counted."""
    return "TRON" in coordinate["optimizer"].upper()


def value_and_grad_flops(rows: float, d: int) -> float:
    """One value-and-gradient of a dense GLM: X.c (2 r d) and X^T.u
    (2 r d); the pointwise loss is left out."""
    return 4.0 * rows * d


def fe_iteration_bytes(n: int, d: int) -> float:
    """One solver iteration of the dense fixed effect reads X twice: once
    for the direction's margins, once for the gradient; the gradient
    needs the margins first, so one read cannot serve both."""
    return 2.0 * n * d * ITEM


def re_sweep_bytes(buckets) -> float:
    """One sweep of the random effect reads every block once (features,
    labels, offsets, weights) and writes the coefficients."""
    total = 0.0
    for e, r, d in buckets:
        total += e * r * d * ITEM + 3 * e * r * ITEM + e * d * ITEM
    return total
