"""Operations and bytes a sparse GLM product NEEDS, from shapes alone.

Kept with the benchmark, beside ``work_model.py`` (whose peaks and helpers
it uses), so that no PR that claims a gain can change the yardstick. The
model reads the matrix' true shape (the non-zeros, the rows, the columns)
and never the layout that implements it: padding, a second copy, an index
list the program keeps, a re-layout each product, all count against the
program, and the roofline reads the same work whatever runs it.
"""

from __future__ import annotations

ITEM = 4  # float32 values, int32 column ids


def product_flops(nnz: float) -> float:
    """One ``X.w`` or ``X^T.u``: a multiply and an add a non-zero."""
    return 2.0 * nnz


def product_bytes(nnz: float, n: int, d: int) -> float:
    """One product reads every non-zero's value and column id once, and
    reads or writes one n-vector and one d-vector."""
    return 2.0 * ITEM * nnz + ITEM * (n + d)


def shape_of(config: dict):
    """``(nnz, n, d)`` of a configuration of the ``sparse_glm`` recipe."""
    n = int(config["n_rows"])
    fixed = config["fixed"]
    return float(n) * int(fixed["nnz_per_row"]), n, int(fixed["d"])


def probe_busy(ctx: dict, layer: str):
    """The device-busy seconds inside each ``bench.probe.<layer>`` span of
    a traced run, or nothing."""
    return ((ctx.get("trace") or {}).get("probe_busy_s") or {}).get(layer)


def product_ms(ctx: dict, layer: str):
    """Mean device-busy ms of one product run alone under its span."""
    busy = probe_busy(ctx, layer)
    return 1e3 * sum(busy) / len(busy) if busy else None


def product_roofline(ctx: dict, layer: str, hbm_bytes_per_s: float):
    """A product's least time at the chip's HBM peak over its device-busy
    time, in percent; ``None``, never 0, where nothing was read."""
    busy = probe_busy(ctx, layer)
    if not busy or not sum(busy):
        return None
    least = product_bytes(*shape_of(ctx["config"])) / hbm_bytes_per_s
    return 100.0 * least * len(busy) / sum(busy)
