"""The traced jobs' device time by the program's own scopes (PR 40): the
join of two things the benchmark never had together.

``ctx["trace"]["op_seconds"]`` holds the summed seconds of every operation
of the traced jobs by its HLO instruction name (``%fusion.262``), and drops
the scope path. The program says, from the executable it dispatched, which
``op_name`` path each instruction name of its ``cd_block`` carries
(``photon_ml_tpu.utils.compile_cache.instruction_scopes``), and how a path
resolves to its table of scopes (``photon_ml_tpu.telemetry.scopes.place``:
an operation counts under the innermost table scope on its path). Summing
by name is exact where one name carries one path: the operations of the
``XLA Ops`` line are serial (a ``while`` spans its body: containers are left
out, by the reduction's name list and here by opcode), and a benchmark job
starts cold, so ``cd_block`` is the only program of its own that it runs.

Every reader under ``metrics/`` that sums a scope is a few lines over
``by_scope``. It says nothing sooner than something wrong: ``None`` where
there is no trace, no traced job, no table (a program from before the
table: the import is guarded, nothing raises), or where the names the table
holds cover under 95% of the summed seconds (the table is not this trace's
program).
"""

from __future__ import annotations

from typing import Iterable, Optional

COVERAGE_FLOOR = 0.95
CONTAINER_OPCODES = ("while", "conditional", "call")


def by_scope(ctx, floor: float = COVERAGE_FLOOR) -> Optional[dict]:
    """Milliseconds a traced job, mean over chips:

    - ``leaf``: by the innermost table scope (``photon.fe.solve``, ...);
    - ``product``: a sparse fixed effect's products and their parts, keyed
      as ``place`` keys them (``<leaf>/<product>[/<part>]``);
    - ``kernel``: the entity kernel's events (``scopes.KERNEL`` prefix), by
      leaf;
    - ``coordinate``: by ``photon.cd.<coordinate>``;
    - ``unscoped``: under no ``photon.*`` name, or a name the table does not
      hold (another program's);
    - ``total``: all of it (containers apart); ``coverage``: the share of
      ``total`` whose names the table holds."""
    trace = ctx.get("trace")
    if not trace or not trace.get("traced_jobs"):
        return None
    table, opcodes = ctx.get("instruction_scopes"), ctx.get(
        "instruction_opcodes", {})
    try:
        from photon_ml_tpu.telemetry import scopes

        place = scopes.place
        if table is None:
            from photon_ml_tpu.utils import compile_cache

            table = compile_cache.instruction_scopes()
            opcodes = compile_cache.instruction_opcodes()
    except (ImportError, AttributeError):  # a program from before the table
        return None
    if not table:
        return None
    leaf, product, kernel, coordinate = {}, {}, {}, {}
    unscoped = total = held = 0.0
    for name, seconds in trace["op_seconds"].items():
        name = name.lstrip("%")
        if opcodes.get(name) in CONTAINER_OPCODES:
            continue
        total += seconds
        path = table.get(name)
        if path is None:
            unscoped += seconds
            continue
        held += seconds
        where = place(path)
        if not where["scoped"]:
            unscoped += seconds
            continue
        for key, into in ((where["leaf"], leaf),
                          (where["product"], product),
                          (where["part"], product),
                          (where["coordinate"], coordinate)):
            if key:
                into[key] = into.get(key, 0.0) + seconds
        if where["leaf"] and name.startswith(scopes.KERNEL):
            kernel[where["leaf"]] = kernel.get(where["leaf"], 0.0) + seconds
    if total <= 0 or held < floor * total:
        return None
    per_job_ms = 1e3 / trace["traced_jobs"]
    scale = lambda d: {k: v * per_job_ms for k, v in d.items()}
    return {"leaf": scale(leaf), "product": scale(product),
            "kernel": scale(kernel), "coordinate": scale(coordinate),
            "unscoped": unscoped * per_job_ms, "total": total * per_job_ms,
            "coverage": held / total}


def leaf_ms(ctx, leaves: Iterable[str]) -> Optional[float]:
    """The summed ms a job of the operations whose leaf scope is one of
    ``leaves``; nothing where ``by_scope`` says nothing or none ran."""
    found = by_scope(ctx)
    if found is None:
        return None
    ms = sum(found["leaf"].get(s, 0.0) for s in leaves)
    return ms if ms > 0 else None


def product_ms(ctx, product: str) -> Optional[float]:
    """The summed ms a job of one sparse product (``photon.fe.matvec``),
    under whichever scope ran it (the solve and the scoring pass)."""
    found = by_scope(ctx)
    if found is None:
        return None
    ms = sum(v for k, v in found["product"].items()
             if k.endswith("/" + product))
    return ms if ms > 0 else None
