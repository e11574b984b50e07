"""``block_trace_lower_s``: what the fused block costs every process
before the compile cache can be asked: the seconds JAX spent tracing
``cd_block`` to a jaxpr and lowering that to an MLIR module, from the
program's compile ledger (``photon_ml_tpu.utils.compile_cache``, fed by
JAX's monitoring events). A program counter, on the host's clock. Nothing
where the program keeps no ledger or the ledger holds no such function."""


def read(ctx):
    ledger = ctx.get("compile_ledger")
    if ledger is None:
        try:
            from photon_ml_tpu.utils.compile_cache import compile_ledger
        except ImportError:  # a program from before the ledger
            return None
        ledger = compile_ledger()
    row = ledger.get("functions", {}).get("cd_block")
    if not row:
        return None
    return row["trace_s"] + row["lower_s"]
