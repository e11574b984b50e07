"""``fe_slot_ratio``: the slots the program's layout stores over the true
non-zeros, from the job's counters (``slots`` and ``nnz`` as the program's
chooser counted them on the device): what every product pays over the true
work in index operations. 1 is a layout without padding. Nothing where the
job reports no sparse matrix."""


def read(ctx):
    counters = ctx.get("counters") or {}
    slots, nnz = counters.get("slots"), counters.get("nnz")
    if not slots or not nnz:
        return None
    return slots / nnz
