"""``re_slot_imbalance``: the fullest chip's random-effect slots over the
mean chip's (entities x padded rows of every size class, the empty
entities that fill a class to a multiple of the mesh included), from the
job's counters, which read the addressable shards of the program's own
blocks. 1 is an even split. Nothing where the job reports no mesh."""


def read(ctx):
    slots = (ctx.get("counters") or {}).get("slots_per_device")
    if not slots or not sum(slots):
        return None
    return max(slots) * len(slots) / sum(slots)
