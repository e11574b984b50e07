"""Job kind ``cd_fit_mesh``: ``cd_fit``'s job with every coordinate built
over a device mesh.

The job, its window, its spans and its probes are ``cd_fit``'s, by
inheritance: one ``CoordinateDescent.run`` from zero models on one object,
jobs back to back ended on a job boundary, ``bench.job`` /
``bench.probe.*``, and the containers ``cd_fit`` wraps the problem's arrays
in. What differs is that the coordinates are built with ``mesh=``
(``FixedEffectCoordinate(mesh=)``, ``RandomEffectCoordinate(mesh=)``: the
path ``GameEstimator(mesh=make_mesh(k))`` takes), over the devices the
problem's arrays already lie on (``recipes/dense_glm_mesh.py``).
The program is handed the arrays as they lie: its ``shard_batch`` and
``shard_block`` find them in place, and the counters say whether they
kept the buffers they were given.

The program sees a data set of ``problem.n_rows`` rows, a multiple of the
mesh size, whose last few carry weight 0 (the recipe's padding): every
n-vector then splits evenly by row range.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from benchmark.jobs.cd_fit import CdFitJob


class CdFitMeshJob(CdFitJob):
    def __init__(self, config: dict, workload: dict, problem,
                 storage: str = "float32"):
        from photon_ml_tpu.algorithm.coordinate_descent import (
            CoordinateDescent,
        )
        from photon_ml_tpu.parallel import make_mesh

        # cd_fit's containers over the problem's arrays (nothing is copied
        # or computed yet), then every coordinate again with ``mesh=``:
        # a coordinate lays its data over the mesh when it is built.
        super().__init__(config, workload, problem, storage=storage)
        self.mesh = make_mesh(problem.mesh.devices.size)
        if list(self.mesh.devices.flat) != list(problem.mesh.devices.flat):
            raise ValueError("the problem lies over other devices than the "
                             "program's mesh")
        self.coords = {name: dataclasses.replace(coord, mesh=self.mesh)
                       for name, coord in self.coords.items()}
        self.cd = CoordinateDescent(self.coords, self.cd.task_type)
        self._layout = self._read_layout(storage)

    def _read_layout(self, storage: str) -> Dict[str, object]:
        """What lies where, from the program's own arrays: rows of X and
        slots of the blocks on every device of the mesh, and whether the
        program kept the buffers it was handed (float32 storage only:
        the bfloat16 control stores its own copy of X)."""
        devices = list(self.mesh.devices.flat)
        at = {dev: i for i, dev in enumerate(devices)}
        rows = [0] * len(devices)
        slots = [0] * len(devices)
        x = self.coords[self.fixed_name]._batch.features.x
        for shard in x.addressable_shards:
            rows[at[shard.device]] += shard.data.shape[0]
        kept = storage != "float32" or _same_buffers(x, self.problem.x)
        for name, coord in self.coords.items():
            if name == self.fixed_name:
                continue
            for block, given in zip(coord.dataset.blocks,
                                    self.problem.buckets):
                for shard in block.x.addressable_shards:
                    e, r, _ = shard.data.shape
                    slots[at[shard.device]] += e * r
                kept = kept and _same_buffers(block.x, given.x)
        return {"devices": len(devices), "rows_per_device": rows,
                "slots_per_device": slots, "buffers_kept": bool(kept)}

    def counters(self, window: dict) -> Dict[str, float]:
        out = super().counters(window)
        out.update(self._layout)
        return out

    def release(self) -> None:
        super().release()
        self.mesh = None


def _same_buffers(a, b) -> bool:
    return ([s.data.unsafe_buffer_pointer() for s in a.addressable_shards]
            == [s.data.unsafe_buffer_pointer() for s in b.addressable_shards])


def build(config: dict, workload: dict, problem, **kw) -> CdFitMeshJob:
    return CdFitMeshJob(config, workload, problem, **kw)
