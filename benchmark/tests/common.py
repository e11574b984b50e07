"""A tiny copy of the cell's configuration: the same recipe, job kind,
optimizer strings and law of activity on a few thousand rows."""

import json
from pathlib import Path

from benchmark.recipes import dense_glm

HERE = Path(__file__).resolve().parents[1]
CELL, CONFIG, TINY_ROWS = "glmix.fit", "glmix-ml20m-u30", 6000


def tiny_config() -> dict:
    config = json.loads((HERE / "configs" / f"{CONFIG}.json").read_text())
    return dense_glm.scale_down(config, TINY_ROWS)


def workload() -> dict:
    return json.loads((HERE / "workloads" / f"{CELL}.json").read_text())
