"""What decides ``correct`` for a ``cd_fit_mesh`` cell: the numbers of
``checks/cd_fit.py`` by ``checks/cd_fit.py``'s own code (``obj_gap``,
``coef_gap.<coord>``, ``coef_worst.<group>``, ``score_self_gap``), with the
plain reference that reads arrays laid over several chips
(``reference/glm_cd_mesh.py``) standing where that code names ``glm_cd``:
``glm_cd``'s row blocks slice the sharded axis, and the partitioner would
gather all of X onto every chip for them. The two references have one
interface (``fit``, ``scores_of``), so the swap is the whole difference.

The padding the problem carries (rows of weight 0, empty entities) is in
the program's arrays and in the reference's alike: both fit it, both score
it zero, and it adds nothing to any gap.
"""

from __future__ import annotations

from typing import Dict
from unittest import mock

from benchmark.checks import cd_fit
from benchmark.reference import glm_cd_mesh

reference_fit = glm_cd_mesh.fit


def numbers(problem, config: dict, window: dict, ref: dict = None
            ) -> Dict[str, float]:
    with mock.patch.object(cd_fit, "glm_cd", glm_cd_mesh):
        return cd_fit.numbers(problem, config, window, ref)


def check(problem, config: dict, workload: dict, window: dict) -> dict:
    with mock.patch.object(cd_fit, "glm_cd", glm_cd_mesh):
        return cd_fit.check(problem, config, workload, window)
