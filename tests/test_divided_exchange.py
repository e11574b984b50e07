"""The score exchange of a ``mesh=`` fit is divided over the mesh (PR 32):
each device gathers the residual into the slots of its OWN entity shard and
the margins into its OWN row range, with one collective each way
(``algorithm/coordinates.py``: ``_whole_residual``, ``_gather_residual``,
``_scores_by_row``). The margins' way back is a gather by row through
``slot_of_row`` (PR 34), on one device as over a mesh. On four of the eight
virtual CPU devices: the divided exchange is bitwise the one-device
exchange, both are bitwise the dataset's own scatter-add, a whole mesh fit
agrees with the one-device fit, the compiled block carries no collective on
a block's ``[E, r]`` shape and no scatter in the scoring, the one-device
block is the program recorded here, and the counter and the gauges say
what a run was built with."""

import dataclasses

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu import telemetry
from photon_ml_tpu.algorithm import (
    CoordinateDescent,
    FactoredRandomEffectCoordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.algorithm import coordinates
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.data.random_effect import (
    RandomEffectDataConfiguration,
    build_random_effect_dataset,
)
from photon_ml_tpu.optimization.config import (
    GLMOptimizationConfiguration,
    MFOptimizationConfiguration,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.parallel import make_mesh, make_mesh_2d
from photon_ml_tpu.telemetry import scopes
from photon_ml_tpu.types import TaskType
from tests.conftest import F32_MODE
from tests.test_coordinate_descent import build_coordinates, make_glmix_data

K = 4
TASK = TaskType.LOGISTIC_REGRESSION
MESHES = {"4": lambda: make_mesh(K), "2x2": lambda: make_mesh_2d(2, 2)}
L2 = GLMOptimizationConfiguration(
    max_iterations=10, tolerance=1e-8, regularization_weight=0.1,
    regularization_context=RegularizationContext(RegularizationType.L2))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


# -- (a) the divided exchange is bitwise the one-device exchange -----------------

def _dataset(n_rows: int, projector: str, drop_short_passive: bool = False):
    """13 users of very different activity (several size classes, none a
    multiple of 4 entities: the mesh fills each with empty entities), 16
    active rows a user at most, the rest in passive blocks; padding slots
    in every block. ``drop_short_passive``: the half of the users past the
    cap with the fewest rows past it lose them (rows in no slot)."""
    rng = np.random.default_rng(n_rows)
    users = rng.choice(13, n_rows, p=np.arange(1, 14) / 91.0)
    data = GameDataset.build(
        responses=(rng.random(n_rows) < 0.5).astype(float),
        feature_shards={"u": sp.csr_matrix(  # whole numbers: see _params
            rng.integers(1, 5, (n_rows, 5)).astype(float))},
        ids={"userId": users.astype(str)})
    past_cap = np.sort(np.bincount(users) - 16)
    past_cap = past_cap[past_cap > 0]
    return build_random_effect_dataset(
        data, RandomEffectDataConfiguration(
            "userId", "u", num_active_data_points=16,
            num_passive_data_points_lower_bound=(
                int(past_cap[len(past_cap) // 2]) if drop_short_passive
                else None),
            projector_type=projector), seed=3)


def _coordinate(kind: str, dataset, mesh):
    if kind == "random":
        return RandomEffectCoordinate(
            name="perUser", dataset=dataset, task_type=TASK, config=L2,
            mesh=mesh)
    return FactoredRandomEffectCoordinate(
        name="perUser", dataset=dataset, task_type=TASK, config=L2,
        latent_config=L2, mesh=mesh,
        mf_config=MFOptimizationConfiguration(max_iterations=1,
                                              num_factors=2))


def _params(kind: str, coord, rng, like=None):
    """Random parameters in the coordinate's own form; with ``like`` (the
    one-device parameters) the same ones, zero for the empty entities the
    mesh added. Small whole numbers, like the features: every margin is
    then exact in whatever order a device sums it, and what is compared
    bitwise is the exchange."""
    def per_entity(block, width, given):
        if given is None:
            return jnp.asarray(
                rng.integers(-3, 4, (block.num_entities, width)),
                block.x.dtype)
        fill = block.num_entities - given.shape[0]
        return jnp.pad(given, ((0, fill), (0, 0)))

    blocks = coord.dataset.blocks
    if kind == "random":
        given = like or [None] * len(blocks)
        return tuple(per_entity(b, b.d_pad, g)
                     for b, g in zip(blocks, given))
    given, B = like or ([None] * len(blocks), jnp.asarray(
        rng.integers(-3, 4, (2, coord.dataset.num_global_features)),
        blocks[0].x.dtype))
    return tuple(per_entity(b, 2, g) for b, g in zip(blocks, given)), B


def _exchanged(kind: str, n_rows: int, mesh_name: str) -> dict:
    """One coordinate on one device and the same over a mesh, with the
    same parameters and the same residual."""
    dataset = _dataset(
        n_rows, "INDEX_MAP" if kind == "random" else "IDENTITY")
    mesh = MESHES[mesh_name]()
    one = _coordinate(kind, dataset, None)
    over = _coordinate(kind, dataset, mesh)
    rng = np.random.default_rng(7)
    p_one = _params(kind, one, rng)
    residual = jnp.asarray(rng.normal(0, 1, n_rows),
                           dataset.blocks[0].x.dtype)
    return {"one": one, "over": over, "mesh": mesh, "n_rows": n_rows,
            "p_one": p_one, "p_over": _params(kind, over, rng, like=p_one),
            "residual": residual}


@pytest.fixture(scope="module", params=[
    (kind, n_rows, mesh) for kind in ("random", "factored")
    for n_rows in (240, 243) for mesh in MESHES],
    ids=lambda p: "-".join(map(str, p)))
def exchanged(request):
    return _exchanged(*request.param)


def test_the_fixture_has_what_the_exchange_must_survive(exchanged):
    one, over, n = exchanged["one"], exchanged["over"], exchanged["n_rows"]
    assert len(one.dataset.blocks) >= 2
    assert any(b is not None for b in one.dataset.passive_blocks)
    k = exchanged["mesh"].shape["data"]
    assert any(b.num_entities % k for b in one.dataset.blocks)
    assert any((np.asarray(b.row_ids) == n).any()  # padding slots
               for b in one.dataset.blocks)
    for b1, b4 in zip(one.dataset.blocks, over.dataset.blocks):
        assert b4.num_entities % k == 0
        assert (np.asarray(b4.row_ids)[b1.num_entities:] == n).all()
        assert len(b4.row_ids.sharding.device_set) == K


def test_divided_scores_are_bitwise_the_one_device_scores(exchanged):
    one, over = exchanged["one"], exchanged["over"]
    s_one = one.pure_score(one.step_data(), exchanged["p_one"])
    s_over = over.pure_score(over.step_data(), exchanged["p_over"])
    assert s_over.shape == s_one.shape == (exchanged["n_rows"],)
    assert s_over.dtype == s_one.dtype
    assert np.asarray(s_one).any()
    np.testing.assert_array_equal(np.asarray(s_over), np.asarray(s_one))


def test_divided_gather_is_bitwise_the_one_device_gather(exchanged):
    one, over, mesh = exchanged["one"], exchanged["over"], exchanged["mesh"]
    residual, n = exchanged["residual"], exchanged["n_rows"]
    whole = jax.jit(coordinates._whole_residual, static_argnums=1)(
        residual, mesh)
    assert whole.shape == (-(-n // mesh.shape["data"])
                           * mesh.shape["data"] + 1,)
    np.testing.assert_array_equal(np.asarray(whole)[:n],
                                  np.asarray(residual))
    assert not np.asarray(whole)[n:].any()
    gather = jax.jit(coordinates._gather_residual, static_argnums=2)
    for b1, b4 in zip(one.dataset.blocks + [
            b for b in one.dataset.passive_blocks if b is not None],
            over.dataset.blocks + [
            b for b in over.dataset.passive_blocks if b is not None]):
        want = np.asarray(gather(residual, b1, None))
        got = gather(whole, b4, mesh)
        assert got.sharding.is_equivalent_to(b4.row_ids.sharding, 2)
        np.testing.assert_array_equal(
            np.asarray(got)[:b1.num_entities], want)
        assert not np.asarray(got)[b1.num_entities:].any()


@pytest.mark.parametrize("kind", ["random", "factored"])
def test_an_update_under_the_mesh_takes_the_residual(kind):
    """``pure_update`` hands the divided gather to the solves: the
    coefficients move with the residual as on one device."""
    exchanged = _exchanged(kind, 243, "4")
    one, over = exchanged["one"], exchanged["over"]
    key = jax.random.PRNGKey(0)
    got, _ = over.pure_update(over.step_data(), exchanged["p_over"],
                              exchanged["residual"], key)
    want, _ = one.pure_update(one.step_data(), exchanged["p_one"],
                              exchanged["residual"], key)
    unmoved, _ = over.pure_update(over.step_data(), exchanged["p_over"],
                                  None, key)
    leaves = lambda p: [np.asarray(a) for a in jax.tree.leaves(p)]
    for g, w, u in zip(leaves(got), leaves(want), leaves(unmoved)):
        # float32 blocks: the solvers agree to about their tolerance
        np.testing.assert_allclose(g[:w.shape[0]], w, rtol=2e-3, atol=2e-3)
    assert any(np.abs(g - u).max() > 1e-3
               for g, u in zip(leaves(got), leaves(unmoved)))


# -- (a') the scores by ``slot_of_row`` are the dataset's own scatter-add ----------

def _without_first_entity(block):
    return None if block is None else jax.tree.map(lambda a: a[1:], block)


def _slotted_dataset(n_rows: int, projector: str):
    """``_dataset`` with what the index must survive besides: users past
    the active cap whose passive rows were too few to keep (rows in no
    slot), and one user taken out of its block whole (an entity with no
    slot). Passive blocks and padding slots stay."""
    dataset = _dataset(n_rows, projector, drop_short_passive=True)
    return dataclasses.replace(
        dataset,
        blocks=[_without_first_entity(dataset.blocks[0])]
        + dataset.blocks[1:],
        passive_blocks=[_without_first_entity(dataset.passive_blocks[0])]
        + dataset.passive_blocks[1:],
        entity_codes=[dataset.entity_codes[0][1:]]
        + dataset.entity_codes[1:])


@pytest.fixture(scope="module", params=[
    (kind, n_rows) for kind in ("random", "factored")
    for n_rows in (400, 402)], ids=lambda p: "-".join(map(str, p)))
def slotted(request):
    """A coordinate on one device and over four, the same parameters, and
    what ``RandomEffectDataset.scatter_scores`` (the scatter-add, kept as
    the independent oracle) makes of the one-device margins."""
    kind, n_rows = request.param
    dataset = _slotted_dataset(
        n_rows, "INDEX_MAP" if kind == "random" else "IDENTITY")
    one = _coordinate(kind, dataset, None)
    over = _coordinate(kind, dataset, make_mesh(K))
    rng = np.random.default_rng(17)
    p_one = _params(kind, one, rng)
    if kind == "random":
        coefs = p_one
    else:
        d = dataset.num_global_features
        coefs = [jnp.pad(g @ p_one[1], ((0, 0), (0, b.d_pad - d)))
                 for g, b in zip(p_one[0], dataset.blocks)]
    want = dataset.scatter_scores(
        [b.local_margins(c) for b, c in zip(dataset.blocks, coefs)],
        [None if b is None else b.local_margins(c)
         for b, c in zip(dataset.passive_blocks, coefs)])
    slots = np.concatenate([
        np.asarray(b.row_ids).reshape(-1)
        for b in dataset.blocks + dataset.passive_blocks if b is not None])
    return {"one": (one, p_one),
            "four": (over, _params(kind, over, rng, like=p_one)),
            "want": np.asarray(want), "n_rows": n_rows,
            "in_no_slot": np.setdiff1d(np.arange(n_rows), slots)}


def test_the_slotted_fixture_has_what_the_index_must_survive(slotted):
    one, _ = slotted["one"]
    n = slotted["n_rows"]
    assert any(b is not None for b in one.dataset.passive_blocks)
    assert any(b is None for b in one.dataset.passive_blocks)
    assert any((np.asarray(b.row_ids) == n).any()
               for b in one.dataset.blocks)
    assert 16 < len(slotted["in_no_slot"]) < n // 2
    assert slotted["want"].any()
    assert not slotted["want"][slotted["in_no_slot"]].any()


@pytest.mark.parametrize("devices", ["one", "four"])
def test_scores_by_slot_of_row_are_bitwise_the_scatter_adds(slotted,
                                                            devices):
    coord, params = slotted[devices]
    n = slotted["n_rows"]
    got = coord.pure_score(coord.step_data(), params)
    assert got.shape == (n,) and got.dtype == slotted["want"].dtype
    np.testing.assert_array_equal(np.asarray(got), slotted["want"])
    assert coord.unslotted_rows == len(slotted["in_no_slot"])
    index = np.asarray(coord.step_data()[-1])
    k = K if devices == "four" else 1
    assert index.dtype == np.int32 and index.shape == (-(-n // k) * k,)
    # the rows in no slot, and the rows that fill the range up to a multiple
    # of the mesh, all read one position: the first device's appended zero
    assert len(set(index[slotted["in_no_slot"]]) | set(index[n:])) == 1
    assert len(np.unique(index)) == n - len(slotted["in_no_slot"]) + 1


# -- (b) a whole mesh fit ----------------------------------------------------------

def _glmix(data, mesh):
    base = build_coordinates(data)
    re_data = build_random_effect_dataset(
        data, RandomEffectDataConfiguration("userId", "user"),
        intercept_col=0)
    return {
        "fixed": FixedEffectCoordinate(
            name="fixed", data=data, feature_shard_id="global",
            task_type=TASK, config=base["fixed"].config, mesh=mesh),
        "perUser": RandomEffectCoordinate(
            name="perUser", dataset=re_data, task_type=TASK,
            config=base["perUser"].config, mesh=mesh)}


@pytest.fixture(scope="module")
def fits():
    """The same GLMix fit on one device and over four, 402 rows (not a
    multiple of four) and 400, with the block's arguments recorded."""
    out = {}
    for n_rows in (400, 402):
        data = make_glmix_data(np.random.default_rng(5), n=n_rows)[0]
        for name, mesh in (("one", None), ("four", make_mesh(K))):
            coords = _glmix(data, mesh)
            cd = CoordinateDescent(coords, TASK)
            fn = cd._fused_block_fn(2)
            seen = {}

            def recorder(*args, fn=fn, seen=seen):
                seen["args"] = args
                return fn(*args)

            cd._block_fns[2] = recorder
            result = cd.run(2)
            scores = {n: np.asarray(c.score(result.model.get_model(n)))
                      for n, c in coords.items()}
            out[name, n_rows] = {
                "coords": coords, "result": result, "scores": scores,
                "compiled": (fn.lower(*seen["args"]).compile().as_text()
                             if n_rows == 400 else None)}
    return out


@pytest.mark.parametrize("n_rows", [400, 402])
def test_mesh_fit_agrees_with_the_one_device_fit(fits, n_rows):
    """Parameters, scores and objective history: the reductions of the
    fixed effect reassociate across shards, which the solvers amplify to
    about their tolerance (``test_cd_objective_invariant_across_mesh_
    sizes``); a fault in the exchange shows orders of magnitude above."""
    one, four = fits["one", n_rows], fits["four", n_rows]
    np.testing.assert_allclose(four["result"].objective_history,
                               one["result"].objective_history,
                               rtol=1e-4)  # a float32 program
    tol = dict(rtol=0, atol=1e-2)
    m1, m4 = one["result"].model, four["result"].model
    np.testing.assert_allclose(
        np.asarray(m4.get_model("fixed").glm.coefficients.means),
        np.asarray(m1.get_model("fixed").glm.coefficients.means), **tol)
    for got, want in zip(m4.get_model("perUser").local_coefs,
                         m1.get_model("perUser").local_coefs):
        e = want.shape[0]
        np.testing.assert_allclose(np.asarray(got)[:e], np.asarray(want),
                                   **tol)
        np.testing.assert_array_equal(np.asarray(got)[e:], 0)
    for name in one["scores"]:
        assert four["scores"][name].shape == (n_rows,)
        np.testing.assert_allclose(four["scores"][name],
                                   one["scores"][name], **tol)


def test_the_scores_come_back_row_sharded_like_the_batch(fits):
    coords = fits["four", 400]["coords"]
    model = fits["four", 400]["result"].model
    s_re = coords["perUser"].score(model.get_model("perUser"))
    s_fe = coords["fixed"].score(model.get_model("fixed"))
    assert s_re.sharding.is_equivalent_to(s_fe.sharding, 1)
    assert {s.data.shape for s in s_re.addressable_shards} == {(400 // K,)}


# -- (c) what the compiled block holds -----------------------------------------------

_COLLECTIVE = re.compile(
    r"= (\([^=]*?\)|\S+) "
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _collectives(compiled_text: str):
    """(operation, [shapes it carries]) of every collective instruction."""
    out = []
    for line in compiled_text.splitlines():
        m = _COLLECTIVE.search(line)
        if m:
            shapes = [tuple(int(d) for d in dims.split(",") if d)
                      for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]
            out.append((m.group(2), shapes, line))
    return out


def test_the_compiled_mesh_block_divides_the_exchange(fits):
    """No collective carries an array of a block's ``[E, r]`` shape (whole
    or a device's shard); the residual crosses the devices once per
    random-effect update and the flat margins once per scoring (the scan's
    body is compiled once; the fixed effect moves ``[d]`` and scalars)."""
    fit = fits["four", 400]
    found = _collectives(fit["compiled"])
    assert found
    block_shapes = set()
    for b in fit["coords"]["perUser"].dataset.blocks:
        e, r, d = b.x.shape
        block_shapes |= {(e, r), (e // K, r), (e, r, d), (e // K, r, d)}
    n_vectors = []
    for op, shapes, line in found:
        assert not block_shapes & set(shapes), line
        assert all(len(s) <= 1 for s in shapes), line
        if any(s and s[0] >= 400 // K for s in shapes):
            n_vectors.append((op, line))
    assert len(n_vectors) == 2, n_vectors
    gathers = [line for _, line in n_vectors if scopes.RE_GATHER in line]
    assert len(gathers) == 1
    # the other: every device's margins and the zero behind them, laid end
    slots = sum(b.x.shape[0] * b.x.shape[1]
                for b in fit["coords"]["perUser"].dataset.blocks)
    # to end (``_end_to_end``; a combiner may have merged the collective
    # with the penalties' scalars, under either's name)
    assert [op for op, shapes, _ in found if (slots + K,) in shapes] == [
        "all-reduce"]


def _instructions_under(compiled_text: str, scope: str):
    return [line for line in compiled_text.splitlines()
            if f"/{scope}/" in line and " = " in line]


def test_the_compiled_blocks_hold_no_scatter_in_the_scoring(fits):
    """The margins' way back is a gather: under ``photon.re.scatter`` the
    mesh block and the one-device block hold a gather and no scatter, and
    the one-device block holds no scatter-add anywhere (the solvers'
    history updates are scatters that set)."""
    for name in ("four", "one"):
        compiled = fits[name, 400]["compiled"]
        back = _instructions_under(compiled, scopes.RE_SCATTER)
        assert any(re.search(r" gather\(", line) for line in back), name
        assert not any(re.search(r" scatter\(", line) for line in back)
        assert not any("scatter-add" in line for line in back)
    assert "scatter-add" not in fits["one", 400]["compiled"]
    assert "all-gather" not in fits["one", 400]["compiled"]


def test_the_exchanges_collectives_sit_under_their_scopes(fits):
    compiled = fits["four", 400]["compiled"]
    assert f"{scopes.RE_GATHER}/shard_map/psum" in compiled
    assert f"{scopes.RE_SCATTER}/shard_map/psum" in compiled


def test_the_instruction_table_knows_the_exchanges_collectives(fits):
    """The mesh block's instruction table (PR 40): a collective is known by
    its OPCODE whatever JAX named the instruction (``psum_invariant.<n>`` is
    an ``all-reduce``), and the exchange's two sit under their scopes, so a
    sum of per-operation seconds by name counts them in the exchange."""
    from photon_ml_tpu.utils.compile_cache import parse_instructions

    table = parse_instructions(fits["four", 400]["compiled"])
    collectives = {name: scopes.place(path)["leaf"]
                   for name, (opcode, path) in table.items()
                   if opcode.startswith(scopes.COLLECTIVE_PREFIXES)}
    assert collectives
    leaves = list(collectives.values())
    assert leaves.count(scopes.RE_GATHER) == 1
    assert scopes.RE_SCATTER in leaves or None in leaves  # merged: see above
    by_name = [n for n in collectives if n.startswith("psum")]
    assert by_name and not any(
        n.startswith(scopes.COLLECTIVE_PREFIXES) for n in by_name)
    # the one-device block has none, by either rule
    one = parse_instructions(fits["one", 400]["compiled"])
    assert not [n for n, (opcode, _) in one.items()
                if opcode.startswith(scopes.COLLECTIVE_PREFIXES)]


# -- (d) without a mesh the program is the one it was ---------------------------------

# sha256 of ``cd_block``'s lowered text (``as_text()``: no locations) at
# ``test_fit_tracing``'s tiny size, x64 on. Recorded anew by PR 34, which
# changes the one-device block on purpose (the scoring's ten scatter-adds
# become a concatenate and one gather, and ``slot_of_row`` is one more
# argument), from that PR's own tree (its parent, 576cf65, lowers to
# c7399799675318638c27c5fe662ff8ce4cf3d2d6eec2f2f996c8ab839ebec2de, the
# digest PR 32 took from ITS parent 592a391 and PR 33 left standing). A PR
# that changes the one-device block on purpose records its own here and says
# so.
ONE_DEVICE_BLOCK_SHA256 = (
    "aed299d949fcd16eff0f971d91d133b5cb30a8149b6970cbf9f505737430a4ac")


@pytest.mark.skipif(F32_MODE, reason="the digest is of the x64 program")
def test_without_a_mesh_the_block_lowers_to_the_parents_text():
    data = make_glmix_data(np.random.default_rng(20260729))[0]
    cd = CoordinateDescent(build_coordinates(data), TASK)
    fn = cd._fused_block_fn(2)
    seen = {}

    def recorder(*args):
        seen["args"] = args
        return fn(*args)

    cd._block_fns[2] = recorder
    cd.run(2)
    text = fn.lower(*seen["args"]).as_text()
    assert "shard_map" not in text and "all_reduce" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == \
        ONE_DEVICE_BLOCK_SHA256


# -- (e) the counters and the gauges ----------------------------------------------------

def _exchange_counts(coords, runs=1):
    telemetry.reset()
    telemetry.enable()
    try:
        cd = CoordinateDescent(coords, TASK)
        for _ in range(runs):
            cd.run(1)
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.disable()
        telemetry.reset()
    return counters.get(scopes.COUNTER_RE_EXCHANGE_DIVIDED, 0)


@pytest.mark.parametrize("case, want", [
    ("mesh", 1), ("mesh, two runs", 2), ("no mesh", 0),
    ("mesh, the fixed effect alone", 0)])
def test_exchange_counters(case, want):
    """One count a run for each random-effect coordinate built over a
    mesh."""
    data = make_glmix_data(np.random.default_rng(11), n=200)[0]
    coords = _glmix(data, None if case == "no mesh" else make_mesh(K))
    if "alone" in case:
        del coords["perUser"]
    assert _exchange_counts(coords, runs=2 if "two" in case else 1) == want


@pytest.mark.parametrize("mesh", [None, "4"], ids=["one device", "mesh"])
def test_score_index_gauges(mesh):
    """What one scoring gathers, beside what a scatter-add would walk: n
    rows a coordinate against every slot, and the rows that read the
    appended zero."""
    dataset = _slotted_dataset(402, "INDEX_MAP")
    coord = _coordinate("random", dataset, mesh and MESHES[mesh]())
    telemetry.enable()
    CoordinateDescent({"perUser": coord}, TASK)
    gauges = telemetry.snapshot()["gauges"]
    slots = sum(b.num_entities * b.n_pad for b in coord.dataset.blocks)
    assert gauges[scopes.GAUGE_RE_SLOTS] == slots  # the active blocks'
    assert gauges[scopes.GAUGE_RE_SCORE_ROWS] == 402
    assert gauges[scopes.GAUGE_RE_SCORE_UNSLOTTED_ROWS] == \
        coord.unslotted_rows > 0


def test_the_index_is_built_once_and_outside_the_block():
    """``slot_of_row`` is made when the coordinate is constructed and rides
    in ``step_data()``: runs build nothing, and the block takes it as an
    argument."""
    from photon_ml_tpu.utils import compile_cache

    data = make_glmix_data(np.random.default_rng(12), n=230)[0]
    compile_cache._listen()
    compile_cache.reset_compile_ledger()
    coords = _glmix(data, None)
    built = compile_cache.compile_ledger()["functions"]["_index_rows"]
    assert built["compiles"] == 1
    index = coords["perUser"].step_data()[-1]
    assert index is coords["perUser"].step_data()[-1]
    cd = CoordinateDescent(coords, TASK)
    cd.run(1)
    cd.run(1)
    rows = compile_cache.compile_ledger()["functions"]
    assert rows["_index_rows"] == built
    compile_cache.reset_compile_ledger()
