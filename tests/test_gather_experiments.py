"""Host-side packing logic of dev_scripts/gather_experiments.py — the
block-packed (one-hot MXU) and residue-class (lane-local dynamic_gather)
index layouts must be exact permutations, or chip measurements of the
gather-wall candidates would validate garbage."""

import numpy as np

from dev_scripts.gather_experiments import BLOCK, _prep_blocks, _prep_residue


def test_prep_blocks_is_exact_permutation():
    rng = np.random.default_rng(5)
    d = 6 * BLOCK + 17  # ragged final block
    m = 5000
    idx = rng.integers(0, d, m).astype(np.int32)
    local, mask, slot = _prep_blocks(idx, d)
    kb, e = local.shape
    assert kb == -(-d // BLOCK)
    assert mask.sum() == m
    # Reconstruct each entry's global index from its packed slot.
    flat_local = local.reshape(-1)
    owner_of_slot = np.repeat(np.arange(kb), e)
    got = owner_of_slot[slot] * BLOCK + flat_local[slot]
    np.testing.assert_array_equal(got, idx)
    # Padding slots carry mask 0 and in-range local ids.
    assert (local >= 0).all() and (local < BLOCK).all()


def test_prep_residue_is_exact_permutation():
    rng = np.random.default_rng(7)
    d = 128 * 57
    m = 4096
    idx = rng.integers(0, d, m).astype(np.int32)
    packed, slot = _prep_residue(idx, d)
    chunks, a, lanes = packed.shape
    assert lanes == 128 and a == d // 128
    # Every lane's entries are its own residue class (the dynamic_gather
    # lane-locality contract).
    flat = packed.reshape(-1)  # [chunks * a * 128], lane = pos % 128
    got = flat[slot] * 128 + (slot % 128)
    np.testing.assert_array_equal(got, idx)


def test_prep_residue_skewed_distribution_pads_chunks():
    # All indices share one residue class: per-lane stream is maximally
    # skewed and must round up to whole table-shaped chunks.
    d = 128 * 8
    idx = (np.arange(500, dtype=np.int32) % 8) * 128 + 5  # residue 5 only
    packed, slot = _prep_residue(idx, d)
    chunks, a, lanes = packed.shape
    assert a == 8 and chunks == -(-500 // 8)
    flat = packed.reshape(-1)
    got = flat[slot] * 128 + (slot % 128)
    np.testing.assert_array_equal(got, idx)


def test_prep_blocks_arbitrary_width_is_exact_permutation():
    """The block-width sweep (--sweep) reuses _prep_blocks at non-default
    widths; the packing must stay an exact permutation at every width."""
    rng = np.random.default_rng(11)
    d = 3 * 512 + 100  # ragged final block at width 512
    m = 3000
    idx = rng.integers(0, d, m).astype(np.int32)
    for block in (256, 512, 1024):
        local, mask, slot = _prep_blocks(idx, d, block=block)
        kb, e = local.shape
        assert kb == -(-d // block)
        assert mask.sum() == m
        flat_local = local.reshape(-1)
        owner_of_slot = np.repeat(np.arange(kb), e)
        got = owner_of_slot[slot] * block + flat_local[slot]
        np.testing.assert_array_equal(got, idx)


def test_variant_args_rolls_named_arrays_together(monkeypatch):
    """_time_distinct's per-rep inputs: arrays named in roll_axes shift
    by the EXPECTED variant shift — the same amount for both (keeping
    index/mask pairs aligned) — and unnamed arrays are returned
    untouched (shared tables). The nonce is pinned so the expected roll
    is provably non-identity regardless of test-process pid: a no-op
    regression of _variant_args (which would silently re-open the
    same-args caching hole) fails the equality asserts."""
    import jax.numpy as jnp

    import dev_scripts.gather_experiments as ge

    monkeypatch.setattr(ge, "_NONCE", 4)  # shift (1009+4)*2 % 4 == 2
    a = jnp.arange(12).reshape(3, 4)
    b = jnp.arange(12, 24).reshape(3, 4)
    w = jnp.arange(5)
    va, vb, vw = ge._variant_args((a, b, w), {0: 1, 1: 1}, 2)
    assert vw is w
    shift = (1009 + 4) * 2
    assert shift % a.shape[1] != 0  # the roll below is NOT the identity
    assert not np.array_equal(np.asarray(va), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(va),
                                  np.roll(np.asarray(a), shift, axis=1))
    np.testing.assert_array_equal(np.asarray(vb),
                                  np.roll(np.asarray(b), shift, axis=1))
    # The real per-process nonce keeps cross-process dispatches distinct.
    monkeypatch.undo()
    assert 1 <= ge._NONCE <= 997


def test_variant_args_forces_nonzero_effective_shift(monkeypatch):
    """A raw shift that is a MULTIPLE of the rolled axis length must not
    degrade to an identity roll (the "distinct" rep would repeat the
    warm-up): the effective shift falls back to 1."""
    import jax.numpy as jnp

    import dev_scripts.gather_experiments as ge

    monkeypatch.setattr(ge, "_NONCE", 3)  # (1009+3)*1 % 4 == 0
    a = jnp.arange(8).reshape(2, 4)
    shift = (1009 + 3) * 1
    assert shift % a.shape[1] == 0  # raw roll WOULD be the identity
    (va,) = ge._variant_args((a,), {0: 1}, 1)
    assert not np.array_equal(np.asarray(va), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(va),
                                  np.roll(np.asarray(a), 1, axis=1))
