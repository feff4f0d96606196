"""Packed uint32 bitmap helpers vs bool-mask oracles (property tests).

The lockstep walk's entire per-point state rides on these ops, so each is
checked against the obvious dense-bool computation, including the nasty
cases: duplicate indices in one scatter, already-set bits, negative (pad)
indices, and n not a multiple of 32. The probe and the setter run at both
strides: 1 bit a node (the packed bitmaps) and 2 (the walk's state map, a
visited and a pass bit a node).
"""
import numpy as np
import pytest
import jax.numpy as jnp
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro.core.batched.bitmap import (PASS, STATE_STRIDE, VISITED,
                                       bit_of, gather_words, n_words,
                                       pack_bits, popcount, set_bits,
                                       state_map, unpack_bits, visited_bits)

STRIDES = [1, STATE_STRIDE]


def _rand_mask(rng, q, n):
    return rng.random((q, n)) < rng.random()


def _pack_slots(masks):
    """numpy oracle packer: ``masks[b]`` (q, n) bool is bit b of every
    node's slot, at a stride of ``len(masks)`` bits a node."""
    stride = len(masks)
    q, n = masks[0].shape
    per = 32 // stride
    out = np.zeros((q, n_words(n, stride)), np.uint32)
    for b, mask in enumerate(masks):
        for i in np.nonzero(mask.any(axis=0))[0]:
            out[:, i // per] |= (mask[:, i].astype(np.uint32)
                                 << np.uint32(stride * (i % per) + b))
    return out


def _rand_slots(rng, q, n, stride):
    masks = [_rand_mask(rng, q, n) for _ in range(stride)]
    return masks, jnp.asarray(_pack_slots(masks))


@given(st.integers(1, 5), st.integers(1, 200), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip(q, n, seed):
    rng = np.random.default_rng(seed)
    mask = _rand_mask(rng, q, n)
    bm = pack_bits(jnp.asarray(mask))
    assert bm.shape == (q, n_words(n)) and bm.dtype == jnp.uint32
    np.testing.assert_array_equal(np.asarray(unpack_bits(bm, n)), mask)


@pytest.mark.parametrize("stride", STRIDES)
@given(st.integers(1, 4), st.integers(1, 150), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_test_bits_vs_bool_oracle(stride, q, n, seed):
    """The probe reads every bit of a node's slot, and False for a pad
    index."""
    rng = np.random.default_rng(seed)
    masks, bm = _rand_slots(rng, q, n, stride)
    assert stride > 1 or np.array_equal(
        np.asarray(bm), np.asarray(pack_bits(jnp.asarray(masks[0]))))
    idx = rng.integers(-1, n, (q, 13)).astype(np.int32)
    for b, mask in enumerate(masks):
        got = np.asarray(bit_of(gather_words(bm, jnp.asarray(idx), stride),
                                jnp.asarray(idx), stride, b))
        want = np.where(idx >= 0,
                        mask[np.arange(q)[:, None], np.maximum(idx, 0)],
                        False)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("stride", STRIDES)
@given(st.integers(1, 4), st.integers(1, 150), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_set_bits_vs_bool_oracle(stride, q, n, seed):
    """Scatter-OR == dense bool scatter, with duplicate indices (forced by
    concatenating a slice of idx onto itself), off flags, pad indices, and
    bits that are already set; it sets only a slot's first bit (at stride
    2 the visited bit: no pass bit changes), and the words the probe
    already gathered give the same result."""
    rng = np.random.default_rng(seed)
    masks, bm = _rand_slots(rng, q, n, stride)
    m = 11
    idx = rng.integers(-1, n, (q, m)).astype(np.int32)
    idx = np.concatenate([idx, idx[:, : m // 2 + 1]], axis=1)
    on = rng.random(idx.shape) < 0.7
    got = set_bits(bm, jnp.asarray(idx), jnp.asarray(on), stride)
    probed = set_bits(bm, jnp.asarray(idx), jnp.asarray(on), stride,
                      gather_words(bm, jnp.asarray(idx), stride))
    want = masks[0].copy()
    for qi in range(q):
        for j in range(idx.shape[1]):
            if idx[qi, j] >= 0 and on[qi, j]:
                want[qi, idx[qi, j]] = True
    np.testing.assert_array_equal(np.asarray(got),
                                  _pack_slots([want] + masks[1:]))
    np.testing.assert_array_equal(np.asarray(probed), np.asarray(got))


@given(st.integers(1, 4), st.integers(1, 300), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_popcount_vs_sum(q, n, seed):
    rng = np.random.default_rng(seed)
    mask = _rand_mask(rng, q, n)
    got = np.asarray(popcount(pack_bits(jnp.asarray(mask))))
    np.testing.assert_array_equal(got, mask.sum(axis=1).astype(np.int32))


@pytest.mark.parametrize("stride", STRIDES)
def test_set_bits_is_idempotent_or(stride):
    """Setting the same bits twice changes nothing (add == or exactly)."""
    rng = np.random.default_rng(3)
    _, bm = _rand_slots(rng, 3, 90, stride)
    idx = rng.integers(0, 90, (3, 20)).astype(np.int32)
    on = np.ones((3, 20), bool)
    once = set_bits(bm, jnp.asarray(idx), jnp.asarray(on), stride)
    twice = set_bits(once, jnp.asarray(idx), jnp.asarray(on), stride)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))


@pytest.mark.parametrize("helper", ["state_map", "visited_bits"])
@given(st.integers(1, 4), st.integers(1, 300), st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_state_map_vs_bool_oracle(helper, q, n, seed):
    """The walk's state map built from a packed pass bitmap holds each
    node's pass bit and no visited bit; its visited bits come back as the
    packed bitmap of the visited mask."""
    rng = np.random.default_rng(seed)
    passes, visited = _rand_mask(rng, q, n), _rand_mask(rng, q, n)
    if helper == "state_map":
        got = state_map(pack_bits(jnp.asarray(passes)), n)
        slots = [np.zeros_like(passes), passes]
        assert [VISITED, PASS] == [0, 1]
        np.testing.assert_array_equal(np.asarray(got), _pack_slots(slots))
    else:
        got = visited_bits(jnp.asarray(_pack_slots([visited, passes])), n)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(pack_bits(
                                          jnp.asarray(visited))))
