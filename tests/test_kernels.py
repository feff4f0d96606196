"""Pallas kernel vs pure-jnp oracles, and the jnp expansion op: shape
sweeps + properties."""
import numpy as np
import jax.numpy as jnp
import pytest
try:
    from hypothesis import example, given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import example, given, settings, strategies as st

from repro.core.types import FilterPredicate
from repro.kernels import ops, ref


def _mk(n, d, Q, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    corpus = rng.standard_normal((n, d)).astype(dtype)
    queries = rng.standard_normal((Q, d)).astype(dtype)
    nw = (n + 31) // 32
    bitmap = rng.integers(0, 2**32, (Q, nw), dtype=np.uint32)
    return corpus, queries, bitmap


@given(st.integers(10, 300), st.integers(1, 3), st.integers(0, 100))
@settings(max_examples=15, deadline=None)
@example(n=65, n_clauses=1, seed=0)  # a failure hypothesis once found
def test_filter_eval_matches_core_mask(n, n_clauses, seed):
    rng = np.random.default_rng(seed)
    F = 6
    meta = rng.integers(-1, 40, (n, F)).astype(np.int32)
    clauses = {int(f): rng.integers(0, 40, rng.integers(1, 4)).tolist()
               for f in rng.choice(F, n_clauses, replace=False)}
    pred = FilterPredicate.make(clauses)
    fields, allowed = ops.predicate_tables(pred, F)
    bm = np.asarray(ops.filter_eval(jnp.asarray(meta), jnp.asarray(fields),
                                    jnp.asarray(allowed), tn=64))
    unpacked = np.unpackbits(bm.view(np.uint8), bitorder="little")[:n]
    np.testing.assert_array_equal(unpacked.astype(bool), pred.mask(meta))


def test_filter_eval_vs_ref_oracle():
    rng = np.random.default_rng(0)
    meta = rng.integers(-1, 50, (777, 8)).astype(np.int32)
    fields = np.asarray([2, 5, -1, -1], np.int32)
    allowed = np.zeros((4, 256), np.uint8)
    allowed[0, [3, 4, 5]] = 1
    allowed[1, list(range(25))] = 1
    out_k = ops.filter_eval(jnp.asarray(meta), jnp.asarray(fields),
                            jnp.asarray(allowed), tn=128)
    out_r = ref.filter_eval(jnp.asarray(meta), jnp.asarray(fields),
                            jnp.asarray(allowed))
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


@pytest.mark.parametrize("n,d,Q,R", [(64, 16, 2, 5), (500, 64, 7, 24),
                                     (1000, 128, 3, 48)])
def test_fiber_expand_walk_sweep(n, d, Q, R):
    """The walk's expansion op: its first output is the plain gather+dot
    masked by id validity only, its second additionally by the id's pass
    bit, as the walk's probe hands it in (checked against a float64 numpy
    gather+dot)."""
    corpus, queries, bitmap = _mk(n, d, Q, seed=R + 1)
    rng = np.random.default_rng(R + 1)
    ids = rng.integers(-1, n, (Q, R)).astype(np.int32)
    safe = np.maximum(ids, 0)
    bits = (np.take_along_axis(bitmap, safe >> 5, axis=1)
            >> (safe & 31).astype(np.uint32)) & 1
    s_r, p_r = ref.fiber_expand_walk(jnp.asarray(queries), jnp.asarray(corpus),
                                     jnp.asarray(ids), jnp.asarray(bits == 1))
    want = np.einsum("qrd,qd->qr", corpus[safe].astype(np.float64),
                     queries.astype(np.float64))
    valid = ids >= 0
    np.testing.assert_allclose(np.asarray(s_r),
                               np.where(valid, want, -np.inf),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(p_r),
                               np.where(valid & (bits == 1), want, -np.inf),
                               rtol=1e-5, atol=1e-5)


@given(st.integers(10, 300), st.integers(1, 3), st.integers(0, 100))
@settings(max_examples=10, deadline=None)
def test_filter_eval_batch_matches_core_mask(n, n_clauses, seed):
    """Batched kernel == oracle == per-query FilterPredicate.mask, from the
    pack_predicates clause tables (the engine's single-dispatch path)."""
    from repro.core.device_atlas import pack_predicates

    rng = np.random.default_rng(seed)
    F = 6
    meta = rng.integers(-1, 40, (n, F)).astype(np.int32)
    preds = []
    for _ in range(3):
        clauses = {int(f): rng.integers(0, 40, rng.integers(1, 4)).tolist()
                   for f in rng.choice(F, n_clauses, replace=False)}
        preds.append(FilterPredicate.make(clauses))
    preds.append(FilterPredicate.make({}))  # unconstrained: pad bits stay 0
    f_np, a_np = pack_predicates(preds, v_cap=64)
    out_k = np.asarray(ops.filter_eval_batch(
        jnp.asarray(meta), jnp.asarray(f_np), jnp.asarray(a_np), tn=64))
    out_r = np.asarray(ref.filter_eval_batch(
        jnp.asarray(meta), jnp.asarray(f_np), jnp.asarray(a_np)))
    np.testing.assert_array_equal(out_k, out_r)
    for qi, pred in enumerate(preds):
        unpacked = np.unpackbits(out_k[qi].view(np.uint8),
                                 bitorder="little")[:n]
        np.testing.assert_array_equal(unpacked.astype(bool), pred.mask(meta))


def _tables(form, q_n, n_fields, rng, d_n=2, c_n=3, wv=2):
    """Random clause tables of one form: conjunctive (Q, C), disjunctive
    (Q, D, C) with a dead-disjunct tail, or disjunctive with interval
    bounds. Returns (fields, allowed, n_disj, bounds)."""
    if form == "rank2":
        fields = rng.integers(-1, n_fields, (q_n, c_n)).astype(np.int32)
        allowed = rng.integers(0, 2**32, (q_n, c_n, wv), dtype=np.uint32)
        return fields, allowed, None, None
    fields = rng.integers(-1, n_fields, (q_n, d_n, c_n)).astype(np.int32)
    fields[1::3, 1:, :] = -2                     # dead disjuncts
    allowed = rng.integers(0, 2**32, (q_n, d_n, c_n, wv), dtype=np.uint32)
    from repro.kernels.filter_eval import table_n_disj
    n_disj = np.asarray(table_n_disj(jnp.asarray(fields)))
    if form == "dnf":
        return fields, allowed, n_disj, None
    bounds = np.zeros((q_n, d_n, c_n, 2), np.int32)
    bounds[..., 1] = -1                          # value-set clauses
    bounds[::2, 0, 0] = [5, 20]                  # interval clauses
    bounds[1::4, -1, -1] = [0, 3]
    return fields, allowed, n_disj, bounds


@pytest.mark.parametrize("form", ["rank2", "dnf", "dnf_bounds"])
@pytest.mark.parametrize("n,q_n,tn", [(300, 11, 8192), (9000, 20, 4096)])
def test_filter_eval_batch_forms_tiles(form, n, q_n, tn):
    """Every table form, one corpus tile and several (lane-aligned 4096-row
    tiles with a ragged tail), query counts that pad the 8-query tile:
    kernel == oracle bit for bit, and pad bits beyond n are 0."""
    rng = np.random.default_rng(n + q_n)
    meta = rng.integers(-1, 64, (n, 5)).astype(np.int32)
    fields, allowed, n_disj, bounds = _tables(form, q_n, 5, rng)
    args = [jnp.asarray(meta), jnp.asarray(fields), jnp.asarray(allowed),
            None if n_disj is None else jnp.asarray(n_disj),
            None if bounds is None else jnp.asarray(bounds)]
    out_k = np.asarray(ops.filter_eval_batch(*args, tn=tn))
    out_r = np.asarray(ref.filter_eval_batch(*args))
    np.testing.assert_array_equal(out_k, out_r)
    assert out_k.shape == (q_n, (n + 31) // 32)
    if n % 32:
        assert not (out_k[:, -1] >> np.uint32(n % 32)).any()


def test_filter_eval_batch_wide_tables():
    """The widest tables the packers emit: 8 disjuncts x 8 clauses over a
    1024-value bitmap (32 words), with intervals riding along."""
    rng = np.random.default_rng(5)
    meta = rng.integers(-1, 1024, (700, 6)).astype(np.int32)
    fields, allowed, n_disj, bounds = _tables("dnf_bounds", 9, 6, rng,
                                              d_n=8, c_n=8, wv=32)
    args = [jnp.asarray(meta), jnp.asarray(fields), jnp.asarray(allowed),
            jnp.asarray(n_disj), jnp.asarray(bounds)]
    np.testing.assert_array_equal(
        np.asarray(ops.filter_eval_batch(*args)),
        np.asarray(ref.filter_eval_batch(*args)))


@pytest.mark.parametrize("tn", [96, 2048])
def test_filter_eval_tile_must_be_lane_aligned(tn):
    """A Mosaic sweep over several tiles needs whole 32-row x 128-lane
    tiles; the knob is named at trace time, before any lowering."""
    from repro.kernels.filter_eval import filter_eval_batch

    meta = jnp.zeros((9000, 2), jnp.int32)
    fields = jnp.zeros((1, 4), jnp.int32)
    allowed = jnp.zeros((1, 4, 8), jnp.uint32)
    with pytest.raises(ValueError, match="filter_tile"):
        filter_eval_batch(meta, fields, allowed, tn=tn, interpret=False)
