"""Interval-native range predicates (ISSUE 6): Range over huge vocabs must
compile to symbolic (field, lo, hi) clauses whose table bytes are O(1) in
the vocabulary, evaluate bit-identically to the numpy expression-tree
oracle through the kernel / jnp oracle / engine / sharded paths, and
degenerate windows must canonicalize to never() before any table is
packed."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_fallback import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import AnchorAtlas, FiberIndex, build_alpha_knn
from repro.core.batched.bitmap import pack_bits
from repro.core.device_atlas import pack_dnf
from repro.core.predicate import (And, In, Interval, Not, Or, Range,
                                  compile_to_dnf, disjunct_selectivity)
from repro.core.types import Query
from repro.data.ground_truth import attach_ground_truth, recall_at_k
from repro.data.synth import (add_timestamp_field, make_range_queries,
                              make_selectivity_dataset)

from conftest import engine_over, walk_config

BIG = 100_000           # per-field domain the value-set path can't afford
F = 3
VOCAB = [BIG, BIG, 7]
V_CAP = 64

RANGE_SELS = (0.5, 0.1, 0.02)


# -- degenerate windows canonicalize to never() (satellite 2) ---------------

DEGENERATE = [Range(0, 5, 2),              # lo > hi
              Range(0, BIG + 7, BIG + 9),  # entirely out of domain
              Range(2, 7, None),           # beyond a small field's edge
              In(0, [])]                   # empty value-set


@pytest.mark.parametrize("expr", DEGENERATE)
def test_degenerate_windows_compile_to_never(expr):
    d = compile_to_dnf(expr, VOCAB, v_cap=V_CAP)
    assert d.n_disjuncts == 0
    meta = np.asarray([[0, 0, 0], [BIG - 1, 5, 6], [-1, -1, -1]], np.int32)
    assert not d.mask(meta).any()
    assert not expr.mask(meta, VOCAB).any()


def test_degenerate_windows_pack_and_eval_empty():
    """The whole batch of degenerate predicates packs (no blow-up, no
    raise) and every device path returns all-zero pass bitmaps, matching
    the numpy oracle."""
    from repro.kernels import ops, ref

    rng = np.random.default_rng(3)
    meta = np.stack([rng.integers(-1, BIG, 64),
                     rng.integers(-1, BIG, 64),
                     rng.integers(-1, 7, 64)], axis=1).astype(np.int32)
    dnfs = [compile_to_dnf(e, VOCAB, v_cap=V_CAP) for e in DEGENERATE]
    f_np, a_np, b_np, nd = pack_dnf(dnfs, v_cap=V_CAP)
    np.testing.assert_array_equal(nd, 0)
    m = jnp.asarray(meta)
    out_k = np.asarray(ops.filter_eval_batch(
        m, jnp.asarray(f_np), jnp.asarray(a_np), jnp.asarray(nd),
        jnp.asarray(b_np), tn=64))
    out_r = np.asarray(ref.filter_eval_batch(
        m, jnp.asarray(f_np), jnp.asarray(a_np),
        bounds=jnp.asarray(b_np)))
    assert not out_k.any() and not out_r.any()


def test_degenerate_complement_is_whole_domain():
    """Not of an empty window matches every populated code — including
    codes far beyond any bitmap capacity."""
    d = compile_to_dnf(Not(Range(0, 5, 2)), VOCAB, v_cap=V_CAP)
    assert d.disjuncts == (((0, Interval(0, BIG - 1)),),)
    meta = np.asarray([[-1, 0, 0], [0, 0, 0], [BIG - 1, 0, 0]], np.int32)
    np.testing.assert_array_equal(d.mask(meta), [False, True, True])


# -- hypothesis property: device eval == tree oracle on huge vocabs ----------
# (satellite 4)

@st.composite
def big_vocab_expr(draw, max_depth: int = 4):
    """Random expression over two BIG-domain fields and one small field:
    Range windows at interesting scales, In sets straddling v_cap, nested
    And/Or/Not."""
    def leaf():
        kind = draw(st.integers(0, 2))
        if kind == 0:
            f = draw(st.integers(0, 1))
            lo = draw(st.integers(-10, BIG + 10))
            w = draw(st.sampled_from([0, 1, 100, BIG // 10, BIG]))
            return Range(f, lo, lo + w)
        if kind == 1:
            f = draw(st.integers(0, 1))
            vals = draw(st.lists(
                st.sampled_from([0, 1, V_CAP - 1, V_CAP, 1000, BIG - 1]),
                min_size=0, max_size=3))
            return In(f, vals)
        return In(2, draw(st.lists(st.integers(0, 7), min_size=0,
                                   max_size=3)))

    def node(depth):
        kind = draw(st.integers(0, 3)) if depth > 0 else 4
        if kind == 0:
            return Not(node(depth - 1))
        if kind in (1, 2):
            cls = And if kind == 1 else Or
            n_kids = draw(st.integers(0, 2))
            return cls(*[node(depth - 1) for _ in range(n_kids)])
        return leaf()

    return node(draw(st.integers(1, max_depth)))


@st.composite
def big_meta_and_expr(draw):
    n = draw(st.integers(8, 64))
    cols = [draw(st.lists(st.sampled_from(
        [-1, 0, 1, V_CAP - 1, V_CAP, 999, 1000, 1001, BIG // 10,
         BIG - 1]), min_size=n, max_size=n)) for _ in range(2)]
    cols.append(draw(st.lists(st.integers(-1, 7), min_size=n, max_size=n)))
    return (np.stack(cols, axis=1).astype(np.int32),
            draw(big_vocab_expr()))


@given(big_meta_and_expr())
@settings(max_examples=60, deadline=None)
def test_device_eval_matches_tree_oracle_on_big_vocab(me):
    """The tentpole property: for random nested expressions over 10^5-code
    domains, the packed interval tables evaluate bit-identically to the
    expression tree on device (interpret-mode kernel) AND the table bytes
    never depend on the vocabulary width."""
    from repro.kernels import ops

    meta, expr = me
    try:
        dnf = compile_to_dnf(expr, VOCAB, max_disjuncts=64, v_cap=V_CAP)
    except ValueError:
        return  # disjunct bound exceeded: loud, not wrong
    f_np, a_np, b_np, nd = pack_dnf([dnf], v_cap=V_CAP)
    # bitmap rows sized by v_cap (2 words), bounds rows 8 bytes/clause:
    # both independent of the 10^5 domain
    assert a_np.shape[-1] == V_CAP // 32
    assert b_np.nbytes == np.prod(f_np.shape) * 8
    out = np.asarray(ops.filter_eval_batch(
        jnp.asarray(meta), jnp.asarray(f_np), jnp.asarray(a_np),
        jnp.asarray(nd), jnp.asarray(b_np), tn=64))
    got = np.unpackbits(out[0].view(np.uint8),
                        bitorder="little")[: meta.shape[0]].astype(bool)
    want = expr.mask(meta, VOCAB)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(dnf.mask(meta), want)


# -- end-to-end: fused engine on a ~10^6-vocab timestamp field ---------------

@pytest.fixture(scope="module")
def range_sweep():
    """Selectivity corpus + a 2^20-domain timestamp field + prefix-window
    Range queries at engineered selectivities {0.5, 0.1, 0.02}."""
    ds = add_timestamp_field(
        make_selectivity_dataset(RANGE_SELS, n=2400, d=48, n_components=16))
    graph = build_alpha_knn(ds.vectors, k=16, r_max=48, alpha=1.2)
    atlas = AnchorAtlas.build(ds, seed=0)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    queries = []
    for sel in RANGE_SELS:
        queries.extend(make_range_queries(ds, sel, 12))
    attach_ground_truth(ds, queries, k=10)
    return ds, index, queries


@pytest.fixture(scope="module")
def range_engine(range_sweep):
    ds, index, _ = range_sweep
    return engine_over(index, walk_config(k=10, beam_width=4),
                       vocab_sizes=ds.vocab_sizes)


def test_range_batch_packs_interval_tables(range_sweep, range_engine):
    """Range traffic takes the rank-3 + bounds path; the bounds table is
    O(clauses), not O(window width), and a pure-categorical batch keeps
    bounds=None (legacy byte-compat)."""
    ds, _, queries = range_sweep
    _, fields, allowed, bounds = range_engine._pack_queries(queries[:8])
    assert fields.ndim == 3 and bounds is not None
    assert bounds.shape == (*fields.shape, 2)
    assert bounds.nbytes == int(np.prod(fields.shape)) * 8  # 2 i32 / clause
    from repro.core.types import FilterPredicate
    cat = [Query(vector=q.vector, predicate=FilterPredicate.make({0: [1]}))
           for q in queries[:4]]
    _, f_c, _, b_c = range_engine._pack_queries(cat)
    assert f_c.ndim == 2 and b_c is None


def test_range_pass_bitmaps_match_tree_oracle_bitexact(range_sweep,
                                                       range_engine):
    ds, _, queries = range_sweep
    _, fields, allowed, bounds = range_engine._pack_queries(queries)
    got = np.asarray(range_engine._passes(range_engine.shards[0].metadata,
                                          fields, allowed, bounds))
    want = np.asarray(pack_bits(jnp.asarray(np.stack(
        [q.predicate.mask(ds.metadata, ds.vocab_sizes) for q in queries]))))
    np.testing.assert_array_equal(got, want)


def test_range_fused_single_dispatch_matches_hostloop(range_sweep,
                                                      range_engine):
    """One device dispatch per Range batch; fused results == host-driven
    round loop (the migration baseline), and every result obeys the window
    with solid recall at each selectivity."""
    from repro.core.search import SearchParams, run_queries

    ds, index, queries = range_sweep
    d0 = range_engine.dispatches
    ids_f, _ = range_engine.search(queries)
    assert range_engine.dispatches - d0 == 1
    ids_h, _ = range_engine.search_hostloop(queries)
    by_sel: dict = {}
    for q, row_f, row_h in zip(queries, ids_f, ids_h):
        np.testing.assert_array_equal(np.asarray(row_f), np.asarray(row_h))
        row = np.asarray(row_f)
        assert row.size > 0
        assert q.predicate.mask(ds.metadata, ds.vocab_sizes)[row].all()
        by_sel.setdefault(q.selectivity, []).append(
            recall_at_k(row, q.gt_ids))
    # the sequential host path (atlas dict-scan over interval specs) is the
    # reference the fused recall must stay within epsilon of
    ids_seq, _ = run_queries(index, queries,
                             SearchParams(k=10, walk="guided", beam_width=2))
    rec_seq = float(np.mean([recall_at_k(ids_seq[i], queries[i].gt_ids)
                             for i in range(len(queries))]))
    rec_b = float(np.mean([r for recs in by_sel.values() for r in recs]))
    assert rec_b > rec_seq - 0.1, (rec_b, rec_seq)
    for sel, recs in by_sel.items():
        assert float(np.mean(recs)) > 0.5, (sel, np.mean(recs))


def test_mixed_interval_and_categorical_batch(range_sweep, range_engine):
    """A query's result must not depend on its batch-mates: a categorical
    conjunction answered alone == answered next to Range queries (the
    mixed batch takes the interval program; semantics are unchanged)."""
    from repro.core.types import FilterPredicate
    ds, _, queries = range_sweep
    conj = Query(vector=queries[0].vector,
                 predicate=FilterPredicate.make({0: [1]}))
    solo_ids, _ = range_engine.search([conj])
    mixed_ids, _ = range_engine.search([conj] + queries[:3])
    np.testing.assert_array_equal(np.asarray(solo_ids[0]),
                                  np.asarray(mixed_ids[0]))


def test_rare_disjuncts_pack_first(range_sweep, range_engine):
    """Short-circuit ordering: in an interval batch, a query's disjuncts
    are packed ascending by estimated selectivity, so the kernel evaluates
    the rare window first and can skip the broad tail."""
    ds, _, queries = range_sweep
    f_ts = ds.field_names.index("ts")
    narrow = Range(f_ts, 0, 99)                      # ~1e-4 of the domain
    wide = Range(f_ts, 0, (1 << 20) - 1)             # the whole domain
    q = Query(vector=queries[0].vector, predicate=Or(wide, narrow))
    _, fields, allowed, bounds = range_engine._pack_queries([q])
    b = np.asarray(bounds)
    assert b[0, 0, 0, 1] == 99          # narrow window first
    assert b[0, 1, 0, 1] == (1 << 20) - 1
    sels = []
    for dd in range(2):
        iv = Interval(int(b[0, dd, 0, 0]), int(b[0, dd, 0, 1]))
        sels.append(disjunct_selectivity(((f_ts, iv),), ds.vocab_sizes))
    assert sels == sorted(sels)


def test_atlas_interval_cluster_match_is_conservative(range_sweep,
                                                      range_engine):
    """Device envelope-overlap cluster matching is a superset of the exact
    host scan (never misses a candidate cluster), for every range query."""
    ds, index, queries = range_sweep
    from repro.core.predicate import as_dnf
    datlas = range_engine.shards[0].datlas
    for q in queries[::6]:
        dnf = as_dnf(q.predicate, ds.vocab_sizes, v_cap=datlas.v_cap)
        host = set(index.atlas.matching_clusters(dnf).tolist())
        f_np, a_np, b_np, _ = pack_dnf([dnf], v_cap=datlas.v_cap)
        dev = np.nonzero(np.asarray(datlas.matching_clusters_batch(
            jnp.asarray(f_np), jnp.asarray(a_np), jnp.asarray(b_np)))[0])[0]
        assert host <= set(dev.tolist())


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import numpy as np, jax
    from repro.core.batched.engine import Engine
    from repro.core.batched.sharded import build_sharded_index
    from repro.core.config import FnsConfig, WalkConfig
    from repro.core.predicate import Range
    from repro.core.types import Query
    from repro.data.synth import (add_timestamp_field, make_range_queries,
                                  make_selectivity_dataset)

    from repro.launch.mesh import make_local_mesh

    ds = add_timestamp_field(
        make_selectivity_dataset((0.5, 0.1, 0.02), n=1200, d=32,
                                 n_components=12))
    queries = []
    for sel in (0.5, 0.1, 0.02):
        queries.extend(make_range_queries(ds, sel, 4))
    f_ts = ds.field_names.index("ts")
    # a degenerate window rides along: empty result, batch unharmed
    queries.append(Query(vector=queries[0].vector,
                         predicate=Range(f_ts, 10, 2)))
    sidx = build_sharded_index(ds.vectors, ds.metadata, 4, graph_k=8,
                               r_max=24)
    mesh = make_local_mesh(data=4, model=1)
    eng = Engine(sidx, FnsConfig(walk=WalkConfig(k=10, beam_width=4)), mesh)
    ids_m, st_m = eng.search(queries)
    assert eng.dispatches == 1, eng.dispatches
    ids_r, st_r = eng.search_reference(queries)
    for i, (a, b) in enumerate(zip(ids_m, ids_r)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), i
    assert np.array_equal(st_m["walks"], st_r["walks"])
    assert np.array_equal(st_m["hops"], st_r["hops"])
    assert np.asarray(ids_m[-1]).size == 0    # the degenerate window
    for q, row in zip(queries[:-1], ids_m):
        row = np.asarray(row)
        assert row.size > 0
        assert q.predicate.mask(ds.metadata, ds.vocab_sizes)[row].all()
    print("sharded-range-parity ok")
""")


@pytest.mark.slow
def test_sharded_range_bit_identity_subprocess():
    """4-shard mesh dispatch == single-device per-shard programs + merge,
    bit-identical, for interval-clause Range batches (8 virtual CPU
    devices in a subprocess), with a degenerate window riding along."""
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True, timeout=420, cwd=".")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sharded-range-parity ok" in r.stdout
