"""Tag-set fields (``core.tags``) and the ``HasAny`` leaf, against plain
references on seeded random data: the ``filter_eval`` kernel (interpret
mode) against the jnp oracle and numpy, ``RetrievalService.query_batch``
against a brute-force filtered top-k, the atlas presence of a tag-set
field, ingest and snapshots of tagged rows, and the errors the schema
raises."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import HasAny, In, Not, Or, Range, compile_to_dnf
from repro.core.batched.bitmap import unpack_bits
from repro.core.batched.engine import clause_passes
from repro.core.batched.insert import emit_device_atlas
from repro.core.config import FnsConfig
from repro.core.device_atlas import pack_dnf
from repro.core.predicate import DNF, AnyOf, Interval
from repro.core.tags import label_bits, n_tag_words, pack_labels
from repro.core.types import Dataset, FilterPredicate, Query
from repro.data.synth import SynthSpec, make_dataset
from repro.kernels import ref
from repro.kernels.filter_eval import filter_eval_batch
from repro.serve.retrieval import RetrievalService

V = 150                  # labels: five words, the last one part-used
W = n_tag_words(V)
TAG = 1                  # the tag-set field's first column
DATE = TAG + W           # a day-number field after it
CAT = DATE + 1           # a small categorical field
N_DAYS = 1000
VOCAB = [4] + [V] + [0] * (W - 1) + [N_DAYS, 6]
K = 10
# budgets at which the lockstep walk reproduces the exact filtered top-k
# on this corpus (the comparisons below are exact, not recall floors)
KNOBS = {"graph.graph_k": 16, "graph.r_max": 32, "walk.k": K,
         "walk.jump_budget": 8, "walk.beam_width": 16,
         "walk.frontier_cap": 32, "walk.n_seeds": 32, "walk.c_max": 8,
         "walk.max_hops": 300, "walk.stall_budget": 300}


def _labels(rng, n):
    """1-4 labels a row, one row in eight with none."""
    return [rng.choice(V, rng.integers(1, 5), replace=False)
            if rng.random() > 0.125 else [] for _ in range(n)]


def _metadata(rng, n):
    return np.concatenate([
        rng.integers(0, 4, (n, 1)), pack_labels(_labels(rng, n), V),
        rng.integers(0, N_DAYS, (n, 1)), rng.integers(0, 6, (n, 1))],
        axis=1).astype(np.int32)


def _names():
    return (["kind", "labels"] + [f"labels.{w}" for w in range(1, W)]
            + ["date", "cat"])


def _dataset(n=600, seed=3):
    base = make_dataset(SynthSpec(n=n, d=32, n_components=12, n_fields=1,
                                  seed=seed))
    meta = _metadata(np.random.default_rng(seed), n)
    return Dataset(base.vectors, meta, _names(), list(VOCAB),
                   tag_fields={TAG: V})


def _window(lo, width):
    return Range(DATE, lo, lo + width)


# -- the filter_eval sweep: kernel (interpret) == jnp oracle == numpy -------

SWEEP_CASES = {
    "labels_in_several_words": [HasAny(TAG, [0, 40, 77, 149]),
                                HasAny(TAG, [31, 32, 63, 64])],
    "empty_sets": [HasAny(TAG, []), HasAny(TAG, [5]),
                   HasAny(TAG, range(V))],
    "word_4_only": [HasAny(TAG, [130, 149]), HasAny(TAG, [128])],
    "tag_and_interval": [HasAny(TAG, [3, 90]) & _window(100, 400),
                         _window(0, 50) & HasAny(TAG, [140, 141, 142])],
    "two_tag_clauses_one_field": [
        HasAny(TAG, [1, 2, 130]) & HasAny(TAG, [2, 60, 149])],
    "tag_value_set_and_interval": [
        HasAny(TAG, [7, 8]) & In(CAT, [1, 2]) & _window(200, 500)],
    **{f"{d}_disjuncts": [Or(*[HasAny(TAG, [j, 37 * j % V])
                               & _window(90 * j, 120) if j % 2 else
                               HasAny(TAG, [140 - j]) & In(0, [j % 4])
                               for j in range(d)])]
       for d in (2, 3, 5, 8)},
}


@pytest.mark.parametrize("case", sorted(SWEEP_CASES))
def test_filter_eval_tag_clauses_match_oracle_and_numpy(case):
    rng = np.random.default_rng(len(case))
    meta = _metadata(rng, 700)
    meta[::9, TAG:TAG + W] = 0           # empty sets
    meta[1::13, TAG:TAG + W] = 0         # labels in word 4 only
    meta[1::13, TAG + 4] = 1 << 20
    exprs = SWEEP_CASES[case]
    dnfs = [compile_to_dnf(e, VOCAB, v_cap=256) for e in exprs]
    if case == "empty_sets":             # an empty label set never passes
        dnfs.append(DNF((((TAG, AnyOf(())),),)))
    d_max = max(1, max(d.n_disjuncts for d in dnfs))
    f, a, b, nd = (jnp.asarray(x) for x in pack_dnf(
        dnfs, max_disjuncts=d_max, v_cap=256))
    args = (jnp.asarray(meta), f, a, nd, b)
    got = filter_eval_batch(*args, tn=256, interpret=True, tag_cols=(TAG,))
    want = ref.filter_eval_batch(*args, tag_cols=(TAG,))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    dense = np.asarray(unpack_bits(got, meta.shape[0]))
    for j, dnf in enumerate(dnfs):
        np.testing.assert_array_equal(dense[j], dnf.mask(meta))
        if j < len(exprs):
            np.testing.assert_array_equal(dense[j],
                                          exprs[j].mask(meta, VOCAB))
    assert dense.any(axis=1).sum() >= len(exprs) - (case == "empty_sets")


def test_tag_oracle_reads_each_word_of_the_field():
    """``ref.tag_ok`` against a per-row set test, label by label."""
    rng = np.random.default_rng(4)
    sets = _labels(rng, 300)
    meta = np.concatenate([np.zeros((300, 1)), pack_labels(sets, V)],
                          axis=1).astype(np.int32)
    np.testing.assert_array_equal(
        label_bits(meta[:, 1:], V),
        np.asarray([[lab in s for lab in range(V)] for s in map(set, sets)]))
    queries = [[0], [33, 149], [], list(range(64, 96))]
    words = pack_labels(queries, V)
    words = np.pad(words, ((0, 0), (0, 8 - W))).view(np.uint32)
    got = np.asarray(ref.tag_ok(jnp.asarray(meta), jnp.full(4, 1),
                                jnp.asarray(words)))
    want = [[bool(set(q) & set(s)) for s in sets] for q in queries]
    np.testing.assert_array_equal(got, np.asarray(want))


# -- the service against a brute-force filtered top-k -----------------------

@pytest.fixture(scope="module")
def tagged():
    ds = _dataset()
    svc = RetrievalService.build(ds, config=FnsConfig().with_knobs(KNOBS))
    return ds, svc


def _families(rng):
    """Predicate families by name, 4-8 queries each."""
    out = {"tag_and_window": [], "tag_only": [], "window_only": [],
           "disjunctive": [], "no_row_passes": []}
    for _ in range(8):
        labs = rng.choice(V, rng.integers(1, 9), replace=False)
        lo = int(rng.integers(0, 800))
        out["tag_and_window"].append(
            HasAny(TAG, labs) & _window(lo, int(rng.integers(50, 400))))
    for _ in range(4):
        out["tag_only"].append(HasAny(TAG, rng.choice(V, 3, replace=False)))
        lo = int(rng.integers(0, 900))
        out["window_only"].append(_window(lo, 60))
        j = int(rng.integers(0, 140))
        out["disjunctive"].append(Or(HasAny(TAG, [j, j + 1]),
                                     HasAny(TAG, [j + 5]) & _window(0, 300),
                                     In(CAT, [5]) & _window(600, 650)))
    out["no_row_passes"] = [HasAny(TAG, [3]) & _window(N_DAYS, 100),
                            HasAny(TAG, [149]) & _window(500, -1),
                            HasAny(TAG, [10]) & HasAny(TAG, [11])
                            & _window(0, 0) & In(CAT, [9])]
    return out


@pytest.fixture(scope="module")
def answered(tagged):
    """One batch of every family, and the brute-force answers."""
    ds, svc = tagged
    rng = np.random.default_rng(7)
    fams = _families(rng)
    preds = [p for ps in fams.values() for p in ps]
    src = rng.integers(0, ds.n, len(preds))
    q = ds.vectors[src] + 0.05 * rng.standard_normal(
        (len(preds), ds.d)).astype(np.float32)
    q /= np.linalg.norm(q, axis=1, keepdims=True)
    ids, stats = svc.query_batch(q, preds)
    out, at = {}, 0
    for name, ps in fams.items():
        rows = []
        for p in ps:
            passes = p.mask(ds.metadata, ds.vocab_sizes)
            cand = np.nonzero(passes)[0]
            sims = ds.vectors[cand] @ q[at]
            order = np.argsort(-sims, kind="stable")[:K]
            rows.append((passes, cand[order], sims[order], q[at], ids[at]))
            at += 1
        out[name] = rows
    return out, stats


@pytest.mark.parametrize("family", ["tag_and_window", "tag_only",
                                    "window_only", "disjunctive",
                                    "no_row_passes"])
def test_query_batch_matches_brute_force(tagged, answered, family):
    ds, _ = tagged
    rows, stats = answered
    assert "errors" not in stats
    for passes, want, want_sims, q, got in rows[family]:
        got = np.asarray(got)
        assert passes[got].all()
        np.testing.assert_array_equal(got, want)
        np.testing.assert_allclose(ds.vectors[got] @ q, want_sims,
                                   rtol=0, atol=1e-6)
        if family == "no_row_passes":
            assert not passes.any() and got.size == 0


def test_tag_batch_takes_the_rank3_tables_and_counts_its_passes(tagged):
    ds, svc = tagged
    eng = svc.engine()
    v = ds.vectors[:3]
    tag_q = [Query(v[0], HasAny(TAG, [0, 40, 149]) & _window(1, 9)),
             Query(v[1], Or(HasAny(TAG, [2]), In(CAT, [1, 3]))),
             Query(v[2], FilterPredicate.make({CAT: [0]}))]
    _, f, a, b = eng._pack_queries(tag_q)
    assert f.ndim == 3 and b is not None
    # three words + one interval; one word + one word; one word
    assert int(clause_passes(f, a, b)) == 4 + 2 + 1
    _, stats = svc.query_batch(v, [q.predicate for q in tag_q])
    assert stats["clause_passes"] == 7


@pytest.mark.parametrize("tags", [False, True])
def test_categorical_batches_keep_the_rank2_program(tags):
    """A value-set batch packs the (Q, C) tables with no bounds, on an
    index with or without a tag-set field, and counts one pass a word."""
    ds = _dataset(n=300)
    if not tags:
        ds = Dataset(ds.vectors, ds.metadata[:, [0, CAT]], ["kind", "cat"],
                     [4, 6])
    col = ds.n_fields - 1
    svc = RetrievalService.build(ds, config=FnsConfig().with_knobs(KNOBS))
    preds = [FilterPredicate.make({0: [1], col: [2, 3]}),
             FilterPredicate.make({col: [4]})]
    queries = [Query(ds.vectors[i], p) for i, p in enumerate(preds)]
    _, f, a, b = svc.engine()._pack_queries(queries)
    assert f.ndim == 2 and b is None
    ids, stats = svc.query_batch(ds.vectors[:2], preds)
    assert stats["clause_passes"] == 3
    for p, row in zip(preds, ids):
        assert p.mask(ds.metadata)[np.asarray(row)].all()


def test_sequential_path_answers_tag_predicates(tagged):
    ds, svc = tagged
    pred = HasAny(TAG, [4, 5, 6]) & _window(100, 600)
    ids, _, _ = svc.query(ds.vectors[0], pred)
    assert len(ids) and pred.mask(ds.metadata, ds.vocab_sizes)[ids].all()


# -- the atlas: presence of a tag-set field is the OR of its rows' masks ----

def test_atlas_presence_is_the_or_of_row_masks(tagged):
    ds, svc = tagged
    atlas = svc.index.atlas
    dev = atlas.to_device()
    labels = label_bits(ds.metadata[:, TAG:TAG + W], V)
    k = atlas.n_clusters
    want = np.zeros((k, V), bool)
    np.logical_or.at(want, atlas.assign, labels)
    pres = np.asarray(dev.presence[TAG]).view(np.uint32)
    got = ((pres[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1)
    np.testing.assert_array_equal(got.reshape(k, -1)[:, :V], want)
    assert not got.reshape(k, -1)[:, V:].any()
    assert not np.asarray(dev.presence[TAG + 1:DATE]).any()
    # the slab path emits the same presence
    slab = RetrievalService.build(
        ds, config=FnsConfig().with_knobs(
            {**KNOBS, "serve.capacity": ds.n + 32})).engine()
    datlas = slab.shards[0].datlas
    np.testing.assert_array_equal(np.asarray(datlas.presence[TAG]),
                                  np.asarray(dev.presence[TAG]))
    np.testing.assert_array_equal(
        np.asarray(emit_device_atlas(slab.state.shards[0], datlas.v_cap,
                                     ds.tag_fields).presence),
        np.asarray(datlas.presence))


# -- ingest and snapshots ----------------------------------------------------

def _live_service(n=600):
    ds = _dataset(n=n)
    base = Dataset(ds.vectors[:n - 40], ds.metadata[:n - 40],
                   ds.field_names, ds.vocab_sizes, tag_fields=ds.tag_fields)
    cfg = FnsConfig().with_knobs({**KNOBS, "serve.capacity": n + 64})
    return ds, RetrievalService.build(base, config=cfg)


def test_ingested_tagged_rows_are_found_by_has_any():
    ds, svc = _live_service()
    fresh = ds.metadata[-40:].copy()
    fresh[:, TAG:TAG + W] = pack_labels([[148, 3]] * 20 + [[148]] * 20, V)
    pred = HasAny(TAG, [148]) & Range(DATE, 0, N_DAYS - 1)
    before = pred.mask(ds.metadata[:-40], ds.vocab_sizes).sum()
    gids = svc.ingest(ds.vectors[-40:], fresh)
    ids, _ = svc.query_batch(ds.vectors[-40:-30], [pred] * 10)
    for g, row in zip(gids[:10], ids):
        assert int(g) in np.asarray(row).tolist()
    meta = np.concatenate([ds.metadata[:-40], fresh])
    for row in ids:
        assert pred.mask(meta, ds.vocab_sizes)[np.asarray(row)].all()
    assert before + 40 == pred.mask(meta, ds.vocab_sizes).sum()
    bad = fresh[:1].copy()
    bad[0, TAG + W - 1] = -1             # labels 128..159: past V
    with pytest.raises(ValueError, match="beyond the vocabulary"):
        svc.ingest(ds.vectors[:1], bad)


def test_snapshot_round_trip_keeps_the_tag_schema(tmp_path):
    ds, svc = _live_service()
    svc.ingest(ds.vectors[-40:], ds.metadata[-40:])
    svc.enable_durability(str(tmp_path))
    preds = [HasAny(TAG, [j, j + 50]) & _window(10 * j, 300)
             for j in range(8)]
    ids0, _ = svc.query_batch(ds.vectors[:8], preds)
    svc2 = RetrievalService.recover(str(tmp_path))
    eng = svc2.engine()
    assert eng.tag_fields == ((TAG, V),)
    assert eng.state.tag_fields == ((TAG, V),)
    ids1, _ = svc2.query_batch(ds.vectors[:8], preds)
    for a, b in zip(ids0, ids1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- what the schema refuses -------------------------------------------------

def test_not_over_has_any_raises(tagged):
    ds, svc = tagged
    expr = Not(HasAny(TAG, [1])) & _window(0, 10)
    with pytest.raises(ValueError, match="Not over HasAny"):
        expr.mask(ds.metadata, ds.vocab_sizes)
    with pytest.raises(ValueError, match="Not over HasAny"):
        compile_to_dnf(expr, ds.vocab_sizes)
    ids, stats = svc.query_batch(ds.vectors[:2], [expr, HasAny(TAG, [1])])
    assert "Not over HasAny" in stats["errors"][0]
    assert stats["errors"][1] is None and len(ids[0]) == 0


@pytest.mark.parametrize("pred, match", [
    (HasAny(DATE, [1]), "not a tag-set field"),
    (HasAny(TAG + 1, [1]), "not a tag-set field"),
    (In(TAG, [1]), "constrain it with HasAny"),
    (Range(TAG + 2, 0, 5), "constrain it with HasAny"),
    (FilterPredicate.make({TAG: [3]}), "constrain it with HasAny"),
    (HasAny(TAG, [1]) & In(TAG, [1]), "constrain it with HasAny"),
])
def test_clauses_that_misread_the_schema_are_refused(tagged, pred, match):
    ds, svc = tagged
    ids, stats = svc.query_batch(ds.vectors[:1], [pred])
    assert match in stats["errors"][0] and len(ids[0]) == 0


@pytest.mark.parametrize("tag_fields, match", [
    ({0: 1025}, "auto_v_cap_max"),
    ({0: 200_000}, "auto_v_cap_max"),
    ({38: 96}, "needs columns"),
    ({0: 40, 1: 8}, "overlaps"),
])
def test_tag_schemas_that_cannot_be_served_raise(tag_fields, match):
    meta = np.zeros((4, 40), np.int32)
    with pytest.raises(ValueError, match=match):
        Dataset(np.eye(4, 8, dtype=np.float32), meta,
                [f"f{c}" for c in range(40)], [1] * 40,
                tag_fields=tag_fields)


def test_a_tag_vocabulary_wider_than_the_value_bitmaps_raises():
    ds = _dataset(n=200)
    cfg = FnsConfig().with_knobs({**KNOBS, "atlas.v_cap": 128})
    with pytest.raises(ValueError, match="value bitmaps hold 128"):
        RetrievalService.build(ds, config=cfg).engine()


def test_interval_clause_specs_are_unchanged_by_tags():
    dnf = compile_to_dnf(HasAny(TAG, [2]) & Range(DATE, 5, 9), VOCAB)
    assert dnf.disjuncts == (((TAG, AnyOf((2,))), (DATE, Interval(5, 9))),)
    assert dnf.has_tags and dnf.has_intervals
    assert not compile_to_dnf(Range(DATE, 5, 9), VOCAB).has_tags
    with pytest.raises(ValueError, match="both as a tag set"):
        compile_to_dnf(HasAny(TAG, [1]) & In(TAG, [1]))


# -- the counter on the unpack span, and the sharded engine -----------------

def test_unpack_span_carries_the_clause_passes(tagged, tmp_path):
    import jax
    from jax.profiler import ProfileData

    ds, svc = tagged
    preds = [HasAny(TAG, [0, 40, 149]) & _window(0, 500),
             HasAny(TAG, [9])]
    svc.query_batch(ds.vectors[:2], preds)      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _, stats = svc.query_batch(ds.vectors[:2], preds)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    unpack = [dict(ev.stats) for plane in
              ProfileData.from_file(str(path)).planes
              for line in plane.lines for ev in line.events
              if ev.name == "fns.unpack"]
    assert len(unpack) == 1
    assert int(unpack[0]["clause_passes"]) == stats["clause_passes"] == 5


SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path[:0] = ["src", "tests"]
    import numpy as np
    import test_tag_sets as T
    from repro.core.batched.engine import Engine
    from repro.core.batched.sharded import build_sharded_index
    from repro.core.config import FnsConfig
    from repro.core.types import Query
    from repro.launch.mesh import make_local_mesh

    ds = T._dataset()
    cfg = FnsConfig().with_knobs(T.KNOBS)
    sidx = build_sharded_index(ds.vectors, ds.metadata, 4, config=cfg,
                               tag_fields=ds.tag_fields)
    eng = Engine(sidx, cfg, make_local_mesh(data=4, model=1))
    rng = np.random.default_rng(8)
    preds = [T.HasAny(T.TAG, rng.choice(T.V, 4, replace=False))
             & T._window(int(rng.integers(0, 600)), 300) for _ in range(8)]
    queries = [Query(ds.vectors[i], p) for i, p in enumerate(preds)]
    ids_m, st_m = eng.search(queries)
    ids_r, st_r = eng.search_reference(queries)
    for a, b in zip(ids_m, ids_r):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert st_m["clause_passes"] == st_r["clause_passes"] > 0
    found = 0
    for q, row in zip(queries, ids_m):
        passes = q.predicate.mask(ds.metadata, ds.vocab_sizes)
        assert passes[np.asarray(row)].all()
        found += np.asarray(row).size > 0
    assert found == len(queries)
    print("sharded-tags-ok")
""")


def test_sharded_mesh_answers_tag_predicates_as_its_reference():
    """Four virtual devices: the shard_map program, compiled for the
    schema's tag-set field, returns what the shard-at-a-time reference
    does, and every id passes."""
    root = Path(__file__).resolve().parents[1]
    proc = subprocess.run([sys.executable, "-c", SHARDED], cwd=root,
                          capture_output=True, text=True, timeout=600,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "sharded-tags-ok" in proc.stdout


@pytest.mark.parametrize("capacity", [None, 400])
def test_value_bitmaps_hold_every_label_of_the_vocabulary(capacity):
    """A 600-label field whose rows carry labels below 100 only: the
    bitmaps still hold all 600, so a query on an absent label packs and
    answers nothing, and a row ingested with one is found."""
    rng = np.random.default_rng(9)
    n, v = 300, 600
    words = pack_labels([rng.choice(100, 2, replace=False)
                         for _ in range(n)], v)
    meta = np.concatenate([words, rng.integers(0, 5, (n, 1))],
                          axis=1).astype(np.int32)
    names = [f"w{i}" for i in range(words.shape[1])] + ["cat"]
    ds = Dataset(_dataset(n=n).vectors, meta, names,
                 [v] + [0] * (words.shape[1] - 1) + [5], tag_fields={0: v})
    cfg = FnsConfig().with_knobs({**KNOBS, "serve.capacity": capacity})
    svc = RetrievalService.build(ds, config=cfg)
    assert svc.engine().v_cap >= v
    pred = HasAny(0, [550])
    ids, stats = svc.query_batch(ds.vectors[:2], [pred, HasAny(0, [5])])
    assert "errors" not in stats and len(ids[0]) == 0 and len(ids[1])
    if capacity:
        row = meta[:1].copy()
        row[0, :words.shape[1]] = pack_labels([[550]], v)
        gid = svc.ingest(ds.vectors[:1], row)
        ids, _ = svc.query_batch(ds.vectors[:1], [pred])
        assert np.asarray(ids[0]).tolist() == gid.tolist()
