"""The one engine's index layout and its choice of program.

* ``ShardedIndex.from_index`` wraps a built ``FiberIndex`` as one shard
  without copying it: the engine's device arrays equal the index's own —
  vectors, adjacency at the graph's width, metadata, every atlas leaf of
  ``index.atlas.to_device`` — for a categorical schema and for a tag-set +
  day-window schema (the two chip deployments' kinds). Only reserved
  append capacity widens the adjacency.
* The program follows from what the engine observes: a mesh of two
  devices runs the ``shard_map`` program, no mesh over one shard runs
  ``search_batch`` itself (module ``jit_search_batch``, its scopes
  recorded), no mesh over two shards runs the per-shard programs one after
  the other. Each reads the arrays the engine placed, never a per-batch
  copy, and answers as ``search_reference`` does.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from repro.core import AnchorAtlas, FiberIndex, build_alpha_knn
from repro.core.batched import scopes
from repro.core.batched.bitmap import unpack_bits
from repro.core.batched.engine import Engine
from repro.core.batched.sharded import ShardedIndex, build_sharded_index
from repro.core.config import FnsConfig
from repro.core.predicate import HasAny, Range
from repro.core.tags import pack_labels
from repro.core.types import Dataset, FilterPredicate, Query, normalize

N, D, Q = 700, 16, 6
LABELS, DAYS = 155, 5000
CONFIG = FnsConfig().with_knobs({"graph.graph_k": 8, "graph.r_max": 24,
                                 "walk.k": 5})
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dataset(schema: str) -> Dataset:
    rng = np.random.default_rng(11)
    vectors = normalize(rng.standard_normal((N, D))).astype(np.float32)
    if schema == "categorical":
        meta = rng.integers(0, 6, (N, 4)).astype(np.int32)
        return Dataset(vectors, meta, [f"f{i}" for i in range(4)], [6] * 4)
    labels = [rng.choice(LABELS, rng.integers(1, 4), replace=False)
              for _ in range(N)]
    words = pack_labels(labels, LABELS)
    meta = np.concatenate([words, rng.integers(0, DAYS, (N, 1))],
                          axis=1).astype(np.int32)
    w = words.shape[1]
    return Dataset(vectors, meta,
                   ["labels"] + [f"labels.{i}" for i in range(1, w)]
                   + ["day"], [LABELS] + [0] * (w - 1) + [DAYS],
                   tag_fields={0: LABELS})


def _index(ds: Dataset, config: FnsConfig) -> FiberIndex:
    graph = build_alpha_knn(ds.vectors, config=config.graph)
    atlas = AnchorAtlas.build(ds, seed=0)
    return FiberIndex(ds.vectors, ds.metadata, graph, atlas,
                      tag_fields=ds.tag_fields)


def _queries(ds: Dataset) -> list[Query]:
    rng = np.random.default_rng(5)
    if ds.tag_fields:
        preds = [HasAny(0, [i, i + 40]) & Range(ds.n_fields - 1, 100 * i,
                                                100 * i + 2500)
                 for i in range(Q)]
    else:
        preds = [FilterPredicate.make({i % 4: [i % 6]}) for i in range(Q)]
    return [Query(vector=ds.vectors[rng.integers(N)], predicate=p)
            for p in preds]


@pytest.fixture(scope="module", params=["categorical", "tags_and_days"])
def schema(request):
    ds = _dataset(request.param)
    return ds, _index(ds, CONFIG)


def test_one_shard_holds_the_index_as_it_is(schema):
    ds, index = schema
    sidx = ShardedIndex.from_index(index, CONFIG)
    assert sidx.n_shards == 1 and sidx.insert_state is None
    # the host arrays are the index's own, not a slab copy
    assert np.shares_memory(sidx.vectors, index.vectors)
    assert np.shares_memory(sidx.metadata, index.metadata)
    eng = Engine(sidx, CONFIG)
    (ix,) = eng.shards
    np.testing.assert_array_equal(np.asarray(ix.vectors), index.vectors)
    assert ix.adjacency.shape == index.graph.neighbors.shape
    np.testing.assert_array_equal(np.asarray(ix.adjacency),
                                  index.graph.neighbors)
    np.testing.assert_array_equal(np.asarray(ix.metadata), index.metadata)
    np.testing.assert_array_equal(np.asarray(ix.global_ids), np.arange(N))
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(ix.valid_bm[None], N))[0], np.ones(N, bool))
    want = index.atlas.to_device(v_cap=CONFIG.atlas.v_cap)
    assert ix.datlas.v_cap == want.v_cap == eng.v_cap
    got_leaves = jax.tree.leaves(ix.datlas)
    want_leaves = jax.tree.leaves(want)
    assert len(got_leaves) == len(want_leaves) == 8
    for got, exp in zip(got_leaves, want_leaves):
        assert got.dtype == exp.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


def test_only_append_capacity_widens_the_adjacency(schema):
    ds, index = schema
    cfg = CONFIG.with_knobs({"graph.graph_k": 20, "serve.capacity": N + 50})
    r = index.graph.neighbors.shape[1]
    assert r < 30  # the graph is narrower than 1.5 x graph_k
    sidx = ShardedIndex.from_index(index, cfg)
    assert sidx.adjacency.shape == (1, N + 50, 30)
    np.testing.assert_array_equal(sidx.adjacency[0, :N, :r],
                                  index.graph.neighbors)
    assert (sidx.adjacency[0, :N, r:] == -1).all()
    assert sidx.valid[0].sum() == N and sidx.insert_state is not None
    eng = Engine(sidx, cfg)
    gids = eng.insert_batch(ds.vectors[:3], ds.metadata[:3])
    assert gids.tolist() == [N, N + 1, N + 2]


def _observe(case: str) -> dict:
    """Build the engine of ``case`` and run a batch twice: what program
    the engine took, the scopes its first batch recorded, and, of the
    second batch, the program calls, the dispatches, whether every index
    argument was an array the engine holds, and whether the ids are
    ``search_reference``'s."""
    ds = _dataset("categorical")
    if case == "one_shard":
        eng = Engine(ShardedIndex.from_index(_index(ds, CONFIG), CONFIG),
                     CONFIG)
    else:
        from repro.launch.mesh import make_local_mesh

        sidx = build_sharded_index(ds.vectors, ds.metadata, 2,
                                   config=CONFIG)
        mesh = make_local_mesh(data=2, model=1) if case == "mesh2" else None
        eng = Engine(sidx, CONFIG, mesh)
    queries = _queries(ds)
    saved, scopes.OP_SCOPES = scopes.OP_SCOPES, {}
    try:
        eng.search(queries)
        modules = sorted(scopes.OP_SCOPES)
    finally:
        scopes.OP_SCOPES = saved
    ref, _ = eng.search_reference(queries)
    held = {id(leaf) for part in (eng.stacked, *eng.shards)
            for leaf in jax.tree.leaves(part)}
    calls = []

    def spy(name):
        program = getattr(eng, name)

        def call(*args, **kwargs):
            calls.append([leaf for leaf in jax.tree.leaves((args, kwargs))
                          if leaf.shape[:1] != (Q,)])
            return program(*args, **kwargs)
        setattr(eng, name, call)

    spy("_search")
    spy("_shard_search")
    d0 = eng.dispatches
    ids, _ = eng.search(queries)
    return {"mesh": eng.mesh is not None, "modules": modules,
            "calls": len(calls), "dispatches": eng.dispatches - d0,
            "held": all(id(leaf) in held for c in calls for leaf in c),
            "same_as_reference": all(np.array_equal(a, b)
                                     for a, b in zip(ids, ref))}


PROGRAMS = {
    "mesh2": {"mesh": True, "modules": ["jit_sharded_search"], "calls": 1,
              "dispatches": 1},
    "one_shard": {"mesh": False, "modules": ["jit_search_batch"],
                  "calls": 1, "dispatches": 1},
    "two_shards": {"mesh": False, "modules": [], "calls": 2,
                   "dispatches": 2},
}

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, sys
sys.path[:0] = ["src", "tests"]
import test_engine_layout as T
print("OBSERVED " + json.dumps(T._observe(sys.argv[1])))
"""


@pytest.mark.parametrize("case", sorted(PROGRAMS))
def test_the_engine_picks_its_program_from_what_it_observes(case):
    if case == "mesh2" and len(jax.devices()) < 2:
        r = subprocess.run([sys.executable, "-c", SCRIPT, case],
                           capture_output=True, text=True, timeout=300,
                           cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        (line,) = [x for x in r.stdout.splitlines()
                   if x.startswith("OBSERVED ")]
        got = json.loads(line[len("OBSERVED "):])
    else:
        got = _observe(case)
    assert got == {**PROGRAMS[case], "held": True,
                   "same_as_reference": True}
