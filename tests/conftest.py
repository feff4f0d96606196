"""Shared fixtures: one small synthetic corpus + index per session, plus
the engineered-selectivity sweep shared by the device-atlas and fused
single-dispatch parity tests.

NOTE: no XLA_FLAGS here — tests run on the single real CPU device; only the
dry-run sets the 512-device placeholder count (see launch/dryrun.py).
"""
import numpy as np
import pytest

from repro.core import AnchorAtlas, FiberIndex, build_alpha_knn
from repro.core.batched.engine import Engine
from repro.core.batched.sharded import ShardedIndex
from repro.core.config import FnsConfig, WalkConfig
from repro.data.ground_truth import attach_ground_truth
from repro.data.synth import SynthSpec, make_dataset, make_queries

SELECTIVITIES = (0.5, 0.1, 0.02)


def walk_config(**knobs) -> FnsConfig:
    """The default config with these walk knobs."""
    return FnsConfig(walk=WalkConfig(**knobs))


def engine_over(index: FiberIndex, config: FnsConfig | None = None, *,
                vocab_sizes=None) -> Engine:
    """An engine over a built single-device index, as one shard."""
    return Engine(ShardedIndex.from_index(index, config,
                                          vocab_sizes=vocab_sizes), config)

try:  # property tests draw the same examples on every run and worker
    from hypothesis import settings as _hypothesis_settings

    _hypothesis_settings.register_profile("deterministic", derandomize=True)
    _hypothesis_settings.load_profile("deterministic")
except ImportError:  # tests/_hypothesis_fallback.py is seeded already
    pass


@pytest.fixture(scope="session")
def small_ds():
    return make_dataset(SynthSpec(n=3000, d=64, n_components=24,
                                  n_fields=10, seed=0))


@pytest.fixture(scope="session")
def small_queries(small_ds):
    qs = make_queries(small_ds, n_queries=40, seed=1)
    attach_ground_truth(small_ds, qs, k=10)
    return qs


@pytest.fixture(scope="session")
def small_graph(small_ds):
    return build_alpha_knn(small_ds.vectors, k=24, r_max=64, alpha=1.2)


@pytest.fixture(scope="session")
def small_atlas(small_ds):
    return AnchorAtlas.build(small_ds, seed=0)


@pytest.fixture(scope="session")
def small_index(small_ds, small_graph, small_atlas):
    return FiberIndex(small_ds.vectors, small_ds.metadata, small_graph,
                      small_atlas)


@pytest.fixture(scope="session")
def sel_sweep():
    """Corpus + queries with engineered filter selectivities ~{0.5,0.1,0.02}
    (the shared ``make_selectivity_dataset`` recipe — same distribution the
    end-to-end search benchmark measures)."""
    from repro.data.synth import (make_selectivity_dataset,
                                  make_selectivity_queries)

    ds = make_selectivity_dataset(SELECTIVITIES)
    graph = build_alpha_knn(ds.vectors, k=16, r_max=48, alpha=1.2)
    atlas = AnchorAtlas.build(ds, seed=0)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    queries = []
    for v, _target in enumerate(SELECTIVITIES):
        queries.extend(make_selectivity_queries(ds, v, 12))
    attach_ground_truth(ds, queries, k=10)
    return ds, index, queries
