"""PR 8 acceptance: the unified typed tuning-config layer.

* FnsConfig flat addressing (flatten / with_knobs / from_flat) and the
  stable fingerprint round-trip;
* deprecation shims: legacy knob kwargs and bare BatchedParams keep
  working, warn exactly once, and land in the config tree;
* Pallas tile knobs are validated against shape constraints at trace
  time with errors naming the KernelConfig field;
* the config rides through the PR 7 durability snapshot: a matching
  config restores zero-rebuild, a shape-incompatible knob (changed
  graph_k) raises ``ConfigMismatch``, a PRE-config snapshot (extra
  without a "config" key) still restores, and the checkpoint manifest
  carries the fingerprint;
* the knob-guard CI lint passes on the repo itself.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.core import config as config_mod
from repro.core.config import (ConfigMismatch, FnsConfig, KernelConfig,
                               WalkConfig, check_state_config, coerce_config)
from repro.core.search import SearchParams
from repro.core.types import Dataset
from repro.serve.retrieval import RetrievalService

SELS = (0.5, 0.1, 0.02)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ds():
    from repro.data.synth import make_selectivity_dataset

    return make_selectivity_dataset(SELS, n=240, d=16, n_components=8,
                                    seed=3)


def _service_config(capacity=320):
    return FnsConfig().with_knobs({
        "graph.graph_k": 8, "graph.r_max": 24, "walk.k": 5,
        "serve.capacity": capacity})


def _build(ds, cfg):
    base = Dataset(ds.vectors[:200], ds.metadata[:200], ds.field_names,
                   list(ds.vocab_sizes))
    return RetrievalService.build(base, config=cfg,
                                  params=SearchParams(k=5))


# -- flat addressing + fingerprint -------------------------------------------

def test_flatten_with_knobs_roundtrip():
    cfg = FnsConfig()
    flat = cfg.flatten()
    assert flat["walk.beam_width"] == 4
    assert flat["graph.graph_k"] == 32
    cfg2 = cfg.with_knobs({"walk.beam_width": 8,
                           "kernel.filter_tile": 4096})
    assert cfg2.walk.beam_width == 8 and cfg2.kernel.filter_tile == 4096
    assert cfg.walk.beam_width == 4  # frozen: with_knobs never mutates
    assert FnsConfig.from_flat(cfg2.flatten()) == cfg2
    # tolerant of unknown keys (configs from newer releases)
    assert FnsConfig.from_flat({"walk.beam_width": 8,
                                "future.knob": 1}).walk.beam_width == 8
    with pytest.raises(KeyError):
        cfg.with_knobs({"walk.no_such_knob": 1})
    with pytest.raises(KeyError):
        cfg.with_knobs({"nosection.k": 1})


def test_fingerprint_stable_and_knob_sensitive():
    a, b = FnsConfig(), FnsConfig()
    assert a.fingerprint() == b.fingerprint()
    c = a.with_knobs({"walk.beam_width": 8})
    assert c.fingerprint() != a.fingerprint()
    # json round-trip (how snapshots store it) preserves the fingerprint
    thawed = FnsConfig.from_flat(json.loads(json.dumps(c.flatten())))
    assert thawed.fingerprint() == c.fingerprint()
    assert hash(c) is not None  # frozen => hashable (program cache key)


def test_check_state_config():
    cfg = FnsConfig().with_knobs({"graph.graph_k": 16})
    check_state_config(cfg, graph_k=16)          # agrees: fine
    check_state_config(cfg, v_cap=512)           # cfg side None: fine
    with pytest.raises(ConfigMismatch, match="graph.graph_k"):
        check_state_config(cfg, graph_k=32)


# -- deprecation shims -------------------------------------------------------

def test_coerce_config_shims(monkeypatch):
    monkeypatch.setattr(config_mod, "_WARNED", set())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = coerce_config(None, {"graph.graph_k": 12}, where="shim-test")
        assert cfg.graph.graph_k == 12
        assert len(w) == 1 and issubclass(w[0].category, DeprecationWarning)
        # same call site again: warned once per process, not per call
        coerce_config(None, {"graph.graph_k": 12}, where="shim-test")
        assert len(w) == 1
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = coerce_config(WalkConfig(k=7), {}, where="shim-test2")
        assert cfg.walk.k == 7
        assert len(w) == 1 and issubclass(w[0].category, DeprecationWarning)
    # a full FnsConfig passes through silently and wins over defaults
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        full = FnsConfig().with_knobs({"graph.graph_k": 20})
        assert coerce_config(full, {}, where="shim-test3",
                             defaults={"graph.graph_k": 16}) is full
        assert len(w) == 0
    with pytest.raises(TypeError):
        coerce_config("nope", {}, where="shim-test4")


def test_legacy_engine_kwargs_fold_into_config(ds):
    from repro.core.atlas import AnchorAtlas
    from repro.core.batched.engine import BatchedEngine, BatchedParams
    from repro.core.graph import build_alpha_knn
    from repro.core.search import FiberIndex

    graph = build_alpha_knn(ds.vectors, k=8, r_max=24)
    atlas = AnchorAtlas.build(ds)
    index = FiberIndex(ds.vectors, ds.metadata, graph, atlas)
    eng = BatchedEngine(index, BatchedParams(k=5, beam_width=2),
                        graph_k=8, capacity=320)
    assert eng.cfg.walk.k == 5 and eng.cfg.walk.beam_width == 2
    assert eng.cfg.graph.graph_k == 8
    assert eng.cfg.serve.capacity == 320
    assert eng.p is eng.cfg.walk  # one origin, no duplicated params


# -- kernel tile validation at trace time ------------------------------------

def test_kernel_tile_knobs_validated():
    import jax.numpy as jnp

    from repro.kernels.filter_eval import filter_eval_batch

    meta = jnp.zeros((64, 2), jnp.int32)
    fields = jnp.zeros((1, 1, 4), jnp.int32)
    allowed = jnp.zeros((1, 1, 4, 8), jnp.uint32)
    with pytest.raises(ValueError, match="filter_tile"):
        filter_eval_batch(meta, fields, allowed, tn=100)  # not 32-aligned
    with pytest.raises(ValueError, match="filter_tile"):
        filter_eval_batch(meta, fields, allowed, tn=0)
    big = jnp.zeros((5000, 2), jnp.int32)          # several corpus tiles
    with pytest.raises(ValueError, match="filter_tile"):
        # not lane-aligned: refused for Mosaic before any lowering
        filter_eval_batch(big, fields, allowed, tn=1024, interpret=False)
    assert KernelConfig().filter_tile % (32 * 128) == 0


# -- config through the durability snapshot ----------------------------------

def test_config_rides_snapshot_roundtrip(ds, tmp_path, monkeypatch):
    """Snapshot -> recover with the SAME config: zero rebuild (build entry
    points boobytrapped), identical fingerprint, identical results; the
    checkpoint manifest records the fingerprint."""
    cfg = _service_config()
    svc = _build(ds, cfg)
    svc.ingest(ds.vectors[200:220], ds.metadata[200:220])
    svc.enable_durability(str(tmp_path))
    vec = ds.vectors[:4]
    preds = [None] * 4
    from repro.core.types import FilterPredicate
    preds = [FilterPredicate.make({0: [0]})] * 4
    ids0, _ = svc.query_batch(vec, preds)

    # the manifest alone identifies the config
    from repro.checkpoint import ckpt
    (_, manifest), _step = ckpt.restore_latest(
        os.path.join(str(tmp_path), "snapshots"))
    assert manifest["meta"]["config_fingerprint"] == svc._cfg().fingerprint()
    assert manifest["meta"]["config"]["graph.graph_k"] == 8

    import repro.core.atlas as atlas_mod
    import repro.core.graph as graph_mod

    def trap(name):
        def _boom(*a, **k):
            raise AssertionError(f"restore called {name}: a matching "
                                 f"config must restore zero-rebuild")
        return _boom

    monkeypatch.setattr(graph_mod, "build_alpha_knn", trap("build_alpha_knn"))
    monkeypatch.setattr(atlas_mod.AnchorAtlas, "build", trap("AnchorAtlas"))
    svc2 = RetrievalService.recover(str(tmp_path), config=svc._cfg())
    assert svc2._cfg().fingerprint() == svc._cfg().fingerprint()
    ids1, _ = svc2.query_batch(vec, preds)
    for a, b in zip(ids0, ids1):
        np.testing.assert_array_equal(a, b)


def test_shape_incompatible_config_refuses_restore(ds, tmp_path):
    cfg = _service_config()
    svc = _build(ds, cfg)
    svc.enable_durability(str(tmp_path))
    bad = cfg.with_knobs({"graph.graph_k": 16})
    with pytest.raises(ConfigMismatch, match="graph.graph_k"):
        RetrievalService.recover(str(tmp_path), config=bad)


def test_pre_config_snapshot_still_restores(ds, tmp_path):
    """A snapshot whose extra has NO "config" key (written by the PR 7
    layer, before the config tree existed) restores through the legacy
    fields unchanged."""
    import dataclasses

    from repro.serve.durability import DurableStore
    from repro.serve.retrieval import _engine_state

    cfg = _service_config()
    svc = _build(ds, cfg)
    svc.ingest(ds.vectors[200:220], ds.metadata[200:220])
    store = DurableStore(str(tmp_path))
    extra = {"search_params": dataclasses.asdict(svc.params),
             "graph_build": {"graph_k": 8, "r_max": 24, "alpha": 1.2,
                             "n_clusters": None},
             "capacity": svc.capacity,
             "vocab_sizes": list(ds.vocab_sizes)}  # deliberately no "config"
    store.snapshot(_engine_state(svc._live_engine()), extra)

    svc2 = RetrievalService.recover(str(tmp_path))
    assert svc2.staleness()["corpus_rows"] == 220
    from repro.core.types import FilterPredicate
    preds = [FilterPredicate.make({0: [0]})] * 2
    ids, _ = svc2.query_batch(ds.vectors[:2], preds)
    assert len(ids) == 2
    # and the derived config reports the snapshot's true baked knobs
    assert svc2._cfg().graph.graph_k == 8


def test_engine_from_state_validates_explicit_config(ds, tmp_path):
    from repro.serve.durability import DurableStore, engine_from_state
    from repro.serve.retrieval import _engine_state

    svc = _build(ds, _service_config())
    svc.enable_durability(str(tmp_path))
    state, extra, _ = DurableStore(str(tmp_path)).load_latest()
    with pytest.raises(ConfigMismatch, match="serve.capacity"):
        engine_from_state(state,
                          config=FnsConfig().with_knobs(
                              {"serve.capacity": 999}))
    # legacy params path: no config given, no mismatch possible
    eng = engine_from_state(state, params=WalkConfig(k=5))
    assert eng.cfg.graph.graph_k == state.graph_k


# -- CI lint guard -----------------------------------------------------------

def test_knob_guard_passes_on_repo():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "knob_guard.py")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_compile_cache_dir_rule(monkeypatch):
    """The persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR
    says (JAX reads it; the code names no other path), else at one fixed
    directory in the checkout."""
    from pathlib import Path

    import jax

    from repro.launch import compile_cache as cc

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, "/elsewhere/cache")
        assert cc.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == old
        monkeypatch.delenv(cc.ENV_VAR)
        path = cc.enable_compile_cache()
        assert path == cc.enable_compile_cache()
        assert Path(path) == Path(__file__).resolve().parents[1] / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
