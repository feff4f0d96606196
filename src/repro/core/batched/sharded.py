"""The row-partitioned index every engine serves (DESIGN.md §7).

A ``ShardedIndex`` holds S >= 1 contiguous row shards of a filtered-ANN
corpus on the host: vectors, metadata, a shard-local α-kNN subgraph, a
per-shard ``DeviceAtlas`` and a row-validity mask for the pad rows, each
array with a leading shard dimension, plus the shard-local -> global id
map. ``build_sharded_index`` partitions and builds a corpus;
``ShardedIndex.from_index`` wraps an already built single-device
``FiberIndex`` as one shard without rebuilding it; ``from_state``
re-stacks a restored ``InsertState``. ``engine.Engine`` places it on the
device(s) and runs the search.

The cross-shard merge (``merge_topk``) is exact: every point lives on
exactly one shard and its distance is a pure function of (q, point), so
the k smallest of the union of per-shard top-ks equals the top-k of the
union of the per-shard result sets (the cross-round dedup argument of
DESIGN.md §3, applied across shards).

Corpus capacity scales linearly with device count; each shard walks a
subgraph of ~n/S points, so per-device memory and per-hop gather traffic
drop by S while the batch keeps its one-dispatch property.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.atlas import AnchorAtlas
from repro.core.config import FnsConfig, coerce_config
from repro.core.batched.insert import (InsertState, emit_device_atlas,
                                       make_shard_state)
from repro.core.device_atlas import DeviceAtlas, auto_v_cap
from repro.core.graph import build_shard_graphs
from repro.core.predicate import derived_vocab_sizes
from repro.core.search import FiberIndex
from repro.core.tags import (TagFields, check_tag_fields, field_domains,
                             normalize_tag_fields, value_max)
from repro.core.types import Dataset


@dataclasses.dataclass
class ShardedIndex:
    """Host-built row partition of a filtered-ANN corpus.

    Every array carries a leading shard dim S; shard s owns a balanced
    contiguous row block (``graph.shard_bounds``) padded to the common row
    count m. Adjacency and atlas ids are shard-LOCAL; ``global_ids`` maps
    them back (-1 = pad).
    """

    vectors: np.ndarray     # (S, m, d) f32, zero on pad rows
    adjacency: np.ndarray   # (S, m, R) i32 shard-local ids, -1 padded
    metadata: np.ndarray    # (S, m, F) i32, -1 on pad rows
    global_ids: np.ndarray  # (S, m) i32 local row -> global id, -1 = pad
    valid: np.ndarray       # (S, m) bool row validity
    atlases: list[DeviceAtlas]  # one per shard, all of one shape
    # per-field domains for FilterExpr Not/Range lowering
    vocab_sizes: tuple[int, ...] | None = None
    # host mirror for the append path (DESIGN.md §9): attached only when
    # the build reserved ``capacity`` slack; None = build-once index,
    # insert_batch raises
    insert_state: InsertState | None = None
    # the schema's tag-set fields (core.tags)
    tag_fields: TagFields = ()

    @property
    def n_shards(self) -> int:
        return self.vectors.shape[0]

    @property
    def rows_per_shard(self) -> int:
        return self.vectors.shape[1]

    @classmethod
    def from_index(cls, index: FiberIndex, config: FnsConfig | None = None,
                   *, vocab_sizes=None) -> "ShardedIndex":
        """One shard over an already built ``FiberIndex``: its graph and
        atlas are used as they are, never rebuilt. Without
        ``serve.capacity`` the arrays are views of the index's own (no
        copy) and the atlas is ``index.atlas.to_device(atlas.v_cap)``.
        With it they are copied into a capacity slab whose adjacency is
        widened for the append path's 1.5x ``graph.graph_k`` forward edges
        (``insert.insert_rows``). ``vocab_sizes`` are the fields' declared
        domains, else derived from the metadata."""
        cfg = config if config is not None else FnsConfig()
        v_cap, capacity = cfg.atlas.v_cap, cfg.serve.capacity
        vectors = np.asarray(index.vectors, np.float32)
        metadata = np.asarray(index.metadata, np.int32)
        adjacency = np.asarray(index.graph.neighbors, np.int32)
        n = vectors.shape[0]
        tag_fields = index.tag_fields
        check_tag_fields(tag_fields, metadata.shape[1], v_cap)
        vocab = (tuple(int(v) for v in vocab_sizes)
                 if vocab_sizes is not None else index.vocab_sizes())
        if capacity is None:
            return cls(vectors[None], adjacency[None], metadata[None],
                       np.arange(n, dtype=np.int32)[None],
                       np.ones((1, n), bool),
                       [index.atlas.to_device(v_cap=v_cap)], vocab,
                       tag_fields=tag_fields)
        if capacity < n:
            raise ValueError(f"capacity {capacity} < corpus size {n}")
        graph_k = cfg.graph.graph_k
        w = max(adjacency.shape[1], graph_k + graph_k // 2)
        adjacency = np.pad(adjacency, ((0, 0), (0, w - adjacency.shape[1])),
                           constant_values=-1)
        if v_cap is None:
            # same auto-sizing rule as AnchorAtlas.to_device
            v_cap = auto_v_cap(value_max(metadata, tag_fields))
        slab = make_shard_state(vectors, metadata,
                                np.arange(n, dtype=np.int32), adjacency,
                                index.atlas, cap=capacity)
        state = InsertState(shards=[slab], v_cap=v_cap, graph_k=graph_k,
                            alpha=cfg.graph.alpha, seed=0, next_gid=n,
                            tag_fields=tag_fields)
        return cls.from_state(state, vocab)

    @classmethod
    def from_state(cls, state: InsertState,
                   vocab_sizes=None) -> "ShardedIndex":
        """Re-stack a (restored) host ``InsertState`` with ZERO graph/atlas
        rebuild: the slabs already carry the patched adjacency and
        incremental atlases, so the device atlases are re-*emitted* at the
        same fixed shapes (DESIGN.md §10). The state is attached, so
        ingest continues where the snapshot left off. ``vocab_sizes``
        default to the domains of the written rows."""
        slabs = state.shards
        if vocab_sizes is None:
            written = np.concatenate([sl.metadata[: sl.n_valid]
                                      for sl in slabs])
            vocab_sizes = field_domains(derived_vocab_sizes(written),
                                        state.tag_fields)
        return cls(**stack_slabs(slabs),
                   atlases=[emit_device_atlas(sl, state.v_cap,
                                              state.tag_fields)
                            for sl in slabs],
                   vocab_sizes=tuple(vocab_sizes),
                   insert_state=state, tag_fields=state.tag_fields)


def stack_slabs(slabs) -> dict:
    """The shards' host arrays stacked on a leading shard dim."""
    return {k: np.stack([getattr(sl, k) for sl in slabs])
            for k in ("vectors", "adjacency", "metadata", "global_ids",
                      "valid")}


def build_sharded_index(vectors: np.ndarray, metadata: np.ndarray,
                        n_shards: int, *, config: FnsConfig | None = None,
                        graph_k: int | None = None,
                        r_max: int | None = None,
                        alpha: float | None = None,
                        n_clusters: int | None = None,
                        v_cap: int | None = None,
                        seed: int | None = None,
                        capacity: int | None = None,
                        tag_fields: TagFields = ()) -> ShardedIndex:
    """Partition a corpus into ``n_shards`` row blocks and build each
    shard's subgraph + atlas. All shards share one n_clusters and one v_cap
    (the atlas leaves must stack to fixed shapes for ``shard_map``), and
    every shard is padded to m rows; pad rows are killed by the
    row-validity bitmap, never by luck of the predicate.

    ``capacity`` reserves append room (DESIGN.md §9): m becomes
    ceil(capacity / S), the spare rows are capacity-slab slots that
    ``Engine.insert_batch`` fills later — identical shapes, so growing the
    corpus never recompiles the search program — and the adjacency is
    widened for the append path's 1.5x graph_k forward edges. Without
    it, m = ceil(n / S) and inserts fail on capacity.

    All knobs come from ``config`` (one ``FnsConfig``); the loose kwargs
    are deprecation shims that fold into it, warning once. ``tag_fields``
    declares the metadata's tag-set fields (``core.tags``)."""
    cfg = coerce_config(config,
                        {"graph.graph_k": graph_k, "graph.r_max": r_max,
                         "graph.alpha": alpha, "atlas.n_clusters": n_clusters,
                         "atlas.v_cap": v_cap, "serve.capacity": capacity},
                        where="build_sharded_index")
    if seed is not None:  # plumbing arg, folds silently
        cfg = cfg.with_knobs({"atlas.kmeans_seed": seed})
    graph_k, alpha = cfg.graph.graph_k, cfg.graph.alpha
    n_clusters, v_cap = cfg.atlas.n_clusters, cfg.atlas.v_cap
    seed, capacity = cfg.atlas.kmeans_seed, cfg.serve.capacity
    vectors = np.asarray(vectors, np.float32)
    metadata = np.asarray(metadata, np.int32)
    n, d = vectors.shape
    f_count = metadata.shape[1]
    tag_fields = normalize_tag_fields(tag_fields)
    if capacity is not None and capacity < n:
        raise ValueError(f"capacity {capacity} < corpus size {n}")
    graphs, bounds = build_shard_graphs(vectors, n_shards, k=graph_k,
                                        r_max=cfg.graph.r_max, alpha=alpha,
                                        block=cfg.graph.build_block)
    m = -(-max(n, capacity or 0) // n_shards)
    min_real = min(hi - lo for lo, hi in bounds)
    if n_clusters is None:
        n_clusters = int(np.ceil(np.sqrt(m)))
    n_clusters = min(n_clusters, min_real)
    if v_cap is None:
        v_cap = auto_v_cap(value_max(metadata, tag_fields))
    check_tag_fields(tag_fields, f_count, v_cap)

    # one adjacency width across shards, with room for the forward edges
    # appended rows request later (1.5x graph_k, see insert.insert_rows)
    r = max(g.r_pad for g in graphs)
    if capacity is not None:
        r = max(r, graph_k + graph_k // 2)
    field_names = [f"f{i}" for i in range(f_count)]
    slabs = []
    for s, (lo, hi) in enumerate(bounds):
        ds_s = Dataset(vectors[lo:hi], metadata[lo:hi], field_names,
                       [v_cap] * f_count, tag_fields)
        atlas = AnchorAtlas.build(ds_s, n_clusters=n_clusters, seed=seed)
        adj_s = np.full((hi - lo, r), -1, np.int32)
        adj_s[:, : graphs[s].r_pad] = graphs[s].neighbors
        slabs.append(make_shard_state(
            vectors[lo:hi], metadata[lo:hi],
            np.arange(lo, hi, dtype=np.int32), adj_s, atlas, cap=m))
    # the insert state only exists when append room was reserved: a
    # build-once index must REFUSE inserts rather than silently absorb a
    # few rows into its ceil(n/S) padding slack
    istate = (InsertState(shards=slabs, v_cap=v_cap, graph_k=graph_k,
                          alpha=alpha, seed=seed, next_gid=n,
                          tag_fields=tag_fields)
              if capacity is not None else None)
    return ShardedIndex(
        **stack_slabs(slabs),
        atlases=[emit_device_atlas(sl, v_cap, tag_fields) for sl in slabs],
        vocab_sizes=field_domains(derived_vocab_sizes(metadata),
                                  tag_fields),
        insert_state=istate, tag_fields=tag_fields)


def merge_topk(all_v: jax.Array, all_i: jax.Array, k: int):
    """Exact cross-shard merge: (S, Q, k) per-shard top-ks -> (Q, k)
    global top-k. Ids are globally unique (a point lives on one shard), so
    no dedup is needed; the value of a result is a pure function of
    (q, point), so keeping the k smallest of the union is exact. Ties
    break shard-major (lax.top_k picks the lowest flattened index), which
    both the mesh and the shard-at-a-time paths share."""
    s, q_n, k_in = all_v.shape
    cat_v = jnp.transpose(all_v, (1, 0, 2)).reshape(q_n, s * k_in)
    cat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(q_n, s * k_in)
    top, sel = jax.lax.top_k(-cat_v, k)
    return -top, jnp.take_along_axis(cat_i, sel, axis=1)
