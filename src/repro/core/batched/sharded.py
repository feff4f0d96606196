"""Sharded fused search over the mesh ``data`` axis (DESIGN.md §7).

The single-device ``BatchedEngine`` needs the whole corpus on one chip —
dense (n, d) vectors, (n, R) adjacency, the packed atlas. ``ShardedEngine``
partitions the corpus row-wise into S = mesh.shape["data"] contiguous
shards (vectors, metadata, a shard-local α-kNN subgraph, a per-shard
``DeviceAtlas``, and packed row-validity bitmaps for the pad rows) and runs
the SAME fused ``search_batch`` program on every shard under ``shard_map``
with queries replicated. Each shard emits its local top-k in shard-local
ids; a gather through the shard's global-id map, one ``lax.all_gather``
over the data axis, and a top-k merge yield the global result — still ONE
device dispatch and ONE host sync per batch.

The cross-shard merge is exact: every point lives on exactly one shard and
its distance is a pure function of (q, point), so the k smallest of the
union of per-shard top-ks equals the top-k of the union of the per-shard
result sets (the cross-round dedup argument of DESIGN.md §3, applied across
shards). ``search_reference`` runs the identical per-shard programs one at
a time on the default device with the identical merge — the single-device
fused baseline the mesh dispatch must match bit-for-bit (tested).

Corpus capacity scales linearly with device count; each shard walks a
subgraph of ~n/S points, so per-device memory and per-hop gather traffic
drop by S while the batch keeps its one-dispatch property.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.core.atlas import AnchorAtlas
from repro.core.batched.bitmap import pack_bits
from repro.core.batched.engine import (BatchedParams, _fence_pack,
                                       fetch_results, pack_query_batch,
                                       search_batch)
from repro.core.config import FnsConfig, coerce_config
from repro.core.batched.insert import (InsertState, emit_device_atlas,
                                       insert_rows, make_shard_state)
from repro.core.batched.scopes import dispatch_program
from repro.core.device_atlas import (DeviceAtlas, auto_v_cap,
                                     stack_atlases)
from repro.core.graph import build_shard_graphs
from repro.core.predicate import FilterExpr, derived_vocab_sizes
from repro.core.tags import (TagFields, check_tag_fields, field_domains,
                             first_columns, normalize_tag_fields, value_max)
from repro.core.types import Dataset, Query
from repro.launch.mesh import index_axis_size, query_axis_name
from repro.launch.shardings import index_shardings


@dataclasses.dataclass
class ShardedIndex:
    """Host-built, device-ready row partition of a filtered-ANN corpus.

    Every array carries a leading shard dim S; shard s owns a balanced
    contiguous row block (``graph.shard_bounds``) padded to the common row
    count m = ceil(n/S). Adjacency and atlas ids are shard-LOCAL;
    ``global_ids`` maps them back (-1 = pad).
    """

    vectors: jax.Array      # (S, m, d) f32, zero on pad rows
    adjacency: jax.Array    # (S, m, R) i32 shard-local ids, -1 padded
    metadata: jax.Array     # (S, m, F) i32, -1 on pad rows
    global_ids: jax.Array   # (S, m) i32 local row -> global id, -1 = pad
    valid_bm: jax.Array     # (S, ceil(m/32)) u32 packed row-validity
    datlas: DeviceAtlas     # per-shard atlases, leaves stacked to (S, ...)
    n: int                  # real (unpadded) corpus size
    # per-field domains for FilterExpr Not/Range lowering (derived from the
    # unpadded metadata at build time)
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
    ceil(capacity / S) and the spare rows are capacity-slab slots that
    ``ShardedEngine.insert_batch`` fills later — identical shapes, so
    growing the corpus never recompiles the search program. Without it,
    m = ceil(n / S) and inserts fail on capacity.

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
    r = max(max(g.r_pad for g in graphs), graph_k + graph_k // 2)
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
        vectors=jnp.asarray(np.stack([sl.vectors for sl in slabs])),
        adjacency=jnp.asarray(np.stack([sl.adjacency for sl in slabs])),
        metadata=jnp.asarray(np.stack([sl.metadata for sl in slabs])),
        global_ids=jnp.asarray(np.stack([sl.global_ids for sl in slabs])),
        valid_bm=pack_bits(jnp.asarray(np.stack([sl.valid for sl in slabs]))),
        datlas=stack_atlases([emit_device_atlas(sl, v_cap, tag_fields)
                              for sl in slabs]),
        n=n, vocab_sizes=field_domains(derived_vocab_sizes(metadata),
                                       tag_fields),
        insert_state=istate, tag_fields=tag_fields)


def index_from_state(state: InsertState,
                     vocab_sizes=None) -> ShardedIndex:
    """Re-stack a device-ready ``ShardedIndex`` from a (restored) host
    ``InsertState`` with ZERO graph/atlas rebuild: the slabs already carry
    the patched adjacency and incremental atlases, so the device tables
    are re-*emitted* at the same fixed shapes (DESIGN.md §10). The state
    object is attached, so ingest continues where the snapshot left off."""
    slabs = state.shards
    return ShardedIndex(
        vectors=jnp.asarray(np.stack([sl.vectors for sl in slabs])),
        adjacency=jnp.asarray(np.stack([sl.adjacency for sl in slabs])),
        metadata=jnp.asarray(np.stack([sl.metadata for sl in slabs])),
        global_ids=jnp.asarray(np.stack([sl.global_ids for sl in slabs])),
        valid_bm=pack_bits(jnp.asarray(np.stack([sl.valid
                                                 for sl in slabs]))),
        datlas=stack_atlases([emit_device_atlas(sl, state.v_cap,
                                                state.tag_fields)
                              for sl in slabs]),
        n=state.next_gid, vocab_sizes=vocab_sizes, insert_state=state,
        tag_fields=state.tag_fields)


def merge_topk(all_v: jax.Array, all_i: jax.Array, k: int):
    """Exact cross-shard merge: (S, Q, k) per-shard top-ks -> (Q, k)
    global top-k. Ids are globally unique (a point lives on one shard), so
    no dedup is needed; the value of a result is a pure function of
    (q, point), so keeping the k smallest of the union is exact. Ties
    break shard-major (lax.top_k picks the lowest flattened index), which
    both the mesh and reference paths share."""
    s, q_n, k_in = all_v.shape
    cat_v = jnp.transpose(all_v, (1, 0, 2)).reshape(q_n, s * k_in)
    cat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(q_n, s * k_in)
    top, sel = jax.lax.top_k(-cat_v, k)
    return -top, jnp.take_along_axis(cat_i, sel, axis=1)


class ShardedEngine:
    """One-dispatch filtered search over a row-sharded index.

    ``search`` runs the fused per-shard ``search_batch`` under ``shard_map``
    (index partitioned over the ``data`` axis), maps local result ids to
    global ids, all-gathers the per-shard top-ks and merges them on device —
    one jitted call, one host sync, mirroring ``BatchedEngine.search``'s
    contract. ``dispatches`` counts compiled invocations so tests can
    assert the one-dispatch property.

    On a 1D mesh queries are replicated (every shard walks the whole
    batch). On a 2D query×data mesh (DESIGN.md §13) the batch is further
    partitioned over the query axis: each of the q_lanes lane groups walks
    Q/q_lanes queries against all shards, so batch throughput scales with
    the lane count instead of capping at one batch per mesh. Per-query
    state in the fused program is row-independent and its batch-level
    predicates only gate no-op rounds, so lane-partitioned results stay
    bit-identical to the replicated layout and to ``search_reference``.

    ``dispatch``/``collect`` split the batch into an async half (fenced
    pack + jitted call, no host sync) and a sync half, so a serving
    pipeline can overlap batch N+1's staging with batch N's device time.
    """

    def __init__(self, sindex: ShardedIndex, mesh, config=None,
                 axis: str = "data",
                 params: BatchedParams | None = None):
        s = sindex.n_shards
        if mesh is not None and index_axis_size(mesh, axis) != s:
            raise ValueError(
                f"index has {s} shards but mesh axis {axis!r} spans "
                f"{index_axis_size(mesh, axis)} devices")
        if config is None:
            config = params
        cfg = coerce_config(config, {}, where="ShardedEngine")
        self.cfg = cfg
        self.mesh, self.axis, self.p = mesh, axis, cfg.walk
        self._istate = sindex.insert_state
        # 2D query×data layout (DESIGN.md §13): when the mesh carries a
        # second axis of size > 1 from cfg.mesh.query_axes (a dedicated
        # ``query`` axis, or ``model`` reused), the batch is partitioned
        # into q_lanes blocks of Q/q_lanes queries, each walked against
        # every data shard. q_lanes == 1 is the PR 3 replicated layout.
        self.q_axis = (query_axis_name(mesh, cfg.mesh.query_axes)
                       if mesh is not None and cfg.mesh.query_parallel
                       else None)
        self.q_lanes = (int(mesh.shape[self.q_axis])
                        if self.q_axis is not None else 1)
        if mesh is not None:
            sh = index_shardings(mesh, axis, query_axis=self.q_axis)
            put = functools.partial(jax.device_put, device=sh["rows"])
            # explicit query-side staging: dispatch() places the packed
            # query tensors asynchronously so host->device transfer of
            # batch N+1 overlaps batch N's device time
            self._q_put = functools.partial(jax.device_put,
                                            device=sh["queries"])
        else:
            # reference mode (DESIGN.md §10): no mesh — everything lives
            # on the default device and ``search`` runs the bit-identical
            # shard-at-a-time reference path. This is how an S-shard
            # snapshot restores onto a machine with fewer than S devices
            # with zero rebuild and unchanged results.
            put = jnp.asarray
            self._q_put = jnp.asarray
        self._put = put
        self.vectors = put(sindex.vectors)
        self.adjacency = put(sindex.adjacency)
        self.metadata = put(sindex.metadata)
        self.global_ids = put(sindex.global_ids)
        self.valid_bm = put(sindex.valid_bm)
        datlas = jax.tree.map(put, sindex.datlas)
        self._leaves, self._tdef = jax.tree_util.tree_flatten(datlas)
        self.v_cap = sindex.datlas.v_cap
        self.vocab_sizes = sindex.vocab_sizes
        self.tag_fields = sindex.tag_fields
        tag_cols = first_columns(self.tag_fields)
        self.n, self.n_shards = sindex.n, s
        self._search = (self._build_program(has_bounds=False)
                        if mesh is not None else None)
        self._search_iv = None  # built lazily on the first interval query
        self._ref = jax.jit(
            lambda datlas, vec, adj, meta, vbm, qv, f, a, b: search_batch(
                datlas, vec, adj, meta, qv, f, a, cfg.walk,
                valid_bm=vbm, bounds=b, kcfg=cfg.kernel, tag_cols=tag_cols))
        self.dispatches = 0
        self.publish_generation = 0
        self.fence_retries = 0

    def _build_program(self, has_bounds: bool):
        axis, p = self.axis, self.p
        kcfg = self.cfg.kernel
        tag_cols = first_columns(self.tag_fields)
        nl, tdef = len(self._leaves), self._tdef

        def sharded_search(*args):
            leaves, rest = args[:nl], args[nl:]
            vectors, adjacency, metadata, global_ids, valid_bm = rest[:5]
            q_vecs, fields, allowed = rest[5:8]
            bounds = rest[8] if has_bounds else None
            datlas = jax.tree_util.tree_unflatten(
                tdef, [l[0] for l in leaves])
            out = search_batch(datlas, vectors[0], adjacency[0], metadata[0],
                               q_vecs, fields, allowed, p,
                               valid_bm=valid_bm[0], bounds=bounds,
                               kcfg=kcfg, tag_cols=tag_cols)
            gids = jnp.where(out["res_i"] >= 0,
                             global_ids[0][jnp.maximum(out["res_i"], 0)], -1)
            all_v = jax.lax.all_gather(out["res_v"], axis)
            all_i = jax.lax.all_gather(gids, axis)
            res_v, res_i = merge_topk(all_v, all_i, p.k)
            # the slowest shard's rounds, iterations and slots, one per
            # query lane so that they follow the queries' layout; every
            # shard sweeps its lane's clause tables alike
            return dict(res_v=res_v, res_i=res_i,
                        hops=jax.lax.psum(out["hops"], axis),
                        walks=jax.lax.psum(out["walks"], axis),
                        rounds=jax.lax.pmax(out["rounds"], axis)[None],
                        iters=jax.lax.pmax(out["iters"], axis)[None],
                        slots=jax.lax.pmax(out["slots"], axis)[None],
                        clause_passes=jax.lax.pmax(out["clause_passes"],
                                                   axis)[None])

        # index leaves are partitioned row-wise over the data axis; the
        # query tensors (and the bounds table, when the batch carries
        # interval clauses) are replicated on a 1D mesh, or partitioned on
        # their leading batch dim over the query axis on a 2D mesh — each
        # lane then walks its Q/q_lanes block against every shard, and the
        # all_gather/psum over ``axis`` stay within the lane's shard group.
        # Outputs follow the queries: per-lane rows on the query axis.
        q_spec = P(self.q_axis) if self.q_axis is not None else P()
        n_q = 4 if has_bounds else 3
        in_specs = tuple([P(axis)] * (nl + 5) + [q_spec] * n_q)
        out_specs = dict(res_v=q_spec, res_i=q_spec, hops=q_spec,
                         walks=q_spec, rounds=q_spec, iters=q_spec,
                         slots=q_spec, clause_passes=q_spec)
        return jax.jit(jax.shard_map(sharded_search, mesh=self.mesh,
                                     in_specs=in_specs, out_specs=out_specs,
                                     check_vma=False))

    def insert_batch(self, vectors: np.ndarray, metadata: np.ndarray, *,
                     gids: np.ndarray | None = None) -> np.ndarray:
        """Append (vector, metadata) rows to the live index (DESIGN.md §9):
        balance-aware shard placement, slab writes + validity-bit flips,
        reverse-edge graph repair, and incremental atlas updates all happen
        on the host mirror; the sharded device arrays are then re-placed
        with the same shapes and shardings, so the compiled ``shard_map``
        search program is reused as-is. Returns the new rows' global ids.

        Ingest costs host->device transfers only — ``dispatches`` (the
        search-path contract counter) is untouched."""
        if self._istate is None:
            raise ValueError(
                "index has no insert state; build_sharded_index(...) it "
                "with capacity=... to reserve append room")
        from repro.core.batched.lifecycle import ensure_capacity

        st, mcfg = self._istate, self.cfg.maintenance
        room = ensure_capacity(st, np.asarray(vectors).shape[0], mcfg)
        if room["grown"]:
            # keep the shape-baked knob truthful for snapshot/restore
            self.cfg = self.cfg.with_knobs(
                {"serve.capacity": room["new_cap"] * len(st.shards)})
        gids, touched = insert_rows(st, vectors, metadata, gids=gids,
                                    defer_repair=mcfg.defer_repair)
        if room["compacted"] or room["grown"]:
            self.refresh_device()  # rows moved / shapes changed: full
        else:
            self._refresh_device_index(touched)
        return gids

    def delete_batch(self, gids) -> int:
        """Tombstone documents by global id (DESIGN.md §12): clear their
        bits on the host mirror and re-place the packed validity bitmap —
        the single liveness source the fused search reads — so a delete
        costs one bit-pack + transfer. No recompile, no graph/atlas work
        (tombstones keep routing walks until compaction recycles them).
        Returns the number of rows tombstoned."""
        if self._istate is None:
            raise ValueError(
                "index has no insert state; deletes need a capacity-slab "
                "index (build_sharded_index(..., capacity=...))")
        from repro.core.batched.lifecycle import delete_rows

        st = self._istate
        n, touched = delete_rows(st, gids)
        if hasattr(self, "_host"):
            for s in touched:
                self._host["valid"][s] = st.shards[s].valid
            valid = self._host["valid"]
        else:
            valid = np.stack([sl.valid for sl in st.shards])
        self.valid_bm = self._put(pack_bits(jnp.asarray(valid)))
        self.publish_generation += 1
        return n

    def refresh_device(self, touched: list[int] | None = None) -> None:
        """Re-place the sharded device arrays from the host mirror after
        host-side maintenance (compaction, growth, deferred repair) —
        the uniform engine hook ``MaintenanceLoop`` publishes through.
        ``touched=None`` refreshes every shard; slab growth invalidates
        the stacked host cache so the new shapes propagate (the jitted
        shard_map program retraces once)."""
        st = self._istate
        if st is None:
            return
        if (hasattr(self, "_host")
                and self._host["vectors"].shape[1] != st.shards[0].cap):
            del self._host  # stale stacked shapes after grow_state
            touched = None
        if touched is None:
            touched = list(range(len(st.shards)))
        self._refresh_device_index(touched)

    @property
    def state(self):
        """The host ``InsertState`` mirror (None on a build-once index) —
        what the lifecycle/maintenance subsystem mutates."""
        return self._istate

    def _refresh_device_index(self, touched: list[int]) -> None:
        st, put = self._istate, self._put
        if not hasattr(self, "_host"):
            # first insert: snapshot the host stacks + per-shard emitted
            # atlases once, so later batches re-emit only touched shards
            # (touched ones are emitted by the loop below, not twice here)
            self._host = {
                "vectors": np.stack([sl.vectors for sl in st.shards]),
                "adjacency": np.stack([sl.adjacency for sl in st.shards]),
                "metadata": np.stack([sl.metadata for sl in st.shards]),
                "global_ids": np.stack([sl.global_ids
                                        for sl in st.shards]),
                "valid": np.stack([sl.valid for sl in st.shards])}
            self._shard_atlases = [
                None if s in touched
                else emit_device_atlas(sl, self.v_cap, st.tag_fields)
                for s, sl in enumerate(st.shards)]
        for s in touched:
            sl = st.shards[s]
            self._host["vectors"][s] = sl.vectors
            self._host["adjacency"][s] = sl.adjacency
            self._host["metadata"][s] = sl.metadata
            self._host["global_ids"][s] = sl.global_ids
            self._host["valid"][s] = sl.valid
            self._shard_atlases[s] = emit_device_atlas(sl, self.v_cap,
                                                       st.tag_fields)
        self.vectors = put(jnp.asarray(self._host["vectors"]))
        self.adjacency = put(jnp.asarray(self._host["adjacency"]))
        self.metadata = put(jnp.asarray(self._host["metadata"]))
        self.global_ids = put(jnp.asarray(self._host["global_ids"]))
        self.valid_bm = put(pack_bits(jnp.asarray(self._host["valid"])))
        datlas = jax.tree.map(put, stack_atlases(self._shard_atlases))
        self._leaves, self._tdef = jax.tree_util.tree_flatten(datlas)
        self.n = st.next_gid
        self.vocab_sizes = st.expand_vocab(self.vocab_sizes)
        self.publish_generation += 1

    @property
    def insert_stats(self) -> dict | None:
        """Ingest/staleness accounting, or None on a build-once index."""
        return self._istate.stats() if self._istate is not None else None

    def _pack_queries(self, queries: list[Query]):
        return pack_query_batch(queries, v_cap=self.v_cap,
                                vocab_sizes=self.vocab_sizes,
                                tag_fields=self.tag_fields)

    def _pad_to_lanes(self, queries: list[Query]) -> list[Query]:
        """Pad the batch to a multiple of the query-axis size (shard_map
        needs the partitioned dim divisible by the axis). Pads are inert —
        ``FilterExpr.never()`` admits no point, so they never seed — and
        carry a unit basis vector: a zero vector would go NaN under cosine
        normalization and could poison the lane's top-k merge."""
        rem = len(queries) % self.q_lanes
        if self.q_lanes == 1 or rem == 0:
            return queries
        basis = np.zeros(np.asarray(queries[0].vector).shape, np.float32)
        basis[0] = 1.0
        dummy = Query(vector=basis, predicate=FilterExpr.never())
        return list(queries) + [dummy] * (self.q_lanes - rem)

    def dispatch(self, queries: list[Query], seed: int = 0, *,
                 batch: int = -1) -> dict:
        """Fenced pack + ONE jitted shard_map call; returns an in-flight
        token without syncing the host (see BatchedEngine.dispatch, also
        for ``batch``). The packed query tensors are staged onto the mesh's
        query sharding explicitly, so batch N+1's host->device transfer
        overlaps batch N's device time. Reference mode (mesh=None)
        dispatches the shard-at-a-time program instead — same token
        contract."""
        del seed
        q_n = len(queries)
        padded = self._pad_to_lanes(queries)
        packed, gen = _fence_pack(self, padded, batch)
        q_vecs, fields, allowed, bounds = packed
        token = {"q_n": q_n, "generation": gen, "batch": batch}
        if self.mesh is None:
            with TraceAnnotation("fns.dispatch", batch=batch):
                token["out"] = self._run_reference(q_vecs, fields, allowed,
                                                   bounds)
            self.dispatches += self.n_shards
            return token
        q_args = [self._q_put(a) for a in (q_vecs, fields, allowed)]
        args = (*self._leaves, self.vectors, self.adjacency,
                self.metadata, self.global_ids, self.valid_bm, *q_args)
        if bounds is None:
            program = self._search
        else:
            if self._search_iv is None:
                self._search_iv = self._build_program(has_bounds=True)
            program = self._search_iv
            args = (*args, self._q_put(bounds))
        token["out"] = dispatch_program(program, batch, *args)
        self.dispatches += 1
        return token

    def collect(self, token: dict, finish=None):
        """Sync an in-flight ``dispatch`` token: one host sync + result
        post-processing (``engine.fetch_results``). ``stats["generation"]``
        is the scalar publish generation the batch was dispatched against;
        ``stats["rounds"]``/``stats["iters"]``/``stats["slots"]`` the
        slowest shard's."""
        return fetch_results(token, finish)

    def search(self, queries: list[Query], seed: int = 0, *,
               batch: int = -1, finish=None):
        """Filtered top-k for a batch across all shards: one device
        dispatch, one host sync. Stats sum device work over shards (every
        shard walks every query)."""
        del seed
        return self.collect(self.dispatch(queries, batch=batch), finish)

    def _run_reference(self, q_vecs, fields, allowed, bounds):
        """Shard-at-a-time device program behind both the reference-mode
        ``dispatch`` and the ``search_reference`` oracle: the identical
        per-shard fused programs + the identical merge, no host sync."""
        # every shard's slice moves to the default device: a program over
        # mesh-sharded inputs would be partitioned, and a Pallas kernel
        # cannot be partitioned automatically
        local = functools.partial(jax.device_put, device=jax.devices()[0])
        per_v, per_i, hops, walks = [], [], 0, 0
        rounds = iters = slots = passes = 0
        for s in range(self.n_shards):
            datlas = jax.tree_util.tree_unflatten(
                self._tdef, [local(l[s]) for l in self._leaves])
            out = self._ref(datlas, local(self.vectors[s]),
                            local(self.adjacency[s]), local(self.metadata[s]),
                            local(self.valid_bm[s]),
                            q_vecs, fields, allowed, bounds)
            per_v.append(out["res_v"])
            per_i.append(jnp.where(
                out["res_i"] >= 0,
                local(self.global_ids[s])[jnp.maximum(out["res_i"], 0)], -1))
            hops = hops + out["hops"]
            walks = walks + out["walks"]
            rounds = jnp.maximum(rounds, out["rounds"])
            iters = jnp.maximum(iters, out["iters"])
            slots = jnp.maximum(slots, out["slots"])
            passes = jnp.maximum(passes, out["clause_passes"])
        res_v, res_i = merge_topk(jnp.stack(per_v), jnp.stack(per_i),
                                  self.p.k)
        return dict(res_v=res_v, res_i=res_i, hops=hops, walks=walks,
                    rounds=rounds, iters=iters, slots=slots,
                    clause_passes=passes)

    def search_reference(self, queries: list[Query]):
        """Single-device fused baseline: the identical per-shard
        ``search_batch`` programs run shard-at-a-time on the default
        device, merged by the same ``merge_topk`` in the same shard order.
        The mesh path must match this bit-for-bit (tested at selectivities
        {0.5, 0.1, 0.02} on 1D and 2D meshes)."""
        q_vecs, fields, allowed, bounds = self._pack_queries(queries)
        out = self._run_reference(q_vecs, fields, allowed, bounds)
        return fetch_results({"out": out, "q_n": len(queries), "batch": -1})
