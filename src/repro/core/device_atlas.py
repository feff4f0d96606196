"""Device-resident anchor atlas: batched anchor selection as fixed-shape
JAX ops (paper §4.2–4.3 moved onto the accelerator; DESIGN.md §3).

``AnchorAtlas`` stores members / cluster_index as host dicts-of-dicts, so
the batched engine used to drop out of JAX every restart round and loop
over queries in Python. ``DeviceAtlas`` packs the same structure into flat
device arrays so one jitted call selects anchors for all Q queries:

* ``csr_pts`` (n,) i32 + ``csr_offsets`` (K+1,) i32 — the members lists
  CSR-flattened: point ids grouped by cluster, ascending id within a
  cluster. The per-(field, value) sublists of the host atlas are recovered
  through the query's pass bitmap, so the pack is O(n), not O(n·F).
* ``presence`` (F, K, W) u32 — the inverted cluster_index transposed into
  fixed-shape bitmaps: bit v of ``presence[f, k]`` is set iff cluster k
  holds ≥1 point with metadata[·, f] == v. A conjunctive cluster-match is
  then a bitwise AND over clauses of OR-reduced words — the host's
  postings intersection without data-dependent shapes.

``select_anchors_batch`` reproduces ``AnchorAtlas.select_anchors`` exactly
(same seed sets, same consumed clusters) for every query in the batch; the
in-cluster nearest-matching-member scan is one masked ``lax.top_k`` over
a shared (Q, n) score sweep per yielding-cluster slot. Scores are
full-f32 matmuls on every platform (a TPU's default f32 matmul rounds
operands to bf16).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.batched.bitmap import pack_bits
from repro.core.batched.bitmap import n_words as _n_words
from repro.core.config import AtlasConfig
from repro.core.predicate import Interval
# sentinel + device-side count derivation live with the kernels that
# consume the tables; re-exported here next to the packers that emit them
from repro.kernels.filter_eval import DEAD_DISJUNCT, table_n_disj

NEG = jnp.float32(-3.4e38)
HIGHEST = jax.lax.Precision.HIGHEST
_ACFG = AtlasConfig()
V_CAP = _ACFG.v_cap_min
# mirrors AnchorAtlas.cluster_members_matching's cap
MEMBER_CAP = _ACFG.member_cap

# ceiling on the *auto-sized* value-bitmap width: beyond this, per-value
# presence bitmaps would scale device memory with the vocabulary (the very
# blow-up interval clauses exist to avoid), so codes past the cap are
# tracked only by the per-cluster [code_min, code_max] envelope and served
# by interval clauses. An explicit v_cap still sizes exactly as asked.
AUTO_V_CAP_MAX = _ACFG.auto_v_cap_max

INT32_MAX = np.int32(2**31 - 1)


def auto_v_cap(vmax: int) -> int:
    """Value-bitmap width for a corpus whose largest metadata code is
    ``vmax``: at least V_CAP (common small vocabularies share one width),
    else the next 32-bit word boundary, ceilinged at AUTO_V_CAP_MAX so a
    vocab-10^6 timestamp field doesn't allocate megabit presence rows —
    the ONE sizing rule shared by atlas packing and the index builders'
    capacity slabs."""
    return min(max(V_CAP, 32 * _n_words(vmax + 1)), AUTO_V_CAP_MAX)


def _pack_clauses(clauses, fields_row: np.ndarray, allowed_row: np.ndarray,
                  v_cap: int, bounds_row: np.ndarray | None = None) -> None:
    """Write one conjunctive clause list into a (C,) fields row + a
    (C, Wv) value-bitmap row (+ optionally a (C, 2) interval-bounds row).
    An ``Interval`` spec writes only its bounds — the bitmap row stays
    zero and the kernels dispatch on ``lo <= hi``. Negative values are
    dropped (code -1 = unpopulated can never match); a non-negative value
    ≥ v_cap cannot be represented in the bitmap and raises — compile with
    ``v_cap=`` so such values lower to interval clauses instead."""
    for ci, (f, spec) in enumerate(clauses):
        fields_row[ci] = f
        if isinstance(spec, Interval):
            if bounds_row is None:
                raise ValueError(
                    "interval clause in a value-set-only table; pack via "
                    "pack_dnf (bounds-capable) instead of pack_predicates")
            bounds_row[ci, 0] = max(spec.lo, 0)
            bounds_row[ci, 1] = min(spec.hi, int(INT32_MAX))
            continue
        for v in spec:
            if 0 <= v < v_cap:
                allowed_row[ci, v >> 5] |= np.uint32(1) << np.uint32(v & 31)
            elif v >= v_cap:
                raise ValueError(
                    f"clause value {v} >= v_cap={v_cap} cannot pack into "
                    f"the value bitmap; compile the predicate with "
                    f"v_cap={v_cap} so it lowers to interval clauses")


def pack_predicates(preds, *, max_clauses: int | None = None,
                    v_cap: int = V_CAP) -> tuple[np.ndarray, np.ndarray]:
    """FilterPredicates -> clause tables (fields (Q, C) i32, -1 = inactive;
    allowed (Q, C, ceil(v_cap/32)) u32 value bitmaps)."""
    n_cl = max((p.n_clauses for p in preds), default=0)
    C = max(1, n_cl) if max_clauses is None else max_clauses
    if n_cl > C:
        raise ValueError(f"predicate has {n_cl} clauses > max_clauses={C}")
    Q = len(preds)
    fields = np.full((Q, C), -1, np.int32)
    allowed = np.zeros((Q, C, _n_words(v_cap)), np.uint32)
    for qi, pred in enumerate(preds):
        _pack_clauses(pred.clauses, fields[qi], allowed[qi], v_cap)
    return fields, allowed


def pack_dnf(dnfs, *, max_disjuncts: int | None = None,
             max_clauses: int | None = None, v_cap: int = V_CAP,
             ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Compiled DNF predicates -> disjunctive clause tables:
    fields (Q, D, C) i32 (-1 inactive clause, DEAD_DISJUNCT = -2 for the
    dead-disjunct padding tail), allowed (Q, D, C, ceil(v_cap/32)) u32
    value bitmaps, bounds (Q, D, C, 2) i32 interval-bounds rows, n_disj
    (Q,) i32 per-query live-disjunct counts. A clause is *either* a
    value-set (its bitmap populated, bounds at the inert (0, -1) sentinel)
    *or* an interval (bounds = [lo, hi] with lo <= hi, bitmap zero) — the
    kernels dispatch per clause on ``lo <= hi``, so bounds bytes are O(1)
    in the field's vocabulary. Disjunct d of query q is the same
    conjunctive table ``pack_predicates`` emits (shared ``_pack_clauses``);
    the kernels OR the per-disjunct pass words (DESIGN.md §8). Live
    disjuncts pack densely from 0, so ``table_n_disj`` recovers the counts
    on device."""
    n_dj = max((d.n_disjuncts for d in dnfs), default=0)
    D = max(1, n_dj) if max_disjuncts is None else max_disjuncts
    if n_dj > D:
        raise ValueError(f"predicate has {n_dj} disjuncts > "
                         f"max_disjuncts={D}")
    n_cl = max((d.max_clauses for d in dnfs), default=0)
    C = max(1, n_cl) if max_clauses is None else max_clauses
    if n_cl > C:
        raise ValueError(f"disjunct has {n_cl} clauses > max_clauses={C}")
    Q = len(dnfs)
    fields = np.full((Q, D, C), DEAD_DISJUNCT, np.int32)
    allowed = np.zeros((Q, D, C, _n_words(v_cap)), np.uint32)
    bounds = np.zeros((Q, D, C, 2), np.int32)
    bounds[..., 1] = -1
    n_disj = np.zeros(Q, np.int32)
    for qi, dnf in enumerate(dnfs):
        n_disj[qi] = dnf.n_disjuncts
        for di, clauses in enumerate(dnf.disjuncts):
            fields[qi, di, :] = -1
            _pack_clauses(clauses, fields[qi, di], allowed[qi, di], v_cap,
                          bounds[qi, di])
    return fields, allowed, bounds, n_disj


# canonical packer lives in core/batched/bitmap.py; kept under the original
# name for existing callers
pack_bitmap = pack_bits


def stack_atlases(atlases: list["DeviceAtlas"]) -> "DeviceAtlas":
    """Stack per-shard atlases into one DeviceAtlas pytree whose leaves
    carry a leading shard dim (the form ``shard_map`` partitions over the
    mesh ``data`` axis). Shards must agree on n_clusters / row count /
    v_cap — the sharded build pads them to common shapes first."""
    caps = {a.v_cap for a in atlases}
    if len(caps) != 1:
        raise ValueError(f"shard atlases disagree on v_cap: {sorted(caps)}")
    shapes = {tuple(l.shape for l in jax.tree_util.tree_leaves(a))
              for a in atlases}
    if len(shapes) != 1:
        raise ValueError(f"shard atlases disagree on shapes: {shapes}")
    return jax.tree.map(lambda *ls: jnp.stack(ls), *atlases)


def _excl_cumsum(x: jax.Array) -> jax.Array:
    return jnp.cumsum(x, axis=-1) - x


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceAtlas:
    centroids: jax.Array    # (K, d) f32 unit-norm
    assign: jax.Array       # (n,) i32 point -> cluster
    csr_pts: jax.Array      # (n,) i32 point ids grouped by cluster
    csr_offsets: jax.Array  # (K+1,) i32
    inv_perm: jax.Array     # (n,) i32 point id -> position in csr_pts
    presence: jax.Array     # (F, K, W) u32 cluster/field/value bitmap
    code_min: jax.Array     # (F, K) i32 smallest code present (INT32_MAX if
    #                         the cluster holds no populated code on field f)
    code_max: jax.Array     # (F, K) i32 largest code present (-1 if none);
    #                         the [code_min, code_max] envelope is the
    #                         interval-clause cluster-match test — exact
    #                         codes >= v_cap never enter the presence bitmap
    v_cap: int = V_CAP

    def tree_flatten(self):
        return ((self.centroids, self.assign, self.csr_pts, self.csr_offsets,
                 self.inv_perm, self.presence, self.code_min, self.code_max),
                (self.v_cap,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, v_cap=aux[0])

    @property
    def n_clusters(self) -> int:
        return self.centroids.shape[0]

    @staticmethod
    def from_atlas(atlas, v_cap: int | None = None) -> "DeviceAtlas":
        """CSR/bitmap-pack a host AnchorAtlas (numpy build, arrays land on
        the default device). ``v_cap=None`` auto-sizes to the largest
        metadata code in the inverted index (≥ V_CAP, rounded up to a
        32-bit word, ceilinged at AUTO_V_CAP_MAX) — codes beyond the
        auto ceiling are tracked only by the per-cluster code_min/code_max
        envelope and must be queried through interval clauses. An explicit
        v_cap must cover every code (fails loudly otherwise)."""
        assign = np.asarray(atlas.assign, np.int32)
        n = assign.shape[0]
        k = atlas.n_clusters
        explicit = v_cap is not None
        if v_cap is None:
            # a tag-set field's bitmap holds all V labels, present or not
            vmax = max([v for by_f in atlas.cluster_index for v in by_f]
                       + [v - 1 for _, v in atlas.tag_fields], default=-1)
            v_cap = auto_v_cap(vmax)
        order = np.argsort(assign, kind="stable").astype(np.int32)
        offsets = np.zeros(k + 1, np.int64)
        offsets[1:] = np.cumsum(np.bincount(assign, minlength=k))
        inv_perm = np.empty(n, np.int32)
        inv_perm[order] = np.arange(n, dtype=np.int32)
        f_count = len(atlas.cluster_index)
        pres = np.zeros((f_count, k, _n_words(v_cap)), np.uint32)
        cmin = np.full((f_count, k), INT32_MAX, np.int32)
        cmax = np.full((f_count, k), -1, np.int32)
        for f in range(f_count):
            for v, clusters in atlas.cluster_index[f].items():
                if v < 0 or (explicit and v >= v_cap):
                    raise ValueError(
                        f"metadata code {v} out of DeviceAtlas range "
                        f"[0, {v_cap}); rebuild with a larger v_cap")
                cmin[f, clusters] = np.minimum(cmin[f, clusters], v)
                cmax[f, clusters] = np.maximum(cmax[f, clusters], v)
                if v < v_cap:
                    pres[f, clusters, v >> 5] |= (np.uint32(1)
                                                  << np.uint32(v & 31))
        return DeviceAtlas(
            jnp.asarray(atlas.centroids, jnp.float32), jnp.asarray(assign),
            jnp.asarray(order), jnp.asarray(offsets, jnp.int32),
            jnp.asarray(inv_perm), jnp.asarray(pres), jnp.asarray(cmin),
            jnp.asarray(cmax), v_cap=v_cap)

    def pad_rows(self, m: int) -> "DeviceAtlas":
        """Extend the point-indexed arrays to ``m`` rows with inert pad
        entries (sharded indexes pad every shard to a common row count).

        Pads are assigned to cluster 0 and appended at the tail of
        ``csr_pts``/``inv_perm`` (each pad maps to itself). That leaves the
        real-row CSR ranks untouched — ``_matched_counts`` cumsums run over
        positions, and a pad position contributes 0 because the caller's
        pass bitmap (ANDed with the shard's row-validity bitmap) is always
        False on pads — so selection math never sees them."""
        n = self.assign.shape[0]
        if m < n:
            raise ValueError(f"pad_rows to {m} < current {n} rows")
        if m == n:
            return self
        tail = jnp.arange(n, m, dtype=jnp.int32)
        return DeviceAtlas(
            self.centroids,
            jnp.concatenate([self.assign, jnp.zeros(m - n, jnp.int32)]),
            jnp.concatenate([self.csr_pts, tail]),
            self.csr_offsets,
            jnp.concatenate([self.inv_perm, tail]),
            self.presence, self.code_min, self.code_max, v_cap=self.v_cap)

    # -- batched query-time operations (all jittable, fixed shapes) ----------
    def matching_clusters_batch(self, fields: jax.Array, allowed: jax.Array,
                                bounds: jax.Array | None = None) -> jax.Array:
        """Clause tables -> (Q, K) bool match mask (host matching_clusters
        for every query at once): AND over active clauses of 'cluster has
        ≥1 point with an allowed value on that field'. Disjunctive (Q, D, C)
        tables (``pack_dnf``) OR the per-disjunct conjunctive masks, with
        dead disjuncts contributing False. Interval clauses (``bounds``
        rows with lo <= hi) use the conservative per-cluster
        [code_min, code_max] envelope-overlap test — a superset of the
        exact host match, safe because matched *counts* still gate which
        clusters yield seeds."""
        if fields.ndim == 3:
            return self._disjunct_cluster_masks(fields, allowed,
                                                bounds).any(axis=1)
        pres = self.presence[jnp.maximum(fields, 0)]        # (Q, C, K, W)
        hit = ((pres & allowed[:, :, None, :]) != 0).any(-1)  # (Q, C, K)
        return jnp.where((fields >= 0)[:, :, None], hit, True).all(axis=1)

    def _disjunct_cluster_masks(self, fields: jax.Array, allowed: jax.Array,
                                bounds: jax.Array | None = None) -> jax.Array:
        """(Q, D, C) DNF tables -> (Q, D, K) bool per-disjunct conjunctive
        cluster-match masks (dead disjuncts all-False) — the pre-union form
        the per-disjunct seed quota needs."""
        pres = self.presence[jnp.maximum(fields, 0)]        # (Q, D, C, K, W)
        hit = ((pres & allowed[..., None, :]) != 0).any(-1)  # (Q, D, C, K)
        if bounds is not None:
            lo, hi = bounds[..., 0], bounds[..., 1]         # (Q, D, C)
            cmin = self.code_min[jnp.maximum(fields, 0)]    # (Q, D, C, K)
            cmax = self.code_max[jnp.maximum(fields, 0)]
            overlap = (cmin <= hi[..., None]) & (cmax >= lo[..., None])
            hit = jnp.where((lo <= hi)[..., None], overlap, hit)
        conj = jnp.where((fields >= 0)[..., None], hit, True).all(axis=2)
        alive = fields[:, :, 0] > DEAD_DISJUNCT             # (Q, D)
        return conj & alive[:, :, None]

    def _matched_counts(self, passes: jax.Array) -> tuple[jax.Array, jax.Array]:
        """passes (Q, n) bool -> (counts (Q, K) of matching points per
        cluster, per-point within-cluster matched rank (Q, n) in id order,
        for the member-cap cutoff)."""
        k = self.n_clusters
        cnt = jax.vmap(lambda p: jax.ops.segment_sum(
            p.astype(jnp.int32), self.assign, num_segments=k))(passes)
        p_csr = passes[:, self.csr_pts].astype(jnp.int32)     # (Q, n) csr order
        inc0 = jnp.pad(jnp.cumsum(p_csr, axis=1), ((0, 0), (1, 0)))
        starts = self.csr_offsets[self.assign[self.csr_pts]]  # (n,)
        rank_csr = inc0[:, :-1] - inc0[:, starts]
        return cnt, rank_csr[:, self.inv_perm]

    def select_anchors_batch(
        self, q_vecs: jax.Array, clause_tables: tuple,
        processed: jax.Array, vectors: jax.Array, passes: jax.Array, *,
        n_seeds: int = 10, c_max: int = 5, member_cap: int = MEMBER_CAP,
        disjunct_quota: int = 2,
    ) -> tuple[jax.Array, jax.Array]:
        """One anchor-selection round for Q queries (Alg. 2 lines 3–14,
        batched). Exact host semantics: rank matching unprocessed clusters
        by centroid score, scan until the seed budget fills or c_max
        clusters yield, take the nearest matching members of each visited
        cluster (quota = remaining budget), consume every scanned cluster.

        q_vecs (Q, d); clause_tables from ``pack_predicates``; processed
        (Q, K) bool; vectors (n, d); passes (Q, n) bool (the batched
        engine unpacks its packed pass bitmap once per batch and hands the
        dense form to every round). Returns (seeds (Q, n_seeds) i32
        -1-padded, used (Q, K) bool to OR into ``processed``).

        Disjunctive (Q, D, C) tables add a minimum per-disjunct quota
        (``disjunct_quota`` seeds): the union scan ranks clusters purely by
        centroid score, so a dominant disjunct whose nearest cluster holds
        ≥ n_seeds matches can exhaust the whole budget before any cluster
        of a rare disjunct is visited. Each *starved* live disjunct — one
        with an available matching cluster but none visited this round —
        gets its best-scoring cluster force-visited and up to
        ``disjunct_quota`` nearest passing members spliced into the seed
        set (displacing tail main seeds; the conjunctive rank-2 path is
        byte-identical to before).
        """
        fields, allowed = clause_tables[0], clause_tables[1]
        bounds = clause_tables[2] if len(clause_tables) > 2 else None
        if allowed.shape[-1] != self.presence.shape[-1]:
            raise ValueError(
                f"clause tables packed for {32 * allowed.shape[-1]} codes "
                f"but atlas v_cap is {self.v_cap}; pack_predicates with "
                f"v_cap=atlas.v_cap")
        q_n, k = q_vecs.shape[0], self.n_clusters
        n = vectors.shape[0]
        n_seeds = min(n_seeds, n)
        qidx = jnp.arange(q_n)[:, None]

        # one presence expansion per round: the pre-union (Q, D, K) masks
        # feed both the availability union and the disjunct-quota repair
        dmasks = (self._disjunct_cluster_masks(fields, allowed, bounds)
                  if fields.ndim == 3 else None)
        match = (dmasks.any(axis=1) if dmasks is not None
                 else self.matching_clusters_batch(fields, allowed))
        avail = match & ~processed
        scores = jnp.matmul(q_vecs, self.centroids.T,
                            precision=HIGHEST)                # (Q, K)
        order = jnp.argsort(-jnp.where(avail, scores, NEG), axis=1)

        cnt, rank_id = self._matched_counts(passes)
        cnt = jnp.minimum(cnt, member_cap)

        # scan ranked clusters with exclusive cumsums: a cluster is visited
        # iff neither stop condition held when its turn came; monotone
        # cumsums make the all-available prefix equal the visited prefix.
        avail_r = jnp.take_along_axis(avail, order, axis=1)
        cnt_r = jnp.take_along_axis(cnt, order, axis=1) * avail_r
        yld_r = (cnt_r > 0).astype(jnp.int32)
        visited_r = (avail_r & (_excl_cumsum(cnt_r) < n_seeds)
                     & (_excl_cumsum(yld_r) < c_max))
        used = jnp.zeros((q_n, k), bool).at[qidx, order].set(visited_r)

        elig = passes & used[:, self.assign] & (rank_id < member_cap)
        # one dense (Q, n) score sweep shared by the quota fill and the
        # disjunct-quota repair
        sims = jnp.einsum("qd,nd->qn", q_vecs, vectors, precision=HIGHEST)
        seeds = self._seed_by_topk(sims, elig, order, cnt_r, visited_r,
                                   yld_r, n_seeds, c_max)
        if dmasks is not None and disjunct_quota > 0:
            seeds, used = self._apply_disjunct_quota(
                dmasks, processed, sims, passes,
                rank_id, scores, used, seeds,
                n_seeds=n_seeds, member_cap=member_cap,
                quota=min(disjunct_quota, n_seeds))
        return seeds, used

    def _apply_disjunct_quota(self, dmasks, processed, sims, passes,
                              rank_id, scores, used, seeds,
                              *, n_seeds: int, member_cap: int, quota: int):
        """Starved-disjunct repair: force-visit each starved live
        disjunct's best available cluster and splice up to ``quota`` of its
        nearest passing members into the seed set (deduped against the
        main seeds, quota entries winning the truncation to n_seeds).

        "Passing" means the WHOLE predicate (the union pass bitmap): the
        kernels never emit per-disjunct row bitmaps, so in a mixed cluster
        the quota seeds may be another disjunct's members that happen to
        be nearer — the walk still enters the starved disjunct's cluster,
        but row-level per-disjunct seeding is a possible refinement
        (ROADMAP)."""
        q_n, k = used.shape
        n = sims.shape[1]
        d_tab = dmasks.shape[1]
        dmask = dmasks & ~processed[:, None, :]             # (Q, D, K)
        best_c = jnp.argmax(jnp.where(dmask, scores[:, None, :], NEG),
                            axis=2)                         # (Q, D)
        starved = dmask.any(axis=2) & ~(dmask & used[:, None, :]).any(axis=2)
        used = used | (starved[:, :, None]
                       & (jnp.arange(k)[None, None, :] == best_c[..., None])
                       ).any(axis=1)

        def with_quota():
            big = jnp.int32(d_tab * quota + n_seeds)
            pos = jnp.arange(quota, dtype=jnp.int32)[None, :]
            q_ids, q_keys = [], []
            for dj in range(d_tab):
                m = (passes & starved[:, dj, None]
                     & (self.assign[None, :] == best_c[:, dj, None])
                     & (rank_id < member_cap))
                s_j, ids_j = jax.lax.top_k(jnp.where(m, sims, -jnp.inf),
                                           quota)
                ok = jnp.isfinite(s_j)
                q_ids.append(jnp.where(ok, ids_j.astype(jnp.int32), -1))
                q_keys.append(jnp.where(ok, dj * quota + pos, big))
            # merge: quota entries carry keys < main entries; dedup by id
            # via a lexicographic (id, key) sort, then re-sort by key and
            # truncate to the seed budget
            main_pos = jnp.arange(n_seeds, dtype=jnp.int32)[None, :]
            all_ids = jnp.concatenate(q_ids + [seeds], axis=1)
            all_keys = jnp.concatenate(
                q_keys + [jnp.where(seeds >= 0, d_tab * quota + main_pos,
                                    big)], axis=1)
            sort_ids = jnp.where(all_keys < big, all_ids, n)  # invalid last
            ids_s, keys_s = jax.lax.sort((sort_ids, all_keys), num_keys=2)
            dup = jnp.concatenate(
                [jnp.zeros((q_n, 1), bool), ids_s[:, 1:] == ids_s[:, :-1]],
                axis=1) & (ids_s < n)
            keys_f = jnp.where(dup | (ids_s >= n), big, keys_s)
            keys_o, ids_o = jax.lax.sort((keys_f, ids_s), num_keys=1)
            return jnp.where(keys_o[:, :n_seeds] < big, ids_o[:, :n_seeds],
                             -1)

        # the per-disjunct top-k sweeps only run when some disjunct in the
        # batch is actually starved (batch-level gate: one starved query
        # pays for the batch, none starved pays only the mask algebra)
        seeds = jax.lax.cond(starved.any(), with_quota, lambda: seeds)
        return seeds, used

    def _seed_by_topk(self, sims, elig, order, cnt_r, visited_r, yld_r,
                      n_seeds: int, c_max: int):
        """Quota fill via masked top-k: one ``lax.top_k`` per
        yielding-cluster slot (≤ c_max) over the shared score sweep with
        the eligibility mask restricted to that slot's cluster."""
        q_n = sims.shape[0]
        # slot j (yield order) -> cluster id and its matched count
        slot_pos = jnp.where(visited_r & (yld_r > 0), _excl_cumsum(yld_r),
                             c_max)
        qidx = jnp.arange(q_n)[:, None]
        init = jnp.full((q_n, c_max + 1), -1, jnp.int32)
        slot_cluster = init.at[qidx, slot_pos].set(order)[:, :c_max]
        slot_cnt = (jnp.zeros((q_n, c_max + 1), jnp.int32)
                    .at[qidx, slot_pos].set(cnt_r)[:, :c_max])
        take = jnp.clip(n_seeds - _excl_cumsum(slot_cnt), 0, slot_cnt)
        all_keys, all_ids = [], []
        pos = jnp.arange(n_seeds, dtype=jnp.int32)[None, :]
        for j in range(c_max):
            mask = elig & (self.assign[None, :] == slot_cluster[:, j, None])
            s_j, ids_j = jax.lax.top_k(
                jnp.where(mask, sims, -jnp.inf), n_seeds)
            ids_j = jnp.where(jnp.isfinite(s_j), ids_j, -1)
            keep = pos < take[:, j, None]
            all_keys.append(jnp.where(keep, j * n_seeds + pos,
                                      jnp.int32(c_max * n_seeds)))
            all_ids.append(jnp.where(keep, ids_j.astype(jnp.int32), -1))
        keys = jnp.concatenate(all_keys, axis=1)
        ids = jnp.concatenate(all_ids, axis=1)
        ks, ids_s = jax.lax.sort((keys, ids), num_keys=1)
        return jnp.where(ks[:, :n_seeds] < c_max * n_seeds,
                         ids_s[:, :n_seeds], -1)
