"""The lockstep walk narrows in stages as its lanes finish: a compacted
walk gives every lane exactly what the same lane gives in a walk too
narrow to compact, and its ``slots`` counter says how much width the
iterations ran at."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.batched.bitmap import unpack_bits
from repro.core.batched.engine import (LANE_FLOOR, TERM_MAXHOP,
                                       BatchedEngine, stage_widths,
                                       walk_batch)
from repro.core.config import FnsConfig

Q = 64
EXACT = ("res_i", "term", "hops", "p1_hops", "visited_bm")


def test_stage_widths_halve_down_to_the_floor():
    assert LANE_FLOOR == 8
    assert stage_widths(256) == (256, 128, 64, 32, 16, 8)
    assert stage_widths(36) == (36, 18, 9)
    assert stage_widths(16) == (16, 8)
    for q in (1, 6, 8, 15):
        assert stage_widths(q) == (q,)


@pytest.fixture(scope="module")
def lanes(sel_sweep):
    """Q lanes of the selectivity sweep's queries (its 36, then the first
    28 again), their packed pass bitmaps, and two rounds of anchor seeds
    as ``atlas_round`` selects them: the first round's, and a restart's
    from the clusters the first did not use."""
    _, index, queries = sel_sweep
    cfg = FnsConfig().with_knobs({"walk.k": 10, "walk.beam_width": 4})
    eng = BatchedEngine(index, cfg)
    qs = (queries * 2)[:Q]
    q_vecs, fields, allowed, bounds = eng._pack_queries(qs)
    assert bounds is None
    pass_bm = eng._passes(eng.metadata, fields, allowed, bounds)
    passes = unpack_bits(pass_bm, eng.vectors.shape[0])
    p = eng.p

    def select(gate):
        return eng.datlas.select_anchors_batch(
            q_vecs, (fields, allowed), gate, eng.vectors, passes,
            n_seeds=p.n_seeds, c_max=p.c_max,
            disjunct_quota=p.disjunct_quota)

    seeds, used = select(jnp.zeros((Q, eng.datlas.n_clusters), bool))
    restart, _ = select(used)
    return eng, q_vecs, pass_bm, seeds, restart


@functools.lru_cache(maxsize=None)
def _program(p):
    return jax.jit(functools.partial(walk_batch, p=p))


def _walk(eng, p, q_vecs, pass_bm, seeds, init):
    return jax.device_get(_program(p)(eng.vectors, eng.adjacency, pass_bm,
                                      q_vecs, seeds, init_results=init))


def _in_groups(eng, p, q_vecs, pass_bm, seeds, init, width=LANE_FLOOR):
    """The same lanes walked ``width`` at a time: one stage, no
    compaction."""
    assert stage_widths(width) == (width,)
    outs = []
    for g in range(0, Q, width):
        part = slice(g, g + width)
        outs.append(_walk(eng, p, q_vecs[part], pass_bm[part], seeds[part],
                          None if init is None else
                          tuple(x[part] for x in init)))
    return outs


def _cases(lanes):
    eng, q_vecs, pass_bm, seeds, restart = lanes
    unseeded = np.asarray(seeds).copy()
    unseeded[::3] = -1                  # every third lane gets no seed
    first = _walk(eng, eng.p, q_vecs, pass_bm, seeds, None)
    init = (jnp.asarray(first["res_v"]), jnp.asarray(first["res_i"]))
    return {"sel_sweep": (seeds, None),
            "some_unseeded": (jnp.asarray(unseeded), None),
            "restart_round": (restart, init)}


@pytest.mark.parametrize("case", ["sel_sweep", "some_unseeded",
                                  "restart_round"])
def test_compacted_walk_matches_uncompacted_lanes(lanes, case):
    eng, q_vecs, pass_bm, _, _ = lanes
    seeds, init = _cases(lanes)[case]
    got = _walk(eng, eng.p, q_vecs, pass_bm, seeds, init)
    groups = _in_groups(eng, eng.p, q_vecs, pass_bm, seeds, init)
    for key in EXACT:
        np.testing.assert_array_equal(
            got[key], np.concatenate([g[key] for g in groups]), err_msg=key)
    np.testing.assert_allclose(
        got["res_v"], np.concatenate([g["res_v"] for g in groups]),
        rtol=0, atol=1e-6)
    # the whole batch runs until its slowest lane stops
    assert int(got["iters"]) == max(int(g["iters"]) for g in groups)
    hops = int(got["hops"].sum())
    assert hops <= int(got["slots"]) < Q * int(got["iters"])
    # the lanes finish at different times: the walk narrowed
    assert got["hops"].min() < got["hops"].max()
    if case == "some_unseeded":
        dead = np.arange(Q) % 3 == 0
        assert (got["hops"][dead] == 0).all()
        assert (got["res_i"][dead] == -1).all()


def test_a_round_nobody_seeded_runs_no_iteration(lanes):
    eng, q_vecs, pass_bm, seeds, _ = lanes
    out = _walk(eng, eng.p, q_vecs, pass_bm, jnp.full_like(seeds, -1), None)
    assert int(out["iters"]) == 0 and int(out["slots"]) == 0
    assert (out["hops"] == 0).all() and (out["res_i"] == -1).all()


def test_slots_are_the_full_width_when_every_lane_runs_to_the_cap(lanes):
    eng, q_vecs, pass_bm, seeds, _ = lanes
    p = eng.cfg.with_knobs({"walk.max_hops": 3}).walk
    out = _walk(eng, p, q_vecs, pass_bm, seeds, None)
    assert (out["term"] == TERM_MAXHOP).all()
    assert int(out["iters"]) == 3
    assert int(out["slots"]) == Q * 3 == int(out["hops"].sum())
