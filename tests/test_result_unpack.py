"""The result unpack after a batch's fetch (``fetch_results``, host span
``fns.unpack``) is host numpy alone: every query's ids come from one float32
compare over the whole ``(Q, k)`` result, equal, dtype included, to the
per-row ``res_i[i][res_v[i] < INF / 2]`` kept here as the oracle, and no JAX
op or device transfer runs inside it (the transfer guards below raise on
one)."""
import contextlib

import jax
import numpy as np
import pytest

from repro.core.batched import engine
from repro.core.batched.engine import (INF, BatchedEngine, BatchedParams,
                                       fetch_results)

K = 6


@contextlib.contextmanager
def _host_only():
    with jax.transfer_guard_host_to_device("disallow"), \
            jax.transfer_guard_device_to_host("disallow"):
        yield


def _result(seed: int = 0):
    """A (Q, K) result with rows of 0, some (not a prefix) and all K slots
    found, plus 3 lane-pad rows past ``q_n``; unfilled slots hold INF and
    id -1, as the program leaves them."""
    rng = np.random.default_rng(seed)
    q_n, q = 9, 12
    res_v = np.sort(rng.random((q, K), dtype=np.float32), axis=1)
    res_i = rng.integers(0, 50, (q, K)).astype(np.int32)
    unfilled = np.zeros((q, K), bool)
    unfilled[0] = True                    # nothing found
    unfilled[2, 3:] = True                # a found prefix
    unfilled[3, [1, 4]] = True            # found slots with holes between
    unfilled[5, :K - 1] = True            # only the last slot found
    unfilled[q_n:] = rng.random((q - q_n, K)) < 0.5
    res_v[unfilled] = np.float32(3.4e38)
    res_i[unfilled] = -1
    return q_n, res_v, res_i


def _old_ids(res_v, res_i, q_n, gids=None):
    ids = [res_i[i][res_v[i] < INF / 2] for i in range(q_n)]
    return ids if gids is None else [gids[i] for i in ids]


def _token(res_v, res_i, q_n, gids=None, generation=None):
    q = res_v.shape[0]
    out = {"res_v": res_v, "res_i": res_i,
           "rounds": np.array(3, np.int32), "iters": np.array(17, np.int32),
           "slots": np.array(40, np.int32),
           "clause_passes": np.array(5, np.int32),
           "hops": np.arange(q, dtype=np.int32),
           "walks": np.ones(q, np.int32)}
    token = {"out": out, "q_n": q_n, "batch": 4, "gids": gids}
    if generation is not None:
        token["generation"] = generation
    return token


def _assert_same_ids(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("with_gids", [False, True])
def test_fetch_results_unpacks_on_the_host(with_gids):
    q_n, res_v, res_i = _result()
    gids = (np.random.default_rng(1).permutation(50).astype(np.int32)
            if with_gids else None)
    want = _old_ids(res_v, res_i, q_n, gids)
    with _host_only():
        ids, stats = fetch_results(_token(res_v, res_i, q_n, gids, 7))
    _assert_same_ids(ids, want)
    assert [a.size for a in ids[:6]] == [0, K, 3, K - 2, K, 1]
    assert stats.keys() == {"walks", "hops", "rounds", "iters", "slots",
                            "clause_passes", "generation"}
    assert stats["walks"].dtype == np.int32 and stats["walks"].shape == (q_n,)
    assert stats["hops"].dtype == np.int64
    np.testing.assert_array_equal(stats["hops"], np.arange(q_n))
    assert (stats["rounds"], stats["iters"], stats["slots"],
            stats["clause_passes"], stats["generation"]) == (3, 17, 40, 5, 7)
    assert all(type(stats[k]) is int
               for k in ("rounds", "iters", "slots", "clause_passes"))


def test_fetch_results_ids_are_fresh_arrays():
    q_n, res_v, res_i = _result(2)
    with _host_only():
        ids, _ = fetch_results(_token(res_v, res_i, q_n))
    for row in ids:
        assert not np.shares_memory(row, res_i)


def test_fetch_results_runs_finish_inside_the_unpack():
    q_n, res_v, res_i = _result(3)
    seen = {}

    def finish(ids, stats):
        seen["ids"], seen["stats"] = ids, stats
        return "done"

    with _host_only():
        assert fetch_results(_token(res_v, res_i, q_n), finish) == "done"
    _assert_same_ids(seen["ids"], _old_ids(res_v, res_i, q_n))
    assert "generation" not in seen["stats"]


@pytest.mark.parametrize("seed", [0, 4, 5])
def test_found_ids_matches_the_per_row_compare(seed):
    q_n, res_v, res_i = _result(seed)
    gids = np.arange(50, dtype=np.int64)[::-1].copy()
    for n in (0, 1, q_n, res_v.shape[0]):
        for g in (None, gids):
            with _host_only():
                got = engine._found_ids(res_v, res_i, n, g)
            _assert_same_ids(got, _old_ids(res_v, res_i, n, g))


def test_search_hostloop_unpacks_through_the_host_helper(
        small_index, small_queries, monkeypatch):
    """search_hostloop's final unpack goes through the same helper, with
    the device guarded off, and still matches the fused search."""
    calls = []
    helper = engine._found_ids

    def guarded(*a, **k):
        calls.append(a[2])
        with _host_only():
            return helper(*a, **k)

    monkeypatch.setattr(engine, "_found_ids", guarded)
    eng = BatchedEngine(small_index, BatchedParams(k=10, beam_width=4))
    queries = small_queries[:8]
    ids_h, _ = eng.search_hostloop(queries)
    ids_f, _ = eng.search(queries)
    assert calls == [8, 8]
    _assert_same_ids(ids_h, ids_f)
    assert any(0 < a.size for a in ids_h)
