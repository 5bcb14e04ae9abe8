"""Execution tests: partition-aware execution, shuffle elision, correctness."""

import numpy as np
import pytest

from repro.api import Session
from repro.core import (author_integrator, enumerate_candidates,
                        pagerank_iteration)
from repro.data.partition_store import PartitionStore


def _reddit_data(n_sub=5000, n_auth=1000, seed=0):
    rng = np.random.default_rng(seed)
    subs = {"author": rng.integers(0, n_auth, n_sub).astype(np.int64),
            "score": rng.normal(size=n_sub).astype(np.float32)}
    auths = {"author": np.arange(n_auth, dtype=np.int64),
             "karma": rng.normal(size=n_auth).astype(np.float32)}
    return subs, auths


def _join_oracle(subs, auths):
    karma = auths["karma"][subs["author"]]
    return subs["author"], subs["score"], karma


def _run(store_partitioned: bool):
    wl = author_integrator()
    subs, auths = _reddit_data()
    store = PartitionStore(num_workers=8)
    if store_partitioned:
        store.write("submissions", subs,
                    enumerate_candidates(wl.graph, "submissions")[0])
        store.write("authors", auths,
                    enumerate_candidates(wl.graph, "authors")[0])
    else:
        store.write("submissions", subs)
        store.write("authors", auths)
    vals, stats = Session(store).run(wl)
    join_node = max(n for n, nd in wl.graph.nodes.items()
                    if nd.kind == "join")
    return vals[join_node], stats


def test_join_correct_and_shuffles_elided():
    out_rr, st_rr = _run(False)
    out_part, st_part = _run(True)
    assert st_rr.shuffles_performed == 2 and st_rr.shuffles_elided == 0
    assert st_part.shuffles_performed == 0 and st_part.shuffles_elided == 2
    assert st_part.shuffle_bytes == 0 and st_rr.shuffle_bytes > 0

    # both paths produce the same multiset of joined rows
    subs, auths = _reddit_data()
    oa, os_, ok = _join_oracle(subs, auths)
    for out in (out_rr, out_part):
        assert out.num_rows == len(oa)
        order = np.lexsort((out.columns["score"], out.columns["author"]))
        ref_order = np.lexsort((os_, oa))
        np.testing.assert_array_equal(out.columns["author"][order],
                                      oa[ref_order])
        np.testing.assert_allclose(out.columns["karma"][order],
                                   ok[ref_order], rtol=1e-6)


def test_pagerank_iteration_correct():
    n, fanout = 2000, 5
    rng = np.random.default_rng(1)
    neighbors = rng.integers(0, n, (n, fanout)).astype(np.int64)
    pages = {"url": np.arange(n, dtype=np.int64), "neighbors": neighbors}
    ranks = {"url": np.arange(n, dtype=np.int64),
             "rank": np.full(n, 1.0 / n, np.float64)}

    wl = pagerank_iteration()
    # emit contribs: each neighbor gets rank/fanout
    def emit(cols):
        contrib = np.repeat((cols["rank"] / fanout)[:, None], fanout, 1)
        return {"url": cols["neighbors"], "contrib": contrib}
    for node in wl.graph.nodes.values():
        if node.params.get("tag") == "emit_contribs":
            node.params["fn"] = emit

    store = PartitionStore(num_workers=4)
    store.write("pages", pages, enumerate_candidates(wl.graph, "pages")[0])
    store.write("ranks", ranks, enumerate_candidates(wl.graph, "ranks")[0])
    vals, stats = Session(store).run(wl)
    agg_node = max(n_ for n_, nd in wl.graph.nodes.items()
                   if nd.kind == "aggregate")
    out = vals[agg_node]

    # oracle: sum of incoming rank/fanout per page
    oracle = np.zeros(n)
    np.add.at(oracle, neighbors.reshape(-1),
              np.repeat(ranks["rank"] / fanout, fanout))
    got = np.zeros(n)
    got[out.columns["key"]] = out.columns["contrib"]
    mask = oracle > 0
    np.testing.assert_allclose(got[mask], oracle[mask], rtol=1e-6)
    # pages/ranks co-partitioned on url: the join shuffles are elided, only
    # the aggregate repartition (by destination url) runs
    assert stats.shuffles_elided >= 2


def test_repartition_counts_bytes():
    subs, _ = _reddit_data(1000, 100)
    store = PartitionStore(num_workers=4)
    ds = store.write("s", subs)
    wl = author_integrator()
    c = enumerate_candidates(wl.graph, "submissions")[0]
    new, moved = store.repartition(ds, c)
    assert moved > 0
    assert new.num_rows == ds.num_rows
