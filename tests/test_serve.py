"""Serve-layer tests: coalescing, bit-identity, cache eviction, backpressure,
and the JSON-lines front-ends.

The acceptance contract (ISSUE 3): N concurrent requests for the same qrel
must be coalesced into FEWER backend ``evaluate_*`` calls than N, with
per-query results bit-identical to direct ``RelevanceEvaluator.evaluate``.
Socket-spinning suites (TCP, stdio subprocess) are marked ``slow``.
"""

import asyncio
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import RelevanceEvaluator, concat_run_buffers
from repro.data.synthetic_ir import synthesize_run
from repro.serve import (EvaluationService, LRUCache, MicroBatcher,
                         handle_line)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
QREL_PATH = os.path.join(FIXTURES, "conformance.qrel")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MEASURES = ("map", "ndcg", "recip_rank", "P", "bpref")


@pytest.fixture(scope="module")
def collection():
    run, qrel = synthesize_run(n_queries=24, n_docs=16, seed=7)
    return run, qrel


def _runs_with_perturbed_scores(run, n, seed=0):
    """n runs over the same documents with different scores."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        out.append({qid: {d: float(s + rng.normal())
                          for d, s in docs.items()}
                    for qid, docs in run.items()})
    return out


# -- evaluator coalescing hook (the backend primitive) -----------------------


def test_evaluate_buffers_bit_identical_to_evaluate(collection):
    run, qrel = collection
    ev = RelevanceEvaluator(qrel, MEASURES)
    runs = _runs_with_perturbed_scores(run, 5)
    bufs = [ev.tokenize_run(r) for r in runs]
    coalesced = ev.evaluate_buffers(bufs)
    for r, got in zip(runs, coalesced):
        want = ev.evaluate(r)
        assert got == want  # bit-identical: same floats, not approx


def test_evaluate_buffers_scores_list(collection):
    run, qrel = collection
    ev = RelevanceEvaluator(qrel, ("map",))
    buf = ev.tokenize_run(run)
    flip = -np.asarray(buf.scores)
    a, b = ev.evaluate_buffers([buf, buf], scores_list=[None, flip])
    assert a == ev.evaluate_buffer(buf)
    assert b == ev.evaluate_buffer(buf, scores=flip)


def test_evaluate_buffers_empty_and_mixed(collection):
    run, qrel = collection
    ev = RelevanceEvaluator(qrel, ("map",))
    empty = ev.tokenize_run({})
    buf = ev.tokenize_run(run)
    out = ev.evaluate_buffers([empty, buf, empty])
    assert out[0] == {} and out[2] == {}
    assert out[1] == ev.evaluate_buffer(buf)
    assert ev.evaluate_buffers([]) == []


def test_concat_run_buffers_validation(collection):
    run, qrel = collection
    ev = RelevanceEvaluator(qrel, ("map",))
    with pytest.raises(ValueError):
        concat_run_buffers([])
    unscored = ev.buffer_from_tokens(
        [list(qrel)[0]], counts=[1], tokens=[0])
    with pytest.raises(ValueError):
        concat_run_buffers([unscored, unscored])


def test_sharded_evaluate_buffers_matches_single(collection):
    run, qrel = collection
    from repro.distributed import ShardedEvaluator

    ev = RelevanceEvaluator(qrel, MEASURES)
    sev = ShardedEvaluator(ev)
    runs = _runs_with_perturbed_scores(run, 3)
    bufs = [ev.tokenize_run(r) for r in runs]
    results = sev.evaluate_buffers(bufs)
    singles = [sev.evaluate_buffer(b) for b in bufs]
    for got, want in zip(results, singles):
        assert got.per_query == want.per_query
        for k, v in want.aggregates.items():
            assert got.aggregates[k] == pytest.approx(v, rel=1e-6), k


# -- the service: coalescing acceptance test ---------------------------------


def test_service_coalesces_concurrent_requests(collection, monkeypatch):
    """N concurrent same-qrel requests → fewer backend calls than N, with
    per-query results bit-identical to direct RelevanceEvaluator.evaluate."""
    run, qrel = collection
    n = 8
    runs = _runs_with_perturbed_scores(run, n)
    direct = RelevanceEvaluator(qrel, MEASURES)
    want = [direct.evaluate(r) for r in runs]

    backend_calls = []
    real = RelevanceEvaluator.evaluate_buffers

    def counting(self, bufs, scores_list=None):
        backend_calls.append(len(bufs))
        return real(self, bufs, scores_list)

    monkeypatch.setattr(RelevanceEvaluator, "evaluate_buffers", counting)

    async def main():
        svc = EvaluationService(window=0.02, backend="single")
        svc.register_qrel("c", qrel, MEASURES)
        return await asyncio.gather(
            *(svc.evaluate("c", run=r) for r in runs)), svc

    results, svc = asyncio.run(main())
    assert len(backend_calls) < n  # coalesced: fewer evaluate_* calls than N
    assert sum(backend_calls) == n  # ... but every request was evaluated
    assert svc.stats()["backend_calls"] == len(backend_calls)
    for res, w in zip(results, want):
        assert res.per_query == w  # bit-identical floats


def test_service_max_batch_bounds_coalescing(collection):
    run, qrel = collection
    runs = _runs_with_perturbed_scores(run, 4)

    async def main():
        svc = EvaluationService(window=0.05, max_batch=2, backend="single")
        svc.register_qrel("c", qrel, ("map",))
        await asyncio.gather(*(svc.evaluate("c", run=r) for r in runs))
        return svc.stats()

    stats = asyncio.run(main())
    assert stats["backend_calls"] == 2  # 4 requests, size cap 2


def test_service_run_ref_rescoring_hot_path(collection):
    """register_run once, then score-only requests (zero string work)."""
    run, qrel = collection
    ev = RelevanceEvaluator(qrel, ("map", "recip_rank"))
    buf = ev.tokenize_run(run)
    rng = np.random.default_rng(3)
    score_sets = [rng.normal(size=buf.scores.shape[0]).astype(np.float32)
                  for _ in range(4)]

    async def main():
        svc = EvaluationService(window=0.02, backend="single")
        svc.register_qrel("c", qrel, ("map", "recip_rank"))
        info = svc.register_run("c", "bm25", run=run)
        assert info["n_queries"] == len(buf)
        res = await asyncio.gather(
            *(svc.evaluate("c", run_ref="bm25", scores=s)
              for s in score_sets))
        return res, svc.stats()

    results, stats = asyncio.run(main())
    assert stats["backend_calls"] < len(score_sets)
    for s, res in zip(score_sets, results):
        assert res.per_query == ev.evaluate_buffer(buf, scores=s)


def test_service_tokens_payload(collection):
    _, qrel = collection
    qid = sorted(qrel)[0]

    async def main():
        svc = EvaluationService(backend="single")
        svc.register_qrel("c", qrel, ("recip_rank",))
        return await svc.evaluate("c", tokens={
            "qids": [qid], "counts": [2], "tokens": [0, 1],
            "scores": [0.1, 0.9]})

    res = asyncio.run(main())
    ev = RelevanceEvaluator(qrel, ("recip_rank",))
    buf = ev.buffer_from_tokens([qid], [2], [0, 1], scores=[0.1, 0.9])
    assert res.per_query == ev.evaluate_buffer(buf)


def test_service_sharded_backend_matches_single(collection):
    run, qrel = collection
    from repro.distributed import ShardedEvaluator

    async def main():
        svc = EvaluationService(backend="sharded")
        svc.register_qrel("c", qrel, MEASURES)
        return await svc.evaluate("c", run=run)

    res = asyncio.run(main())
    ev = RelevanceEvaluator(qrel, MEASURES)
    # bit-identical to the direct sharded pipeline ...
    assert res.per_query == ShardedEvaluator(ev).evaluate(run).per_query
    # ... and to the single evaluator, whose classes and cores it runs
    want = ev.evaluate(run)
    for qid in want:
        for k, v in want[qid].items():
            assert res.per_query[qid][k] == v, (qid, k)


def test_service_cache_eviction_lru(collection):
    _, qrel = collection

    async def main():
        svc = EvaluationService(max_collections=2, backend="single")
        svc.register_qrel("a", qrel, ("map",))
        svc.register_qrel("b", qrel, ("map",))
        await svc.evaluate("a", run={})  # refresh 'a' → 'b' becomes LRU
        svc.register_qrel("c", qrel, ("map",))  # evicts 'b'
        stats = svc.stats()
        assert stats["collections"] == ["a", "c"]
        assert stats["cache"]["evictions"] == 1
        with pytest.raises(KeyError, match="unknown qrel_id 'b'"):
            await svc.evaluate("b", run={})
        # re-registration brings it back
        svc.register_qrel("b", qrel, ("map",))
        return await svc.evaluate("b", run={})

    res = asyncio.run(main())
    assert res.per_query == {}


def test_service_backpressure_caps_in_flight(collection):
    run, qrel = collection
    runs = _runs_with_perturbed_scores(run, 6)

    async def main():
        svc = EvaluationService(window=0.01, max_pending=2,
                                backend="single")
        svc.register_qrel("c", qrel, ("map",))
        await asyncio.gather(*(svc.evaluate("c", run=r) for r in runs))
        return svc.stats()

    stats = asyncio.run(main())
    assert stats["peak_in_flight"] <= 2
    assert stats["requests"] == 6 and stats["in_flight"] == 0


def test_service_request_validation(collection):
    run, qrel = collection

    async def main():
        svc = EvaluationService(backend="single")
        svc.register_qrel("c", qrel, ("map",))
        with pytest.raises(ValueError, match="exactly one"):
            await svc.evaluate("c")
        with pytest.raises(ValueError, match="exactly one"):
            await svc.evaluate("c", run=run, run_ref="x")
        with pytest.raises(KeyError, match="unknown run_ref"):
            await svc.evaluate("c", run_ref="nope", scores=[1.0])
        with pytest.raises(KeyError, match="unknown qrel_id"):
            await svc.evaluate("zzz", run=run)
        unscored = {"qids": [sorted(qrel)[0]], "counts": [1], "tokens": [0]}
        with pytest.raises(ValueError, match="no scores"):
            await svc.evaluate("c", tokens=unscored)

    asyncio.run(main())


# -- protocol (no sockets) ---------------------------------------------------


def test_protocol_handle_line_roundtrip(collection):
    run, qrel = collection

    async def main():
        svc = EvaluationService(backend="single")
        reg = json.loads(await handle_line(svc, json.dumps(
            {"op": "register_qrel", "id": 1, "qrel_id": "c",
             "qrel": qrel, "measures": ["map"]})))
        assert reg["ok"] and reg["id"] == 1
        assert reg["result"]["backend"] == "single"
        ev_resp = json.loads(await handle_line(svc, json.dumps(
            {"op": "evaluate", "id": 2, "qrel_id": "c", "run": run})))
        assert ev_resp["ok"]
        stats = json.loads(await handle_line(svc, json.dumps(
            {"op": "stats", "id": 3})))
        assert stats["result"]["requests"] == 1
        pong = json.loads(await handle_line(svc, '{"op": "ping", "id": 4}'))
        assert pong["result"] == "pong"
        dropped = json.loads(await handle_line(svc, json.dumps(
            {"op": "drop_qrel", "id": 5, "qrel_id": "c"})))
        assert dropped["result"] == {"dropped": True}
        bad_op = json.loads(await handle_line(svc, '{"op": "frobnicate"}'))
        assert not bad_op["ok"] and "unknown op" in bad_op["error"]
        bad_line = json.loads(await handle_line(svc, "{not json"))
        assert not bad_line["ok"] and "bad request line" in bad_line["error"]
        return ev_resp

    resp = json.loads(json.dumps(asyncio.run(main())))
    want = RelevanceEvaluator(collection[1], ("map",)).evaluate(collection[0])
    got = resp["result"]["per_query"]
    for qid in want:
        assert got[qid]["map"] == pytest.approx(want[qid]["map"], abs=1e-9)


def test_protocol_deadline_ms_enforced_server_side(collection):
    """A worker enforces ``deadline_ms`` on its own: an op that cannot
    finish inside the budget answers ``deadline_exceeded`` instead of
    holding the connection, and an ample budget changes nothing."""
    run, qrel = collection

    async def main():
        svc = EvaluationService(backend="single", window=0.25)
        reg = json.loads(await handle_line(svc, json.dumps(
            {"op": "register_qrel", "id": 1, "qrel_id": "c",
             "qrel": qrel, "measures": ["map"], "deadline_ms": 60000})))
        assert reg["ok"], reg
        # the evaluate sits in the 250 ms coalescing window: a 30 ms
        # budget cannot be met, and the worker says so machine-readably
        late = json.loads(await handle_line(svc, json.dumps(
            {"op": "evaluate", "id": 2, "qrel_id": "c", "run": run,
             "deadline_ms": 30})))
        assert not late["ok"] and late["code"] == "deadline_exceeded"
        assert "deadline_ms" in late["error"]
        ample = json.loads(await handle_line(svc, json.dumps(
            {"op": "evaluate", "id": 3, "qrel_id": "c", "run": run,
             "deadline_ms": 60000})))
        plain = json.loads(await handle_line(svc, json.dumps(
            {"op": "evaluate", "id": 4, "qrel_id": "c", "run": run})))
        assert ample["ok"] and plain["ok"]
        assert ample["result"] == plain["result"]  # budget leaves no trace
        for bad in (0, -5, True, "soon"):
            resp = json.loads(await handle_line(svc, json.dumps(
                {"op": "ping", "id": 9, "deadline_ms": bad})))
            assert not resp["ok"] and resp["code"] == "invalid", resp
        return True

    assert asyncio.run(main())


def test_unjudged_queries_skipped_across_serve_roundtrip(collection):
    """Run-only queries are skipped trec_eval-style, bit-identically across
    the dict path, the RunBuffer path, and a serve round-trip."""
    run, qrel = collection
    noisy = {**run, "zz_unjudged": {"dA": 2.0, "dB": 1.0},
             "zz_also": {"dC": 0.5}}
    ev = RelevanceEvaluator(qrel, MEASURES)
    want = ev.evaluate(noisy)
    assert set(want) == set(qrel) & set(noisy)
    assert "zz_unjudged" not in want and "zz_also" not in want
    # dict path == RunBuffer path, bit-identical
    assert ev.evaluate_buffer(ev.tokenize_run(noisy)) == want

    async def main():
        svc = EvaluationService(backend="single")
        reg = json.loads(await handle_line(svc, json.dumps(
            {"op": "register_qrel", "id": 1, "qrel_id": "c", "qrel": qrel,
             "measures": list(MEASURES)})))
        assert reg["ok"], reg
        return json.loads(await handle_line(svc, json.dumps(
            {"op": "evaluate", "id": 2, "qrel_id": "c", "run": noisy})))

    resp = asyncio.run(main())
    assert resp["ok"], resp
    # JSON round-trips floats exactly: the serve path is bit-identical too
    assert resp["result"]["per_query"] == want


# -- unit: cache + batcher ---------------------------------------------------


def test_lru_cache_eviction_order_and_hook():
    evicted = []
    c = LRUCache(2, on_evict=lambda k, v: evicted.append(k))
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1
    c.put("c", 3)
    assert evicted == ["b"] and sorted(c.keys()) == ["a", "c"]
    assert c.get("b") is None
    assert c.stats()["evictions"] == 1 and c.stats()["misses"] == 1
    with pytest.raises(ValueError):
        LRUCache(0)


def test_batcher_error_fans_out_to_all_waiters():
    async def main():
        async def flush(key, items):
            raise RuntimeError("backend down")

        mb = MicroBatcher(flush, window=0.005)
        results = await asyncio.gather(
            *(mb.submit("k", i) for i in range(3)), return_exceptions=True)
        assert all(isinstance(r, RuntimeError) for r in results)
        assert mb.flushes == 1

    asyncio.run(main())


def test_lru_cache_replacement_fires_hook_and_counter():
    """put() on a resident key must release the displaced value (the leak)."""
    gone = []
    c = LRUCache(4, on_evict=lambda k, v: gone.append((k, v)))
    c.put("a", "old")
    c.put("a", "new")                       # replacement, same key
    assert gone == [("a", "old")]
    assert c.get("a") == "new"
    assert c.stats()["replacements"] == 1
    assert c.stats()["evictions"] == 0      # replacement is not an eviction
    # re-putting the SAME object is a recency refresh, not a displacement
    c.put("a", "new")
    assert gone == [("a", "old")]
    assert c.stats()["replacements"] == 1
    # a stored None is still a real entry: replacing it fires too
    c.put("n", None)
    c.put("n", 0)
    assert gone[-1] == ("n", None)


def test_lru_cache_replacement_and_eviction_compose():
    gone = []
    c = LRUCache(2, on_evict=lambda k, v: gone.append((k, v)))
    c.put("a", 1)
    c.put("b", 2)
    c.put("a", 10)      # replace refreshes recency: 'b' is now LRU
    c.put("c", 3)       # capacity eviction drops 'b'
    assert gone == [("a", 1), ("b", 2)]
    assert sorted(c.keys()) == ["a", "c"]
    s = c.stats()
    assert s["replacements"] == 1 and s["evictions"] == 1


def test_batcher_cancelled_flush_cancels_all_waiters():
    """CancelledError from flush_fn must not strand coalesced waiters.

    It is a BaseException, so the generic error fan-out never sees it; the
    regression was three submit() coroutines awaiting futures nobody would
    ever resolve.  wait_for puts a hard bound on the hang.
    """
    async def main():
        async def flush(key, items):
            raise asyncio.CancelledError()

        mb = MicroBatcher(flush, window=0.005)
        results = await asyncio.wait_for(
            asyncio.gather(*(mb.submit("k", i) for i in range(3)),
                           return_exceptions=True),
            timeout=2.0)
        assert all(isinstance(r, asyncio.CancelledError) for r in results)
        assert mb.flushes == 1          # the flush still counts
        assert mb.idle()                # nothing left pending or in flight

    asyncio.run(main())


def test_batcher_timer_cancellation_rejects_pending_waiters():
    """Cancelling a window timer (teardown) cancels the waiters it covered."""
    async def main():
        async def flush(key, items):
            return items

        mb = MicroBatcher(flush, window=30.0)   # far beyond the test
        waiter = asyncio.ensure_future(mb.submit("k", 1))

        async def timer_sleeping():
            # deterministic, load-immune sync: wait for the timer task to
            # exist and then to be SUSPENDED at an await (its window
            # sleep), so cancel() lands inside _timed_flush — not before
            # the coroutine's first step, where cleanup could never run
            while not mb._timers:
                await asyncio.sleep(0)
            (t,) = mb._timers.values()
            while t.get_coro().cr_await is None:
                await asyncio.sleep(0)
            return t

        timer = await asyncio.wait_for(timer_sleeping(), timeout=2.0)
        timer.cancel()
        with pytest.raises(asyncio.CancelledError):
            await asyncio.wait_for(waiter, timeout=2.0)
        assert mb.idle()                        # no orphaned pending state

    asyncio.run(main())


def test_batcher_size_cap_flush_survives_timer_cancel_race():
    """_flush_now's own timer cancel must not touch the claimed batch."""
    async def main():
        async def flush(key, items):
            return [x * 10 for x in items]

        mb = MicroBatcher(flush, window=30.0, max_batch=2)
        # first submit opens the window; second hits the size cap, which
        # cancels the timer and flushes both immediately
        results = await asyncio.wait_for(
            asyncio.gather(mb.submit("k", 1), mb.submit("k", 2)),
            timeout=2.0)
        assert results == [10, 20]

        async def spin_idle():
            # the cancelled timer and the flush task's finally block are
            # plain ready-queue callbacks: yielding (no wall-clock sleep)
            # until idle() is deterministic under any load
            while not mb.idle():
                await asyncio.sleep(0)

        await asyncio.wait_for(spin_idle(), timeout=2.0)
        assert mb.idle()

    asyncio.run(main())


def test_service_reregister_releases_old_collection():
    """Re-registering a qrel_id must release the displaced collection."""
    qrel = {"q1": {"d1": 1, "d2": 0}}
    run = {"q1": {"d1": 2.0, "d2": 1.0}}

    async def main():
        svc = EvaluationService(backend="single")
        svc.register_qrel("c", qrel, ("map",))
        svc.register_run("c", "r", run=run)
        old = svc._collections.get("c")
        assert old.runs                  # the state that used to leak
        svc.register_qrel("c", qrel, ("map",))
        assert not old.runs              # displaced collection was released
        assert old._sharded is None
        s = svc.stats()
        assert s["cache"]["replacements"] == 1
        assert s["released_collections"] == 1
        # the fresh collection starts clean and still serves
        with pytest.raises(KeyError):
            await svc.evaluate("c", run_ref="r", scores=[1.0, 2.0])
        res = await svc.evaluate("c", run=run)
        assert res.per_query["q1"]["map"] == 1.0

    asyncio.run(main())


def test_service_drop_qrel_releases_state():
    qrel = {"q1": {"d1": 1}}

    async def main():
        svc = EvaluationService(backend="single")
        svc.register_qrel("c", qrel, ("map",))
        svc.register_run("c", "r", run={"q1": {"d1": 1.0}})
        col = svc._collections.get("c")
        assert svc.drop_qrel("c") is True
        assert not col.runs
        assert svc.stats()["released_collections"] == 1
        assert svc.drop_qrel("c") is False

    asyncio.run(main())


def test_batcher_separate_keys_flush_separately():
    async def main():
        calls = []

        async def flush(key, items):
            calls.append((key, len(items)))
            return items

        mb = MicroBatcher(flush, window=0.005)
        await asyncio.gather(mb.submit("a", 1), mb.submit("b", 2),
                             mb.submit("a", 3))
        return sorted(calls)

    assert asyncio.run(main()) == [("a", 2), ("b", 1)]


# -- front-ends (sockets / subprocess: slow) ---------------------------------


async def _tcp_request(host, port, lines):
    reader, writer = await asyncio.open_connection(host, port)
    for line in lines:
        writer.write((json.dumps(line) + "\n").encode())
    await writer.drain()
    out = []
    for _ in lines:
        out.append(json.loads(await reader.readline()))
    writer.close()
    await writer.wait_closed()
    return out


@pytest.mark.slow
def test_tcp_frontend_coalesces_across_connections(collection):
    """Concurrent requests from DIFFERENT TCP clients share backend calls."""
    from repro.serve import serve_tcp

    run, qrel = collection
    n = 6
    runs = _runs_with_perturbed_scores(run, n)
    want = [RelevanceEvaluator(qrel, ("map",)).evaluate(r) for r in runs]

    async def main():
        svc = EvaluationService(window=0.05, backend="single")
        svc.register_qrel("c", qrel, ("map",))
        server = await serve_tcp(svc, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        try:
            replies = await asyncio.gather(*(
                _tcp_request("127.0.0.1", port,
                             [{"op": "evaluate", "id": i, "qrel_id": "c",
                               "run": runs[i]}])
                for i in range(n)))
        finally:
            server.close()
            await server.wait_closed()
        return replies, svc.stats()

    replies, stats = asyncio.run(main())
    assert stats["backend_calls"] < n
    for i, (reply,) in enumerate(replies):
        assert reply["ok"], reply
        got = reply["result"]["per_query"]
        for qid in want[i]:
            assert got[qid]["map"] == pytest.approx(want[i][qid]["map"],
                                                    abs=1e-9)


@pytest.mark.slow
def test_tcp_large_qrel_regression(collection):
    """ISSUE 4 repro: a >64 KiB register_qrel line used to raise
    ``ValueError: Separator is found, but chunk is longer than limit`` in
    the reader loop and kill the connection with an empty response.  At the
    server's DEFAULT limit it must round-trip bit-identically."""
    from repro.serve import serve_tcp

    run, qrel = collection
    # pad ids so the qrel line clears 64 KiB by a wide margin
    big_qrel = {f"{qid}-{'x' * 220}": {f"{d}-{'y' * 220}": r
                                      for d, r in docs.items()}
                for qid, docs in qrel.items()}
    big_run = {f"{qid}-{'x' * 220}": {f"{d}-{'y' * 220}": s
                                     for d, s in docs.items()}
               for qid, docs in run.items()}
    line = json.dumps({"op": "register_qrel", "id": 1, "qrel_id": "big",
                       "qrel": big_qrel, "measures": ["map", "ndcg"]})
    assert len(line) > (1 << 16)

    async def main():
        svc = EvaluationService(backend="single")
        server = await serve_tcp(svc, "127.0.0.1", 0)  # default limit
        port = server.sockets[0].getsockname()[1]
        try:
            reg, res = await _tcp_request("127.0.0.1", port, [
                json.loads(line),
                {"op": "evaluate", "id": 2, "qrel_id": "big",
                 "run": big_run}])
        finally:
            server.close()
            await server.wait_closed()
        return reg, res

    reg, res = asyncio.run(main())
    assert reg["ok"], reg
    assert res["ok"], res
    want = RelevanceEvaluator(big_qrel, ("map", "ndcg")).evaluate(big_run)
    assert res["result"]["per_query"] == want  # bit-identical


@pytest.mark.slow
def test_stdio_frontend_subprocess():
    """python -m repro.serve end to end over stdin/stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    requests = "\n".join([
        json.dumps({"op": "ping", "id": 0}),
        json.dumps({"op": "evaluate", "id": 1, "qrel_id": "default",
                    "run": {"q1": {"APPLE": 2.0, "BANANA": 1.0}}}),
        json.dumps({"op": "stats", "id": 2}),
    ]) + "\n"
    out = subprocess.run(
        [sys.executable, "-m", "repro.serve", "--qrel", QREL_PATH,
         "-m", "map", "--window-ms", "1"],
        input=requests, capture_output=True, text=True, env=env,
        timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    replies = {r["id"]: r for r in map(json.loads,
                                       out.stdout.strip().splitlines())}
    assert replies[0]["result"] == "pong"
    assert replies[1]["ok"], replies[1]
    assert replies[1]["result"]["per_query"]["q1"]["map"] > 0
    assert replies[2]["result"]["requests"] == 1
    assert "registered qrel 'default'" in out.stderr
