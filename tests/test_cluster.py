"""Cluster acceptance tests (ISSUE 8): router, ring, and fault injection.

The contract proven here:

* a 2-worker cluster answers every op **bit-identically** to a
  single-process server / the in-process evaluator — including the CLI
  conformance golden reproduced byte-for-byte through ``EvalClient``;
* killing a worker **mid-request** is invisible to idempotent callers:
  the supervisor restarts the process, replays the registration journal,
  and the router retries the forwarded request transparently;
* non-idempotent ``drop_qrel`` against a down worker surfaces a
  machine-readable ``worker_unavailable`` error
  (:class:`~repro.client.errors.WorkerUnavailableError`) instead of
  retrying behind the caller's back;
* router drain answers in-flight requests and refuses new connections;
* membership changes (:meth:`Router.add_worker` / ``remove_worker``)
  move only the collections the ring reassigns, with no gap in service.

Worker processes cost ~1 s each to boot, so clusters are module-scoped:
``cluster`` (fast window, identity tests) and ``fault_cluster`` (wide
coalescing window so requests are reliably in flight when we kill the
worker under them).
"""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from repro import cli
from repro.client import (DeadlineExceededError, EvalClient, ServerError,
                          WorkerUnavailableError)
from repro.core import RelevanceEvaluator, aggregate_results, trec
from repro.core import supported_measures
from repro.data.synthetic_ir import synthesize_run
from repro.serve import EvaluationService
from repro.serve.cluster import (CircuitBreaker, HashRing,
                                 RegistrationJournal, Router)
from repro.serve.cluster.journal import JOURNAL_FILE
from repro.serve.cluster.testing import ClusterThread
from repro.serve.frontend import serve_protocol

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
QREL = os.path.join(FIXTURES, "conformance.qrel")
RUN = os.path.join(FIXTURES, "conformance.run")
GOLDEN = os.path.join(FIXTURES, "conformance.golden")

MEASURES = ("map", "ndcg", "recip_rank", "P")


# -- the hash ring (pure, no processes) ---------------------------------------


def test_ring_deterministic_across_instances():
    a = HashRing(["w0", "w1", "w2"])
    b = HashRing(["w2", "w0", "w1"])  # construction order must not matter
    keys = [f"col{i}" for i in range(200)]
    assert [a.owner(k) for k in keys] == [b.owner(k) for k in keys]


def test_ring_balance():
    ring = HashRing([f"w{i}" for i in range(4)])
    keys = [f"collection-{i}" for i in range(2000)]
    counts = {}
    for k in keys:
        counts[ring.owner(k)] = counts.get(ring.owner(k), 0) + 1
    assert set(counts) == {"w0", "w1", "w2", "w3"}
    for n in counts.values():  # 64 virtual nodes: no worker is starved
        assert 0.10 * len(keys) < n < 0.45 * len(keys)


def test_ring_minimal_remap_on_membership_change():
    ring = HashRing(["w0", "w1", "w2"])
    keys = [f"doc{i}" for i in range(1000)]
    before = {k: ring.owner(k) for k in keys}
    grown = ring.copy()
    grown.add("w3")
    moved = [k for k in keys if grown.owner(k) != before[k]]
    # every moved key lands on the newcomer, and only ~1/4 of keys move
    assert moved and all(grown.owner(k) == "w3" for k in moved)
    assert len(moved) < 0.45 * len(keys)
    grown.remove("w3")  # removal restores the previous assignment exactly
    assert {k: grown.owner(k) for k in keys} == before


def test_ring_owners_replica_sets_distinct_and_deterministic():
    ring = HashRing(["w0", "w1", "w2", "w3"])
    other = HashRing(["w3", "w1", "w0", "w2"])  # construction order agnostic
    for i in range(300):
        key = f"col{i}"
        owners = ring.owners(key, 2)
        assert len(owners) == 2 and len(set(owners)) == 2
        assert owners[0] == ring.owner(key)  # primary == the R=1 owner
        assert owners == other.owners(key, 2)
        # asking for more replicas than workers degrades to "everybody"
        assert sorted(ring.owners(key, 99)) == ["w0", "w1", "w2", "w3"]
    with pytest.raises(ValueError):
        ring.owners("x", 0)


def test_ring_owners_minimal_disturbance_on_membership_change():
    ring = HashRing(["w0", "w1", "w2"])
    keys = [f"doc{i}" for i in range(800)]
    before = {k: ring.owners(k, 2) for k in keys}
    grown = ring.copy()
    grown.add("w3")
    changed = 0
    for k in keys:
        after = set(grown.owners(k, 2))
        # the successor walk only gains stops: a set can change only by
        # the newcomer displacing ONE previous member, never by reshuffle
        assert after <= set(before[k]) | {"w3"}
        assert len(after & set(before[k])) >= 1
        if after != set(before[k]):
            assert "w3" in after
            changed += 1
    assert 0 < changed < 0.8 * len(keys)
    grown.remove("w3")  # removal restores every replica set exactly
    assert {k: grown.owners(k, 2) for k in keys} == before


# -- the circuit breaker (pure, no processes) ---------------------------------


def test_breaker_trips_probes_and_recovers():
    now = [0.0]
    b = CircuitBreaker(failures=3, cooldown=2.0, clock=lambda: now[0])
    assert b.state == "closed" and b.would_allow() and b.allow()
    b.record_failure()
    b.record_failure()
    b.record_success()   # any success resets the consecutive count
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()   # third CONSECUTIVE failure trips it
    assert b.state == "open" and b.trips == 1
    assert not b.would_allow() and not b.allow()
    now[0] = 1.0         # still cooling
    assert not b.would_allow()
    now[0] = 2.5         # cooled: exactly one half-open probe
    assert b.would_allow()       # pure check does not consume the probe
    assert b.would_allow()
    assert b.allow()             # the consuming check takes the slot
    assert b.state == "half_open"
    assert not b.would_allow() and not b.allow()  # single probe in flight
    b.record_failure()   # probe failed: straight back to open
    assert b.state == "open" and b.trips == 2
    now[0] = 5.0
    assert b.allow()
    b.record_success()   # probe succeeded: closed again
    assert b.state == "closed" and b.would_allow()
    assert b.stats() == {"state": "closed", "trips": 2,
                         "consecutive_failures": 0}


# -- the registration journal (durable, no processes) -------------------------


def test_journal_durable_roundtrip_and_drop_prune(tmp_path):
    """The prune-on-drop regression: a dropped collection must leave the
    durable log too, or replay after a restart resurrects it."""
    d = str(tmp_path)
    j = RegistrationJournal(d)
    j.record_qrel("web", {"qrel_id": "web", "qrel": {"q1": {"d1": 1}}})
    j.record_run("web", "bm25", {"qrel_id": "web", "run_id": "bm25"})
    j.record_qrel("news", {"qrel_id": "news", "qrel": {"q2": {"d2": 2}}})

    j2 = RegistrationJournal(d)  # a restarted router recovers both
    assert sorted(j2) == ["news", "web"]
    assert list(j2.get("web")["runs"]) == ["bm25"]
    assert j2.counters["recovered_collections"] == 2

    assert j2.record_drop("web") is True
    assert j2.record_drop("web") is False  # already gone
    assert "web" not in j2 and len(j2) == 1

    j3 = RegistrationJournal(d)  # ...and the drop is durable: no zombie
    assert sorted(j3) == ["news"]
    assert j3.get("web") is None


def test_journal_compaction_folds_dead_records(tmp_path):
    d = str(tmp_path)
    j = RegistrationJournal(d, compact_min_dead=4, fsync=False)
    for i in range(6):  # re-registrations supersede: dead records pile up
        j.record_qrel("col", {"qrel_id": "col", "n": i})
    assert j.counters["compactions"] >= 1
    path = os.path.join(d, JOURNAL_FILE)
    with open(path, "rb") as fh:
        lines = fh.read().splitlines()
    assert len(lines) <= 2  # snapshot: only the live entry survives
    j2 = RegistrationJournal(d)
    assert j2.get("col")["qrel"]["n"] == 5


def test_journal_tolerates_torn_tail_and_corrupt_lines(tmp_path):
    d = str(tmp_path)
    j = RegistrationJournal(d)
    j.record_qrel("ok", {"qrel_id": "ok"})
    path = os.path.join(d, JOURNAL_FILE)
    with open(path, "ab") as fh:
        fh.write(b"this is not json\n")                    # corrupt record
        fh.write(b'{"kind": "qrel", "qrel_id": "torn"')    # crash mid-append
    j2 = RegistrationJournal(d)
    assert sorted(j2) == ["ok"]  # torn tail + garbage skipped, not fatal
    assert j2.stats()["skipped_records"] == 1  # the torn line never framed


def test_journal_memory_only_mode(tmp_path):
    j = RegistrationJournal(None)
    j.record_qrel("a", {"qrel_id": "a"})
    assert "a" in j and j.stats()["durable"] is False
    assert j.record_drop("a") is True and len(j) == 0
    assert not os.listdir(tmp_path)  # nothing written anywhere


# -- live clusters ------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    with ClusterThread(
            2, worker_args=["--backend", "single", "--window-ms", "1"],
            router_kw=dict(health_interval=5.0)) as c:
        yield c


@pytest.fixture(scope="module")
def fault_cluster():
    # a wide coalescing window so an evaluate is reliably *in flight* at
    # the worker when the test kills it; health checks pushed out of the
    # way so restarts are driven by the supervisor's proc.wait alone
    with ClusterThread(
            2, worker_args=["--backend", "single", "--window-ms", "250"],
            router_kw=dict(retries=4, health_interval=30.0)) as c:
        yield c


def _distinct_owner_ids(cluster, n=2):
    """qrel_ids owned by n different workers (deterministic: SHA-1 ring)."""
    picked, owners = [], set()
    for i in range(200):
        qid = f"col{i}"
        owner = cluster.owner_of(qid)
        if owner not in owners:
            owners.add(owner)
            picked.append(qid)
            if len(picked) == n:
                return picked
    raise AssertionError(f"ring maps 200 candidate ids onto < {n} workers")


def _wait_all_ready(cluster, timeout=90.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cluster.health()["status"] == "ok":
            return
        time.sleep(0.05)
    raise AssertionError(f"cluster not ready: {cluster.health()}")


# -- bit-identity vs the in-process evaluator ---------------------------------


def test_cluster_ping_health_and_worker_spread(cluster):
    with EvalClient(cluster.host, cluster.port) as client:
        assert client.ping() == "pong"
        health = client.health()
    assert health["status"] == "ok" and health["ready"] == 2
    assert {w["name"] for w in health["workers"]} == {"w0", "w1"}
    ids = _distinct_owner_ids(cluster, n=2)  # both workers take traffic
    assert cluster.owner_of(ids[0]) != cluster.owner_of(ids[1])


def test_cluster_evaluate_bit_identical_across_workers(cluster):
    """One collection per worker; both answer == RelevanceEvaluator."""
    ids = _distinct_owner_ids(cluster, n=2)
    with EvalClient(cluster.host, cluster.port) as client:
        for seed, qrel_id in enumerate(ids):
            run, qrel = synthesize_run(n_queries=12, n_docs=10, seed=seed)
            info = client.register_qrel(qrel_id, qrel, MEASURES)
            assert info["n_queries"] == len(qrel)
            res = client.evaluate(qrel_id, run=run)
            want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
            assert res.per_query == want  # bit-identical floats
            assert res.aggregates == aggregate_results(want)
        # each collection is resident on exactly ONE worker
        stats = client.stats()
    residence = {name: set(w["collections"]) if w else set()
                 for name, w in stats["workers"].items()}
    for qrel_id in ids:
        holders = [n for n, cols in residence.items() if qrel_id in cols]
        assert holders == [cluster.owner_of(qrel_id)], (qrel_id, residence)


def test_cluster_rescoring_run_ref_bit_identical(cluster):
    run, qrel = synthesize_run(n_queries=10, n_docs=8, seed=41)
    ev = RelevanceEvaluator(qrel, ("map", "recip_rank"))
    buf = ev.tokenize_run(run)
    rng = np.random.default_rng(8)
    score_sets = [rng.normal(size=buf.qidx.shape[0]).astype(np.float32)
                  for _ in range(4)]
    with EvalClient(cluster.host, cluster.port) as client:
        client.register_qrel("rescore", qrel, ("map", "recip_rank"))
        client.register_run("rescore", "bm25", run=run)
        results = client.evaluate_many("rescore", run_ref="bm25",
                                       scores_list=score_sets)
    for scores, res in zip(score_sets, results):
        assert res.per_query == ev.evaluate_buffer(buf, scores=scores)


def test_cluster_compare_matches_single_process(cluster):
    run_a, qrel = synthesize_run(n_queries=9, n_docs=7, seed=3)
    run_b, _ = synthesize_run(n_queries=9, n_docs=7, seed=4)
    runs = {"a": run_a, "b": run_b}
    with EvalClient(cluster.host, cluster.port) as client:
        client.register_qrel("cmp", qrel, ("map",))
        got = client.compare("cmp", runs=runs, measure="map",
                             tests=["t", "permutation"],
                             n_permutations=200, seed=7)

    async def direct():
        svc = EvaluationService(backend="single")
        svc.register_qrel("cmp", qrel, ("map",))
        return await svc.compare("cmp", runs=runs, measure="map",
                                 tests=("t", "permutation"),
                                 n_permutations=200, seed=7)

    want = asyncio.run(direct())
    # json round-trip on both sides: NaN-safe bit-exact comparison
    assert json.dumps(got, sort_keys=True) == json.dumps(want,
                                                         sort_keys=True)


def test_cluster_conformance_golden_byte_match(cluster):
    """The CLI golden, reproduced through a 2-worker cluster."""
    selected = sorted(supported_measures)
    keys = cli.ordered_keys(selected)
    qrel = trec.load_qrel(QREL)
    run = trec.load_run(RUN)
    with EvalClient(cluster.host, cluster.port) as client:
        client.register_qrel("conformance", qrel, selected,
                             relevance_level=1)
        res = client.evaluate("conformance", run=run)
    summary = cli._summarize(res.per_query, keys, qrel, complete=False,
                             relevance_level=1)
    lines = [cli.format_line("runid", "all", trec.run_id(RUN)),
             cli.format_line("num_q", "all", summary["num_q"])]
    lines.extend(cli.format_line(k, "all", summary[k]) for k in keys)
    with open(GOLDEN, newline="") as fh:
        assert "\n".join(lines) + "\n" == fh.read()


def test_cluster_large_payload_roundtrip(cluster):
    """>64 KiB register_qrel + evaluate through the router, bit-identical
    (the forwarded frame also carries the spliced router id — headroom)."""
    qrel, run = {}, {}
    rng = np.random.default_rng(17)
    for q in range(80):
        qid = f"query-{q:05d}"
        docs = [f"document-{q:05d}-{d:05d}-padpadpad" for d in range(24)]
        qrel[qid] = {doc: int(rng.integers(0, 3)) for doc in docs}
        run[qid] = {doc: float(rng.normal()) for doc in docs}
    payload = json.dumps({"op": "evaluate", "qrel_id": "big",
                          "run": run}).encode()
    assert len(payload) > 64 * 1024
    with EvalClient(cluster.host, cluster.port) as client:
        client.register_qrel("big", qrel, MEASURES)
        res = client.evaluate("big", run=run)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    assert res.per_query == want


# -- membership changes -------------------------------------------------------


def test_cluster_add_then_remove_worker_rebalances(cluster):
    # pick a collection the grown ring reassigns to the newcomer, using a
    # local replica of the router's (deterministic) ring
    local = HashRing(["w0", "w1"])
    grown = local.copy()
    grown.add("wx")
    moving = next(f"move{i}" for i in range(500)
                  if grown.owner(f"move{i}") == "wx")
    staying = next(f"move{i}" for i in range(500)
                   if grown.owner(f"move{i}") != "wx")

    run, qrel = synthesize_run(n_queries=8, n_docs=6, seed=9)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    with EvalClient(cluster.host, cluster.port) as client:
        for qrel_id in (moving, staying):
            client.register_qrel(qrel_id, qrel, MEASURES)
        before = cluster.owner_of(moving)

        assert cluster.add_worker("wx") == "wx"
        assert cluster.owner_of(moving) == "wx" != before
        assert cluster.owner_of(staying) != "wx"
        # no gap in service: the moved collection answers bit-identically
        assert client.evaluate(moving, run=run).per_query == want
        rebalanced = cluster.stats()["router"]["rebalanced_collections"]
        assert rebalanced >= 1

        cluster.remove_worker("wx")
        assert cluster.owner_of(moving) == before
        assert "wx" not in cluster.worker_names
        for qrel_id in (moving, staying):  # moved back, still identical
            assert client.evaluate(qrel_id, run=run).per_query == want
            client.drop_qrel(qrel_id)


# -- fault injection ----------------------------------------------------------


def _wait_worker_inflight(cluster, worker, timeout=20.0):
    """Block until ``worker`` reports an in-flight service request."""

    async def poll():
        slot = cluster.router._slots[worker]
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while loop.time() < deadline:
            health = await asyncio.wait_for(slot.proc.client.health(), 5)
            if health["in_flight"] > 0:
                return True
            await asyncio.sleep(0.002)
        return False

    assert cluster.call(poll(), timeout=timeout + 10)


def test_worker_kill_midrequest_retries_transparently(fault_cluster):
    """SIGKILL the owner while an evaluate sits in its coalescing window:
    the caller sees nothing but a slower, still bit-identical response."""
    _wait_all_ready(fault_cluster)
    qrel_id = _distinct_owner_ids(fault_cluster, n=1)[0]
    owner = fault_cluster.owner_of(qrel_id)
    run, qrel = synthesize_run(n_queries=10, n_docs=8, seed=21)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)

    restarts_before = fault_cluster.router.counters["restarts"]
    with EvalClient(fault_cluster.host, fault_cluster.port,
                    timeout=180) as client:
        client.register_qrel(qrel_id, qrel, MEASURES)
        future = client.submit(qrel_id, run=run)
        _wait_worker_inflight(fault_cluster, owner)  # inside the window
        fault_cluster.kill_worker(owner)
        res = future.result(180)  # transparent retry after restart+replay
    assert res.per_query == want
    counters = fault_cluster.router.counters
    assert counters["restarts"] > restarts_before
    assert counters["worker_retries"] >= 1
    assert counters["replayed_collections"] >= 1


def test_drop_qrel_on_down_worker_is_worker_unavailable(fault_cluster):
    """Non-idempotent drop_qrel is never retried: a down owner surfaces a
    machine-readable error, and the journal keeps the collection so the
    restarted worker still has it."""
    _wait_all_ready(fault_cluster)
    qrel_id = _distinct_owner_ids(fault_cluster, n=1)[0] + "-drop"
    owner = fault_cluster.owner_of(qrel_id)
    run, qrel = synthesize_run(n_queries=6, n_docs=5, seed=33)
    with EvalClient(fault_cluster.host, fault_cluster.port,
                    timeout=180) as client:
        client.register_qrel(qrel_id, qrel, MEASURES)
        fault_cluster.kill_worker(owner)
        with pytest.raises(WorkerUnavailableError) as exc_info:
            client.drop_qrel(qrel_id)
        assert exc_info.value.code == "worker_unavailable"
        assert fault_cluster.router.counters["worker_unavailable"] >= 1
        # after the restart the journal was replayed: the collection is
        # back, evaluates identically, and NOW the drop goes through
        _wait_all_ready(fault_cluster)
        res = client.evaluate(qrel_id, run=run)
        want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
        assert res.per_query == want
        assert client.drop_qrel(qrel_id) is True


def test_router_drain_answers_inflight_and_refuses_new():
    """Drain contract: the listener closes first, in-flight requests are
    answered through the cascade, new connections are refused."""

    async def main():
        router = Router(1, worker_args=["--backend", "single",
                                        "--window-ms", "300"],
                        health_interval=30.0)
        await router.start()
        server = await serve_protocol(router.handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        async def rpc(obj):
            writer.write(json.dumps(obj).encode() + b"\n")
            await writer.drain()
            return json.loads(await reader.readline())

        reg = await rpc({"op": "register_qrel", "id": 1, "qrel_id": "c",
                         "qrel": {"q1": {"d1": 1, "d2": 0}},
                         "measures": ["map"]})
        assert reg["ok"], reg
        # the evaluate sits in the worker's 300 ms coalescing window;
        # wait until the router has it in flight, then start the drain
        writer.write(json.dumps({"op": "evaluate", "id": 2, "qrel_id": "c",
                                 "run": {"q1": {"d1": 1.0}}}).encode()
                     + b"\n")
        await writer.drain()
        while router._inflight == 0:
            await asyncio.sleep(0.001)
        server.close()  # stops listening at once
        drain = asyncio.get_running_loop().create_task(router.drain())
        answered = json.loads(await reader.readline())
        await drain
        writer.close()
        await writer.wait_closed()
        # wait_closed() returns only once every connection has closed
        # (Python >= 3.12), so it comes after this test's own connection
        await server.wait_closed()
        refused = None
        try:
            r2, w2 = await asyncio.open_connection("127.0.0.1", port)
            w2.close()
        except OSError as exc:
            refused = exc
        return answered, refused

    answered, refused = asyncio.run(main())
    assert answered["ok"] and answered["id"] == 2
    assert answered["result"]["per_query"]["q1"]["map"] == 1.0
    assert isinstance(refused, OSError)  # listener gone


# -- replication (R=2): fan-out, failover, durable drops ----------------------


@pytest.fixture(scope="module")
def replicated_cluster(tmp_path_factory):
    # R=2 over 2 workers: every collection lives on BOTH, reads balance
    # with power-of-two-choices, and the journal is durable on disk.
    # Health probes pushed out of the way: the hedging test SIGSTOPs a
    # worker and must not race the prober's kill-on-hang path.
    state = str(tmp_path_factory.mktemp("cluster-state"))
    with ClusterThread(
            2, worker_args=["--backend", "single", "--window-ms", "1"],
            router_kw=dict(replication=2, retries=4, health_interval=30.0,
                           rng_seed=0, state_dir=state)) as c:
        yield c


def test_replicated_register_fans_out_to_all_replicas(replicated_cluster):
    _wait_all_ready(replicated_cluster)
    run, qrel = synthesize_run(n_queries=10, n_docs=8, seed=51)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    assert sorted(replicated_cluster.replicas_of("fanout")) == ["w0", "w1"]
    with EvalClient(replicated_cluster.host, replicated_cluster.port) as c:
        c.register_qrel("fanout", qrel, MEASURES)
        assert c.evaluate("fanout", run=run).per_query == want
        stats = c.stats()
    # acked register == resident on EVERY replica, not just the primary
    for name, w in stats["workers"].items():
        assert "fanout" in w["collections"], (name, stats["workers"])
    assert stats["router"]["replication"] == 2
    assert stats["router"]["journal"]["durable"] is True


def test_replicated_kill_one_replica_is_invisible(replicated_cluster):
    """Evaluate keeps answering bit-identically the instant a replica
    dies: the sibling already holds the collection, no restart needed."""
    _wait_all_ready(replicated_cluster)
    run, qrel = synthesize_run(n_queries=12, n_docs=9, seed=52)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    with EvalClient(replicated_cluster.host, replicated_cluster.port,
                    timeout=120) as c:
        c.register_qrel("failover", qrel, MEASURES)
        victim = replicated_cluster.replicas_of("failover")[0]
        replicated_cluster.kill_worker(victim)
        for _ in range(6):  # p2c will aim some of these at the corpse
            assert c.evaluate("failover", run=run).per_query == want
    _wait_all_ready(replicated_cluster)


def test_replicated_drop_succeeds_with_one_replica_down(replicated_cluster):
    """R=2 drop with a dead replica: acks (any live replica suffices),
    prunes the journal, and the restarted sibling does NOT resurrect it."""
    _wait_all_ready(replicated_cluster)
    run, qrel = synthesize_run(n_queries=6, n_docs=5, seed=53)
    with EvalClient(replicated_cluster.host, replicated_cluster.port,
                    timeout=120) as c:
        c.register_qrel("durable-drop", qrel, MEASURES)
        victim = replicated_cluster.replicas_of("durable-drop")[0]
        replicated_cluster.kill_worker(victim)
        assert c.drop_qrel("durable-drop") is True  # no WorkerUnavailable
        assert "durable-drop" not in replicated_cluster.router._journal
        # the dead replica restarts and replays the journal: the dropped
        # collection must stay dropped everywhere (the resurrection bug)
        _wait_all_ready(replicated_cluster)
        with pytest.raises(ServerError) as exc_info:
            c.evaluate("durable-drop", run=run)
        assert exc_info.value.code == "not_found"


def test_replicated_hedged_request_wins_past_hung_replica(replicated_cluster):
    """SIGSTOP one replica: deadline-carrying evaluates that land on it
    are hedged to the sibling at half the budget and still answer
    bit-identically, well before the deadline expires."""
    _wait_all_ready(replicated_cluster)
    run, qrel = synthesize_run(n_queries=8, n_docs=6, seed=54)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    counters = replicated_cluster.router.counters
    with EvalClient(replicated_cluster.host, replicated_cluster.port,
                    timeout=120) as c:
        c.register_qrel("hedged", qrel, MEASURES)
        victim = replicated_cluster.replicas_of("hedged")[0]
        hedges_before = counters["hedges"]
        replicated_cluster.pause_worker(victim)
        try:
            for _ in range(16):  # stop as soon as one request hedged
                res = c.evaluate("hedged", run=run, timeout=1.0)
                assert res.per_query == want
                if counters["hedges"] > hedges_before:
                    break
        finally:
            replicated_cluster.resume_worker(victim)
    assert counters["hedges"] > hedges_before
    assert counters["hedge_wins"] > 0
    _wait_all_ready(replicated_cluster)


# -- deadlines ----------------------------------------------------------------


def test_deadline_exceeded_is_a_typed_error(fault_cluster):
    """A deadline shorter than the worker's 250 ms coalescing window
    surfaces as DeadlineExceededError with the machine-readable code —
    and a generous deadline changes nothing about the bytes."""
    _wait_all_ready(fault_cluster)
    run, qrel = synthesize_run(n_queries=6, n_docs=5, seed=55)
    want = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    counters = fault_cluster.router.counters
    before = counters["deadline_exceeded"]
    with EvalClient(fault_cluster.host, fault_cluster.port,
                    timeout=120) as c:
        c.register_qrel("deadline", qrel, MEASURES)
        with pytest.raises(DeadlineExceededError) as exc_info:
            c.evaluate("deadline", run=run, timeout=0.05)
        assert exc_info.value.code == "deadline_exceeded"
        assert counters["deadline_exceeded"] > before
        # generous deadline: same bytes as no deadline at all
        assert c.evaluate("deadline", run=run, timeout=60).per_query == want
        assert c.drop_qrel("deadline") is True
    with pytest.raises(ValueError):  # local validation, never sent
        with EvalClient(fault_cluster.host, fault_cluster.port) as c:
            c.evaluate("deadline", run=run, timeout=-1)


# -- whole-cluster restart from --state-dir -----------------------------------


def test_cluster_restart_from_state_dir_byte_matches_golden(tmp_path):
    """Kill the WHOLE cluster; boot a fresh one against the same
    --state-dir; the conformance golden reproduces byte-for-byte without
    re-registering anything (acceptance criterion for durability)."""
    state = str(tmp_path / "state")
    selected = sorted(supported_measures)
    keys = cli.ordered_keys(selected)
    qrel = trec.load_qrel(QREL)
    run = trec.load_run(RUN)
    kw = dict(worker_args=["--backend", "single", "--window-ms", "1"],
              router_kw=dict(replication=2, health_interval=30.0,
                             state_dir=state))
    with ClusterThread(2, **kw) as first:
        with EvalClient(first.host, first.port) as c:
            c.register_qrel("conformance", qrel, selected,
                            relevance_level=1)

    with ClusterThread(2, **kw) as reborn:  # same state dir, cold start
        stats = reborn.stats()
        assert stats["router"]["journal"]["recovered_collections"] == 1
        with EvalClient(reborn.host, reborn.port) as c:
            res = c.evaluate("conformance", run=run)  # NO re-registration
    summary = cli._summarize(res.per_query, keys, qrel, complete=False,
                             relevance_level=1)
    lines = [cli.format_line("runid", "all", trec.run_id(RUN)),
             cli.format_line("num_q", "all", summary["num_q"])]
    lines.extend(cli.format_line(k, "all", summary[k]) for k in keys)
    with open(GOLDEN, newline="") as fh:
        assert "\n".join(lines) + "\n" == fh.read()
