"""Developer workflow helpers: ``python -m repro.dev verify``.

The ``verify`` target is the one-command pre-merge check documented in
README.md:

1. the tier-1 pytest suite (fast correctness, ``-m 'not slow'`` default),
2. a 2-device sharded smoke test under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` — the sharded
   pipeline must stay bit-identical to the single-device evaluator on the
   conformance fixtures, and
3. the serve smoke test (also ``python -m repro.dev serve-smoke`` /
   ``make serve-smoke``): boot a TCP evaluation service, fire concurrent
   requests from several connections, and assert they were coalesced into
   fewer backend calls with per-query results bit-identical to direct
   evaluation, and
4. the client smoke test (``python -m repro.dev client-smoke`` /
   ``make client-smoke``): drive a TCP server AND a stdio subprocess
   server through ``repro.client.EvalClient`` — pipelined requests that
   must coalesce, plus one >64 KiB ``register_qrel`` payload on each
   transport (the frame size that crashed the seed serve layer) —
   asserting bit-identical results throughout, and
5. the cluster smoke test (``python -m repro.dev cluster-smoke`` /
   ``make cluster-smoke``): boot a 2-worker ``repro.serve.cluster`` over
   TCP, round-trip a >64 KiB payload through the consistent-hash router
   bit-identically, then SIGKILL the owning worker while a request is in
   its coalescing window and assert the router restarts it, replays the
   registration journal, and retries transparently, and
6. the sweep smoke test (``python -m repro.dev sweep-smoke`` /
   ``make sweep-smoke``): evaluate a small K-run sweep
   (:func:`repro.core.evaluate_sweep`) and assert it is bit-identical to
   the K independent ``evaluate_buffer`` calls it replaces, then run the
   all-pairs paired t-test + Holm correction (:mod:`repro.stats`) and
   check the statistics invariants (symmetric unit-diagonal p matrices,
   Holm <= Bonferroni) plus the conformance fixture's known p-value, and
7. the sweep benchmark smoke: ``python -m benchmarks.run --only sweep``
   must complete and record its rows (CI asserts the >=5x
   significance-stack speedup from the recorded results).

Exit status is non-zero if any step fails.  ``make verify`` wraps this.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
SRC = os.path.join(ROOT, "src")

_SMOKE = """
    import jax
    from repro.core import RelevanceEvaluator, supported_measures, trec
    from repro.distributed import ShardedEvaluator

    assert len(jax.devices()) == 2, jax.devices()
    qrel = trec.load_qrel({qrel!r})
    run = trec.load_run({run!r})
    ev = RelevanceEvaluator(qrel, supported_measures)
    want = ev.evaluate(run)
    res = ShardedEvaluator(ev).evaluate(run)
    for qid in want:
        for key, val in want[qid].items():
            assert res.per_query[qid][key] == val, (qid, key)
    print("sharded 2-device smoke: OK "
          f"({{len(want)}} queries x {{len(ev.measure_keys)}} measures)")
"""


_SERVE_SMOKE = """
    import asyncio, json
    from repro.core import RelevanceEvaluator, trec
    from repro.serve import EvaluationService, serve_tcp

    qrel = trec.load_qrel({qrel!r})
    run = trec.load_run({run!r})
    measures = ("map", "ndcg", "recip_rank")
    n = 6
    runs = [{{q: {{d: s + 0.25 * i for d, s in docs.items()}}
             for q, docs in run.items()}} for i in range(n)]
    want = [RelevanceEvaluator(qrel, measures).evaluate(r) for r in runs]

    async def client(port, i):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        req = {{"op": "evaluate", "id": i, "qrel_id": "smoke",
                "run": runs[i]}}
        writer.write((json.dumps(req) + "\\n").encode())
        await writer.drain()
        reply = json.loads(await reader.readline())
        writer.close(); await writer.wait_closed()
        assert reply["ok"], reply
        return reply["result"]["per_query"]

    async def main():
        svc = EvaluationService(window=0.05)
        svc.register_qrel("smoke", qrel, measures)
        server = await serve_tcp(svc, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        got = await asyncio.gather(*(client(port, i) for i in range(n)))
        server.close(); await server.wait_closed()
        stats = svc.stats()
        assert stats["backend_calls"] < n, stats  # coalesced
        for g, w in zip(got, want):
            for qid in w:
                for key, val in w[qid].items():
                    assert g[qid][key] == val, (qid, key)  # bit-identical
        print(f"serve smoke: OK ({{n}} concurrent requests -> "
              f"{{stats['backend_calls']}} backend call(s), bit-identical)")

    asyncio.run(main())
"""


_CLIENT_SMOKE = """
    import json, sys
    from repro.client import EvalClient
    from repro.core import RelevanceEvaluator, trec
    from repro.serve.testing import ServerThread

    qrel_path = sys.argv[1]

    # a register_qrel payload comfortably past the seed's 64 KiB limit
    big_qrel = {"Q%04d-%s" % (i, "x" * 80):
                {"D%04d-%s" % (d, "y" * 80): int((i + d) % 3)
                 for d in range(24)} for i in range(36)}
    big_run = {q: {d: float((i * 31 + j * 7) % 97) / 97.0
                   for j, d in enumerate(docs)}
               for i, (q, docs) in enumerate(big_qrel.items())}
    payload = json.dumps({"op": "register_qrel", "qrel_id": "big",
                          "qrel": big_qrel})
    assert len(payload) > (1 << 16), len(payload)

    # stdio first: a private subprocess server, same >64 KiB payload.  This
    # process stays off JAX until the child has exited — on a TPU host a
    # parent that holds the chip would leave the child none.
    with EvalClient.spawn_stdio(
            [sys.executable, "-m", "repro.serve", "--qrel", qrel_path,
             "-m", "map", "--window-ms", "1"]) as client:
        assert client.ping() == "pong"
        r = client.evaluate("default",
                            run={"q1": {"APPLE": 2.0, "BANANA": 1.0}})
        assert r.per_query["q1"]["map"] > 0
        client.register_qrel("big", big_qrel, ("map", "ndcg"))
        stdio_res = client.evaluate("big", run=big_run)

    want = RelevanceEvaluator(big_qrel, ("map", "ndcg")).evaluate(big_run)
    assert stdio_res.per_query == want  # bit-identical through stdio pipes

    # TCP: persistent connection, pipelining, >64 KiB payload
    with ServerThread(service_kw=dict(window=0.02)) as srv:
        with EvalClient(srv.host, srv.port) as client:
            assert client.ping() == "pong"
            client.register_qrel("big", big_qrel, ("map", "ndcg"))
            res = client.evaluate("big", run=big_run)
            assert res.per_query == want  # bit-identical through TCP
            many = client.evaluate_many("big", runs=[big_run] * 4)
            assert all(m.per_query == want for m in many)
        stats = srv.stats()
        assert stats["backend_calls"] < stats["requests"], stats

    print("client smoke: OK (TCP pipelined + stdio, >64 KiB payloads, "
          f"{stats['requests']} reqs -> {stats['backend_calls']} backend "
          "calls, bit-identical)")
"""


_CLUSTER_SMOKE = """
    import asyncio, json
    from repro.client import EvalClient
    from repro.core import RelevanceEvaluator
    from repro.serve.cluster.testing import ClusterThread

    # a payload comfortably past 64 KiB, through the router's raw path
    big_qrel = {"Q%04d-%s" % (i, "x" * 80):
                {"D%04d-%s" % (d, "y" * 80): int((i + d) % 3)
                 for d in range(24)} for i in range(36)}
    big_run = {q: {d: float((i * 31 + j * 7) % 97) / 97.0
                   for j, d in enumerate(docs)}
               for i, (q, docs) in enumerate(big_qrel.items())}
    payload = json.dumps({"op": "evaluate", "qrel_id": "big",
                          "run": big_run})
    assert len(payload) > (1 << 16), len(payload)

    # a wide coalescing window so the kill lands mid-request.  This process
    # stays off JAX while the workers run (they hold the chips on a TPU
    # host); the reference answer is computed after they exit.
    with ClusterThread(2, worker_args=["--backend", "single",
                                       "--window-ms", "250"],
                       router_kw=dict(retries=4,
                                      health_interval=30.0)) as cluster:
        with EvalClient(cluster.host, cluster.port, timeout=180) as client:
            assert client.ping() == "pong"
            health = client.health()
            assert health["status"] == "ok" and health["ready"] == 2, health
            client.register_qrel("big", big_qrel, ("map", "ndcg"))
            res = client.evaluate("big", run=big_run)  # >64 KiB round trip

            owner = cluster.owner_of("big")
            future = client.submit("big", run=big_run)

            async def wait_inflight():
                slot = cluster.router._slots[owner]
                while True:
                    h = await slot.proc.client.health()
                    if h["in_flight"]:
                        return
                    await asyncio.sleep(0.002)

            cluster.call(wait_inflight(), timeout=60)
            cluster.kill_worker(owner)  # SIGKILL mid-request
            retried = future.result(180)  # transparent retry
        counters = dict(cluster.router.counters)
    want = RelevanceEvaluator(big_qrel, ("map", "ndcg")).evaluate(big_run)
    assert res.per_query == want  # bit-identical
    assert retried.per_query == want
    assert counters["restarts"] >= 1 and counters["worker_retries"] >= 1, \\
        counters
    print("cluster smoke: OK (2 workers, >64 KiB through the router, "
          "worker killed mid-request -> restart + journal replay + "
          "transparent retry, bit-identical)")
"""


_SWEEP_SMOKE = """
    import numpy as np
    from repro import stats
    from repro.core import RelevanceEvaluator, evaluate_sweep, trec

    qrel = trec.load_qrel({qrel!r})
    base = trec.load_run({run!r})
    measures = ("map", "ndcg", "P_5")
    k = 6
    runs = [{{q: {{d: s + 0.25 * i * (1 if hash(d) % 2 else -1)
                 for d, s in docs.items()}}
             for q, docs in base.items()}} for i in range(k)]
    ev = RelevanceEvaluator(qrel, measures)
    result = evaluate_sweep(ev, [ev.tokenize_run(r) for r in runs])
    # bit-identity: the sweep table IS the K independent evaluations
    for ki, r in enumerate(runs):
        want = ev.evaluate(r)
        for qi, qid in enumerate(result.qids):
            for mi, key in enumerate(result.measure_keys):
                assert result.table[ki, qi, mi] == want[qid][key], \\
                    (ki, qid, key)

    x = np.asarray(result.measure("map"))
    t, p = stats.paired_t_matrix(x)
    holm = stats.holm_matrix(p)
    bonf = stats.bonferroni_matrix(p)
    t, p, holm, bonf = (np.asarray(a) for a in (t, p, holm, bonf))
    assert np.array_equal(p, p.T) and np.array_equal(np.diag(p),
                                                     np.ones(k))
    assert np.array_equal(t, -t.T)
    assert (holm <= bonf + 1e-7).all() and (holm <= 1.0).all()
    # closed form at df=1: d=[0.1, 0.3] -> t=2, p = 1 - (2/pi)atan(2)
    _, p2 = stats.paired_t_matrix(
        np.array([[0.4, 0.6], [0.3, 0.3]], np.float32))
    assert abs(float(p2[0, 1]) - 0.29516723) < 1e-6, float(p2[0, 1])
    print(f"sweep smoke: OK ({{k}} runs x {{len(result.qids)}} queries x "
          f"{{len(result.measure_keys)}} measures, bit-identical; "
          "stats invariants + df=1 closed form hold)")
"""


def _env(extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if extra:
        env.update(extra)
    return env


def _fixture(name: str) -> str:
    return os.path.join(ROOT, "tests", "fixtures", name)


def serve_smoke() -> int:
    """Boot a TCP service, assert coalescing + bit-identity (step 3)."""
    print("== serve smoke (TCP, concurrent clients) ==", flush=True)
    code = textwrap.dedent(_SERVE_SMOKE.format(
        qrel=_fixture("conformance.qrel"), run=_fixture("conformance.run")))
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=_env()).returncode


def client_smoke() -> int:
    """EvalClient over TCP + stdio with >64 KiB payloads (step 4)."""
    print("== client smoke (EvalClient: TCP + stdio, large frames) ==",
          flush=True)
    code = textwrap.dedent(_CLIENT_SMOKE)
    return subprocess.run(
        [sys.executable, "-c", code, _fixture("conformance.qrel")],
        cwd=ROOT, env=_env()).returncode


def cluster_smoke() -> int:
    """2-worker cluster: big frames + kill-retry fault path (step 5)."""
    print("== cluster smoke (2 workers, router, worker-kill retry) ==",
          flush=True)
    code = textwrap.dedent(_CLUSTER_SMOKE)
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=_env()).returncode


def sweep_smoke() -> int:
    """K-run sweep bit-identity + statistics invariants (step 6)."""
    print("== sweep smoke (evaluate_sweep + repro.stats) ==", flush=True)
    code = textwrap.dedent(_SWEEP_SMOKE.format(
        qrel=_fixture("conformance.qrel"), run=_fixture("conformance.run")))
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          env=_env()).returncode


def verify() -> int:
    print("== tier-1 pytest ==", flush=True)
    rc = subprocess.run([sys.executable, "-m", "pytest", "-x", "-q"],
                        cwd=ROOT, env=_env()).returncode
    if rc != 0:
        return rc
    print("== sharded smoke (2 host-platform devices) ==", flush=True)
    code = textwrap.dedent(_SMOKE.format(
        qrel=_fixture("conformance.qrel"), run=_fixture("conformance.run")))
    rc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT,
        env=_env({"XLA_FLAGS":
                  "--xla_force_host_platform_device_count=2"})).returncode
    if rc != 0:
        return rc
    rc = serve_smoke()
    if rc != 0:
        return rc
    rc = client_smoke()
    if rc != 0:
        return rc
    rc = cluster_smoke()
    if rc != 0:
        return rc
    rc = sweep_smoke()
    if rc != 0:
        return rc
    print("== sweep bench smoke (--only sweep) ==", flush=True)
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--only", "sweep"],
        cwd=ROOT, env=_env()).returncode


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv == ["verify"]:
        return verify()
    if argv == ["serve-smoke"]:
        return serve_smoke()
    if argv == ["client-smoke"]:
        return client_smoke()
    if argv == ["cluster-smoke"]:
        return cluster_smoke()
    if argv == ["sweep-smoke"]:
        return sweep_smoke()
    print("usage: python -m repro.dev "
          "{verify|serve-smoke|client-smoke|cluster-smoke|sweep-smoke}",
          file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main())
