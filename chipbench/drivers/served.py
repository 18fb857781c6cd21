"""Open loop over the TCP service, with the service in this process.

This process holds the chip: it runs ``serve_tcp(EvaluationService())``
with ``python -m repro.serve``'s defaults (window 2 ms, max batch 64) and
can trace the device.  The load comes from a child process
(``chipbench/drivers/loadgen.py``) that never initializes a JAX backend.

Set-up registers the collection and its run with the service, warms the
measure core at every padded query count that a coalesced batch of 1 to
``max_batch`` requests can take, and has the child send ``warm_requests``
requests through the wire.  The child then runs the window at
``rate_per_s`` and compares a sample of its answers with the plain
reference.

Traffic parameters (``chipbench/traffic/<mix>.json``): ``rate_per_s``,
``warm_requests``, ``fresh_offsets`` (distinct score slices: an upper
bound on the requests of all windows), ``score_spread`` (the scores'
standard deviation before rounding to the configuration's grid).
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
from typing import List

from chipbench import check, harness
from chipbench import trace as tr
from chipbench.drivers.stall import StallWatch

QREL_ID = "bench"
RUN_ID = "run"


def warm_measure_core(service, max_batch: int) -> List[int]:
    """Run the collection once at each padded query count of a coalesced
    batch of 1..``max_batch`` requests; returns the batch sizes run."""
    from repro.kernels import bucketing

    col = service._require(QREL_ID)
    buf = col.runs[RUN_ID]
    sizes = {}
    for n in range(1, max_batch + 1):
        sizes.setdefault(bucketing.bucket_queries(n * len(buf)), n)
    for n in sizes.values():
        col.evaluator.evaluate_buffers([buf] * n)
    return sorted(sizes.values())


async def _send(proc, obj) -> None:
    proc.stdin.write((json.dumps(obj) + "\n").encode())
    await proc.stdin.drain()


async def _expect(proc, event: str) -> dict:
    while True:
        line = await proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the load generator exited before "
                               f"{event!r} (code {await proc.wait()})")
        msg = json.loads(line)
        if msg.get("event") == event:
            return msg


async def session(ctx: harness.Context, windows: List[dict]) -> List[dict]:
    """Set up once, then run each window (``rate``, ``seconds``, ``seed``,
    ``check``, ``trace``); returns one record per window."""
    from repro.kernels import bucketing
    from repro.serve.frontend import serve_tcp
    from repro.serve.service import EvaluationService

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # the child holds no chip
    proc = await asyncio.create_subprocess_exec(
        sys.executable, "-m", "chipbench.drivers.loadgen",
        cwd=str(harness.ROOT), env=env, stdin=asyncio.subprocess.PIPE,
        stdout=asyncio.subprocess.PIPE, limit=1 << 24)
    records = []
    try:
        await _send(proc, {"config": cfg, "traffic": mix, "seed": ctx.seed,
                           "qrel_id": QREL_ID, "run_id": RUN_ID,
                           "warm": mix["warm_requests"]})
        coll = ctx.collection()
        service = EvaluationService()
        service.register_qrel(QREL_ID, coll.qrel, cfg["measures"])
        service.register_run(QREL_ID, RUN_ID, run=check.run_dict(
            coll.qids, coll.docnos, coll.scores))
        del coll
        batches = warm_measure_core(service, service.stats()["max_batch"])
        server = await serve_tcp(service, "127.0.0.1", 0)
        try:
            await _expect(proc, "prepared")
            await _send(proc, {"port": server.sockets[0].getsockname()[1]})
            await _expect(proc, "ready")
            for w in windows:
                compiles = sum(bucketing.trace_counts().values())
                before = service.stats()
                with tr.record(w["trace"]) as rec:
                    watch = StallWatch().start()
                    await _send(proc, {"cmd": "window", **w})
                    start = await _expect(proc, "window_start")
                    done = await _expect(proc, "window_done")
                    stalls = watch.stop()
                after = service.stats()
                rec_out = dict(
                    w, start=start["t"], done=done,
                    **{f"server_{k}": v for k, v in stalls.items()},
                    warm_batches=batches,
                    compiles_in_window=sum(
                        bucketing.trace_counts().values()) - compiles,
                    requests=after["requests"] - before["requests"],
                    backend_calls=(after["backend_calls"]
                                   - before["backend_calls"]),
                    memory_peak_bytes=harness.device_peak_bytes(ctx.devices))
                if w["check"]:
                    rec_out["result"] = await _expect(proc, "result")
                rec_out["reduced"] = rec.get("reduced")
                records.append(rec_out)
            await _send(proc, {"cmd": "quit"})
        finally:
            server.close()
            await server.wait_closed()
            await service.drain()
        code = await proc.wait()
        if code:
            raise RuntimeError(f"the load generator exited with code {code}")
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    return records


def run(ctx: harness.Context) -> harness.Outcome:
    rec, = asyncio.run(session(ctx, [{
        "rate": ctx.cell.traffic["rate_per_s"], "seconds": ctx.seconds,
        "seed": ctx.seed, "check": True, "trace": ctx.trace}]))
    done, result = rec["done"], rec["result"]
    ctx.note(setup_s=rec["start"] - ctx.t0, rate_per_s=rec["rate"],
             compiles_in_window=rec["compiles_in_window"],
             server_loop_stall_ms_max=rec["server_loop_stall_ms_max"],
             server_gc_ms_max=rec["server_gc_ms_max"],
             warm_batches=rec["warm_batches"],
             requests=rec["requests"], backend_calls=rec["backend_calls"],
             **{k: v for k, v in done.items() if k != "event"},
             **{k: v for k, v in result.items()
                if k not in ("event", "checks")})
    return harness.Outcome(
        attempted=done["attempted"], failed=done["failed"],
        end_to_end={"latency_ms_p50": done["latency_ms_p50"],
                    "latency_ms_p95": done["latency_ms_p95"],
                    "setup_s": rec["start"] - ctx.t0},
        checks={k: tuple(v) for k, v in result["checks"].items()},
        memory_peak_bytes=rec["memory_peak_bytes"],
        reduced=rec["reduced"], calls_traced=rec["requests"],
        counters={"requests": rec["requests"],
                  "backend_calls": rec["backend_calls"]})
