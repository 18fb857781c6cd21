"""Open-loop load generator for the served cells, run as a child process.

    python3 -m chipbench.drivers.loadgen     (from the checkout's root)

It never initializes a JAX backend: the parent holds the chip.  It talks
JSON lines with the parent: on standard input a job (the configuration, the
traffic mix, the seed and the server's port), then commands; on standard
output events.  Each ``window`` command runs one schedule:

* arrivals: Poisson at ``rate`` requests/s over ``seconds``.  Every seed
  gets the same gaps (the exponential's quantiles) in its own order, so
  seeds change the order of the work and not its amount;
* each request is ``evaluate(qrel_id, run_ref=..., scores=[...])`` through
  ``repro.client.AsyncEvalClient`` on one persistent connection.  Its scores
  are a slice of a seeded pool of grid values, at an offset no other request
  uses; the pool is rendered to JSON text once, so a request's payload is a
  slice of that text and building it costs no time in the window;
* latency runs from the time a request was due to the time its response
  arrived; a request that fails, or has no answer a minute after the window
  closed, counts as missing every limit (its latency is that wait);
* lateness (sent - due) is reported beside it, so a starved generator is
  not read as a slow server.

Once the window has closed, a sample of the requests, drawn from the seed,
is compared with the plain reference.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import math
import os
import sys
import time
from typing import Dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from chipbench import check, reference  # noqa: E402
from chipbench.drivers.stall import StallWatch  # noqa: E402

#: seconds past the window's close that an answer may still come
GRACE_S = 60.0
#: seconds between "go" and the first due time
LEAD_S = 0.05


def emit(**event) -> None:
    sys.stdout.write(json.dumps(event) + "\n")
    sys.stdout.flush()


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in [0, seconds): the same multiset of gaps for every seed."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= seconds / gaps.sum()  # the schedule spans the window exactly
    order = np.random.default_rng([seed, 0xA77]).permutation(n)
    return np.concatenate([[0.0], np.cumsum(gaps[order])[:-1]])


class Payloads:
    """Request ``i``'s scores: ``pool[o_i : o_i + n]``, and their JSON text."""

    def __init__(self, n: int, grid: float, spread: float, offsets: int,
                 seed: int):
        rng = np.random.default_rng([seed, 0x5C0E])
        denom = round(1 / grid)
        ticks = np.rint(rng.normal(scale=spread, size=n + offsets)
                        * denom).astype(np.int64)
        self.n = n
        self.values = ticks / denom
        parts = [repr(v) for v in self.values.tolist()]
        self.text = ",".join(parts)
        self.starts = np.concatenate(
            [[0], np.cumsum([len(p) + 1 for p in parts])])
        self.offsets = rng.permutation(offsets)
        self.used = 0

    def take(self, count: int) -> int:
        """The first of ``count`` request numbers no request has used."""
        if self.used + count > len(self.offsets):
            raise ValueError(f"{self.used + count} requests need more than "
                             f"the {len(self.offsets)} fresh offsets")
        self.used += count
        return self.used - count

    def scores(self, i: int) -> np.ndarray:
        o = self.offsets[i]
        return self.values[o:o + self.n].astype(np.float32)

    def frame(self, i: int, head: bytes) -> bytes:
        o = self.offsets[i]
        body = self.text[self.starts[o]:self.starts[o + self.n] - 1]
        return b'%s,"scores":[%s]}' % (head, body.encode())


async def window(client, job: dict, cmd: dict, payloads: Payloads,
                 coll, head: bytes) -> None:
    cfg = job["config"]
    due = arrivals(cmd["rate"], cmd["seconds"], cmd["seed"])
    n = len(due)
    first = payloads.take(n)
    sample = set(np.random.default_rng([cmd["seed"], 0xC4EC]).choice(
        n, min(cfg["check"]["answers"], n), replace=False).tolist())
    latency = np.full(n, np.nan)
    late = np.zeros(n)
    ok = np.zeros(n, dtype=bool)
    kept: Dict[int, bytes] = {}
    loop = asyncio.get_running_loop()

    async def one(i: int, t_due: float) -> None:
        try:
            resp = await client.forward(payloads.frame(first + i, head))
        except Exception as e:  # noqa: BLE001 — counted as failed
            print(f"loadgen: request {i}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return
        latency[i] = loop.time() - t_due
        ok[i] = b'"ok": true' in resp[:64]
        if i in sample:
            kept[i] = resp

    watch = StallWatch().start()
    start = loop.time() + LEAD_S
    emit(event="window_start", t=time.monotonic() + LEAD_S)
    tasks = []
    for i, d in enumerate(due.tolist()):
        delay = start + d - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        late[i] = loop.time() - (start + d)
        tasks.append(asyncio.ensure_future(one(i, start + d)))
    close = start + cmd["seconds"]
    done, pending = await asyncio.wait(
        tasks, timeout=max(close + GRACE_S - loop.time(), 0.0))
    for t in pending:
        t.cancel()
    stalls = watch.stop()
    answered = ok & ~np.isnan(latency)
    cap = close + GRACE_S - start
    lat_ms = np.where(answered, latency, np.maximum(cap, 0.0)) * 1e3
    last = float(np.nanmax(latency + (start + due) - close)) if \
        answered.any() else math.nan
    emit(event="window_done", attempted=n, failed=int(n - answered.sum()),
         latency_ms_p50=float(np.percentile(lat_ms, 50)),
         latency_ms_p95=float(np.percentile(lat_ms, 95)),
         latency_ms_max=float(lat_ms.max()),
         lateness_ms_p50=float(np.percentile(late, 50) * 1e3),
         lateness_ms_p95=float(np.percentile(late, 95) * 1e3),
         lateness_ms_max=float(late.max() * 1e3),
         drained_s_after_close=last,
         **{f"gen_{k}": v for k, v in stalls.items()})
    if not cmd.get("check", True):
        return
    reading = check.Reading()
    for i in sorted(sample):
        if i not in kept:
            continue
        msg = json.loads(kept.pop(i))
        if not msg.get("ok"):
            continue
        run = check.run_dict(coll.qids, coll.docnos,
                             payloads.scores(first + i))
        want = reference.evaluate(run, coll.qrel, cfg["reference_measures"])
        reading.add(msg["result"]["per_query"], want, cfg["keys"])
    unanswered = int(n - answered.sum())
    emit(event="result",
         checks=reading.checks(cfg["check"]["max_abs_diff"], unanswered),
         compared_answers=len(sample), widest_gap_at=reading.where,
         compared_queries=reading.compared_queries,
         compared_values=reading.compared_values)


async def serve(job: dict, coll, payloads: Payloads) -> None:
    from repro.client import AsyncEvalClient

    loop = asyncio.get_running_loop()
    client = await AsyncEvalClient.connect("127.0.0.1", job["port"])
    try:
        # a request frame without its closing brace: the scores follow
        head = json.dumps({"op": "evaluate", "qrel_id": job["qrel_id"],
                           "run_ref": job["run_id"]})[:-1].encode()
        for i in range(job["warm"]):  # the wire path, warmed in set-up
            resp = await client.forward(payloads.frame(payloads.take(1), head))
            if b'"ok": true' not in resp[:64]:
                raise RuntimeError(f"warm-up request failed: {resp[:300]!r}")
        emit(event="ready")
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line:
                return
            cmd = json.loads(line)
            if cmd["cmd"] == "quit":
                return
            await window(client, job, cmd, payloads, coll, head)
    finally:
        await client.aclose()


def main() -> int:
    job = json.loads(sys.stdin.readline())
    cfg, mix = job["config"], job["traffic"]
    gen = importlib.import_module(f"chipbench.generators.{cfg['generator']}")
    coll = gen.generate(cfg, job["seed"])
    payloads = Payloads(len(coll.scores), cfg["score_grid"],
                        mix["score_spread"], mix["fresh_offsets"], job["seed"])
    emit(event="prepared")
    port = json.loads(sys.stdin.readline())
    job.update(port)
    asyncio.run(serve(job, coll, payloads))
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            print("loadgen: a JAX backend was initialized", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
