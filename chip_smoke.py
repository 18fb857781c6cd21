"""On-chip smoke test: the evaluator's user paths on a TPU, checked end to end.

    python chip_smoke.py [--seed N]              # one chip: phases A-D, G
    python chip_smoke.py --chips 4 [--seed N]    # four chips: E and F only

Every collection is generated from ``--seed`` at a published shape, and
every phase checks its answers against the plain reference engine
(``repro.baselines.pure_eval``) at ``abs=1e-5``:

* **A · deep-judged ad hoc** (TREC Robust04 shape: 249 topics, depth 1,000,
  ~1,250 judgments per topic graded 0-2, score ties) through the CLI
  (``python -m repro``) and ``RelevanceEvaluator.evaluate`` — the XLA
  full-sort path.
* **B · sparse dev set** (MS MARCO passage dev shape: 6,980 queries x depth
  1,000, ~1.07 binary judgments per query) ingested with
  ``buffer_from_arrays``; depth-bounded measures take the top-k kernel.
* **C · sharded**: G's ragged lists through ``ShardedEvaluator`` on the
  chip's mesh (the CLI's ``--sharded`` path): the evaluator's depth
  classes, the wide ones on the top-k kernel under ``shard_map``, with the
  bits ``evaluate_buffer`` gives.
* **D · served**: an in-process ``serve_tcp`` service answering concurrent
  ``repro.client`` requests on A's collection; they must coalesce and equal
  the direct evaluation.
* **E · cluster** (``--chips 4``): the router over 4 worker processes, one
  chip each; this process stays off JAX while they run.
* **F · sharded mesh** (``--chips 4``): ``ShardedEvaluator`` over a 4-chip
  mesh against single-device evaluation of the same data: A's collection
  on the full sort and G's ragged lists on both routes.
* **G · ragged graded lists** (learning-to-rank shape: a few hundred
  queries, 1 to 1,251 documents each, every one judged 0-4) through
  ``evaluate_buffer``, twice: the buffer is evaluated in depth classes,
  the narrow ones on the XLA sort and the wide ones on the top-k kernel.

Lines before the last report each phase's shapes, route, compile counts,
largest difference from the reference and cold wall time (set-up time,
compilation included; not a metric).  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU, or with the kernels resolved to interpret mode on one, the
script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import io
import json
import os
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro import runtime  # noqa: E402
from repro.baselines import pure_eval  # noqa: E402

TOL = 1e-5


class SmokeFailure(Exception):
    """A phase produced a wrong answer or took the wrong path."""


@dataclass(frozen=True)
class AdHocShape:
    """TREC Robust04: 249 topics (301-450, 601-700 without 672), depth
    1,000, ~311k judgments (~1,250 per topic), grades 0-2."""

    topics: int = 249
    depth: int = 1000
    judged: int = 1250
    collection: int = 528_155  # documents in TREC disks 4+5 minus CR
    grade_p: tuple = (0.94, 0.045, 0.015)
    judged_share: float = 0.55  # retrieved documents that are judged


@dataclass(frozen=True)
class DevSetShape:
    """MS MARCO passage dev: 6,980 queries, 7,437 binary judgments,
    8,841,823 passages, depth 1,000 runs."""

    queries: int = 6980
    depth: int = 1000
    extra_judged: float = 457 / 6980  # queries with a second judgment
    collection: int = 8_841_823
    sample: int = 600  # queries checked against the reference


@dataclass(frozen=True)
class RaggedShape:
    """Learning-to-rank lists (MSLR-WEB30K's kind): list lengths spread
    log-uniformly over 1-1,251, every listed document judged 0-4."""

    queries: int = 400
    longest: int = 1251
    grade_p: tuple = (0.52, 0.32, 0.13, 0.02, 0.01)


ROBUST04 = AdHocShape()
MSMARCO_DEV = DevSetShape()
RAGGED = RaggedShape()
SERVED_REQUESTS = 8

MEASURES_A = ("map", "bpref", "ndcg", "Rprec", "recip_rank", "P", "recall",
              "ndcg_cut")
# RR takes no cutoff in the registry, so MS MARCO's MRR@10 is not offered;
# these three are depth-bounded at 10, which routes to the top-k kernel.
MEASURES_B = ("nDCG@10", "P@10", "Success@10")
REFERENCE_B = ("ndcg_cut", "P", "success")
MEASURES_G = ("nDCG@5", "nDCG@10")
REFERENCE_G = ("ndcg_cut",)


# -- collections --------------------------------------------------------------


def robust04_topics(n: int):
    ids = list(range(301, 451)) + [t for t in range(601, 701) if t != 672]
    return [str(t) for t in ids[:n]]


def adhoc_collection(seed: int, shape: AdHocShape = None):
    """Qrels (dict) and a run (flat qid/docno/score arrays), Robust04-shaped.

    Scores sit on a 0.1 grid, so ties across judged and unjudged documents
    exercise trec_eval's docno tie-break.
    """
    shape = shape or ROBUST04
    rng = np.random.default_rng(seed)
    qrel, qids, docnos, scores = {}, [], [], []
    for qid in robust04_topics(shape.topics):
        n_j = int(rng.integers(shape.judged * 3 // 5, shape.judged * 7 // 5))
        pool = rng.choice(shape.collection, n_j + shape.depth, replace=False)
        grades = rng.choice(3, n_j, p=shape.grade_p)
        qrel[qid] = {f"D{d:06d}": int(g) for d, g in zip(pool[:n_j], grades)}
        n_in = min(n_j, int(shape.depth * shape.judged_share))
        pick = rng.choice(n_j, n_in, replace=False)
        ret = np.concatenate([pool[pick], pool[n_j:n_j + shape.depth - n_in]])
        gain = np.concatenate([grades[pick], np.zeros(shape.depth - n_in)])
        score = np.round(1.5 * gain + rng.normal(size=shape.depth), 1)
        qids.append(np.full(shape.depth, qid))
        docnos.append(np.char.add("D", np.char.zfill(ret.astype(str), 6)))
        scores.append(score.astype(np.float32))
    return (qrel, np.concatenate(qids), np.concatenate(docnos),
            np.concatenate(scores))


def devset_collection(seed: int, shape: DevSetShape = None):
    """Qrels (dict) and a run (flat arrays), MS MARCO passage dev-shaped."""
    shape = shape or MSMARCO_DEV
    rng = np.random.default_rng(seed)
    nq, depth = shape.queries, shape.depth
    qid_ids = np.sort(rng.choice(1_102_400, nq, replace=False))
    pids = rng.choice(shape.collection, nq * depth + 2 * nq, replace=False)
    ret = pids[:nq * depth].reshape(nq, depth)
    unretrieved = pids[nq * depth:].reshape(nq, 2)
    scores = np.round(rng.normal(size=(nq, depth)), 2).astype(np.float32)
    qrel = {}
    for q in range(nq):
        rels = []
        for extra in range(1 + int(rng.random() < shape.extra_judged)):
            if rng.random() < 0.9:  # retrieved somewhere in the top 1,000
                j = int(rng.integers(depth))
                scores[q, j] += np.float32(2.0)
                rels.append(ret[q, j])
            else:
                rels.append(unretrieved[q, extra])
        qrel[str(qid_ids[q])] = {str(p): 1 for p in rels}
    qids = np.repeat(qid_ids.astype(str), depth)
    return qrel, qids, ret.reshape(-1).astype(str), scores.reshape(-1)


def ragged_collection(seed: int, shape: RaggedShape = None):
    """Qrels (dict) and a run (flat arrays) of lists of many lengths; the
    list of a query is its judged set, scores on a 0.01 grid."""
    shape = shape or RAGGED
    rng = np.random.default_rng(seed)
    lengths = np.exp(rng.uniform(0, np.log(shape.longest), shape.queries))
    lengths = np.clip(np.rint(lengths), 1, shape.longest).astype(np.int64)
    lengths[0] = shape.longest
    qrel, qids, docnos, scores = {}, [], [], []
    for q, n in enumerate(lengths.tolist()):
        qid = f"{q + 1:04d}"
        names = [f"D{j:04d}" for j in range(n)]
        grades = rng.choice(5, n, p=shape.grade_p)
        qrel[qid] = dict(zip(names, grades.tolist()))
        qids.append(np.full(n, qid))
        docnos.append(np.array(names))
        scores.append(np.round(grades + rng.normal(size=n), 2)
                      .astype(np.float32))
    return (qrel, np.concatenate(qids), np.concatenate(docnos),
            np.concatenate(scores))


def run_dict(qids, docnos, scores):
    """Flat arrays → ``{qid: {docno: score}}`` in file order."""
    run = {}
    for q, d, s in zip(qids.tolist(), docnos.tolist(), scores.tolist()):
        run.setdefault(q, {})[d] = s
    return run


def write_trec(tmp: str, qrel, qids, docnos, scores):
    """A TREC qrel file and run file under ``tmp``; returns both paths."""
    qrel_path = os.path.join(tmp, "smoke.qrel")
    run_path = os.path.join(tmp, "smoke.run")
    with open(qrel_path, "w") as fh:
        for qid, docs in qrel.items():
            fh.writelines(f"{qid} 0 {d} {r}\n" for d, r in docs.items())
    with open(run_path, "w") as fh:
        fh.writelines(f"{q} Q0 {d} {r} {s:.6f} smoke\n" for r, (q, d, s) in
                      enumerate(zip(qids.tolist(), docnos.tolist(),
                                    scores.tolist())))
    return qrel_path, run_path


# -- checks -------------------------------------------------------------------


class Diff(float):
    """A largest |difference| that remembers where it was taken."""

    where = ""


def max_diff(got, want, keys=None) -> Diff:
    """Largest |got - want| over every (query, key) of ``want``."""
    if set(got) != set(want):
        raise SmokeFailure(
            f"query sets differ: {len(set(got) ^ set(want))} not shared")
    worst = Diff(0.0)
    for qid, vals in want.items():
        for key in keys or vals:
            d = abs(float(got[qid][key]) - float(vals[key]))
            if d > worst:
                worst = Diff(d)
                worst.where = (f"{qid}/{key}: {got[qid][key]!r} vs "
                               f"{vals[key]!r}")
    return worst


def check(name: str, diff: float, tol: float = TOL) -> None:
    if not diff <= tol:
        raise SmokeFailure(f"{name}: max |diff| {diff:.3g} > {tol:g} at "
                           f"{getattr(diff, 'where', '?')}")


def expect_kernel(compiled_text: str, where: str) -> None:
    """The compiled program must hold a Pallas TPU kernel."""
    if "tpu_custom_call" not in compiled_text:
        raise SmokeFailure(f"{where}: no tpu_custom_call in the compiled "
                           "program")


def require_tpu(count: int):
    """The TPU devices, or a failure: no fallback hides the device."""
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < count:
        raise SmokeFailure(f"need {count} TPU chips, JAX sees {len(devices)}")
    if ops.interpret_mode():
        raise SmokeFailure("Pallas kernels resolved to interpret mode on a "
                           "TPU (REPRO_INTERPRET="
                           f"{os.environ.get('REPRO_INTERPRET')!r})")
    return devices


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


@contextlib.contextmanager
def timed(fields: dict):
    t0 = time.perf_counter()
    yield
    fields["setup_s_cold"] = f"{time.perf_counter() - t0:.3f}"


# -- phases -------------------------------------------------------------------


def phase_a(tmp: str, coll) -> None:
    from repro import cli
    from repro.core import RelevanceEvaluator
    from repro.kernels import bucketing

    qrel, qids, docnos, scores = coll
    run = run_dict(qids, docnos, scores)
    want = pure_eval.evaluate(run, qrel, MEASURES_A)
    qrel_path, run_path = write_trec(tmp, qrel, qids, docnos, scores)
    fields = {"shape": f"{len(run)}x{ROBUST04.depth}",
              "judgments": sum(map(len, qrel.values()))}
    before = bucketing.trace_counts()
    with timed(fields):
        out = io.StringIO()
        rc = cli.main(["-q", *sum((["-m", m] for m in MEASURES_A), []),
                       qrel_path, run_path], out=out)
        got = RelevanceEvaluator(qrel, MEASURES_A).evaluate(run)
    if rc != 0:
        raise SmokeFailure(f"CLI exited {rc}")
    printed = {}
    for line in out.getvalue().splitlines():
        key, qid, val = (f.strip() for f in line.split("\t"))
        if qid != "all":
            printed.setdefault(qid, {})[key] = float(val)
    # the CLI prints 4 decimals: half a unit of the last digit, plus TOL
    cli_diff = max_diff(printed, want)
    check("A (CLI output)", cli_diff, 5e-5 + TOL)
    diff = max_diff(got, want)
    check("A (RelevanceEvaluator.evaluate)", diff)
    after = bucketing.trace_counts()
    if after.get("measure_core_topk", 0) != before.get("measure_core_topk", 0):
        raise SmokeFailure("A: unbounded measures took the top-k route")
    report("A deep-judged ad hoc", route="xla_full_sort",
           compiles=after.get("measure_core", 0) - before.get(
               "measure_core", 0),
           cli_max_abs_diff=f"{cli_diff:.3g}",
           max_abs_diff=f"{diff:.3g}", **fields)


def phase_b(seed: int) -> None:
    from repro.core import RelevanceEvaluator, measures as M
    from repro.kernels import bucketing

    qrel, qids, docnos, scores = devset_collection(seed + 1)
    fields = {"shape": f"{MSMARCO_DEV.queries}x{MSMARCO_DEV.depth}",
              "judgments": sum(map(len, qrel.values()))}
    before = bucketing.trace_counts()
    with timed(fields):
        ev = RelevanceEvaluator(qrel, MEASURES_B)
        buf = ev.buffer_from_arrays(qids, docnos, scores)
        got = ev.evaluate_buffer(buf)
    after = bucketing.trace_counts()
    topk = after.get("measure_core_topk", 0) - before.get(
        "measure_core_topk", 0)
    full = after.get("measure_core", 0) - before.get("measure_core", 0)
    if topk < 1 or full:
        raise SmokeFailure(f"B: expected the top-k route, got {topk} top-k "
                           f"and {full} full-sort compile(s)")
    batch = ev.batch_from_buffer(buf, topk_layout=True)
    expect_kernel(M.compute_measures_topk_packed_jit.lower(
        batch, ev.measures, ev.relevance_level,
        ev.judged_docs_only).compile().as_text(), "B (top-k measure core)")

    rng = np.random.default_rng(seed + 2)
    depth = MSMARCO_DEV.depth
    sample = rng.choice(MSMARCO_DEV.queries, min(MSMARCO_DEV.sample,
                                                 MSMARCO_DEV.queries),
                        replace=False)
    rows = np.concatenate([np.arange(q * depth, (q + 1) * depth)
                           for q in sample])
    run = run_dict(qids[rows], docnos[rows], scores[rows])
    want = pure_eval.evaluate(run, qrel, REFERENCE_B)
    keys = ev.measure_keys
    diff = max_diff({q: got[q] for q in want}, want, keys)
    check("B (top-k route)", diff)
    report("B sparse dev set", route="topk_kernel", topk_compiles=topk,
           checked_queries=len(want), max_abs_diff=f"{diff:.3g}", **fields)


def sharded_run(coll, measures, reference, mesh=None):
    """``coll`` through ``ShardedEvaluator`` on ``mesh`` (default: every
    device) beside ``evaluate_buffer``; the larger difference of the two
    from the reference, the depth classes and each class's compiled
    sharded program."""
    from repro.core import RelevanceEvaluator, measures as M
    from repro.distributed import ShardedEvaluator

    qrel, qids, docnos, scores = coll
    want = pure_eval.evaluate(run_dict(qids, docnos, scores), qrel,
                              reference)
    fields = {}
    with timed(fields):
        ev = RelevanceEvaluator(qrel, measures)
        buf = ev.buffer_from_arrays(qids, docnos, scores)
        sev = ShardedEvaluator(ev, mesh=mesh)
        got = sev.evaluate_buffer(buf).per_query
    single = ev.evaluate_buffer(buf)
    classes, batches = ev._class_batches(buf, q_multiple=sev.n_shards)
    texts = [M.packed_core(c.topk, sev.mesh).lower(
        b, ev.measures, ev.relevance_level,
        ev.judged_docs_only).compile().as_text()
        for c, b in zip(classes, batches)]
    diff = max(max_diff(got, want, ev.measure_keys),
               max_diff(single, want, ev.measure_keys))
    return {"ev": ev, "sev": sev, "got": got, "single": single,
            "diff": diff, "fields": fields,
            "classes": classes, "batches": batches, "texts": texts}


def phase_c(seed: int) -> None:
    r = sharded_run(ragged_collection(seed + 4), MEASURES_G, REFERENCE_G)
    topk = [t for c, t in zip(r["classes"], r["texts"]) if c.topk]
    if not topk or len(topk) == len(r["classes"]):
        raise SmokeFailure("C: expected depth classes on both routes, got "
                           f"{[(c.d_pad, c.topk) for c in r['classes']]}")
    for text in topk:
        expect_kernel(text, "C (sharded top-k core)")
    if r["got"] != r["single"]:
        raise SmokeFailure("C: sharded values differ from evaluate_buffer "
                           f"by {max_diff(r['got'], r['single']):.3g}")
    check("C (sharded)", r["diff"])
    report("C sharded", route="sharded", mesh=r["sev"].n_shards,
           classes=len(r["classes"]), topk_classes=len(topk),
           bits_equal_evaluate_buffer=True,
           max_abs_diff=f"{r['diff']:.3g}", **r["fields"])


def phase_d(coll, seed: int) -> None:
    from repro.client import AsyncEvalClient
    from repro.core import RelevanceEvaluator
    from repro.serve.frontend import serve_tcp
    from repro.serve.service import EvaluationService

    qrel, qids, docnos, scores = coll
    run = run_dict(qids, docnos, scores)
    rng = np.random.default_rng(seed + 3)
    score_sets = [np.round(scores + rng.normal(scale=0.5, size=scores.shape),
                           1).astype(np.float32)
                  for _ in range(SERVED_REQUESTS)]

    async def serve_and_ask():
        # a wide window: each request carries ~250k scores, whose JSON
        # parse outlasts the default 2 ms window
        service = EvaluationService(window=0.5, max_batch=64)
        server = await serve_tcp(service, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        client = await AsyncEvalClient.connect("127.0.0.1", port)
        try:
            await client.register_qrel("robust04", qrel, MEASURES_A)
            await client.register_run("robust04", "a", run=run)
            results = await client.evaluate_many(
                "robust04", run_ref="a", scores_list=score_sets)
        finally:
            await client.aclose()
            server.close()
            await server.wait_closed()
            await service.drain()
        return results, service.stats()

    fields = {}
    with timed(fields):
        results, stats = asyncio.run(serve_and_ask())
    if not stats["backend_calls"] < stats["requests"]:
        raise SmokeFailure(f"D: requests did not coalesce: {stats}")
    ev = RelevanceEvaluator(qrel, MEASURES_A)
    buf = ev.tokenize_run(run)
    worst = 0.0
    for res, s in zip(results, score_sets):
        direct = ev.evaluate_buffer(buf, scores=s)
        if res.per_query != direct:
            raise SmokeFailure(
                "D: a served answer differs from the direct evaluate by "
                f"{max_diff(res.per_query, direct):.3g}")
        worst = max(worst, max_diff(direct, pure_eval.evaluate(
            run_dict(qids, docnos, s), qrel, MEASURES_A)))
    check("D (served)", worst)
    report("D served", requests=stats["requests"],
           backend_calls=stats["backend_calls"],
           max_abs_diff=f"{worst:.3g}", **fields)


def phase_g(seed: int) -> None:
    from repro.core import RelevanceEvaluator

    qrel, qids, docnos, scores = ragged_collection(seed + 4)
    rescored = np.round(scores + np.random.default_rng(seed + 5).normal(
        scale=0.5, size=scores.shape), 2).astype(np.float32)
    fields = {"shape": f"{len(qrel)} lists of 1-{RAGGED.longest}",
              "rows": len(scores)}
    with timed(fields):
        ev = RelevanceEvaluator(qrel, MEASURES_G)
        buf = ev.buffer_from_arrays(qids, docnos, scores)
        got = [ev.evaluate_buffer(buf),
               ev.evaluate_buffer(buf, scores=rescored)]
    classes = buf.layout[0][1].classes
    topk = [c.d_pad for c in classes if c.topk]
    if len(classes) < 4 or not topk or len(topk) == len(classes):
        raise SmokeFailure(
            f"G: expected four or more depth classes on both routes, got "
            f"{[(c.d_pad, c.topk) for c in classes]}")
    worst = max(max_diff(g, pure_eval.evaluate(
        run_dict(qids, docnos, s), qrel, REFERENCE_G), ev.measure_keys)
        for g, s in zip(got, (scores, rescored)))
    check("G (depth classes)", worst)
    cells = sum(c.q_pad * c.d_pad for c in classes)
    report("G ragged graded lists", route="depth_classes",
           classes=len(classes), topk_classes=len(topk),
           pad_share=f"{1 - len(scores) / cells:.4f}",
           max_abs_diff=f"{worst:.3g}", **fields)


def phase_e(coll, chip_ids) -> None:
    """Cluster: 4 worker processes, one chip each; this process stays off
    JAX until they have exited."""
    from jax._src import xla_bridge

    from repro.client import EvalClient
    from repro.serve.cluster.testing import ClusterThread

    qrel, qids, docnos, scores = coll
    run = run_dict(qids, docnos, scores)
    want = pure_eval.evaluate(run, qrel, MEASURES_A)
    fields = {}
    worst = 0.0
    with timed(fields):
        with ClusterThread(4, worker_args=["--window-ms", "2"],
                           boot_timeout=300) as cluster:
            procs = {name: cluster.router._slots[name].proc
                     for name in cluster.worker_names}
            try:
                with EvalClient(cluster.host, cluster.port,
                                timeout=600) as client:
                    # one collection per worker: every chip serves requests
                    owned = {}
                    i = 0
                    while len(owned) < 4:
                        owned.setdefault(cluster.owner_of(f"robust04-{i}"),
                                         f"robust04-{i}")
                        i += 1
                    for qrel_id in owned.values():
                        client.register_qrel(qrel_id, qrel, MEASURES_A)
                    for qrel_id in owned.values():
                        res = client.evaluate(qrel_id, run=run)
                        worst = max(worst, max_diff(res.per_query, want))
            except Exception as e:
                tails = {name: list(p.last_stderr)[-4:]
                         for name, p in procs.items()}
                raise SmokeFailure(f"E: {type(e).__name__}: {e}; workers' "
                                   f"last stderr lines: {tails}") from e
            chips = {name: p.chip for name, p in procs.items()}
            forwarded = cluster.stats()["router"]["forwarded"]
    if sorted(chips.values()) != sorted(chip_ids):
        raise SmokeFailure(f"E: workers not bound one per chip: {chips}")
    if xla_bridge.backends_are_initialized():
        raise SmokeFailure("E: this process initialized a JAX backend "
                           "while the workers held the chips")
    check("E (cluster)", worst)
    report("E cluster", workers=len(chips), chips=chips,
           forwarded=forwarded, max_abs_diff=f"{worst:.3g}", **fields)


def phase_f(coll, devices, seed: int) -> None:
    """ShardedEvaluator on a 4-chip mesh vs single-device evaluate."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.core import measures as M

    mesh = Mesh(devices[:4], ("data",))
    runs = {"full-sort": sharded_run(coll, MEASURES_A, MEASURES_A, mesh),
            "ragged": sharded_run(ragged_collection(seed + 4), MEASURES_G,
                                  REFERENCE_G, mesh)}
    fields = {}
    for name, r in runs.items():
        vs_single = max_diff(r["got"], r["single"])
        check(f"F ({name}, sharded vs single-device)", vs_single)
        check(f"F ({name}, vs reference)", r["diff"])
        fields[f"{name}_vs_single_max_abs_diff"] = f"{vs_single:.3g}"
        fields[f"{name}_max_abs_diff"] = f"{r['diff']:.3g}"
        fields[f"{name}_setup_s_cold"] = r["fields"]["setup_s_cold"]
    ragged = runs["ragged"]
    for c, text in zip(ragged["classes"], ragged["texts"]):
        if c.topk:
            expect_kernel(text, "F (sharded top-k core)")
    c, batch, ev = ragged["classes"][-1], ragged["batches"][-1], ragged["ev"]
    rows = M.packed_core(c.topk, mesh)(
        jax.device_put(batch, NamedSharding(mesh, PartitionSpec("data"))),
        ev.measures, ev.relevance_level, ev.judged_docs_only)
    placed = sorted({shard.device.id for shard in rows.addressable_shards})
    if len(placed) != 4:
        raise SmokeFailure(f"F: shards landed on devices {placed}")
    report("F sharded mesh", mesh=ragged["sev"].n_shards,
           shard_devices=placed,
           shard_rows=rows.addressable_shards[0].data.shape[1], **fields)


# -- main ---------------------------------------------------------------------


def run_phases(phases) -> None:
    """Run every phase, even after one fails, then fail if any did."""
    failed = []
    for name, phase in phases:
        try:
            phase()
        except Exception as e:  # noqa: BLE001 — reported, then re-raised
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            print(f"[{name}] FAILED: {e}", file=sys.stderr, flush=True)
            failed.append(f"{name}: {e}")
    if failed:
        raise SmokeFailure("; ".join(failed))


def one_chip(seed: int):
    devices = require_tpu(1)
    coll = adhoc_collection(seed)

    def a():
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            phase_a(tmp, coll)

    run_phases([("A", a), ("B", lambda: phase_b(seed)),
                ("C", lambda: phase_c(seed)),
                ("D", lambda: phase_d(coll, seed)),
                ("G", lambda: phase_g(seed))])
    return devices


def four_chips(seed: int):
    chips = runtime.tpu_chips()
    if len(chips) < 4:
        raise SmokeFailure(f"--chips 4 needs a host with 4 TPU chips; this "
                           f"one has {len(chips)}")
    coll = adhoc_collection(seed)
    devices = []

    def f():
        devices.extend(require_tpu(4))
        phase_f(coll, devices, seed)

    run_phases([("E", lambda: phase_e(coll, chips[:4])), ("F", f)])
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed every generated collection (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-D and G on one chip (default); 4: the "
                         "cluster and the sharded mesh across four chips")
    args = ap.parse_args(argv)
    runtime.enable_compile_cache()
    try:
        devices = (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
