"""Benchmark driver — one section per paper table/figure plus system segments.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--list]

Segments (repeat ``--only`` to pick several):

* ``rq1``       — paper Fig. 1: in-process vs serialize-invoke-parse grid.
* ``rq2``       — paper Fig. 2: tiny-ranking crossover vs trec_eval.
* ``densify``   — run→``EvalBatch`` conversion in isolation: seed per-query
  loop vs the vectorized flat pipeline (cold dict ingest) vs the
  pre-tokenized session path (``batch_from_buffer`` on a ``RunBuffer``).
* ``kernels``   — the compile-count accounting behind shape bucketing:
  distinct wave sizes through the packed measure core vs the closed set of
  signatures; see ``bench_kernels``.
* ``sharded``   — multi-device scaling of the sharded evaluation pipeline
  (``repro.distributed.sharded_evaluator``) over meshes of the first
  1/2/4/8 visible devices, in this process; see ``bench_sharded``.
* ``serve``     — sustained throughput/latency of the async evaluation
  service (``repro.serve``) at several client-concurrency levels, including
  the request-coalescing factor; see ``bench_serve``.
* ``client``    — the same serving hot path measured END TO END through a
  real TCP socket and ``repro.client``: a raw-socket lockstep baseline vs
  ``AsyncEvalClient`` pipelining at several depths; see ``bench_client``.
* ``cluster``   — multi-worker scale-out (``repro.serve.cluster``): the
  same multi-collection workload through one in-process server vs the
  consistent-hash router at 1/2/4 workers (8 under ``--full``), with
  ``speedup_vs_single`` and the host core count; see ``bench_cluster``.
  CPU hosts only: on a TPU host this process would hold the chips the
  workers need, so this script refuses the segment there.
* ``qlearning`` — the paper's RL demo, episodes/s.
* ``batched``   — dense batched evaluation vs the dict API.
* ``sweep``     — K-run sweep evaluation (``evaluate_sweep``) vs K
  independent ``evaluate_buffer`` calls, and the vectorized all-pairs
  paired t-test + Holm (``repro.stats``) vs a scipy-per-pair baseline;
  see ``bench_sweep``.

Prints ``name,us_per_call,derived`` CSV rows (plus a JSON dump under
experiments/bench_results.json for EXPERIMENTS.md).
"""

from __future__ import annotations

import argparse
import json
import os

#: Segment name -> "module.function" (resolved lazily in main(); keeping the
#: registry import-free lets ``--list`` answer without loading jax, and gives
#: the docs-drift test one authoritative name list to compare against).
SEGMENTS = {
    "rq1": "bench_rq1.run",
    "rq2": "bench_rq2.run",
    "densify": "bench_rq1.densify",
    "kernels": "bench_kernels.run",
    "sharded": "bench_sharded.run",
    "serve": "bench_serve.run",
    "client": "bench_client.run",
    "cluster": "bench_cluster.run",
    "qlearning": "bench_qlearning.run",
    "batched": "bench_batched.run",
    "sweep": "bench_sweep.run",
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (20 reps, 10k queries)")
    ap.add_argument("--only", action="append", default=None,
                    choices=tuple(SEGMENTS),
                    help="segment to run (repeatable; default: all): "
                         "rq1/rq2 = paper figures, densify = run->EvalBatch "
                         "conversion paths, kernels = roofline + compile "
                         "accounting, sharded = multi-device scaling, "
                         "serve = async service throughput/latency, "
                         "client = TCP client library end to end, "
                         "cluster = multi-worker router scale-out, "
                         "qlearning = RL demo, batched = dense batched "
                         "eval, sweep = K-run sweep + significance stats")
    ap.add_argument("--list", action="store_true",
                    help="print the segment names (one per line) and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in SEGMENTS:
            print(name)
        return

    from repro import runtime

    selected = args.only or list(SEGMENTS)
    if "cluster" in selected and runtime.tpu_chips():
        # Every segment runs in this process, which takes the chips at its
        # first device call; the cluster's worker processes need them too.
        ap.error("the cluster segment cannot run on a TPU host: this "
                 "process (its in-process baseline server and every other "
                 "segment) holds the chips its worker processes need; run "
                 "it on a CPU host or leave it out with --only")
    runtime.enable_compile_cache()

    from benchmarks import bench_batched, bench_client, bench_cluster, \
        bench_kernels, bench_qlearning, bench_rq1, bench_rq2, bench_serve, \
        bench_sharded, bench_sweep

    modules = {
        "bench_batched": bench_batched, "bench_client": bench_client,
        "bench_cluster": bench_cluster, "bench_kernels": bench_kernels,
        "bench_qlearning": bench_qlearning, "bench_rq1": bench_rq1,
        "bench_rq2": bench_rq2, "bench_serve": bench_serve,
        "bench_sharded": bench_sharded, "bench_sweep": bench_sweep,
    }
    suites = {}
    for name, ref in SEGMENTS.items():
        mod, fn = ref.split(".")
        suites[name] = getattr(modules[mod], fn)
    results = {}
    for name in selected:
        print(f"=== {name} ===", flush=True)
        results[name] = suites[name](full=args.full)

    os.makedirs("experiments", exist_ok=True)
    # Merge into the existing record: a partial run (--only X) must refresh
    # segment X without dropping every other segment's stored results.
    path = "experiments/bench_results.json"
    merged = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                merged = json.load(fh)
        except (json.JSONDecodeError, OSError):
            merged = {}
    merged.update(results)
    with open(path, "w") as fh:
        json.dump(merged, fh, indent=1)

    print("\nname,us_per_call,derived")
    for row in results.get("rq1", []):
        for k in row:
            if k.startswith("speedup_"):
                print(f"rq1_q{row['n_queries']}_d{row['n_docs']}_{k[8:]},"
                      f"{row['inprocess_us']:.1f},speedup={row[k]:.2f}")
    for row in results.get("rq2", []):
        print(f"rq2_d{row['n_docs']},{row['ours_us']:.1f},"
              f"speedup={row['speedup']:.2f}")
    for row in results.get("densify", []):
        print(f"densify_q{row['n_queries']}_d{row['n_docs']},"
              f"{row['session_us']:.1f},"
              f"speedup={row['speedup_densify']:.2f}")
    for row in results.get("kernels", []):
        print(f"kernels_bucketing_w{row['distinct_wave_sizes']},"
              f"{1e6 * row['elapsed_s'] / row['distinct_wave_sizes']:.1f},"
              f"compiles={row['compiles']}/{row['signature_bound']}")
    for row in results.get("sharded", []):
        sp = row.get("speedup_vs_1dev")
        sp_str = f"{sp:.2f}" if sp is not None else "nan"
        print(f"sharded_dev{row['devices']},{row['sharded_us']:.1f},"
              f"speedup={sp_str}")
    for row in results.get("serve", []):
        print(f"serve_c{row['concurrency']},"
              f"{1e6 / row['runs_per_s']:.1f},"
              f"runs_per_s={row['runs_per_s']:.1f}")
    for row in results.get("client", []):
        print(f"client_{row['mode']}_d{row['depth']},"
              f"{1e6 / row['runs_per_s']:.1f},"
              f"p99_ms={row['p99_ms']:.1f}")
    for row in results.get("cluster", []):
        sp = row.get("speedup_vs_single")
        sp_str = f"{sp:.2f}" if sp is not None else "nan"
        print(f"cluster_{row['mode']}_w{row['workers']},"
              f"{1e6 / row['runs_per_s']:.1f},"
              f"speedup={sp_str}")
    for row in results.get("qlearning", []):
        print(f"qlearning,{1e6 / row['episodes_per_s']:.1f},"
              f"tail_reward={row['tail_avg_reward']:+.4f}")
    for row in results.get("batched", []):
        print(f"batched_dense,{row['dense_batched_us']:.1f},"
              f"speedup_vs_dict={row['dense_speedup_vs_dict']:.2f}")
    for row in results.get("sweep", []):
        sp = row.get("stats_speedup")
        sp_str = f"{sp:.2f}" if sp is not None else "nan"
        print(f"sweep_k{row['n_runs']},{row['sweep_us']:.1f},"
              f"stats_speedup={sp_str}")


if __name__ == "__main__":
    main()
