"""Three-term roofline analysis from the dry-run's compiled artifacts.

    PYTHONPATH=src python -m repro.analysis.roofline \
        [--dryrun experiments/dryrun] [--out experiments/roofline.md]

Per (arch × shape × mesh):
  compute_s    = HLO_FLOPs_per_device / PEAK_FLOPS      (197 TF/s bf16, v5e)
  memory_s     = HLO_bytes_per_device / HBM_BW          (819 GB/s)
  collective_s = collective_bytes_per_device / ICI_BW   (~50 GB/s/link)

``cost_analysis()`` / the parsed HLO describe the per-device SPMD program, so
the spec's global formulation (global / (chips × bw)) reduces to the
per-device quantities used here.  MODEL_FLOPS is the analytic useful compute
(6·N_active·D for training, 2·N for single-token decode, family-specific
estimates elsewhere); MODEL/HLO exposes remat and dispatch overcompute.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, Optional

PEAK_FLOPS = 197e12  # bf16 / chip (TPU v5e)
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
VMEM_BYTES = 16 * 2**20  # on-chip vector memory per core (Pallas tile budget)


def kernel_roofline(bytes_moved: float, seconds: float,
                    flops: float = 0.0) -> Dict:
    """Achieved-vs-peak terms for one measured kernel invocation.

    The kernel-benchmark counterpart of :func:`analyze`: instead of HLO
    cost estimates it takes *measured* wall time plus the analytic bytes
    the kernel must move (its HBM traffic floor) and reports achieved
    bandwidth against :data:`HBM_BW`.  Consumed by
    ``benchmarks.bench_kernels`` and the ``--only kernels`` segment.
    """
    achieved_bw = bytes_moved / seconds if seconds > 0 else 0.0
    achieved_flops = flops / seconds if seconds > 0 else 0.0
    return {
        "bytes_moved": bytes_moved,
        "seconds": seconds,
        "achieved_bytes_per_s": achieved_bw,
        "peak_bytes_per_s": HBM_BW,
        "achieved_flops_per_s": achieved_flops,
        "flops_fraction": achieved_flops / PEAK_FLOPS,
    }


# ---------------------------------------------------------------------------
# Analytic MODEL_FLOPS per family (global, whole step)
# ---------------------------------------------------------------------------


def _lm_model_flops(arch_name: str, shape: Dict) -> Optional[float]:
    from repro.launch.api import get_arch

    cfg = get_arch(arch_name).make_config(False)
    n_mm = cfg.active_param_count() - cfg.vocab_size * cfg.d_model
    if cfg.tie_embeddings:
        n_mm += cfg.vocab_size * cfg.d_model  # head matmul still happens
    b = shape.get("global_batch")
    s = shape.get("seq_len")
    l, h, hd = cfg.n_layers, cfg.n_heads, cfg.head_dim
    kind = shape["kind"]
    if kind == "train":
        tokens = b * s
        return 6.0 * n_mm * tokens + 12.0 * l * b * s * s * h * hd
    if kind == "prefill":
        tokens = b * s
        return 2.0 * n_mm * tokens + 4.0 * l * b * s * s * h * hd
    if kind == "decode":
        return 2.0 * n_mm * b + 4.0 * l * b * s * h * hd
    return None


def _gnn_model_flops(shape: Dict) -> float:
    from repro.launch.api import get_arch

    cfg = get_arch("gatedgcn").make_config(False)
    d = cfg.d_hidden
    n, e = shape["n_nodes"], shape["n_edges"]
    d_in = shape.get("d_feat", cfg.d_in)
    nc = shape.get("n_classes", cfg.n_classes)
    per_layer = 4.0 * n * d * d + 6.0 * e * d * d
    fwd = cfg.n_layers * per_layer + 2.0 * n * d_in * d + 2.0 * n * d * nc
    return 3.0 * fwd  # train step ≈ fwd + 2×fwd backward


def _recsys_model_flops(arch_name: str, shape: Dict) -> Optional[float]:
    from repro.launch.api import get_arch

    cfg = get_arch(arch_name).make_config(False)
    kind = shape["kind"]
    b = shape.get("batch", 1)

    def fwd_per_example() -> float:
        if arch_name == "xdeepfm":
            dmodel, f = cfg.table.dim, cfg.table.n_fields
            flops, h_prev = 0.0, f
            for h in cfg.cin_layers:
                flops += 2.0 * dmodel * h_prev * f * h
                h_prev = h
            dims = (f * dmodel,) + tuple(cfg.mlp_dims) + (1,)
            flops += sum(2.0 * a * bb for a, bb in zip(dims[:-1], dims[1:]))
            return flops
        if arch_name == "autoint":
            f = cfg.table.n_fields
            da, nh = cfg.d_attn, cfg.n_attn_heads
            d_in = cfg.table.dim
            flops = 0.0
            for _ in range(cfg.n_attn_layers):
                flops += 4.0 * 2.0 * f * d_in * da * nh  # q,k,v,res proj
                flops += 2.0 * 2.0 * f * f * da * nh  # scores + weighted sum
                d_in = da * nh
            return flops + 2.0 * f * d_in
        if arch_name == "sasrec":
            d, s = cfg.embed_dim, cfg.seq_len
            per_block = 2.0 * s * d * 3 * d + 2.0 * s * d * d * 2 + \
                4.0 * s * s * d
            return cfg.n_blocks * per_block + 4.0 * s * d  # + BCE dots
        if arch_name == "mind":
            d, t, k = cfg.table.dim, cfg.hist_len, cfg.n_interests
            route = cfg.capsule_iters * (2.0 * k * t * d * 2)
            return 2.0 * t * d * d + route + 2.0 * d * 4 * d * 2
        return 0.0

    per_ex = fwd_per_example()
    if kind == "train":
        return 3.0 * b * per_ex
    if kind == "serve":
        slate = shape.get("slate", 0)
        if slate and arch_name in ("sasrec", "mind"):
            d = cfg.embed_dim if arch_name == "sasrec" else cfg.table.dim
            return b * (per_ex + 2.0 * slate * d)
        return b * per_ex
    if kind == "retrieval":
        nc = shape["n_candidates"]
        if arch_name in ("sasrec", "mind"):
            d = cfg.embed_dim if arch_name == "sasrec" else cfg.table.dim
            k = getattr(cfg, "n_interests", 1) or 1
            return per_ex + 2.0 * nc * d * k
        return nc * per_ex  # CTR: full forward per candidate
    return None


def _eval_model_flops(shape: Dict) -> float:
    # sort (~D log2 D compares) + ~8 cumulative passes over [Q, D]
    import math

    q, d = shape["n_queries"], shape["n_docs"]
    return q * d * (math.log2(max(d, 2)) + 8.0)


def model_flops(rec: Dict) -> Optional[float]:
    from repro.launch.api import get_arch

    arch = rec["arch"]
    fam = rec["family"]
    spec = get_arch(arch).shapes[rec["shape"]]
    shape = dict(spec.meta)
    shape["kind"] = spec.kind
    if fam == "lm":
        return _lm_model_flops(arch, shape)
    if fam == "gnn":
        return _gnn_model_flops(shape)
    if fam == "recsys":
        return _recsys_model_flops(arch, shape)
    if fam == "eval":
        return _eval_model_flops(shape)
    return None


# ---------------------------------------------------------------------------
# Per-record analysis
# ---------------------------------------------------------------------------


def analyze(rec: Dict, probe: Optional[Dict] = None) -> Optional[Dict]:
    if rec["status"] != "ok":
        return None
    chips = rec["n_chips"]
    flops_dev = rec["cost"].get("flops", 0.0)
    bytes_dev = rec["cost"].get("bytes accessed", 0.0)
    coll_dev = rec["collectives"]["total"]
    scan_corrected = False
    if probe and probe.get("status") == "ok":
        # XLA counts the scan body once; correct the full compile's totals
        # with (L−1) extra copies of the true per-layer cost measured by the
        # unrolled L=1/L=2 probe (see launch/dryrun.py::run_scan_probe).
        t = probe["trips"]
        body = probe["body"]
        flops_dev += (t - 1) * max(body["flops"], 0.0)
        bytes_dev += (t - 1) * max(body["bytes"], 0.0)
        coll_dev += (t - 1) * max(body["collective"], 0.0)
        scan_corrected = True
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values())
    mf = model_flops(rec)
    hlo_global = flops_dev * chips
    ratio = (mf / hlo_global) if (mf and hlo_global > 0) else None
    # roofline fraction: useful model FLOP/s at the bound vs peak
    frac = None
    if mf is not None and step_s > 0:
        frac = (mf / chips / step_s) / PEAK_FLOPS
    suggestion = {
        "compute": "compute-bound: raise MXU utilization (bf16 everywhere, "
                   "fuse small ops, cut remat recompute)",
        "memory": "memory-bound: raise arithmetic intensity (fuse passes, "
                  "larger per-device tiles, avoid fp32 spills)",
        "collective": "collective-bound: reshard to cut cross-device bytes "
                      "(overlap with compute, compress, change TP split)",
    }[dominant]
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "chips": chips, "scan_corrected": scan_corrected,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "dominant": dominant,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "model_over_hlo": ratio, "roofline_fraction": frac,
        "peak_bytes_per_dev": rec["memory"].get("peak_bytes") or
        (rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]),
        "suggestion": suggestion,
    }


def fmt_s(x: Optional[float]) -> str:
    if x is None:
        return "–"
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}µs"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline.md")
    ap.add_argument("--mesh", default="single",
                    help="mesh for the main table (spec: single-pod)")
    args = ap.parse_args(argv)

    probes = {}
    for path in glob.glob(os.path.join(args.dryrun, "*__probe.json")):
        p = json.load(open(path))
        probes[(p["arch"], p["shape"], p["mesh"])] = p

    rows, skips = [], []
    for path in sorted(glob.glob(os.path.join(args.dryrun, "*.json"))):
        if path.endswith("__probe.json"):
            continue
        rec = json.load(open(path))
        if rec["status"] == "skipped":
            skips.append(rec)
            continue
        a = analyze(rec, probes.get((rec["arch"], rec["shape"],
                                     rec["mesh"])))
        if a:
            rows.append(a)

    lines = [
        "| arch | shape | mesh | compute | memory | collective | dominant |"
        " MODEL/HLO | roofline frac | HBM/dev |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["mesh"] != args.mesh:
            continue
        ratio = f"{r['model_over_hlo']:.2f}" if r["model_over_hlo"] else "–"
        frac = (f"{100*r['roofline_fraction']:.1f}%"
                if r["roofline_fraction"] is not None else "–")
        hbm = f"{r['peak_bytes_per_dev']/2**30:.2f}GiB"
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} |"
            f" {fmt_s(r['compute_s'])} | {fmt_s(r['memory_s'])} |"
            f" {fmt_s(r['collective_s'])} | **{r['dominant']}** |"
            f" {ratio} | {frac} | {hbm} |")
    lines.append("")
    lines.append("Skipped cells: " + "; ".join(
        sorted({f"{s['arch']}×{s['shape']} ({s['skip_reason'][:40]}…)"
                for s in skips})) if skips else "No skips.")
    out = "\n".join(lines)
    print(out)
    with open(args.out, "w") as fh:
        fh.write(out + "\n")
    with open(args.out.replace(".md", ".json"), "w") as fh:
        json.dump(rows, fh, indent=1)


if __name__ == "__main__":
    main()
