"""Roofline analysis from the dry-run artifacts (assignment §Roofline).

Peaks come from one table keyed by ``device_kind`` (``PEAKS``); a device
that is not in it is an error, not a default.

For every (arch × shape × mesh) record in experiments/dryrun_*.jsonl,
against the v5e peaks the dry run targets:
  compute term    = HLO_FLOPs_per_device / peak FLOP/s       [s]
  memory term     = HLO_bytes_per_device / peak HBM B/s      [s]
  collective term = collective_bytes_per_device / ICI B/s    [s]
(cost_analysis on the SPMD-partitioned module is per-device, so dividing by
per-chip peaks gives the same number as global/(chips × peak).)

Also: MODEL_FLOPS = 6·N·D (train) / 2·N·D (serve) with N = active params,
D = processed tokens/examples — and the usefulness ratio MODEL/HLO that
catches remat/redundancy waste.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, Optional

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of inter-chip interconnect (4 links, so 50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"   # the chip the dry-run meshes stand for


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; raises for any other."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None

_LM_TOKENS = {"train_4k": 256 * 4096, "prefill_32k": 32 * 32_768,
              "decode_32k": 128, "long_500k": 1}


def model_flops(arch: str, shape: str) -> Optional[float]:
    """Analytic MODEL_FLOPS per step (6·N·D dense-train convention)."""
    from repro.configs import get_arch
    spec = get_arch(arch)
    if spec.family == "lm":
        cfg = spec.config
        n = cfg.active_param_count()
        d = _LM_TOKENS[shape]
        if shape == "train_4k":
            return 6.0 * n * d
        return 2.0 * n * d          # forward-only serving
    if spec.family == "gnn":
        return None                  # segment/gather dominated; no 6ND analogue
    # recsys: dense-compute params × examples (tables are lookups, ~0 flops)
    import jax
    import numpy as np
    cfg = spec.config
    params = spec.abstract_params()
    dense = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        p = "/".join(getattr(k, "key", str(k)) for k in path)
        if any(t in p for t in ("table", "embed", "linear/")):
            continue
        dense += int(np.prod(leaf.shape))
    b = {"train_batch": 65_536, "serve_p99": 512, "serve_bulk": 262_144,
         "retrieval_cand": 1_000_000}[shape]
    mult = 6.0 if shape == "train_batch" else 2.0
    return mult * dense * b


_SCAN_TRIPS = {"qwen2.5-14b": 48, "yi-9b": 48, "internlm2-1.8b": 24,
               "qwen3-moe-235b-a22b": 94, "qwen2-moe-a2.7b": 24,
               "nequip": 5, "sasrec": 2}


def correct_scan_once(r1: Dict, r2: Optional[Dict]) -> Dict:
    """XLA cost_analysis counts a while-loop body ONCE regardless of trip
    count.  Two-point probe: lowering the same cell with scan unroll=1 vs
    unroll=2 differs by exactly one layer's cost, so

        true = u1 + (L - 1) · (u2 - u1)

    for FLOPs, bytes and collective bytes alike (the unrolled body contains
    two copies of the layer's collectives)."""
    L = _SCAN_TRIPS.get(r1["arch"], 1)
    if L <= 1 or r2 is None or not r2.get("ok"):
        return r1
    out = dict(r1)
    c1, c2 = dict(r1.get("cost", {})), r2.get("cost", {})
    for key in ("flops", "bytes accessed"):
        if key in c1 and key in c2:
            per_layer = max(c2[key] - c1[key], 0.0)
            c1[key] = c1[key] + (L - 1) * per_layer
    out["cost"] = c1
    coll1 = {k: dict(v) for k, v in r1.get("collectives", {}).items()}
    coll2 = r2.get("collectives", {})
    for k in set(coll1) | set(coll2):
        b1 = coll1.get(k, {"bytes": 0.0, "count": 0})
        b2 = coll2.get(k, {"bytes": 0.0, "count": 0})
        per_layer = max(b2["bytes"] - b1["bytes"], 0.0)
        b1["bytes"] = b1["bytes"] + (L - 1) * per_layer
        coll1[k] = b1
    out["collectives"] = coll1
    out["scan_corrected"] = True
    return out


def analyze(record: Dict) -> Dict:
    cost = record.get("cost", {})
    flops = cost.get("flops", 0.0)
    nbytes = cost.get("bytes accessed", 0.0)
    coll = sum(v["bytes"] for v in record.get("collectives", {}).values())
    peak = peaks(DRYRUN_DEVICE_KIND)
    terms = {
        "compute_s": flops / peak["flops"],
        "memory_s": nbytes / peak["hbm_bw"],
        "collective_s": coll / peak["ici_bw"],
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(record["arch"], record["shape"])
    n_dev = record.get("n_devices", 256)
    ratio = (mf / (flops * n_dev)) if (mf and flops) else None
    bound = {"compute_s": "compute", "memory_s": "memory",
             "collective_s": "collective"}[dominant]
    suggestion = {
        "compute": "raise MXU efficiency: fuse elementwise chains, bf16 "
                   "matmuls, avoid remat recompute",
        "memory": "cut HBM traffic: block/flash attention, fused scans, "
                  "smaller activation dtypes, better layouts",
        "collective": "reshard to reduce resharding collectives, overlap "
                      "collectives with compute, hierarchical/compressed "
                      "reduction",
    }[bound]
    return {**record, "terms": terms, "bound": bound, "model_flops": mf,
            "useful_ratio": ratio, "suggestion": suggestion,
            "collective_bytes": coll}


def load(path: str, u2_path: str = None):
    out = []
    if not os.path.exists(path):
        return out
    probes = {}
    if u2_path and os.path.exists(u2_path):
        with open(u2_path) as fh:
            for line in fh:
                r = json.loads(line)
                probes[(r["arch"], r["shape"])] = r
    with open(path) as fh:
        for line in fh:
            r = json.loads(line)
            if r.get("ok"):
                r = correct_scan_once(r, probes.get((r["arch"], r["shape"])))
                out.append(analyze(r))
    return out


def table(records, title: str) -> str:
    lines = [f"### {title}", "",
             "| arch | shape | compute s | memory s | coll s | bound | "
             "mem GiB/dev | MODEL/HLO | roofline frac |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in records:
        t = r["terms"]
        peak = max(t.values())
        # roofline fraction: time the dominant term says vs time an ideal
        # compute-only execution would take
        frac = t["compute_s"] / peak if peak > 0 else 0.0
        mem = r.get("memory", {}).get("peak_bytes", 0) / 2**30
        ur = f"{r['useful_ratio']:.2f}" if r.get("useful_ratio") else "-"
        lines.append(
            f"| {r['arch']} | {r['shape']} | {t['compute_s']:.2e} | "
            f"{t['memory_s']:.2e} | {t['collective_s']:.2e} | {r['bound']} | "
            f"{mem:.1f} | {ur} | {frac:.2f} |")
    return "\n".join(lines)


# --------------------------------------------------------------------- #
# Kernel benches: achieved vs roofline for the two Pallas kernels        #
# --------------------------------------------------------------------- #

def _time_op(fn, *, warmup: int = 1, reps: int = 3) -> float:
    """Median wall seconds per call; blocks on the result each rep."""
    import time as _time

    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    samples = []
    for _ in range(reps):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn())
        samples.append(_time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


def _phase_attribution(kernel: str, host_arrays, compute_fn,
                       reps: int = 3) -> None:
    """DMA-vs-compute attribution: time host->device staging of the
    kernel's inputs separately from compute on already-resident arrays,
    into the ``kernel_phase_ms{kernel,phase}`` histograms — the split
    that tells you whether a slow kernel is data-starved or MXU-bound."""
    import jax

    from repro import obs
    for _ in range(reps):
        with obs.phase_timer(kernel, "dma"):
            dev = [jax.block_until_ready(jax.device_put(a))
                   for a in host_arrays]
        with obs.phase_timer(kernel, "compute"):
            jax.block_until_ready(compute_fn(*dev))


def kernel_bench(smoke: bool = False):
    """Time ``bm25_blockmax_topk`` and ``interval_join`` at a few sizes and
    report achieved GFLOP/s against the roofline bound (min of the compute
    and HBM ceilings for each kernel's FLOP/byte mix) of the device they
    ran on.  Results land in the obs registry as
    ``kernel_achieved_gflops{kernel,size}``,
    ``kernel_roofline_frac{kernel,size}`` and the per-phase
    ``kernel_phase_ms{kernel,phase}`` (DMA staging vs resident compute).
    Raises before timing anything on a device without published peaks,
    the CPU included."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.kernels.bm25_blockmax.ops import bm25_blockmax_topk
    from repro.kernels.interval_join.ops import interval_join

    dev = jax.devices()[0]
    peak = peaks(dev.device_kind)
    print(f"device: {dev.device_kind} ({dev.platform}) x{len(jax.devices())}")
    reg = obs.registry()
    rng = np.random.default_rng(0)
    rows = []

    bm25_sizes = [(8, 32, 64)] if smoke else [(8, 32, 64), (16, 128, 64)]
    for t, nb, bs in bm25_sizes:
        imp_np = np.asarray(
            rng.random((nb, t, bs), dtype=np.float32) *
            (rng.random((nb, t, bs)) < 0.3), dtype=np.float32)
        bmax_np = imp_np.max(axis=2)
        impacts, bmax = jnp.asarray(imp_np), jnp.asarray(bmax_np)
        fn = lambda: bm25_blockmax_topk(impacts, bmax, k=10)  # noqa: E731
        secs = _time_op(fn)
        _phase_attribution(
            "bm25_blockmax", [imp_np, bmax_np],
            lambda i, b: bm25_blockmax_topk(i, b, k=10))
        # per-doc score = sum over T term impacts -> ~T adds per (block, slot)
        flops = float(t * nb * bs)
        nbytes = 4.0 * (t * nb * bs + t * nb)        # impacts + block maxima
        rows.append(("bm25_blockmax", f"{t}x{nb}x{bs}", secs, flops, nbytes))

    join_sizes = [1024] if smoke else [1024, 4096]
    for n in join_sizes:
        a_s_np = rng.integers(0, 1 << 20, n).astype(np.int32)
        a_e_np = a_s_np + rng.integers(1, 64, n).astype(np.int32)
        b_s_np = rng.integers(0, 1 << 20, n).astype(np.int32)
        b_e_np = b_s_np + rng.integers(64, 4096, n).astype(np.int32)
        a_s, a_e = jnp.asarray(a_s_np), jnp.asarray(a_e_np)
        b_s, b_e = jnp.asarray(b_s_np), jnp.asarray(b_e_np)
        fn = lambda: interval_join(a_s, a_e, b_s, b_e)  # noqa: E731
        secs = _time_op(fn)
        _phase_attribution(
            "interval_join", [a_s_np, a_e_np, b_s_np, b_e_np],
            interval_join)
        flops = 3.0 * n * n                     # 2 compares + OR-combine/pair
        nbytes = 4.0 * (4 * n + n)              # four int32 inputs + mask out
        rows.append(("interval_join", f"{n}x{n}", secs, flops, nbytes))

    print("| kernel | size | wall ms | achieved GFLOP/s | roofline frac |")
    print("|---|---|---|---|---|")
    for kernel, size, secs, flops, nbytes in rows:
        achieved = flops / secs / 1e9
        bound_s = max(flops / peak["flops"], nbytes / peak["hbm_bw"])
        frac = bound_s / secs if secs > 0 else 0.0
        reg.gauge("kernel_achieved_gflops",
                  "measured kernel throughput (median of 3 reps)",
                  kernel=kernel, size=size).set(achieved)
        reg.gauge("kernel_roofline_frac",
                  "achieved / roofline-bound time (1.0 = at the ceiling)",
                  kernel=kernel, size=size).set(frac)
        print(f"| {kernel} | {size} | {1e3 * secs:.2f} | {achieved:.3f} | "
              f"{frac:.2e} |")
    print()
    print("| kernel | phase | p50 ms | samples |")
    print("|---|---|---|---|")
    for kernel in dict.fromkeys(k for k, *_ in rows):
        for ph in ("dma", "compute"):
            h = reg.histogram("kernel_phase_ms",
                              "per-phase kernel wall time",
                              kernel=kernel, phase=ph)
            if h.count:
                print(f"| {kernel} | {ph} | {h.percentile(0.5):.3f} | "
                      f"{h.count} |")
    return rows


def main():
    base = os.path.join(os.path.dirname(__file__), "..", "experiments")
    for mesh in ["pod16x16", "pod2x16x16"]:
        recs = load(os.path.join(base, f"dryrun_{mesh}.jsonl"),
                    os.path.join(base, f"dryrun_{mesh}_u2.jsonl"))
        if not recs:
            print(f"(no records for {mesh})")
            continue
        print(table(recs, f"Roofline — {mesh} ({len(recs)} cells)"))
        print()
        with open(os.path.join(base, f"roofline_{mesh}.md"), "w") as fh:
            fh.write(table(recs, f"Roofline — {mesh}") + "\n")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kernels", action="store_true",
                    help="time the Pallas kernels (bm25_blockmax, "
                         "interval_join) instead of analyzing dry-run "
                         "artifacts")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes only")
    args = ap.parse_args()
    if args.kernels:
        kernel_bench(smoke=args.smoke)
        sys.exit(0)
    sys.exit(main())
