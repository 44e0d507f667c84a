"""Benchmark driver: one suite per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig1,...]

Suites:
  fig1     4KB vs 2MB vs ideal-TLB translation overhead   (paper Fig. 1)
  fig5     homogeneous weighted speedup                   (paper Fig. 5)
  fig6     heterogeneous weighted speedup                 (paper Fig. 6)
  fig7     demand-paging on/off                           (paper Fig. 7)
  fig8     L1/L2 TLB hit rates + interference             (paper Fig. 8)
  kernels  paged-attention granularity + CAC copy cost    (beyond paper)
  pagesize TPU-native page-size trade-off                 (paper §1)
  serving  Mosaic vs GPU-MMU on the serving engine        (Figs. 5/6 analogue)
  oversub  2x-oversubscribed host-tier paging + swap cycle (paper §1/§4.2)
  overlap  sync vs async double-buffered fault-in + link contention (§7)
  prefix-reuse  content-hash prefix cache + full-duplex DMA (§8)
  cluster  shared host tier + deadline router + migration (§10)
  router   modeled-µs cost routing + queued steal + pre-staging (§14)
  spill    disk spill tier + write-back back-pressure     (§11)
  faults   crash recovery + spill integrity + degrade     (§12)
  fused-decode  fused gather-attend decode vs sync/async  (§13)
  translation  radix walker + coalesced TLB: mosaic vs scattered,
           walker-contention routing                      (§15)
  roofline dry-run roofline table, if dryrun_all.jsonl exists (deliv. g)

Output: CSV-ish `key=value` rows per suite + a PASS/FAIL claim summary,
plus a machine-readable ``BENCH_serving.json`` artifact (suite/config →
tok/s, exposed_us, hidden_us, dma_count) so the perf trajectory is
tracked across PRs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _json_safe(v):
    """numpy scalars / bools → plain JSON types."""
    import numpy as np
    if isinstance(v, (bool, np.bool_)):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return v


def write_bench_artifact(results, path="BENCH_serving.json"):
    """suite/config → {tok_per_s, exposed_us, hidden_us, dma_count, ...}.

    Every serving-side row (anything reporting a tok/s) gets an entry
    keyed ``<suite>/<bench>/<manager-or-mode>[@factor]``; claim rows are
    collected verbatim so CI can diff trajectories across PRs.
    """
    suites = {}
    claims = {}
    for suite, rows in results.items():
        for r in rows:
            cfg = r.get("manager", r.get("mode", ""))
            if "tok_per_s_cpu" in r:
                label = f"{suite}/{r.get('bench', suite)}/{cfg}"
                if "factor" in r:
                    label += f"@{r['factor']}"
                suites[label] = {
                    "tok_per_s": _json_safe(r["tok_per_s_cpu"]),
                    "exposed_us": _json_safe(r.get("exposed_us", 0.0)),
                    "hidden_us": _json_safe(r.get("hidden_us", 0.0)),
                    "dma_count": _json_safe(
                        r.get("dma_count", r.get("fault_dmas", 0))),
                    "faults": _json_safe(r.get("faults", 0)),
                    "transfer_us": _json_safe(r.get("transfer_us", 0.0)),
                }
            for k, v in r.items():
                if k.startswith("claim_") or k.startswith("hidden_fraction"):
                    label = f"{suite}/{k}"
                    if "factor" in r:       # keep per-factor datapoints
                        label += f"@{r['factor']}"
                    claims[label] = _json_safe(v)
    if not suites:
        # A figure-only run has no serving rows; don't clobber a
        # previously-written trajectory artifact with an empty one.
        return
    # Merge with an existing artifact so partial --only runs refresh
    # their own entries without deleting other suites' datapoints.
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            suites = {**prev.get("suites", {}), **suites}
            claims = {**prev.get("claims", {}), **claims}
        except (json.JSONDecodeError, OSError):
            pass                        # corrupt artifact: rewrite fresh
    with open(path, "w") as f:
        json.dump({"schema": 1, "suites": suites, "claims": claims}, f,
                  indent=2, sort_keys=True)
    print(f"\nwrote {path} ({len(suites)} configs, {len(claims)} claims)",
          flush=True)


def _emit(rows):
    for r in rows:
        print("  " + ",".join(f"{k}={v}" for k, v in r.items()), flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller traces (CI)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--engines", type=int, default=2,
                    help="cluster width for the spill suite")
    args = ap.parse_args(argv)
    n = 2000 if args.fast else 4000

    from benchmarks import kernel_bench, paperfigs, serving_bench
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    suites = {
        "fig1": lambda: paperfigs.fig1_translation_overhead(n_access=n),
        "fig5": lambda: paperfigs.fig5_homogeneous(n_access=n),
        "fig6": lambda: paperfigs.fig6_heterogeneous(n_access=n),
        "fig7": lambda: paperfigs.fig7_demand_paging(n_access=2 * n),
        "fig8": lambda: paperfigs.fig8_tlb_hitrate(n_access=n),
        "kernels": lambda: (kernel_bench.paged_attention_granularity()
                            + kernel_bench.page_compact_cost()),
        "pagesize": kernel_bench.pagesize_sweep,
        "serving": serving_bench.serving_compare,
        "oversub": lambda: (serving_bench.oversubscribed_compare()
                            + serving_bench.swap_cycle_compare()),
        "overlap": lambda: (serving_bench.overlap_compare(
                                factors=(2.0,) if args.fast else (1.5, 2.0),
                                n_requests=8 if args.fast else 12)
                            + serving_bench.overlap_link_contention(
                                n_access=n // 2)),
        "prefix-reuse": lambda: (
            serving_bench.prefix_reuse_compare(
                n_requests=6 if args.fast else 8)
            + serving_bench.duplex_compare(
                n_requests=8 if args.fast else 10)
            + serving_bench.duplex_sim_compare(n_access=n // 2)),
        "cluster": lambda: (
            serving_bench.cluster_prefix_share_compare(
                n_requests=6 if args.fast else 8)
            + serving_bench.cluster_router_compare()
            + serving_bench.cluster_migration_compare()
            + serving_bench.cluster_sim_compare(n_access=n // 2)),
        "router": serving_bench.router_cost_compare,
        "spill": lambda: (
            serving_bench.spill_compare(n_engines=args.engines)
            + serving_bench.spill_backpressure_compare()
            + serving_bench.spill_sim_compare(n_access=n // 2)),
        "faults": lambda: (
            serving_bench.faults_crash_compare()
            + serving_bench.faults_spill_compare()),
        "fused-decode": lambda: (
            serving_bench.fused_decode_compare()
            + serving_bench.fused_kernel_compare()),
        "translation": lambda: (
            serving_bench.translation_radix_compare(n_access=n // 2)
            + serving_bench.translation_router_compare()),
    }
    picked = (args.only.split(",") if args.only else list(suites))
    unknown = [p for p in picked if p not in suites and p != "roofline"]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; choose from "
                 f"{sorted(suites) + ['roofline']}")

    claims = []
    results = {}
    for name in [p for p in picked if p in suites]:
        t0 = time.time()
        print(f"=== {name}", flush=True)
        rows = _emit(suites[name]())
        results[name] = rows
        for r in rows:
            for k, v in r.items():
                if k.startswith("claim_"):
                    claims.append((name, k, bool(v)))
        print(f"  ({time.time() - t0:.1f}s)", flush=True)

    write_bench_artifact(results)

    if os.path.exists("dryrun_all.jsonl") and (args.only is None
                                               or "roofline" in picked):
        print("=== roofline (from dryrun_all.jsonl)", flush=True)
        from benchmarks import roofline
        roofline.main(["dryrun_all.jsonl"])

    print("\n=== claim summary")
    ok = True
    for suite, claim, passed in claims:
        print(f"  {suite:8} {claim:32} {'PASS' if passed else 'FAIL'}")
        ok = ok and passed
    print("ALL CLAIMS PASS" if ok else "SOME CLAIMS FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
