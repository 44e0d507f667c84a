"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST be run as its own process (the XLA_FLAGS line below must execute
before any jax import anywhere — jax locks the device count at first
init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape decode_32k --mesh pod          # 16x16 (256 chips)
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod

For each cell it prints (and appends to --out as JSON lines):
  * compiled.memory_analysis()  — proves the cell fits per-device HBM;
  * compiled.cost_analysis()    — HLO FLOPs / bytes for §Roofline;
  * collective bytes parsed from the compiled HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute);
  * the three roofline terms vs. TPU v5e peaks (DESIGN/EXPERIMENTS).
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import sys
import time
import warnings

warnings.filterwarnings("ignore")


# v5e hardware constants (per chip).
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link (~per-direction useful)

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2,
}

_COLLECTIVE_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(\w+\[[^\]]*\](?:\([^)]*\))?[^=]*?)"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)


def parse_collective_bytes(hlo_text: str):
    """Sum operand bytes of every collective op in (sharded) HLO text."""
    totals = {}
    # Match lines like: %x = bf16[8,128,512]{...} all-gather(...)
    line_re = re.compile(
        r"=\s*(?:\()?\s*((?:\w+\[[^\]]*\][,\s]*)+)[^=]*?"
        r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)")
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        m = line_re.search(line)
        if not m:
            continue
        shapes, kind = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in shape_re.findall(shapes):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        totals[kind] = totals.get(kind, 0) + nbytes
        totals["total"] = totals.get("total", 0) + nbytes
    return totals


def roofline_terms(flops, hbm_bytes, coll_bytes, n_chips):
    return {
        "compute_s": flops / (n_chips * PEAK_FLOPS),
        "memory_s": hbm_bytes / (n_chips * HBM_BW),
        "collective_s": coll_bytes / (n_chips * ICI_BW),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_hlo_text: bool = False, parallelism: str = "megatron",
             remat: str = "block", tp: int = 0,
             microbatch: int = 0, grad_compress: bool = False):
    import jax
    from repro.launch.mesh import make_mesh, make_production_mesh
    from repro.launch.specs import build_cell
    from repro.configs import get_config, SHAPES
    from repro.configs.base import TrainHParams
    from benchmarks.hlo_analysis import analyze_hlo
    from benchmarks.analytic import memory_bytes

    hp = TrainHParams(remat=remat, parallelism=parallelism,
                      microbatch=microbatch, grad_compress=grad_compress)
    if tp:
        # TP-degree re-factoring (EXPERIMENTS.md §Perf iteration 4): same
        # chip count and physical topology, model axis of size `tp`
        # (ICI-contiguous), the rest data parallel.
        per_pod = 256 // tp
        shape_axes = ((2, per_pod, tp) if multi_pod else (per_pod, tp))
        names = (("pod", "data", "model") if multi_pod
                 else ("data", "model"))
        mesh = make_mesh(shape_axes, names)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        cell = build_cell(arch, shape_name, mesh, hp=hp)
        jitted = jax.jit(
            cell["fn"],
            in_shardings=cell["in_shardings"],
            donate_argnums=cell.get("donate", ()),
        )
        lowered = jitted.lower(*cell["args"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # --- per-device memory: XLA buffer assignment (proves the cell fits).
    mem = compiled.memory_analysis()
    peak = getattr(mem, "peak_memory_in_bytes", None)
    peak_is_estimate = peak is None
    if peak is None:
        # Older jax exposes no true peak; temp+args+out is a loose upper
        # bound (no liveness/buffer-sharing), flagged so consumers don't
        # treat it as the XLA buffer-assignment peak.
        peak = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes)
    mem_rec = {
        "peak": int(peak),
        "peak_is_estimate": peak_is_estimate,
        "args": int(mem.argument_size_in_bytes),
        "out": int(mem.output_size_in_bytes),
        "alias": int(mem.alias_size_in_bytes),
    }

    # --- FLOPs / collective bytes, per chip.
    # XLA's cost_analysis() counts a lax.scan body ONCE (trip count
    # ignored), silently under-reporting scanned stacks by ~n_layers x.
    # hlo_analysis walks the compiled (post-SPMD) HLO with while
    # trip-count multipliers instead; the raw XLA numbers are recorded
    # alongside for reference.
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # older jax wraps it in a list
        cost = cost[0] if cost else {}
    xla_flops = float(cost.get("flops", 0.0))
    colls = {}
    hlo_flops = xla_flops
    dcn_bytes = 0.0
    if not skip_hlo_text:
        hlo = compiled.as_text()
        # Replica-group sizes that span the pod (DCN) boundary on this
        # mesh: any group factorization using the 'pod' axis.  Size-based
        # heuristic — exact for the axis factorizations we lower.
        pod_sizes = ()
        if multi_pod:
            dp_in_pod = mesh.shape.get("data", 1)
            pod_sizes = (2, 2 * dp_in_pod, n_chips)
        res = analyze_hlo(hlo, pod_group_sizes=pod_sizes)
        hlo_flops = res["flops"]
        dcn_bytes = res.get("dcn_bytes", 0.0)
        colls = {k: v for k, v in res["collectives"].items() if v}
        colls["total"] = res["collective_bytes"]

    # --- HBM traffic, per chip: analytic model (cost_analysis 'bytes
    # accessed' has the same scan defect and also counts VMEM-resident
    # reuse; see benchmarks/analytic.py for the derivation).
    mem_model = memory_bytes(arch, shape_name, mesh)

    terms = roofline_terms(hlo_flops * n_chips, mem_model["total"] * n_chips,
                           colls.get("total", 0) * n_chips, n_chips)

    cfg = get_config(arch)
    n_active = cfg.active_param_count()
    shape = SHAPES[shape_name]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "parallelism": parallelism,
        "remat": remat,
        "tp": tp or mesh.shape.get("model", 0),
        "microbatch": microbatch,
        "grad_compress": grad_compress,
        "chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device_bytes": mem_rec,
        "hlo_flops_per_chip": hlo_flops,
        "xla_flops_per_chip": xla_flops,
        "hbm_bytes_per_chip": mem_model["total"],
        "collective_bytes_per_chip": colls,
        "dcn_bytes_per_chip": dcn_bytes,
        "model_flops": model_flops,
        "useful_flops_frac": (model_flops / (hlo_flops * n_chips))
                             if hlo_flops else None,
        **terms,
    }
    terms_only = {k: rec[k] for k in
                  ("compute_s", "memory_s", "collective_s")}
    rec["bottleneck"] = max(terms_only, key=terms_only.get)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--parallelism", choices=["megatron", "auto", "fsdp"],
                    default="megatron")
    ap.add_argument("--remat", choices=["none", "block", "save_collectives"],
                    default="save_collectives")
    ap.add_argument("--tp", type=int, default=0,
                    help="re-factor the 256-chip pod as (256/tp) x tp")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.configs import SHAPES, list_archs

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = (f"{arch} × {shape} × "
                         f"{'2x16x16' if mp else '16x16'}"
                         f"{' × fsdp' if args.parallelism == 'fsdp' else ''}")
                print(f"=== {label}", flush=True)
                try:
                    rec = run_cell(arch, shape, mp,
                                   parallelism=args.parallelism,
                                   remat=args.remat, tp=args.tp,
                                   microbatch=args.microbatch,
                                   grad_compress=args.grad_compress)
                    print(json.dumps(rec, default=str), flush=True)
                    if args.out:
                        with open(args.out, "a") as f:
                            f.write(json.dumps(rec, default=str) + "\n")
                except Exception as e:  # noqa: BLE001 — report and continue
                    failures.append((label, repr(e)))
                    print(f"FAILED {label}: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for lab, err in failures:
            print(" ", lab, err[:200])
        sys.exit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
