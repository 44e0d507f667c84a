"""Serving launcher: ``python -m repro.launch.serve --arch <id> ...``

Stands up the multi-tenant continuous-batching engine on the Mosaic pool
and replays a synthetic request stream (or reads prompts from a token
file). ``--manager gpu-mmu`` flips to the baseline allocator for A/B.
``--engines N`` serves the stream from a cluster of N engine replicas
over one shared host tier, with the deadline-aware router dispatching
(and, unless ``--no-migrate``, work-stealing) across them — DESIGN.md
§10; outputs are byte-identical to the single-engine run.

CPU example (smoke-scale):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --smoke --requests 8 --max-new 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --smoke --requests 8 --max-new 8 --engines 2
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.configs.base import PoolGeometry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--manager", default="mosaic",
                    choices=["mosaic", "gpu-mmu"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-tokens", type=int, default=None,
                    help="default: 8 for --smoke, 64 otherwise")
    ap.add_argument("--frame-pages", type=int, default=None,
                    help="default: 4 for --smoke, 16 otherwise")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engines", type=int, default=1,
                    help="engine replicas over one shared host tier "
                         "(cluster tier + deadline router, DESIGN.md §10)")
    ap.add_argument("--router", default="slack",
                    choices=["slack", "fifo"],
                    help="cluster dispatch policy (with --engines > 1)")
    ap.add_argument("--no-migrate", action="store_true",
                    help="disable work-stealing migration between "
                         "replicas (with --engines > 1)")
    ap.add_argument("--translation", default="off",
                    choices=["off", "flat", "radix"],
                    help="meter KV page translations through the "
                         "coalesced-TLB + radix-walker model "
                         "(DESIGN.md §15); prints a per-app "
                         "translation-cycle summary line")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.cluster import ServingCluster
    from repro.serving.engine import Request, ServingEngine

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(
        args.arch)
    geo = PoolGeometry(
        page_tokens=args.page_tokens or (8 if args.smoke else 64),
        frame_pages=args.frame_pages or (4 if args.smoke else 16))
    if args.engines > 1:
        eng = ServingCluster(cfg, geometry=geo, n_engines=args.engines,
                             max_batch=args.max_batch,
                             max_seq=args.max_seq,
                             manager_kind=args.manager, seed=args.seed,
                             router_policy=args.router,
                             migrate=not args.no_migrate,
                             translation=args.translation)
    else:
        eng = ServingEngine(cfg, geometry=geo, max_batch=args.max_batch,
                            max_seq=args.max_seq,
                            manager_kind=args.manager, seed=args.seed,
                            translation=args.translation)
    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        T = int(rng.integers(8, max(9, args.max_seq // 2)))
        r = Request(rid=i, tenant=i % 3,
                    prompt=rng.integers(0, cfg.vocab_size, T).astype(
                        np.int32),
                    max_new=args.max_new)
        reqs.append(r)
        eng.submit(r)
    steps = eng.run_until_drained()
    if args.engines > 1:
        print(f"[{args.manager}] {len(reqs)} requests in {steps} "
              f"cluster steps")
        print(eng.stats().summary())
    else:
        st = eng.cache.stats()
        print(f"[{args.manager}] {len(reqs)} requests in {steps} steps | "
              f"{eng.stats.tok_per_s():.1f} tok/s (this host) | "
              f"coalesced {eng.stats.coalesced_mean:.1%} | "
              f"CAC copies {eng.stats.compaction_copies} | "
              f"bloat {st.get('memory_bloat', 1.0):.2f}")
    if args.translation != "off":
        engines = eng.engines if args.engines > 1 else [eng]
        for e in engines:
            print(f"  engine[{e.engine_id}] "
                  f"{e.translation_meter.summary()}")
    for r in reqs[:4]:
        print(f"  rid={r.rid} tenant={r.tenant} -> {r.out[:10]}")


if __name__ == "__main__":
    main()
