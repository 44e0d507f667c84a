"""Serve qwen2.5-3b at full published width on a TPU through ServingEngine.

    python chip_smoke.py [--seed N]   # one chip: both managers, Pallas on/off
    python chip_smoke.py --chips 4    # 4 one-chip replicas vs one engine

Weights are random from ``--seed``; nothing is downloaded.  Sixteen
requests over three tenants (prompts of 64 to 512 tokens, 16 new tokens
each, one arrival per engine step, tenant t at priority t) go through the
engine with its KV pool 2x oversubscribed, so higher-priority arrivals
evict lower-priority requests' pages to the host tier, and those pages
are faulted back in when the requests resume.  The run fails (exit
code != 0, no result line) where JAX finds no TPU, and on any failed
check.  Its last stdout line is the JSON result.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import PoolGeometry  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.cluster import ServingCluster  # noqa: E402
from repro.serving.engine import Request, ServingEngine  # noqa: E402

ARCH = "qwen2.5-3b"
GEO = PoolGeometry(page_tokens=64, frame_pages=16)
ENGINE_KW = dict(max_batch=8, max_seq=1024, oversubscription=2.0)
N_REQUESTS, N_TENANTS, MAX_NEW = 16, 3, 16
PROMPT_TOKENS = (64, 512)


class _Compiles:
    """Backend compilations JAX reports, with their seconds."""

    def __init__(self):
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs


def check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def differing(a: dict, b: dict, what: str) -> str:
    """'' where two runs' tokens are identical, else which requests part
    and at which token index."""
    diff = sorted(rid for rid in a if a[rid] != b.get(rid))
    if not diff:
        return ""
    first = {rid: next(i for i, (x, y) in enumerate(zip(a[rid], b[rid]))
                       if x != y) for rid in diff}
    return (f"{what} (requests differing, first differing token index: "
            f"{first})")


def requests(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_TOKENS
    return [Request(rid=i, tenant=i % N_TENANTS, priority=i % N_TENANTS,
                    prompt=rng.integers(0, vocab, int(rng.integers(lo, hi + 1))
                                        ).astype(np.int32),
                    max_new=MAX_NEW)
            for i in range(N_REQUESTS)]


def _watch_logits(eng, finite: list) -> None:
    """Record, per prefill and decode call, whether its logits are finite."""
    for name in ("_decode_jit", "_prefill_jit"):
        fn = getattr(eng, name)

        def watched(*args, _fn=fn, **kw):
            out = _fn(*args, **kw)
            finite.append(jnp.isfinite(out[0]).all())
            return out
        setattr(eng, name, watched)


def _peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def serve(target, engines, reqs, compiles: _Compiles, label: str) -> dict:
    """Drain ``reqs`` through ``target`` (an engine or a cluster), check
    that every request finished with finite logits, print a summary."""
    finite: list = []
    for e in engines:
        _watch_logits(e, finite)
    n0, s0 = compiles.n, compiles.s
    t0 = time.perf_counter()
    for r in reqs:
        target.submit(r)
        target.step()
    target.run_until_drained()
    wall = time.perf_counter() - t0
    check(all(r.done and len(r.out) == MAX_NEW for r in reqs),
          f"{label}: every request finishes with {MAX_NEW} tokens")
    check(finite and all(bool(f) for f in finite),
          f"{label}: all logits finite")
    faults = sum(e.stats.faults for e in engines)
    dmas = sum(e.stats.fault_dmas for e in engines)
    coal = float(np.mean([e.stats.coalesced_mean for e in engines]))
    peak = max(_peak_bytes(e.device) for e in engines)
    print(f"[{label}] {len(reqs)} requests finished, "
          f"{sum(len(r.out) for r in reqs)} tokens | faults {faults} in "
          f"{dmas} DMAs | coalesced {coal:.1%} | "
          f"{compiles.n - n0} compilations ({compiles.s - s0:.1f} s) | "
          f"wall {wall:.1f} s | peak_bytes_in_use {peak}", flush=True)
    return {"tokens": {r.rid: tuple(r.out) for r in reqs}, "faults": faults}


def one_chip(cfg, seed: int, compiles: _Compiles) -> None:
    """Both managers with compiled Pallas page movers, then the XLA movers
    under one manager; tokens must agree across all three."""
    params, runs = None, {}
    for label, manager, pallas in (("mosaic", "mosaic", True),
                                   ("gpu-mmu", "gpu-mmu", True),
                                   ("mosaic/xla-movers", "mosaic", False)):
        eng = ServingEngine(cfg, geometry=GEO, manager_kind=manager,
                            params=params, seed=seed, use_pallas=pallas,
                            **ENGINE_KW)
        params = eng.params
        runs[label] = serve(eng, [eng], requests(cfg.vocab_size, seed),
                            compiles, label)
        check(runs[label]["faults"] > 0,
              f"{label}: the oversubscribed pool faults pages back in")
        del eng
    parted = [differing(runs["mosaic"]["tokens"], runs[label]["tokens"],
                        what) for label, what in (
        ("gpu-mmu", "tokens byte-identical across managers"),
        ("mosaic/xla-movers",
         "tokens byte-identical with Pallas and XLA page movers"))]
    check(not any(parted), "; ".join(p for p in parted if p))


def four_chips(cfg, seed: int, compiles: _Compiles) -> None:
    """A 4-replica cluster (one replica per device, slack router,
    migration on) against one engine on the same stream."""
    devices = jax.devices()
    check(len(devices) >= 4, f"4 devices (found {len(devices)})")
    eng = ServingEngine(cfg, geometry=GEO, seed=seed, use_pallas=True,
                        **ENGINE_KW)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    single = serve(eng, [eng], requests(cfg.vocab_size, seed), compiles,
                   "1 engine")
    del eng
    gc.collect()
    cluster = ServingCluster(cfg, geometry=GEO, n_engines=4, seed=seed,
                             router_policy="slack", migrate=True,
                             use_pallas=True, **ENGINE_KW)
    for i, e in enumerate(cluster.engines):
        held = {d for x in jax.tree.leaves((e.params, e.pools))
                for d in x.devices()}
        check(held == {devices[i]}, f"engine {i} held on device {i}")
    multi = serve(cluster, cluster.engines,
                  requests(cfg.vocab_size, seed), compiles, "4 engines")
    parted = differing(multi["tokens"], single["tokens"],
                       "tokens byte-identical between 4 replicas and 1 engine")
    check(not parted, parted)
    for i, e in enumerate(cluster.engines):
        peak = _peak_bytes(e.device)
        print(f"  device {i}: {e.stats.decode_tokens} decode tokens, "
              f"peak_bytes_in_use {peak}", flush=True)
        check(peak > param_bytes,
              f"device {i} peak {peak} above the {param_bytes} param bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    print(f"platform {dev.platform} | kind {dev.device_kind} | "
          f"devices {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compiles = _Compiles()
    cfg = get_config(ARCH)
    if args.chips == 4:
        four_chips(cfg, args.seed, compiles)
    else:
        one_chip(cfg, args.seed, compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
