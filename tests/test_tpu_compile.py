"""The served path's Pallas kernels compiled for a described TPU v5e.

No chip is attached: the TPU compiler builds each kernel for a v5e that is
described, not present, at real widths (qwen2.5-3b's KV geometry: 64-token
pages, 16-page frames, 2 KV heads of 128; mamba2-1.3b's SSD scan), with
``interpret=False``.  That catches what interpret mode cannot: block
shapes the TPU's tiling refuses and blocks that overflow scoped VMEM.
Nothing runs, so nothing here is a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest

B, H, N_KV, DH = 8, 16, 2, 128           # qwen2.5-3b decode attention
PTOK, FP, NP, NBLK = 64, 16, 256, 16     # page tokens, frame pages, pool
SSD = dict(B=1, T=1024, nh=64, hd=64, N=128, chunk=256)   # mamba2-1.3b


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape(topo, no_cache):
    sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=sharding)


def _cases(S):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.page_compact import (page_compact, page_gather,
                                            page_scatter)
    from repro.kernels.paged_attention import (fused_paged_attention_kernel,
                                               paged_attention_kernel)
    from repro.kernels.ssd_scan import ssd_scan

    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    pool = S((NP, PTOK, N_KV, DH), bf16)
    idx = S((NBLK,), i32)
    q = S((B, H, DH), bf16)
    table = S((B, NBLK), i32)

    def paged(gran, nblk):
        return (lambda q, k, v, t, n: paged_attention_kernel(
            q, k, v, t, n, granularity=gran, frame_pages=FP, scale=0.1),
            (q, pool, pool, S((B, nblk), i32), S((B, nblk), i32)))

    s = SSD
    return {
        "page_gather": (page_gather, (pool, idx)),
        "page_scatter": (page_scatter,
                         (pool, idx, S((NBLK, PTOK, N_KV, DH), bf16))),
        "page_compact": (page_compact, (pool, idx, idx)),
        "paged_attention_page": paged("page", NBLK),
        "paged_attention_frame": paged("frame", 1),
        "fused_paged_attention": (
            lambda *a: fused_paged_attention_kernel(*a, scale=0.1),
            (q, pool, pool, S((NBLK, PTOK, N_KV, DH), bf16),
             S((NBLK, PTOK, N_KV, DH), bf16), table, table, table)),
        "flash_attention": (flash_attention,
                            (S((1, 512, H, DH), bf16),
                             S((1, 512, N_KV, DH), bf16),
                             S((1, 512, N_KV, DH), bf16))),
        "ssd_scan": (
            lambda x, dt, A, Bm, Cm: ssd_scan(x, dt, A, Bm, Cm,
                                              chunk=s["chunk"]),
            (S((s["B"], s["T"], s["nh"], s["hd"]), bf16),
             S((s["B"], s["T"], s["nh"]), f32), S((s["nh"],), f32),
             S((s["B"], s["T"], s["nh"], s["N"]), bf16),
             S((s["B"], s["T"], s["nh"], s["N"]), bf16))),
    }


@pytest.mark.parametrize("name", [
    "page_gather", "page_scatter", "page_compact", "paged_attention_page",
    "paged_attention_frame", "fused_paged_attention", "flash_attention",
    "ssd_scan"])
def test_kernel_compiles_for_v5e(shape, name):
    fn, args = _cases(shape)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_kv,dh", [
    (8, 128),      # qwen3-8b, llama3-8b, llava-next-mistral-7b, dbrx-132b
    (32, 96),      # phi3-mini-3.8b: the widest KV row of the catalog
    (32, 64),      # zamba2-1.2b
])
@pytest.mark.parametrize("gran", ["page", "frame"])
def test_paged_attention_compiles_at_other_catalog_geometries(shape, gran,
                                                               n_kv, dh):
    """The step size was chosen at qwen2.5-3b's geometry; the other KV
    geometries of the catalog must compile with it too."""
    from repro.kernels.paged_attention import paged_attention_kernel
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = shape((NP, PTOK, n_kv, dh), bf16)
    nblk = NBLK if gran == "page" else 1
    compiled = jax.jit(lambda q, k, v, t, n: paged_attention_kernel(
        q, k, v, t, n, granularity=gran, frame_pages=FP, scale=0.1)).lower(
        shape((B, n_kv, dh), bf16), pool, pool, shape((B, nblk), i32),
        shape((B, nblk), i32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_frame_path_reads_a_frame_in_vmem_sized_slices():
    """At real widths one 1024-token frame overflows a step's VMEM (the
    v5e compiler refused 64.61M of scoped VMEM for it), so the frame is
    read in 128-token slices; the small frames of the interpret-mode
    tests still go in one step."""
    from repro.kernels.paged_attention import _step_pages
    assert _step_pages(FP, PTOK) == 2
    assert _step_pages(4, 16) == 4
    assert _step_pages(1, 256) == 1
