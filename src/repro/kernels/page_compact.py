"""On-device base-page movement kernels: CAC compaction + host-tier paging.

``page_compact`` executes a compaction plan (pool-internal copies);
``page_gather``/``page_scatter`` are the device halves of demand paging
(DESIGN.md §6): gather packs the evicted pages of a preempted request into
a dense staging block the host reads back, scatter lands a fault batch's
payload at the faulted pages' physical locations.

CAC page-copy kernel: batched on-device base-page migration.

Executes a compaction plan's ``CopyOp`` list in one launch: grid over the
copy list; each step DMAs one base page pool[src[i]] → pool[dst[i]] through
VMEM, with both sides addressed via scalar-prefetched index maps.  Holes
(src/dst = -1) are rewritten to a *duplicate* of the first valid copy op —
duplicates are idempotent because CAC only copies live pages into free
slots (a src page is never also a dst page), so the kernel stays total
without the hole ever clobbering a real destination.  If every op is a
hole, the plan degenerates to copying page 0 onto itself, which is safe
precisely because then nothing else writes.

The paper models compaction as a whole-GPU stall (worst case); this kernel
is the real cost: len(plan) page-sized DMAs, overlappable between decode
steps.  ``benchmarks/kernel_bench.py`` measures it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _copy_kernel(src_ref, dst_ref, pool_in_ref, pool_out_ref):
    pool_out_ref[...] = pool_in_ref[...]


def page_compact(pool, src, dst, *, interpret: bool = False):
    """pool [NP, ptok, kv, dh]; src/dst int32 [n].  Returns updated pool.

    input_output_aliasing keeps this in-place on the real device: only the
    touched pages move.
    """
    n = src.shape[0]
    if n == 0:
        return pool
    NP = pool.shape[0]
    blk = (1, *pool.shape[1:])

    # Rewrite holes to duplicates of the first valid op (see module doc).
    valid = (src >= 0) & (dst >= 0)
    first = jnp.argmax(valid)                      # 0 when no valid op
    any_valid = jnp.any(valid)
    src = jnp.where(valid, src, jnp.where(any_valid, src[first], 0))
    dst = jnp.where(valid, dst, jnp.where(any_valid, dst[first], 0))

    def in_index(i, src, dst):
        return (src[i], *([0] * (len(blk) - 1)))

    def out_index(i, src, dst):
        return (dst[i], *([0] * (len(blk) - 1)))

    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[pl.BlockSpec(blk, in_index)],
            out_specs=pl.BlockSpec(blk, out_index),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(src, dst, pool)
    if interpret:
        # The interpreter does not emulate in-place aliasing of unwritten
        # output blocks; merge untouched pages back (TPU path skips this).
        touched = (jnp.zeros((NP,), jnp.int32).at[jnp.maximum(dst, 0)]
                   .add((dst >= 0).astype(jnp.int32))) > 0
        out = jnp.where(touched[:, None, None, None], out, pool)
    return out


def _gather_kernel(idx_ref, pool_in_ref, out_ref):
    out_ref[...] = pool_in_ref[...]


def page_gather(pool, idx, *, interpret: bool = False):
    """pool [NP, ptok, kv, dh]; idx int32 [n] → pages [n, ptok, kv, dh].

    One page-sized DMA per grid step, both sides scalar-prefetch-addressed;
    holes (idx = -1) read page 0 (caller masks them out).  The dense output
    block is what the host copies back over the I/O link at eviction time.
    """
    n = idx.shape[0]
    blk = (1, *pool.shape[1:])
    idx = jnp.maximum(idx, 0)

    def in_index(i, idx):
        return (idx[i], *([0] * (len(blk) - 1)))

    def out_index(i, idx):
        return (i, *([0] * (len(blk) - 1)))

    return pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec(blk, in_index)],
            out_specs=pl.BlockSpec(blk, out_index),
        ),
        out_shape=jax.ShapeDtypeStruct((n, *pool.shape[1:]), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, pool)


def _scatter_kernel(idx_ref, row_ref, pages_ref, pool_in_ref, pool_out_ref):
    pool_out_ref[...] = pages_ref[...]


def page_scatter(pool, idx, pages, *, interpret: bool = False):
    """pool [NP, ...]; idx int32 [n]; pages [n, ...] → pool'.

    pool'[idx[i]] = pages[i].  Holes (idx = -1) are rewritten to duplicates
    of the first valid entry — idempotent because duplicates write the same
    payload to the same destination.  Aliased in-place on the real device.
    """
    n = idx.shape[0]
    if n == 0:
        return pool
    NP = pool.shape[0]
    blk = (1, *pool.shape[1:])

    valid = idx >= 0
    first = jnp.argmax(valid)
    any_valid = jnp.any(valid)
    safe_idx = jnp.where(valid, idx, jnp.where(any_valid, idx[first], 0))
    src_row = jnp.where(valid, jnp.arange(n),
                        jnp.where(any_valid, first, 0))

    def pages_index(i, safe_idx, src_row):
        return (src_row[i], *([0] * (len(blk) - 1)))

    def out_index(i, safe_idx, src_row):
        return (safe_idx[i], *([0] * (len(blk) - 1)))

    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            # The aliased pool input still needs a spec; its block is the
            # destination page the kernel overwrites, never read.
            in_specs=[pl.BlockSpec(blk, pages_index),
                      pl.BlockSpec(blk, out_index)],
            out_specs=pl.BlockSpec(blk, out_index),
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(safe_idx, src_row, pages, pool)
    if interpret:
        # Same interpreter aliasing caveat as page_compact.
        touched = (jnp.zeros((NP,), jnp.int32).at[jnp.maximum(idx, 0)]
                   .add(valid.astype(jnp.int32))) > 0
        extra = (1,) * (pool.ndim - 1)
        out = jnp.where(touched.reshape(-1, *extra), out, pool)
    else:
        # All-holes degenerate case: the rewrite above aimed every write at
        # page 0, which must then be restored (the oracle treats holes as
        # no-ops).  One-page fixup, traceable under jit.
        out = out.at[0].set(jnp.where(any_valid, out[0], pool[0]))
    return out
