"""Fused hash-partition Pallas TPU kernels — the paper's dispatch hot spot.

Storage-time partitioning (Alg. 3 line 13-14) is a streaming pass over every
object: hash the partition key, take ``% m``, and histogram the destinations
so the store can size per-partition buffers.  Fusing hash + mod + histogram
into one VMEM-resident pass makes the producer-side overhead (paper Tab. 3:
≤10%) bandwidth-bound rather than kernel-launch-bound.

Three kernels (DESIGN §5):

* :func:`hash_partition` — hash + mod + histogram over exactly-sized keys
  (``n`` static; padding tail masked out of the histogram).
* :func:`hash_partition_padded` — the same pass over a shape-bucketed buffer
  with a *dynamic* valid count delivered via scalar prefetch; padding rows
  are assigned an overflow partition ``m`` so the counting sort places them
  past the valid region.  This is what lets one jitted dispatch plan serve
  every N in a shape bucket without retracing.
* :func:`scatter_perm` — the counting-sort scatter stage: consume
  ``(pids, counts)`` and emit the destination permutation directly — an
  O(N) *stable* placement replacing the O(N log N) ``argsort`` the
  re-bucket used to pay.

Tiling: grid over key blocks; each step processes a (block,) tile in VMEM
and carries per-partition state ((m,) histogram / running offsets) in VMEM
scratch across steps (the grid dim is sequential on TPU).

Mosaic constraints the kernels are written around: a boolean vector cannot
be reshaped to a column (masks are applied to int32 pids, never to a
``[:, None]`` bool), and ``cumsum`` has no TPU lowering — the base offsets
are an exclusive prefix sum taken in the wrapper, and the within-block
stable rank is a strictly-lower-triangular matmul on the MXU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 2048


def _wang(x):
    """Wang hash (matches ref.wang_hash / core.ir._mix_hash)."""
    x = x.astype(jnp.uint32)
    x = (x ^ jnp.uint32(61)) ^ (x >> 16)
    x = x * jnp.uint32(9)
    x = x ^ (x >> 4)
    x = x * jnp.uint32(0x27D4EB2D)
    x = x ^ (x >> 15)
    return x


def _kernel(keys_ref, pids_ref, counts_ref, hist_ref, *,
            num_partitions: int, block: int, n_valid: int):
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    x = _wang(keys_ref[...])
    pid = (x % jnp.uint32(num_partitions)).astype(jnp.int32)
    pids_ref[...] = pid

    # padding tail → sentinel m, which matches no histogram column
    pos = i * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
    pid = jnp.where(pos < n_valid, pid, num_partitions)
    onehot = (pid[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, num_partitions), 1))
    hist_ref[...] += onehot.astype(jnp.int32).sum(axis=0)

    @pl.when(i == nb - 1)
    def _flush():
        counts_ref[...] = hist_ref[...]


def hash_partition(keys: jax.Array, num_partitions: int, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """keys: (N,) integer → (pids (N,) int32, counts (m,) int32)."""
    n = keys.shape[0]
    block = min(block, max(8, n))
    pad = (-n) % block
    if pad:
        keys = jnp.pad(keys, (0, pad))
    nb = keys.shape[0] // block

    kernel = functools.partial(_kernel, num_partitions=num_partitions,
                               block=block, n_valid=n)
    pids, counts = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                   pl.BlockSpec((num_partitions,), lambda i: (0,))],
        out_shape=[jax.ShapeDtypeStruct((keys.shape[0],), jnp.int32),
                   jax.ShapeDtypeStruct((num_partitions,), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((num_partitions,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hash_partition",
    )(keys)
    return pids[:n], counts


# ---------------------------------------------------------------------------
# Dynamic-n variant: shape-bucketed keys + scalar-prefetched valid count
# ---------------------------------------------------------------------------

def _kernel_padded(n_ref, keys_ref, pids_ref, counts_ref, hist_ref, *,
                   num_partitions: int, block: int):
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    x = _wang(keys_ref[...])
    pid_raw = (x % jnp.uint32(num_partitions)).astype(jnp.int32)
    # padding rows → overflow partition m, so the counting sort that consumes
    # these pids stably parks them *after* every valid row
    pos = i * block + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
    pid = jnp.where(pos < n_ref[0], pid_raw, num_partitions)
    pids_ref[...] = pid

    onehot = (pid[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (block, num_partitions + 1), 1))
    hist_ref[...] += onehot.astype(jnp.int32).sum(axis=0)

    @pl.when(i == nb - 1)
    def _flush():
        counts_ref[...] = hist_ref[...]


def hash_partition_padded(keys: jax.Array, n_valid: jax.Array,
                          num_partitions: int, *,
                          block: int = DEFAULT_BLOCK,
                          interpret: bool = False
                          ) -> Tuple[jax.Array, jax.Array]:
    """keys: (B,) integer, n_valid: () int32 dynamic →
    (pids (B,) int32 with padding → m, counts (m+1,) int32).

    B must already be a multiple-friendly bucket size (the caller pads); the
    valid count arrives via scalar prefetch so one compiled plan serves every
    N ≤ B without retracing.
    """
    B = keys.shape[0]
    block = min(block, max(8, B))
    assert B % block == 0, "block size must divide the bucketed key count"
    nb = B // block
    m1 = num_partitions + 1

    kernel = functools.partial(_kernel_padded, num_partitions=num_partitions,
                               block=block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i, n_ref: (i,))],
        out_specs=[pl.BlockSpec((block,), lambda i, n_ref: (i,)),
                   pl.BlockSpec((m1,), lambda i, n_ref: (0,))],
        scratch_shapes=[pltpu.VMEM((m1,), jnp.int32)],
    )
    pids, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B,), jnp.int32),
                   jax.ShapeDtypeStruct((m1,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="hash_partition_padded",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), keys)
    return pids, counts


# ---------------------------------------------------------------------------
# Counting-sort scatter: (pids, counts) → destination permutation, O(N)
# ---------------------------------------------------------------------------

RANK_TILE = 128      # rows per MXU rank tile (one 128x128 MXU pass)


def _perm_kernel(pids_ref, offs_ref, dest_ref, run_ref, *, block: int,
                 tile: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        run_ref[...] = offs_ref[...]

    lanes = run_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, lanes), 1)
    # strictly-lower-triangular ones: (tri @ onehot)[r, p] counts the rows
    # above r in the tile with pid p.  0/1 entries are exact in bf16 and the
    # f32 accumulation is exact up to 2^24, far above the tile height.
    tri = (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
           ).astype(jnp.float32).astype(jnp.bfloat16)
    run = run_ref[...]             # (1, lanes): next free slot per partition
    for t in range(block // tile):
        pid = pids_ref[pl.ds(t * tile, tile)]                   # (tile,)
        onehot = pid[:, None] == cols
        oh = onehot.astype(jnp.int32)
        above = jnp.dot(tri, oh.astype(jnp.float32).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        dest_ref[pl.ds(t * tile, tile)] = jnp.where(
            onehot, above.astype(jnp.int32) + run, 0).sum(axis=1)
        run = run + oh.sum(axis=0, keepdims=True)
    run_ref[...] = run


def scatter_perm(pids: jax.Array, counts: jax.Array, *,
                 block: int = DEFAULT_BLOCK,
                 interpret: bool = False) -> jax.Array:
    """(pids (N,) int32, counts (m,) int32) → dest (N,) int32.

    ``dest[i]`` = position of row i in the stable sort of ``pids`` — the
    counting-sort placement (base offset from the exclusive prefix sum of
    ``counts`` + running per-partition fill + within-tile stable rank).
    O(N·m) one-hot work with no sort; sentinel pids outside [0, m) get dest
    0 without perturbing any real row's slot (their one-hot row is empty).
    Blocks above :data:`RANK_TILE` rows round up to a multiple of it.
    """
    n = pids.shape[0]
    if n == 0:
        return jnp.zeros(0, jnp.int32)
    m = counts.shape[0]
    block = min(block, max(8, n))
    if block > RANK_TILE:
        block = -(-block // RANK_TILE) * RANK_TILE
    tile = min(block, RANK_TILE)
    pids = pids.astype(jnp.int32)
    # out-of-range pids (sentinels, padding) → -1: no lane ever matches it
    pids = jnp.where((pids >= 0) & (pids < m), pids, -1)
    pad = (-n) % block
    if pad:
        pids = jnp.pad(pids, (0, pad), constant_values=-1)
    nb = pids.shape[0] // block
    # exclusive prefix sum of the histogram → per-partition base offsets,
    # lane-padded to a multiple of 128
    lanes = -(-m // 128) * 128
    counts = counts.astype(jnp.int32)
    offs = jnp.zeros((1, lanes), jnp.int32).at[0, :m].set(
        jnp.cumsum(counts) - counts)

    kernel = functools.partial(_perm_kernel, block=block, tile=tile)
    dest = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)),
                  pl.BlockSpec((1, lanes), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((pids.shape[0],), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="scatter_perm",
    )(pids, offs)
    return dest[:n]
