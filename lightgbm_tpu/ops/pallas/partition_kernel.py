"""Pallas TPU kernel of the compact core's tile partition: a stable 3-way
reorder of one tile of packed rows by its keys, as lane compactions.

The packed table, every window and every tile of the compact core's
partition are rows-minor (`models/device_learner.py::_rows_minor`): a
(rows, words) u32 tile is, in memory, a (words, rows) array with the rows
along the 128 lanes and the words along sublanes. Seen so (a bitcast,
not a transpose), a stable two-way partition moves whole lanes, and is
the same lane compaction for every word. XLA's `.at[dest].set` of the
same reorder is a row scatter, which the TPU compiler sorts by index and
runs on a words-minor relayout of the tile (512 B for a 44-byte row).

Each 128-lane block of the tile is compacted alone, for class 0 and for
class 1, on vregs of lane numbers that hold one block a sublane (the
BLOCK_LANES // 128 blocks of a grid step at once): a row of the class
moves down by `d`, the count of the block's rows of other classes
before it, in seven steps of static lane rolls and selects, LSB first
(at step k the rows whose bit k of `d` is set move down by 2**k; no two
rows ever meet, and the order is kept). The lanes so compacted, rolled
to the block's running offset (the rows of its class in the blocks
before it, from a prefix over the blocks that the wrapper computes),
gather the block's words once a class (a lane permutation a vreg), and
masked stores write them into the one or two output columns they span.
The class-0 rows go to [0, c0) and the class-1 rows to [c0, c0 + c1);
the key-2 rows must be the tile's tail (both callers key as 2 the rows
past the leaf's count, `arange >= pcount`), and stay where they are. So
the result is bit for bit `_scan_partition`'s. The network's cost does
not grow with the row's width; a word costs a gather and its stores.

Layouts (one grid step a BLOCK_LANES-lane slab of the tile):

    offs  (2 * (nb + 1),) i32 -> SMEM (scalar prefetch): the running
                                 offsets of classes 0 and 1 by block
    x     (words, rows) u32   -> block (words, BLOCK_LANES)
    keys  (nb, 128) i32       -> block (BLOCK_LANES // 128, 128): one
                                 block's keys a sublane
    out   (words, rows) u32   -> the whole array, resident in VMEM
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_LANES = 2048
# The kernel holds its whole output in VMEM (double-buffered), and one
# input slab (double-buffered): a tile whose buffers pass this budget
# goes to XLA's scatter instead (`engaged`).
VMEM_BUDGET = 48 << 20


def _vmem_bytes(rows: int, words: int) -> int:
    padded = -(-rows // BLOCK_LANES) * BLOCK_LANES
    sublanes = -(-words // 8) * 8
    return 4 * (2 * sublanes * padded + 2 * sublanes * BLOCK_LANES
                + 2 * BLOCK_LANES)


def engaged(platform: str, rows: int, words: int) -> bool:
    """Whether the kernel partitions a (rows, words) tile of a program
    lowered for `platform`: the one selection, which the tree program's
    `_partition_tile` and the learner's counter `partition_kernel_rows`
    both call. A Mosaic kernel runs on the TPU alone, and its buffers
    have to fit the VMEM budget."""
    return (platform == "tpu"
            and _vmem_bytes(rows, words) + (4 << 20) <= VMEM_BUDGET)


def _compact_kernel(offs_ref, x_ref, k_ref, o_ref, *, nb: int):
    g = pl.program_id(0)
    words, sub = x_ref.shape[0], x_ref.shape[1] // LANES
    last = (x_ref.shape[1] * pl.num_programs(0)) // LANES - 1
    # one sublane a 128-row block of the step: the lane rolls below act
    # on every block at once and never cross from one block to the next
    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, LANES), 1)
    k = k_ref[...]
    # class-0 rows up to each lane; a row moves down by the rows of other
    # classes before it in its block: for class 0 the lanes before it
    # less the class-0 rows, for class 1 the class-0 rows (the key-2 rows
    # come after every other)
    p = (k == 0).astype(jnp.int32)
    for s in (1, 2, 4, 8, 16, 32, 64):
        p = p + jnp.where(lane >= s, pltpu.roll(p, s, 1), 0)
    src = []
    for c, d in ((0, lane + 1 - p), (1, p)):
        # bits 0-6 how far the row still moves, 8-14 the lane it came
        # from: the network moves these alone, and each block's words
        # are gathered once by the lanes it leaves
        v = jnp.where(k == c, d, 0) | (lane << 8)
        for bit in range(7):
            v_in = pltpu.roll(v, LANES - (1 << bit), 1)
            moves_in = (v_in & (1 << bit)) != 0
            moves_out = (v & (1 << bit)) != 0
            v = jnp.where(moves_in, v_in, jnp.where(moves_out, 0, v))
        src.append(v >> 8)

    # each (8, 128) array below holds one row of a block, replicated over
    # the sublanes, so that a mask made from it serves every vreg of the
    # (words, 128) rows as it is
    lane8 = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)

    def spread(a):
        return jnp.concatenate([a] * -(-words // 8), axis=0)[:words]

    def row(a, j):
        return jnp.broadcast_to(a[j:j + 1], (8, LANES))

    for j in range(sub):
        b = g * sub + j
        x = x_ref[:, j * LANES:(j + 1) * LANES]
        # the key-2 rows stay where they are
        pltpu.store(o_ref.at[:, pl.ds(pl.multiple_of(b * LANES, LANES),
                                      LANES)],
                    x, mask=spread(row(k, j) == 2))
        for c in (0, 1):
            lo = offs_ref[c * (nb + 1) + b]
            cnt = offs_ref[c * (nb + 1) + b + 1] - lo
            r = lo & (LANES - 1)
            t = lo >> 7                      # lo // LANES
            # the source lane of each output lane, rolled to the running
            # offset: rows [r, r + cnt) of column t and what passes the
            # column's end in the next (none at the last column)
            at = spread(pltpu.roll(row(src[c], j), r, 1))
            y = jnp.take_along_axis(x, at, axis=1, mode="promise_in_bounds")
            pltpu.store(o_ref.at[:, pl.ds(pl.multiple_of(t * LANES, LANES),
                                          LANES)],
                        y, mask=spread((lane8 >= r) & (lane8 < r + cnt)))
            nxt = jnp.minimum(t + 1, last)
            pltpu.store(o_ref.at[:, pl.ds(pl.multiple_of(nxt * LANES, LANES),
                                          LANES)],
                        y, mask=spread(lane8 < r + cnt - LANES))


def partition_tile(tile: jax.Array, key3: jax.Array, *,
                   interpret: bool = False):
    """A rows-minor (rows, words) u32 tile in the stable 3-way order of
    `key3` (0, 1, 2; the 2s the tile's tail), and the counts of classes
    0 and 1: what `_scan_partition` returns, bit for bit."""
    rows, words = tile.shape
    padded = -(-rows // BLOCK_LANES) * BLOCK_LANES
    nb = padded // LANES
    x = tile.T                           # rows-minor: a bitcast
    k = key3.astype(jnp.int32)
    if padded != rows:
        x = jnp.pad(x, ((0, 0), (0, padded - rows)))
        k = jnp.pad(k, (0, padded - rows), constant_values=2)
    k = k.reshape(nb, LANES)
    zero = jnp.zeros((1,), jnp.int32)
    off0 = jnp.concatenate(
        [zero, jnp.cumsum(jnp.sum(k == 0, axis=1, dtype=jnp.int32))])
    n0 = off0[nb]
    off1 = n0 + jnp.concatenate(
        [zero, jnp.cumsum(jnp.sum(k == 1, axis=1, dtype=jnp.int32))])
    out = pl.pallas_call(
        functools.partial(_compact_kernel, nb=nb),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(padded // BLOCK_LANES,),
            in_specs=[
                pl.BlockSpec((words, BLOCK_LANES), lambda g, offs: (0, g)),
                pl.BlockSpec((BLOCK_LANES // LANES, LANES),
                             lambda g, offs: (g, 0))],
            out_specs=pl.BlockSpec((words, padded), lambda g, offs: (0, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(jnp.concatenate([off0, off1]), x, k)
    if padded != rows:
        out = out[:, :rows]
    return out.T, n0, off1[nb] - n0
