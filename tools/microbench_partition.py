"""Tile partition throughput: the Pallas lane compaction against the XLA
scan (cumsum ranks + one row scatter), on the TPU.

One tile of 2**14 / 2**15 / 2**16 rows at 11, 21 and 39 u32 words a row
(the packed rows of `higgs`, `criteo-share` and `msltr`), with 1%, 50%
and 99% of the leaf's rows going left and a key-2 tail (the rows past
the leaf's count) of 0% and 30%: ns a tile row, median of 5 timed runs
of REPS partitions chained in one program, and whether the kernel's
result is bit for bit the scan's. Then one tiled window of 2**20 rows a
width, through `partition_window`, with each tile partition: what a rung
over one tile pays, the masked placement at running offsets included.

A width whose 2**16-row tile the kernel does not take (its output would
not fit the VMEM budget: past 80 words, e.g. 96) times the scan there,
the fallback the tree program takes, and the kernel at the tiles it
takes; its window is timed with the scan's tiles and with the kernel's
largest tile (`kernel_fit`), what choosing the tile by the row's width
would pay.

Usage: python tools/microbench_partition.py [reps] [out.jsonl] [words,...]
(default words 11,21,39). Exits non-zero off the TPU (the kernel lowers
for Mosaic only) or when any result differs from the scan's.
"""
import functools
import json
from pathlib import Path
import statistics
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from lightgbm_tpu.models import device_learner as dl  # noqa: E402
from lightgbm_tpu.ops.pallas import partition_kernel  # noqa: E402

REPS = int(sys.argv[1]) if len(sys.argv) > 1 else 20
OUT = sys.argv[2] if len(sys.argv) > 2 else None
WORDS = tuple(int(w) for w in (sys.argv[3] if len(sys.argv) > 3
                               else "11,21,39").split(","))
TIMED = 5


def chained(fn):
    """REPS partitions in one program, each of the last one's output, so
    that none is hoisted or shared."""
    @jax.jit
    def run(tile, key3):
        return jax.lax.fori_loop(0, REPS, lambda i, t: fn(t, key3), tile)
    return run


def ns_a_row(run, tile, key3, rows):
    jax.block_until_ready(run(tile, key3))          # compile + warm
    times = []
    for _ in range(TIMED):
        tick = time.perf_counter()
        jax.block_until_ready(run(tile, key3))
        times.append(time.perf_counter() - tick)
    return 1e9 * statistics.median(times) / (REPS * rows)


def emit(lines, line):
    """Print a reading, and append it to OUT as it comes: a run cut by its
    time limit keeps what it read."""
    lines.append(line)
    print(json.dumps(line), flush=True)
    if OUT:
        with open(OUT, "a") as fh:
            fh.write(json.dumps(line) + "\n")


def keys(rng, rows, left, tail):
    live = int(round(rows * (1.0 - tail)))
    go = rng.rand(rows) < left
    return jnp.asarray(np.where(np.arange(rows) < live,
                                np.where(go, 0, 1), 2).astype(np.int32))


def main():
    platform = jax.devices()[0].platform
    print(f"backend={platform} kind={jax.devices()[0].device_kind} "
          f"reps={REPS}", flush=True)
    if platform != "tpu":
        sys.exit("the kernel lowers for the TPU only")
    rng = np.random.RandomState(0)
    lines, all_equal = [], True
    fns = {"kernel": lambda t, k: partition_kernel.partition_tile(t, k)[0],
           "scan": lambda t, k: dl._scan_partition(t, k)[0]}
    for words in WORDS:
        for rows in (1 << 14, 1 << 15, 1 << 16):
            tile = jnp.asarray(rng.randint(0, 2**32, size=(rows, words),
                                           dtype=np.uint64).astype(np.uint32))
            names = [n for n in fns if n == "scan"
                     or partition_kernel.engaged("tpu", rows, words)]
            runs = {name: chained(fns[name]) for name in names}
            once = {name: jax.jit(fns[name]) for name in names}
            for left in (0.01, 0.5, 0.99):
                for tail in (0.0, 0.3):
                    key3 = keys(rng, rows, left, tail)
                    equal = "kernel" not in once or bool(np.array_equal(
                        np.asarray(once["kernel"](tile, key3)),
                        np.asarray(once["scan"](tile, key3))))
                    all_equal &= equal
                    line = {"what": "tile", "words": words, "rows": rows,
                            "left": left, "tail": tail, "bit_equal": equal}
                    for name, run in runs.items():
                        line[f"{name}_ns_a_row"] = ns_a_row(run, tile, key3,
                                                            rows)
                    emit(lines, line)
    # one tiled window a width: the rung's own path, tile by tile
    rows = 1 << 20
    real = dl._partition_tile
    for words in WORDS:
        win = jnp.asarray(rng.randint(0, 2**32, size=(rows, words),
                                      dtype=np.uint64).astype(np.uint32))
        key3 = keys(rng, rows, 0.5, 0.3)
        line = {"what": "window", "words": words, "rows": rows,
                "left": 0.5, "tail": 0.3}
        # the largest tile the kernel takes at this width
        fit = dl.SCATTER_TILE_ROWS
        while fit > partition_kernel.BLOCK_LANES and \
                not partition_kernel.engaged("tpu", fit, words):
            fit //= 2
        ways = [("scan", dl._scan_partition, dl.SCATTER_TILE_ROWS)]
        if partition_kernel.engaged("tpu", dl.SCATTER_TILE_ROWS, words):
            ways.append(("kernel", real, dl.SCATTER_TILE_ROWS))
        elif partition_kernel.engaged("tpu", fit, words):
            ways.append(("kernel_fit", real, fit))
            line["fit_rows"] = fit
        outs = {}
        for name, tile_fn, tile_rows in ways:
            dl._partition_tile = tile_fn
            part = functools.partial(dl.partition_window,
                                     tile_rows=tile_rows)
            try:
                run = chained(part)
                line[f"{name}_ns_a_row"] = ns_a_row(run, win, key3, rows)
                outs[name] = np.asarray(jax.jit(part)(win, key3))
            finally:
                dl._partition_tile = real
        line["bit_equal"] = all(np.array_equal(o, outs["scan"])
                                for o in outs.values())
        all_equal &= line["bit_equal"]
        emit(lines, line)
    print("ns a row, median over the key patterns")
    for words in WORDS:
        for rows in (1 << 14, 1 << 15, 1 << 16, 1 << 20):
            sel = [x for x in lines if x["words"] == words
                   and x["rows"] == rows]
            cols = sorted({k for x in sel for k in x
                           if k.endswith("_ns_a_row")})
            print(f"words {words:2d} rows {rows:7d}: " + "  ".join(
                f"{k[:-9]} "
                f"{statistics.median(x[k] for x in sel if k in x):.3f}"
                for k in cols))
    print(json.dumps({"all_bit_equal": all_equal}))
    sys.exit(0 if all_equal else 1)


if __name__ == "__main__":
    main()
