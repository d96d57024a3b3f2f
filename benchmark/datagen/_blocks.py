"""One table a configuration, every `--seed` its own shuffle of it.

The table is drawn in blocks of BLOCK rows, block i from child i of
`table_seed`, so its rows depend on `table_seed` and the row count alone,
never on how many threads drew it. `--seed` decides where each block lies
in the table and the order of the rows inside it. Every seed therefore
gives the program other inputs and the same work: seeds that drew fresh
rows grew other trees, and their rates spread by 3% (`PERF.md` §6).
"""
from concurrent.futures import ThreadPoolExecutor
import os

import numpy as np

BLOCK = 262_144


def fill_blocks(seed, table_seed, rows, features, make_block):
    """x float32 [rows, features] and y float32 [rows];
    `make_block(rng, n)` returns one block's (x, y)."""
    x = np.empty((rows, features), dtype=np.float32)
    y = np.empty(rows, dtype=np.float32)
    sizes = np.full(-(-rows // BLOCK), BLOCK)
    sizes[-1] = rows - BLOCK * (len(sizes) - 1)
    children = np.random.SeedSequence(int(table_seed)).spawn(len(sizes))
    order = np.random.default_rng(int(seed)).permutation(len(sizes))
    starts = np.concatenate(([0], np.cumsum(sizes[order])[:-1]))

    def one(job):
        start, block = job
        bx, by = make_block(np.random.default_rng(children[block]),
                            int(sizes[block]))
        rows_at = np.random.default_rng([int(seed), int(block)]) \
            .permutation(len(by))
        x[start:start + len(by)] = bx[rows_at]
        y[start:start + len(by)] = by[rows_at]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(one, zip(starts, order)))
    return x, y
