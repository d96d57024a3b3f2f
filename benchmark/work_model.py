"""The least work a boosting iteration needs, counted from the trees the
window grew and never from the program that grew them, so that the same
trees read the same work whatever implements them.

Per iteration over N rows and F features (one byte a code):
  gradient pass        N x 16 B   (read score and label, write g and h)
  root histogram       N x (F + 8) B
  each split           parent rows x 4 B of index traffic
                       + smaller child's rows x (F + 8) B (the larger
                       child's histogram is the parent's minus it)
  score update         N x 8 B
  operations           2 per (row, feature) histogrammed
Least time = the larger of bytes / HBM bandwidth and operations / peak.
"""
import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks_for(device_kind):
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}: add it with its source")
    return table[device_kind]


def tree_work(rows, features, tree):
    """(bytes, operations) of one iteration that grew `tree` (a dict
    with num_leaves, left_child, right_child, internal_count,
    leaf_count)."""
    n_int = int(tree["num_leaves"]) - 1
    row_bytes = features + 8
    hist_rows = rows
    moved = rows * 16 + rows * row_bytes + rows * 8
    for i in range(n_int):
        kids = [int(tree["leaf_count"][~c]) if c < 0
                else int(tree["internal_count"][c])
                for c in (tree["left_child"][i], tree["right_child"][i])]
        moved += int(tree["internal_count"][i]) * 4 + min(kids) * row_bytes
        hist_rows += min(kids)
    return moved, 2 * features * hist_rows


def least_seconds(moved, operations, peaks):
    """(seconds, which bound) for that much work on one chip."""
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    by_ops = operations / peaks["flops_bf16"]
    return (by_bytes, "hbm_bytes") if by_bytes >= by_ops \
        else (by_ops, "flops_bf16")


def window_work(rows, features, trees, device_kind):
    """Mean least seconds per iteration over the window's trees."""
    peaks = peaks_for(device_kind)
    per_tree = [tree_work(rows, features, t) for t in trees]
    moved = float(np.mean([w[0] for w in per_tree]))
    ops = float(np.mean([w[1] for w in per_tree]))
    seconds, bound = least_seconds(moved, ops, peaks)
    return {"bytes_per_iter": moved, "ops_per_iter": ops,
            "least_s_per_iter": seconds, "bound": bound}
