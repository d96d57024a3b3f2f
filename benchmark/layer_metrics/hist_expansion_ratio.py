"""How much wider the per-feature histogram plane the split scan reads
is than the column histogram it is gathered from: the program's gauge
`hist_expansion_ratio`, (used features x device bins) / (storage
columns x column device bins), set once when `DeviceTreeLearner` is
built. 1.0 where every feature has a column of its own; what a scan
that works in column space drives to 1. A program without the gauge
reads nothing."""
LAYER = "tree program"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("hist_expansion_ratio") or None
