"""The (feature, bin) positions one child's split scan reads, summed
over its planes: the program's gauge `split_scan_plane_elems`, set once
when `DeviceTreeLearner` is built. F x device bins where every feature
has a column of its own (the column histogram is scanned as it is);
on a bundled table each width class of features is scanned in a plane
as wide as its bin counts. A program without the gauge reads
nothing."""
LAYER = "tree program"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("split_scan_plane_elems") or None
