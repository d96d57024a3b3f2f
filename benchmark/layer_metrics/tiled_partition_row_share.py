"""Share of the rows the tree program's splits partition that go through
the tiled partition (windows of more than one scatter tile), from the
program's own counters `partition_tiled_rows` / `partition_rows`, summed
over every tree of the process up to the read. A program without the
counters reads nothing, and so does one that has tiled no row (the
masked core, which moves none; the chunk core, which sorts its chunks;
windows of one tile): the line holds no metric at 0."""
LAYER = "tree program"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    tiled = counters.get("partition_tiled_rows")
    if not tiled:
        return None
    return 100.0 * tiled / counters.get("partition_rows")
