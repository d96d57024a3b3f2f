"""Rows the step loops inside the tree program's rungs ran (the
partition's tiles and the child histogram's chunks) over the rows the
splits needed (parent rows plus the histogrammed child's, the work
model's counts), from the program's own counters `rung_rows_run` /
`rung_rows_needed`, summed over every tree of the process up to the
read: what the window ladder costs over the leaves' own rows, 1.0 where
a rung works for exactly its leaf. The entry keeps no list of cells
(PR 36): every training cell reports it, since the compact core grows
every cell's trees. A program without the counters reads nothing: one
from before PR 35, and a core without rungs (the masked core, which a
table under 65,536 rows gets), so the line leaves the metric out there
and never holds a 0."""
LAYER = "tree program"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    needed = counters.get("rung_rows_needed")
    if not needed:
        return None
    return counters.get("rung_rows_run") / needed
