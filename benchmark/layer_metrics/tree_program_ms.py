"""Device time of the fused-step XLA module per tree, from the trace's
"XLA Modules" line: host gaps are excluded."""
LAYER = "tree program"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_row_trees_per_s"


def read(ctx):
    tr = ctx.get("trace")
    return tr["tree_program_ms"] if tr else None
