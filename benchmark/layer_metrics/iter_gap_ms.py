"""Device-idle time between the end of one iteration's tree program and
the start of the next, mean per traced iteration."""
LAYER = "boosting iteration"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_row_trees_per_s"


def read(ctx):
    tr = ctx.get("trace")
    return tr["iter_gap_ms"] if tr else None
