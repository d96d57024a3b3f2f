"""The tree program's share of its roofline: least time for the work of
the window's trees (`work_model.py`) over `tree_program_ms`. Never 0: a
trace without the program returns nothing."""
LAYER = "tree program"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_row_trees_per_s"


def read(ctx):
    tr, work = ctx.get("trace"), ctx.get("work")
    if not tr or not work or tr["tree_program_ms"] <= 0:
        return None
    return 100.0 * work["least_s_per_iter"] / (tr["tree_program_ms"] / 1e3)
