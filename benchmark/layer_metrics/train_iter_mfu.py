"""The whole boosting iteration's share of the chip's peak, in roofline
form: least time for the work of the trees the window grew
(`work_model.py`) over the wall time per iteration of the run's own
window, host gaps included (the traced run's window less the seconds the
profiler itself took to start and stop). It is what still bounds a gain
once a later PR has replaced the tree program."""
LAYER = "boosting iteration"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_row_trees_per_s"


def read(ctx):
    work, win = ctx.get("work"), ctx.get("window")
    if not work or not win or not win.get("iterations"):
        return None
    per_iter = (win["seconds"] - win.get("profiler_s", 0.0)) \
        / win["iterations"]
    return 100.0 * work["least_s_per_iter"] / per_iter
