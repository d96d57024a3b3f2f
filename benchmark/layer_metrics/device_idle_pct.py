"""Share of the traced window (whole iterations) in which no operation
ran on the device: 1 - union of device-op intervals over the window."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_row_trees_per_s"


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
