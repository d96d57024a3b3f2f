"""Peak bytes in use on the fullest device after the window
(`memory_stats()["peak_bytes_in_use"]`), read before the reference runs."""
LAYER = "device"
UNIT = "GiB"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    peak = ctx.get("device", {}).get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
