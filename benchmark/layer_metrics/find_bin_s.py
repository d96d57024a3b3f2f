"""Seconds the program spent finding bin boundaries (`Dataset`'s
`_build_mappers`: `io/binning.find_bin`, per-value Python), from its own
counter `setup_find_bin_seconds`, summed over every Dataset of the
process up to the read. A program without the counter reads nothing."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("setup_find_bin_seconds") or None
