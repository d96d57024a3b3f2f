"""Seconds the program spent binning every value of its tables
(`Dataset._bin_data`: a host pass a column), from its own counter
`setup_bin_data_seconds`, summed over every Dataset of the process up
to the read. A program without the counter reads nothing."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("setup_bin_data_seconds") or None
