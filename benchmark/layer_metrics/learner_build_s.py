"""Seconds in the device learner's constructor (feature metadata, packing
the codes, `device_put` of the table), from the program's own counter
`setup_learner_build_seconds`, summed over every learner of the process
up to the read. A program without the counter reads nothing."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("setup_learner_build_seconds") or None
