"""Seconds the objective's `init` took where `GBDT` calls it (for
`lambdarank`: max DCG a query, the bucket plan and the upload of its
buffers), from the program's counter `setup_objective_init_seconds`,
summed over every booster of the process up to the read. A program
without the counter reads nothing. Listed for the ranking cells: a
row-wise objective's `init` is an upload of two vectors."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("setup_objective_init_seconds") or None
