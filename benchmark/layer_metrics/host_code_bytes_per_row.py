"""Bytes a row of the per-row code matrices the Dataset holds once it is
constructed (the per-feature view, where it is held, the bundled
columns, and a sparse table's nonzero codes where a conflict row makes
it keep them), from the program's gauge `host_code_bytes_per_row`, set at the
end of each `Dataset` construction: the last one, the table's, is read.
About the bundled column count C where a sparse table is bundled from
its nonzeros, F + C where an (N, F) plane is built first. A program
without the gauge reads nothing."""
LAYER = "start-up"
UNIT = "B"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("host_code_bytes_per_row") or None
