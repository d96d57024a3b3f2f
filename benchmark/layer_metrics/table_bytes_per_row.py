"""Bytes a row of the packed table the tree program partitions and
copies: 4 x (code words + gradient words + the row id), from the
program's gauge `table_bytes_per_row`, set once when the learner packs
the table (the compact and chunk cores). A program without the gauge,
or a core that packs no table (the masked core), reads nothing."""
LAYER = "tree program"
UNIT = "B"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("table_bytes_per_row") or None
