"""Share of the splits whose feature has a missing type (zero or NaN),
so that the split carries a default direction, from the program's own
counters `splits_on_missing_feature` / `splits`, summed over every tree
of the process up to the read. Listed for the cells whose table has
missing values: a table with none has no such split, and a reader that
counts none reads nothing (the line holds no metric at 0)."""
LAYER = "tree program"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    on_missing = counters.get("splits_on_missing_feature")
    if not on_missing:
        return None
    return 100.0 * on_missing / counters.get("splits")
