"""Share of the used features that live in a multi-feature bundle: the
program's gauge `bundled_feature_share` (%), set once when
`DeviceTreeLearner` is built. 0 on a table that bundles nothing, which
reads nothing (the harness prints no share at 0), as does a program
without the gauge."""
LAYER = "tree program"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("bundled_feature_share") or None
