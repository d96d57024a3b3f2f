"""Pair positions the ranking objective's gradient pass evaluates for
each row of the table, every iteration: the gauge
`rank_pair_positions_evaluated` (set once by `LambdarankNDCG.init`:
sum over the length buckets of padded queries x padded length squared)
over the window's rows. The gradient pass's work a row, what a better
bucket plan shrinks. A program without the gauge, or an objective that
plans no pairs, reads nothing."""
LAYER = "objective"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    evaluated = counters.get("rank_pair_positions_evaluated")
    rows = (ctx.get("window") or {}).get("rows")
    if not evaluated or not rows:
        return None
    return evaluated / rows
