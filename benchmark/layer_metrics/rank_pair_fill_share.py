"""Share of the pair positions the ranking objective's gradient pass
evaluates that lie inside a real query: 100 x the gauges
`rank_pair_positions_real` (sum of squared lengths of the queries that
can give a pair) / `rank_pair_positions_evaluated` (sum over the length
buckets of padded queries x padded length squared, the slices' padding
included), both set once by `LambdarankNDCG.init`. What a finer bucket
ladder raises. A program without the gauges, an objective that plans no
pairs, or a table on which no query can give one reads nothing."""
LAYER = "objective"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    evaluated = counters.get("rank_pair_positions_evaluated")
    if not evaluated:
        return None
    return 100.0 * counters.get("rank_pair_positions_real") / evaluated
