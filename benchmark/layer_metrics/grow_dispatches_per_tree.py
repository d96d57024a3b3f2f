"""Device dispatches per grown tree over the window, from the program's
own counters (`grow_dispatches` / `grow_trees`); 1.0 on the fused path."""
LAYER = "learner choice"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("grow_trees") or "grow_dispatches" not in c:
        return None
    return c["grow_dispatches"] / c["grow_trees"]
