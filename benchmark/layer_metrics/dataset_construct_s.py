"""Host clock round `Dataset.construct()`: bin finding and binning."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(ctx):
    return ctx.get("phases", {}).get("dataset_construct_s") or None
