"""Share of the rows the tree program's splits partition whose tiles go
through the Pallas lane compaction (`ops/pallas/partition_kernel.py`),
from the program's own counters `partition_kernel_rows` /
`partition_rows`, summed over every tree of the process up to the read.
A program without the counters reads nothing, and so does one that has
partitioned no row by the kernel (off the TPU, where the scan
partitions; the masked core, which moves no rows; the chunk core, which
sorts its chunks): the line holds no metric at 0."""
LAYER = "tree program"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    kernel = counters.get("partition_kernel_rows")
    if not kernel:
        return None
    return 100.0 * kernel / counters.get("partition_rows")
