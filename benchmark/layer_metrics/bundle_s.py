"""Seconds the program spent on exclusive feature bundling (`Dataset`'s
`_plan_bundles` and `_encode_bundles`; a Dataset built on a `reference`
takes its plan from there), from its own counter `setup_bundle_seconds`,
summed over every Dataset of the process up to the read. With
`find_bin_s` and `bin_data_s` it accounts for `dataset_construct_s`;
what is left is the table's preparation outside those three stages. A
program without the counter reads nothing."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    return counters.get("setup_bundle_seconds") or None
