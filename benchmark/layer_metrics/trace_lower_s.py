"""Seconds of Python tracing and of lowering to StableHLO, from the
program's `counters.compile_seconds()`: the part of `compile_s` that a
warm compile cache does not remove (the tree program's ladder rungs are
traced one by one). The counter is the whole process's up to the read,
after the window and the reference, where `compile_s` is taken when
set-up ends: the two agree as long as nothing compiles after set-up,
which `compiles_in_window` holds the run to."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",)


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    by_event = counters.compile_seconds()
    return sum(by_event.get(event, 0.0) for event in EVENTS) or None
