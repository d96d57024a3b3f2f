"""Seconds in XLA's backend compile, from the program's
`counters.compile_seconds()`; with a warm persistent cache, the cache
loads in its place. Like `trace_lower_s`, the whole process's up to the
read; `trace_lower_s` + this is `compile_s` as long as nothing compiles
after set-up."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"

EVENTS = ("/jax/core/compile/backend_compile_duration",)


def read(ctx):
    from lightgbm_tpu.telemetry import counters
    by_event = counters.compile_seconds()
    return sum(by_event.get(event, 0.0) for event in EVENTS) or None
