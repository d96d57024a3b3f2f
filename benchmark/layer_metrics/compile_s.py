"""Seconds of JAX's `/jax/core/compile/*` events during set-up, as the
program's `counters.compile_seconds()` kept them: tracing, lowering and
the backend's compile; with a warm persistent cache, the cache loads in
its place."""
LAYER = "start-up"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    return ctx.get("phases", {}).get("compile_s") or None
