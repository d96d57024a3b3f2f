"""Traffic of kind `train`: boosting iterations back to back on the
configuration's table, as `engine.train` would run them, until the first
iteration boundary at or past `--seconds`. The mix's file gives
`checked_steps` and `traced_iterations`.

Set-up builds ONE booster, drives it from the seed through its first
`checked_steps` iterations (they compile and warm the pipeline, and they
are the steps the reference follows), and hands that same booster to the
window. Reports `train_row_trees_per_s` and `setup_s`.

One loop for every objective. What an objective needs besides the table
comes with the table: a generator may return `(x, y, fields)`, `fields`
the `lgb.Dataset` keyword arguments made from the seed (`group`,
`weight`), and a configuration may name its plain reference
(`reference`, the module `reference/<name>.py`; `gbdt_reference` where
it names none). `benchmark/README.md` has the contract of both.
"""
import gc
import shutil
import sys
import time

import numpy as np

from benchmark import spec, trace_reduce, work_model
from benchmark.drive import device_record, find_devices, timed

WORK_KEYS = ("num_leaves", "left_child", "right_child", "internal_count",
             "leaf_count")

COMPILE_EVENTS = "/jax/core/compile/"


def tree_arrays(tree, keys):
    """Plain copies of a grown tree's arrays (the reference imports
    nothing of the program, so it is handed arrays, not a Tree)."""
    out = {}
    for k in keys:
        v = getattr(tree, k)
        out[k] = int(v) if k == "num_leaves" else np.array(v)
    return out


def table_and_fields(table):
    """`(x, y, fields)` of what a generator returned: `(x, y)`, or
    `(x, y, fields)` with the Dataset's further keyword arguments."""
    x, y = table[:2]
    return x, y, dict(table[2]) if len(table) > 2 else {}


def first_steps(cell, seed, phases):
    """Set-up: the table from the seed, the Dataset, ONE booster, and its
    first `checked_steps` iterations with the score row after each."""
    import lightgbm_tpu as lgb

    conf = cell["config"]
    ref = spec.load_reference(conf.get("reference", "gbdt_reference"))
    params = dict(conf["params"])
    params.update(cell["workload"].get("params", {}))
    rows, features = int(conf["rows"]), int(conf["features"])
    steps = int(cell["traffic"]["checked_steps"])

    with timed(phases, "datagen_s"):
        gen = spec.load_generator(conf["generator"])
        x, y, fields = table_and_fields(
            gen.generate(seed, rows, features, conf["generator_params"]))
    with timed(phases, "dataset_construct_s"):
        # Bin boundaries come from the table's first `bins_rows` rows,
        # in the order of the configuration's fixed `bins_seed`, as a
        # `reference` Dataset: the program bakes what binning finds (bins
        # a feature, the bin of zero) into its tree program as constants,
        # so boundaries found on each seed's own sample of the rows would
        # make every seed a new program to compile.
        bx, by, bins_fields = table_and_fields(
            gen.generate(int(conf["bins_seed"]), int(conf["bins_rows"]),
                         features, conf["generator_params"]))
        bins_from = lgb.Dataset(bx, by, params=dict(params),
                                **bins_fields).construct()
        train_set = lgb.Dataset(x, y, reference=bins_from,
                                params=dict(params), **fields)
        train_set.construct()
    state = boost(train_set, params, steps, phases, ref)
    state.update(x=x, y=y, fields=fields, params=params, rows=rows,
                 features=features, steps=steps, ref=ref)
    return state


def boost(train_set, params, steps, phases, ref):
    """ONE booster on `train_set`, driven through its first `steps`
    iterations; the score row is read back after each. `ref` is the
    configuration's reference module: it says which of a tree's arrays
    it follows (`TREE_KEYS`) and takes them as its `Outputs`."""
    import jax
    import lightgbm_tpu as lgb

    with timed(phases, "learner_init_s"):
        booster = lgb.Booster(params=params, train_set=train_set)
    gbdt = booster._gbdt
    scores, step_s = [], []
    with timed(phases, "warmup_s"):
        for _ in range(steps):
            tick = time.perf_counter()
            booster.update()
            scores.append(np.asarray(
                jax.device_get(gbdt.score_updater.score))[0].copy())
            step_s.append(time.perf_counter() - tick)
        first_trees = [tree_arrays(t, ref.TREE_KEYS)
                       for t in gbdt.models[:steps]]
    return {"booster": booster,
            "outputs": ref.Outputs(first_trees, scores),
            "step_s": min(step_s)}


def build_reference(state, seed):
    """The configuration's reference on the table the Dataset was made
    of; it is handed the Dataset's `fields` only where there are any."""
    extra = {"fields": state["fields"]} if state["fields"] else {}
    return state["ref"].Reference(state["x"], state["y"], state["params"],
                                  seed, **extra)


def check_first_steps(state, seed):
    """The reference over the first steps: the numbers compared."""
    reference = build_reference(state, seed)
    readings = reference.follow(state["outputs"])
    readings["steps_missing"] = state["steps"] - min(
        len(state["outputs"].trees), len(state["outputs"].scores))
    return reference, readings


def run(cell, seed, seconds, trace, t0, root, allow_cpu=False):
    import jax
    from lightgbm_tpu.telemetry import counters

    traffic = cell["traffic"]
    devices = find_devices(cell["chips"], allow_cpu)
    counters.install_compile_listener()
    phases = {}
    state = first_steps(cell, seed, phases)
    booster = state.pop("booster")
    gbdt = booster._gbdt
    rows, features, steps = state["rows"], state["features"], state["steps"]
    by_event = counters.compile_seconds()
    # tracing, lowering and the backend's compile or cache load; not
    # /jax/compilation_cache/compile_time_saved_sec, which on a cache hit
    # is the cold compile's length
    phases["compile_s"] = float(sum(
        v for k, v in by_event.items() if k.startswith(COMPILE_EVENTS)))
    phases["compile_events_s"] = by_event
    compiles_before = len(counters.compile_events())
    count0 = {k: counters.get(k) for k in ("grow_dispatches", "grow_trees")}
    traced = int(traffic["traced_iterations"]) if trace else 0
    trace_dir = root / ".bench_trace"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2

    # ---- the window --------------------------------------------------
    attempted = failed = 0
    tracing = False
    in_flight_s = state["step_s"]     # a warm-up step, waited for
    returned_s = []                   # when each `update()` came back
    profiler_s = 0.0          # the profiler's own start and stop
    start = time.perf_counter()
    setup_s = start - t0
    while True:
        if traced and attempted == 1:
            # whole iterations from an idle device: drain, then trace
            # traced + 1 program launches
            jax.block_until_ready(gbdt.score_updater.score)
            tick = time.perf_counter()
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=options)
            profiler_s += time.perf_counter() - tick
            tracing = True
        with jax.profiler.TraceAnnotation("bench_update"):
            try:
                stopped = booster.update()
            except Exception as exc:      # counted, reported, not hidden
                print(f"iteration {attempted} raised: {exc!r}",
                      file=sys.stderr)
                stopped = True
        attempted += 1
        failed += bool(stopped)
        returned_s.append(time.perf_counter() - start)
        if tracing and attempted == traced + 2:
            with jax.profiler.TraceAnnotation("bench_drain"):
                jax.block_until_ready(gbdt.score_updater.score)
            tick = time.perf_counter()
            jax.profiler.stop_trace()
            profiler_s += time.perf_counter() - tick
            tracing = False
        # `update()` returns with its tree still in flight, so the host
        # runs one iteration ahead of the device: once the iteration in
        # flight is due to end past `seconds`, wait for it and look.
        if (time.perf_counter() - start + in_flight_s >= seconds
                and attempted >= (traced + 2 if traced else 1)):
            jax.block_until_ready(gbdt.score_updater.score)
            if time.perf_counter() - start >= seconds:
                break
    with jax.profiler.TraceAnnotation("bench_drain"):
        final_score = jax.block_until_ready(gbdt.score_updater.score)
        models = gbdt.models              # materialises the tree in flight
    window_s = time.perf_counter() - start

    # ---- after the window --------------------------------------------
    compiles_in_window = counters.compile_events()[compiles_before:]
    counts = {k: counters.get(k) - v for k, v in count0.items()}
    window_trees = [tree_arrays(t, WORK_KEYS) for t in models[steps:]]
    failed = max(failed, sum(t["num_leaves"] <= 1 for t in window_trees),
                 attempted - len(window_trees))
    finite = bool(np.isfinite(np.asarray(jax.device_get(final_score))).all())
    device = device_record(devices, cell["chips"])
    del final_score, models, gbdt, booster
    gc.collect()

    window = {"seconds": window_s, "iterations": attempted, "rows": rows,
              "profiler_s": profiler_s}
    phases["update_returned_s"] = returned_s[:64]
    work = None
    if window_trees and device["platform"] == "tpu":
        work = work_model.window_work(rows, features, window_trees,
                                      device["kind"])
    summary = None
    if traced:
        summary = trace_reduce.reduce_file(
            trace_reduce.newest_xplane(trace_dir), traced, cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]

    ref_start = time.perf_counter()
    _, readings = check_first_steps(state, seed)
    readings["compiles_in_window"] = len(compiles_in_window)
    readings["nonfinite_score"] = 0 if finite else 1
    phases["reference_s"] = time.perf_counter() - ref_start

    ctx = {"trace": summary, "work": work, "window": window,
           "counters": counts, "phases": phases, "device": device}
    return {
        "attempted": attempted, "failed": int(failed),
        "end_to_end": {
            "train_row_trees_per_s": rows * attempted / window_s,
            "setup_s": setup_s},
        "ctx": ctx, "readings": readings, "phases": phases,
        "compile_events_in_window": compiles_in_window}
