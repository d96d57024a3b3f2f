"""The program's own spans on the profiler's clock, and its stages
inside the tree program.

Host side: `spans.span`, `recorder.phase` and `recorder.iteration` enter
`jax.profiler` annotations named `lgbm/<name>` whatever the telemetry
mode, so a profiler session holds them beside the device's timeline;
set-up stages also feed `setup_*_seconds` counters. Device side: the
tree program's stages are `jax.named_scope("lgbm.<stage>")`, and
`fused_step.stage_map()` maps the compiled module's instructions to
them. The benchmark finds the tree program by the module name
`jit_step_impl`; that name is pinned here.

CPU, toy sizes: names, nesting and counts only — never a time.
"""
import glob
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from conftest import make_binary
from lightgbm_tpu import telemetry
from lightgbm_tpu.telemetry import counters, spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"objective": "binary", "num_leaves": 7, "max_bin": 15,
          "verbosity": -1}


@pytest.fixture(autouse=True)
def _telemetry_off():
    telemetry.set_mode("off")
    telemetry.reset()
    yield
    telemetry.set_mode("off")
    telemetry.reset()


def _booster(n=2000, f=6, **params):
    x, y = make_binary(n=n, f=f, seed=3)
    return lgb.Booster(dict(PARAMS, **params), lgb.Dataset(x, y))


def _step_args(gbdt):
    f = gbdt.train_set.num_features
    return (gbdt.score_updater.score[0], jnp.ones((f,), bool),
            jax.random.PRNGKey(0), jax.random.PRNGKey(1), jnp.float32(0.1))


# ---------------------------------------------------------------------------
# (a) host spans in a profiler session, telemetry off

def _host_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the `lgbm/` events a
    profiler session kept, in time order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_profiler_session_holds_program_spans(tmp_path):
    x, y = make_binary(n=2000, f=6, seed=3)
    bst = _booster()
    bst.update()                          # compile outside the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0       # as the benchmark sets it
    assert telemetry.mode() == "off"
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        lgb.Dataset(x, y, params=PARAMS).construct()
        for _ in range(3):
            bst.update()
        _ = bst._gbdt.models
    assert telemetry.mode() == "off"
    events = _host_events(tmp_path)
    names = [e[0] for e in events]
    assert "lgbm/dataset/find_bin" in names
    assert "lgbm/dataset/bin_data" in names
    iterations = [e for e in events if e[0] == "lgbm/iteration"]
    assert [e[3]["step_num"] for e in iterations] == [1, 2, 3]
    for _, lo, hi, _ in iterations:
        inside = {e[0] for e in events if lo <= e[1] and e[2] <= hi}
        assert {"lgbm/grow_dispatch", "lgbm/record_fetch",
                "lgbm/tree_replay", "lgbm/feature_mask",
                "lgbm/mask_sync"} <= inside
    # off: nothing of the span ring or the recorder moved
    assert spans.events() == []
    assert telemetry.phase_breakdown()["iterations"] == 0


def test_trace_mode_span_is_timed_once_and_still_annotated(tmp_path):
    telemetry.set_mode("trace")
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("probe", rows=3):
            with telemetry.recorder.phase("probe_phase"):
                pass
    ring = {e["name"]: e for e in spans.events()}
    assert ring["probe"]["args"] == {"rows": 3}
    assert set(ring) == {"probe", "probe_phase"}
    assert telemetry.phase_breakdown()["phases"]["probe_phase"]["calls"] == 1
    names = [e[0] for e in _host_events(tmp_path)]
    assert names.count("lgbm/probe") == 1
    assert names.count("lgbm/probe_phase") == 1


def test_stage_feeds_its_counter_and_the_ring_from_one_timing():
    with spans.stage("setup_probe_seconds", "probe_stage"):
        pass                                # off: the counter all the same
    off_s = counters.get("setup_probe_seconds")
    assert off_s > 0 and spans.events() == []
    telemetry.set_mode("trace")
    with spans.stage("setup_probe_seconds", "probe_stage"):
        pass
    (event,) = spans.events()
    assert event["name"] == "probe_stage"
    assert event["dur"] == pytest.approx(
        (counters.get("setup_probe_seconds") - off_s) * 1e6)


# ---------------------------------------------------------------------------
# (b) stages inside the tree program

HLO_TOY = """HloModule jit_step_impl, entry_computation_layout={()->f32[]}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %scatter.9 = f32[4]{0} scatter(%p), metadata={op_name="jit(step_impl)/while/body/lgbm.leaf_select/rung_2/while/body/lgbm.partition/scatter"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %copy.7 = f32[4]{0} copy(%a)
  %fusion.3 = f32[4]{0} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1, metadata={op_type="scatter" op_name="jit(step_impl)/while/body/lgbm.leaf_select/rung_2/while/body/lgbm.partition/scatter" source_file="x.py" source_line=3}
  ROOT add.1 = f32[4]{0} add(%fusion.3, %a), metadata={op_name="jit(step_impl)/lgbm.split_epilogue/lgbm.split_scan/add"}
}
"""


def test_stage_map_reads_innermost_scope_and_rung():
    assert telemetry.stage_map(HLO_TOY) == {
        "scatter.9": ("partition", 2), "fusion.3": ("partition", 2),
        "add.1": ("split_scan", None)}


# the split loop (the one `while` under no stage) with the packed table
# `u32[64,11]` in its carry; %s is the dispatch over two rungs
HLO_SPLIT_LOOP = """HloModule jit_step_impl, entry_computation_layout={()->u32[64,11]}

%%rung_small (p: (pred[], u32[64,11])) -> (pred[], u32[64,11]) {
  %%p = (pred[], u32[64,11]{0,1:T(8,128)}) parameter(0)
  %%table = u32[64,11]{0,1:T(8,128)} get-tuple-element(%%p), index=1
%(rung_copy)s  %%window = u32[16,11]{0,1:T(8,128)} copy(%%slice.1)
  ROOT %%tuple.1 = (pred[], u32[64,11]{0,1:T(8,128)}) tuple(%%done, %%dus.1)
}

%%rung_top (p: (pred[], u32[64,11])) -> (pred[], u32[64,11]) {
  %%p = (pred[], u32[64,11]{0,1:T(8,128)}) parameter(0)
  ROOT %%tuple.2 = (pred[], u32[64,11]{0,1:T(8,128)}) tuple(%%done, %%dus.2)
}

%%once (p: (pred[], u32[64,11])) -> pred[] {
  ROOT %%todo = pred[] get-tuple-element(%%p), index=0
}

%%split_body (c: (s32[], u32[64,11], s32[64])) -> (s32[], u32[64,11], s32[64]) {
  %%c = (s32[], u32[64,11]{0,1:T(8,128)}, s32[64]{0}) parameter(0)
%(dispatch)s
  ROOT %%tuple.3 = (s32[], u32[64,11]{0,1:T(8,128)}, s32[64]{0}) tuple(%%k, %%table.2, %%pos)
}

%%split_cond (c: (s32[], u32[64,11], s32[64])) -> pred[] {
  ROOT %%more = pred[] compare(%%k, %%limit), direction=LT
}

ENTRY %%main (a: u32[64,11]) -> u32[64,11] {
  %%a = u32[64,11]{0,1:T(8,128)} parameter(0)
  %%copy.1 = u32[64,11]{0,1:T(8,128)} copy(%%a)
  %%while.9 = (s32[], u32[64,11]{0,1:T(8,128)}, s32[64]{0}) while(%%tuple.0), condition=%%split_cond, body=%%split_body, metadata={op_name="jit(step_impl)/while"}
  %%scan.2 = (s32[], f32[8]{0}) while(%%tuple.9), condition=%%once, body=%%rung_top, metadata={op_name="jit(step_impl)/lgbm.score_update/while"}
  ROOT %%out = u32[64,11]{0,1:T(8,128)} get-tuple-element(%%while.9), index=1
}
"""
_AS_CONDITIONAL = dict(
    rung_copy="  %copy.5 = u32[64,11]{0,1:T(8,128)} copy(%table)\n",
    dispatch="  %conditional.4 = (pred[], u32[64,11]{0,1:T(8,128)}) "
             "conditional(%j, %arg, %arg), branch_computations="
             "{%rung_small, %rung_top}, metadata={op_name=\"jit(step_impl)"
             "/while/body/lgbm.leaf_select/switch\"}")
_AS_LOOPS = dict(
    rung_copy="",
    dispatch="\n".join(
        "  %%while.%d = (pred[], u32[64,11]{0,1:T(8,128)}) while(%%arg), "
        "condition=%%once, body=%%%s, metadata={op_name=\"jit(step_impl)/while"
        "/body/lgbm.leaf_select/rung_%d/while\"}" % (r, body, r)
        for r, body in enumerate(("rung_small", "rung_top"))))


@pytest.mark.parametrize("dispatch,copies", [
    (_AS_CONDITIONAL, {"rung_small": 1}), (_AS_LOOPS, {})])
def test_table_copies_are_counted_inside_the_split_loop_only(dispatch,
                                                             copies):
    """Counted: a copy of the carry's largest `u32` table in a
    computation the split loop reaches. Not counted: the entry's one
    copy, a window-sized copy, and the loops inside a stage."""
    assert telemetry.table_copies_in_split_loop(
        HLO_SPLIT_LOOP % dispatch) == copies
    assert telemetry.table_copies_in_split_loop(HLO_TOY) == {}


# a histogram product as the TPU compiler writes it: a fusion round a
# `convolution` that contracts the rows; %s are the plane's dimensions
# after the 2,048 rows and the stage's scope
HLO_HIST_PRODUCT = """HloModule jit_step_impl, entry_computation_layout={()->f32[8]}

%%fused_computation.5 (p0: pred[2048,%(plane)s], p1: bf16[2048,6], p2: f32[8]) -> f32[8] {
  %%p0 = pred[2048,%(plane)s]{0,1:T(8,128)(4,1)} parameter(0)
  %%p1 = bf16[2048,6]{0,1} parameter(1)
  %%p2 = f32[8]{0} parameter(2)
  %%fusion.2 = bf16[2048,%(plane)s]{0,1:T(8,128)(2,1)} fusion(%%p0, %%p1), kind=kLoop, calls=%%fused_computation.4
  ROOT %%convolution.1 = f32[8]{0} convolution(%%fusion.2, %%p1), dim_labels=fb_io->bf, metadata={op_name="jit(step_impl)/%(scope)s/dot_general"}
}

ENTRY %%main (a: pred[2048,%(plane)s], g: bf16[2048,6], acc: f32[8]) -> f32[8] {
  %%a = pred[2048,%(plane)s]{0,1:T(8,128)(4,1)} parameter(0)
  %%g = bf16[2048,6]{0,1} parameter(1)
  %%acc = f32[8]{0} parameter(2)
  %%pool = f32[255,67,256,3]{3,2,1,0} broadcast(%%acc), metadata={op_name="jit(step_impl)/%(scope)s/broadcast"}
  ROOT %%fusion.9 = f32[8]{0} fusion(%%a, %%g, %%acc), kind=kOutput, calls=%%fused_computation.5, metadata={op_name="jit(step_impl)/%(scope)s/dot_general"}
}
"""


@pytest.mark.parametrize("plane,scope,per_row", [
    ("17152", "while/body/lgbm.child_hist", 17152),     # 67 x 256 unfactored
    ("17,192", "lgbm.root_hist", 17 * 192),             # factored, LO_BINS 32
    ("17152", "lgbm.split_scan", 0)])                   # no histogram's
def test_hist_plane_elems_per_row_reads_the_products_operands(plane, scope,
                                                              per_row):
    """The widest operand with the contracted length among its
    dimensions, over that length; neither the gradient columns nor an
    array the product does not read (the histogram pool) count."""
    text = HLO_HIST_PRODUCT % {"plane": plane, "scope": scope}
    assert telemetry.hist_plane_elems_per_row(text) == per_row
    assert telemetry.hist_plane_elems_per_row(HLO_TOY) == 0


def test_every_named_scope_is_a_stage_and_every_stage_a_scope():
    used = set()
    for root, _dirs, files in os.walk(os.path.join(REPO, "lightgbm_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as fh:
                    used.update(re.findall(
                        r'named_scope\(\s*"lgbm\.(\w+)"', fh.read()))
    assert used == set(telemetry.STAGES)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+([\w\-]+)\(")


def test_fused_step_stage_map_covers_the_split_loop(monkeypatch):
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")
    bst = _booster(n=10000)               # three rungs of the window ladder
    gbdt = bst._gbdt
    assert gbdt.learner.strategy == "compact"
    step = gbdt.learner.make_fused_step(gbdt.objective)
    text = step.lower(*_step_args(gbdt)).compile().as_text()
    stage_of = telemetry.stage_map(text)
    assert stage_of == step.stage_map(*_step_args(gbdt))
    # (a count of the CPU compiler's copies here: what the TPU's leaves
    # is tests/test_tpu_compile_partition.py's to say)
    assert (step.table_copies(*_step_args(gbdt))
            == telemetry.table_copies_in_split_loop(text))
    # (the CPU compiler's contraction is a `dot`: nothing to size)
    assert (step.hist_plane_elems(*_step_args(gbdt))
            == telemetry.hist_plane_elems_per_row(text)
            == telemetry.counters.get("hist_plane_elems_per_row", -1))
    owned = {}
    for name, (stage, rung) in stage_of.items():
        owned.setdefault(stage, []).append((name, rung))
    assert set(owned) == set(telemetry.STAGES)
    rungs = {rung for _, rung in owned["partition"]}
    assert None not in rungs and len(rungs) == 3
    # everything heavy inside the split loop belongs to a stage: walk the
    # computations reachable from the body of the loop (the `while` whose
    # op_name is no stage's: the others are loops INSIDE a stage)
    comps = telemetry._computations(text)
    loops = telemetry._split_loops(comps)
    assert len(loops) == 1, loops
    reached = telemetry._reached_from_body(comps, loops[0])
    # (an instruction without `op_name`, or with one that stops short of
    # the loop, is the compiler's own — the pieces it cuts a cumsum into,
    # the zeros a rung loop's carry starts from, sunk into the body as a
    # constant — and no scope can reach it)
    unstaged, checked = [], 0
    for comp in reached:
        for line in comps[comp]:
            m = _INSTRUCTION.match(line)
            if (m and re.search(r'op_name="[^"]*/while/body', line)
                    and m.group(2) in (
                        "scatter", "dot", "convolution", "fusion")):
                checked += 1
                if m.group(1) not in stage_of:
                    unstaged.append(line.strip()[:240])
    assert checked > 50
    assert not unstaged, "\n".join(unstaged)


# ---------------------------------------------------------------------------
# (c) the name the benchmark finds the tree program by

@pytest.mark.parametrize("tree_learner,learner_class", [
    ("serial", "DeviceTreeLearner"),
    ("data", "DeviceDataParallelTreeLearner"),
    ("feature", "DeviceFeatureParallelTreeLearner")])
def test_fused_step_module_is_named_jit_step_impl(tree_learner,
                                                  learner_class):
    gbdt = _booster(tree_learner=tree_learner)._gbdt
    assert type(gbdt.learner).__name__ == learner_class
    step = gbdt.learner.make_fused_step(gbdt.objective)
    assert step.impl.__name__ == "step_impl"
    lowered = step.lower(*_step_args(gbdt)).as_text()
    assert re.search(r"module @jit_step_impl\b", lowered)


# ---------------------------------------------------------------------------
# (d) set-up stages as counters, (e) compiles by function

def test_setup_stage_counters_are_positive_and_inside_construct():
    x, y = make_binary(n=4000, f=6, seed=3)
    tick = time.perf_counter()
    ds = lgb.Dataset(x, y, params=PARAMS).construct()
    construct_s = time.perf_counter() - tick
    lgb.Booster(PARAMS, ds)
    stages = {k: counters.get(f"setup_{k}_seconds")
              for k in ("find_bin", "bin_data", "bundle", "learner_build")}
    assert all(v > 0 for v in stages.values()), stages
    assert (stages["find_bin"] + stages["bin_data"] + stages["bundle"]
            <= construct_s)


@pytest.mark.parametrize("tile_rows", [
    None,          # the shipped tile: no rung of a toy ladder is over it
    4096])         # the tile forced under the two upper rungs
def test_partition_row_counters_follow_the_split_records(
        monkeypatch, tile_rows):
    """`partition_rows` rises by the parent rows of the tree's splits;
    `partition_tiled_rows` by those whose rung is over one scatter tile:
    0 at toy size, the splits of more than 4096 rows with the tile
    forced (ladder 4096, 8192, 10000), which also runs the tiled
    partition inside the fused step."""
    from lightgbm_tpu.models import device_learner as dl
    monkeypatch.setenv("LGBM_TPU_STRATEGY", "compact")
    if tile_rows:
        monkeypatch.setattr(dl, "SCATTER_TILE_ROWS", tile_rows)
    bst = _booster(n=10000, num_leaves=15)
    gbdt = bst._gbdt
    assert gbdt.learner.strategy == "compact"
    assert (counters.get("partition_rows"),
            counters.get("partition_tiled_rows")) == (0, 0)
    bst.update()
    tree = gbdt.models[0]
    parents = np.asarray(tree.internal_count[:tree.num_leaves - 1])
    assert tree.num_leaves == 15 and parents.max() == 10000
    assert counters.get("partition_rows") == parents.sum()
    tiled = parents[parents > tile_rows].sum() if tile_rows else 0
    assert counters.get("partition_tiled_rows") == tiled
    if tile_rows:
        assert 0 < tiled < parents.sum()


def test_retrace_is_named_in_compile_seconds_by_function():
    counters.install_compile_listener()

    @jax.jit
    def retraced_probe(v):
        return v * 2 + 1

    retraced_probe(jnp.ones(3))
    before = dict(counters.compile_seconds_by_function()["retraced_probe"])
    assert any("jaxpr_trace" in k for k in before)
    retraced_probe(jnp.ones(3))           # cached: nothing new
    assert counters.compile_seconds_by_function()["retraced_probe"] == before
    retraced_probe(jnp.ones(5))           # a new shape forces a retrace
    after = counters.compile_seconds_by_function()["retraced_probe"]
    assert all(after[k] > before[k] for k in before)
    totals = counters.compile_seconds()
    assert all(after[k] <= totals[k] + 1e-9 for k in after)
