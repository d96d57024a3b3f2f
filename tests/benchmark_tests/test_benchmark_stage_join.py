"""The tree program's device time by stage (`benchmark/stage_join.py`):
the join on planes small enough to check by hand and on the trace
recorded on the chip, and `tools/stage_table.py` in both its forms."""
import json
import sys

import pytest

from bench_rehearsal import ROOT, tiny_root  # noqa: F401 (a fixture)

from benchmark import stage_join

sys.path.insert(0, str(ROOT / "tools"))
import stage_table  # noqa: E402

FIXTURE = ROOT / "benchmark" / "fixtures"
MS = 1e6   # ns

# a map as `telemetry.stage_map` gives it: `while.8` (the split loop)
# and `add.9` have no stage
STAGE_OF = {"fusion.1": ("table_update", None), "copy.3": ("partition", 1),
            "fusion.6": ("score_update", None)}
# a device's trace as `trace_reduce.read_planes` gives it: two tree
# programs of 100 ms each and one other module between them
_OPS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 0, 40 * MS),
    ("%while.8 = (s32[], f32[8]{0}) while(%init)", 40 * MS, 100 * MS),
    ("%copy.3 = f32[8]{0:T(128)} copy(%acc)", 50 * MS, 70 * MS),
    ("%mystery.1 = f32[8]{0} add(%x, %y)", 70 * MS, 80 * MS),
    ("%copy.3 = f32[8]{0:T(128)} copy(%acc)", 120 * MS, 130 * MS),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 150 * MS, 190 * MS),
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 200 * MS, 250 * MS),
    ("%add.9 = f32[8]{0} add(%out, %fusion.6)", 250 * MS, 260 * MS)]
PLANES = {"/device:TPU:0": {
    "XLA Modules": [("jit_step_impl(7)", 0, 100 * MS),
                    ("jit_other(3)", 110 * MS, 140 * MS),
                    ("jit_step_impl(7)", 200 * MS, 300 * MS)],
    "XLA Ops": _OPS}, "/host:CPU": {}}


def test_stage_times_join_the_trace_to_the_map_inside_the_programs():
    out = stage_join.stage_times(PLANES, STAGE_OF)
    assert out["trees"] == 2 and out["tree_program_ms"] == 100
    stage_ms = out["stage_ms"]
    # every stage the map names; one that ran nothing reads 0
    assert stage_ms["score_update"] == 0
    assert set(stage_ms) == {"table_update", "partition", "score_update"}
    # `fusion.1` 40 + 50 in the programs, not the 40 between them;
    # `copy.3` 20 inside the split loop, not the 10 of the other module
    assert stage_ms["table_update"] == (40 + 50) / 2
    assert stage_ms["partition"] == 20 / 2
    # the split loop's own time (60 less the 30 of its body), a name
    # the map lacks and one it holds no stage for
    assert out["unstaged_ms"] == (30 + 10 + 10) / 2
    assert out["idle_ms"] == 40 / 2
    assert (sum(stage_ms.values()) + out["unstaged_ms"] + out["idle_ms"]
            == out["tree_program_ms"])
    assert out["unstaged_tree_pct"] == 25
    assert out["unstaged_ops"][0] == [
        "%while.8 = (s32[], f32[8]) while(%init)", 15]
    # the first program alone
    first = stage_join.stage_times(PLANES, STAGE_OF, count=1)
    assert first["trees"] == 1 and first["stage_ms"]["table_update"] == 40


def test_stage_times_read_nothing_without_a_map_or_a_program():
    assert stage_join.stage_times(PLANES, {}) is None
    assert stage_join.stage_times(PLANES, STAGE_OF, count=3) is None
    assert stage_join.stage_times({"/host:CPU": {}}, STAGE_OF) is None


@pytest.mark.parametrize("op,name", [
    ("%fusion.878 = (u32[65536,11]) fusion(%a)", "fusion.878"),
    ("copy-start.2 = (f32[8], u32[]) copy-start(%b)", "copy-start.2"),
    ("ROOT-less text", "ROOT-less text")])
def test_an_event_is_joined_by_its_instructions_name(op, name):
    assert stage_join.instruction_name(op) == name


def test_stage_table_tool_reads_a_recorded_trace(tmp_path, capsys):
    """On the benchmark's recorded trace of three tree programs: the
    first two last what the benchmark's own reduction says they last,
    and the stages, unstaged and idle add up to it."""
    expected = json.loads((FIXTURE / "expected.json").read_text())
    stage_map_path = tmp_path / "map.json"
    stage_map_path.write_text(json.dumps({"fusion.15": ["partition", 3]}))
    trace = str(FIXTURE / "tiny.xplane.pb")
    assert stage_table.main([trace, str(stage_map_path), "--trees", "2",
                             "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["trees"] == 2
    assert out["tree_program_ms"] == pytest.approx(
        expected["tree_program_ms"], rel=1e-6)
    assert 0 < out["stage_ms"]["partition"] < out["tree_program_ms"]
    assert (sum(out["stage_ms"].values()) + out["unstaged_ms"]
            + out["idle_ms"]) == pytest.approx(out["tree_program_ms"])
    assert stage_table.main([trace, str(stage_map_path), "--trees",
                             "9"]) == 1
    # the table form
    assert stage_table.main([trace, str(stage_map_path)]) == 0
    assert "| partition |" in capsys.readouterr().out


def test_stage_table_tool_runs_a_cell(tiny_root, tmp_path, capsys):
    """The cell form on the CPU: the harness's set-up, three traced
    iterations and the probe, which counts no compile and maps the
    stages of the tiny cell's program (the masked core, which has no
    `row_leaf` pass); a CPU trace holds no device plane, so no stage is
    read (exit 1)."""
    assert stage_table.main(["--cell", "tiny-train", "--seed",
                             str(2**31 + 39), "--root", str(tiny_root),
                             "--out", str(tmp_path)]) == 1
    cost = json.loads(next(ln for ln in capsys.readouterr().err
                           .splitlines() if ln.startswith('{"cell"')))
    assert cost["probe_events_counted"] == 0
    assert cost["probe_seconds_counted"] is False
    assert {"partition", "child_hist", "split_scan"} \
        <= set(cost["map_stages"])
    stage_of = json.loads((tmp_path / "map.json").read_text())
    assert len(stage_of) == cost["map_entries"] > 0
    assert json.loads((tmp_path / "stages.json").read_text())[
        "stages"] is None
    assert not (tiny_root / ".stage_trace").exists()
