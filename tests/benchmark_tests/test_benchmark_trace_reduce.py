"""The reduction from a trace to numbers, on planes small enough to check
by hand and on a trace recorded on the chip; and the work model on a
3-leaf tree."""
import numpy as np
import pytest

from bench_rehearsal import ROOT

from benchmark import trace_reduce, work_model

FIXTURE = ROOT / "benchmark" / "fixtures"

MS = 1e6   # ns


def planes_by_hand():
    """Three tree programs of 8 ms, 10 ms apart; inside each, two ops
    that leave 1 ms idle; a stray 0.5 ms op in the first gap."""
    modules = [("jit_step_impl(1)", k * 10 * MS, k * 10 * MS + 8 * MS)
               for k in range(3)]
    modules.append(("jit_other", 8.2 * MS, 8.7 * MS))
    ops = []
    for k in range(3):
        t = k * 10 * MS
        ops += [("while.1", t, t + 4 * MS), ("fusion.2", t + 5 * MS,
                                              t + 8 * MS)]
    ops.append(("copy.3", 8.2 * MS, 8.7 * MS))
    host = [("bench_update", 7.9 * MS, 10.1 * MS),
            ("bench_update", 17.9 * MS, 20.1 * MS),
            ("PjitFunction(step_impl)", 18.5 * MS, 19.9 * MS)]
    return {"/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
            "/host:CPU": {"python": host}}


def test_reduction_by_hand():
    out = trace_reduce.reduce_planes(planes_by_hand(), iterations=2)
    assert out["window_s"] == pytest.approx(0.020)
    # 2 x (4 + 3) ms of ops + 0.5 ms stray
    assert out["busy_s"] == pytest.approx(0.0145)
    assert out["tree_program_ms"] == pytest.approx(8.0)
    # gaps of 2 ms, the first holds 0.5 ms of work
    assert out["iter_gap_ms"] == pytest.approx((1.5 + 2.0) / 2)
    ops = dict(out["device_ops"])
    assert ops["while.1"] == pytest.approx(0.008)
    assert ops["copy.3"] == pytest.approx(0.0005)
    gaps = dict(out["idle_gaps"])
    # in-program idle has no host span; the second gap's midpoint lies
    # in the innermost span, the first gap's in bench_update
    assert gaps["no_host_span"] == pytest.approx(0.002)
    assert gaps["PjitFunction(step_impl)"] == pytest.approx(0.002)
    assert gaps["bench_update"] == pytest.approx(0.0015)


def test_nested_ops_count_their_own_time_and_names_are_short():
    ops = [("%while.1 = (s32[]{:T(128)}, f32[8]{0}) while(...)", 0, 10 * MS),
           ("%fusion.2 = f32[8]{0:T(128)S(1)} fusion(f32[8]{0} %p)", MS,
            4 * MS),
           ("%fusion.2 = f32[8]{0:T(128)S(1)} fusion(f32[8]{0} %p)",
            5 * MS, 8 * MS),
           ("%copy.3 = f32[8]{0} copy(%q)", 11 * MS, 12 * MS)]
    own = trace_reduce.self_times(ops, 0, 11.5 * MS)
    assert own == {"%while.1 = (s32[], f32[8]) while(...)": 4 * MS,
                   "%fusion.2 = f32[8] fusion(f32[8] %p)": 6 * MS,
                   "%copy.3 = f32[8] copy(%q)": 0.5 * MS}
    assert len(trace_reduce.short_name("%w = " + "x" * 5000)) == \
        trace_reduce.NAME_CHARS


def test_too_few_programs_reads_nothing():
    assert trace_reduce.reduce_planes(planes_by_hand(), iterations=3) is None
    assert trace_reduce.reduce_planes({"/host:CPU": {}}, iterations=1) is None


def test_recorded_chip_trace():
    files = sorted(FIXTURE.glob("*.xplane.pb"))
    assert files, "the recorded trace is part of the benchmark"
    out = trace_reduce.reduce_file(files[0], iterations=2)
    assert out is not None
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["tree_program_ms"] > 0 and out["iter_gap_ms"] >= 0
    assert out["tree_program_ms"] * 2 / 1e3 <= out["window_s"]
    assert 1 <= len(out["device_ops"]) <= 10
    assert 1 <= len(out["idle_gaps"]) <= 10
    expected = (FIXTURE / "expected.json")
    if expected.exists():
        import json
        want = json.loads(expected.read_text())
        for key, value in want.items():
            assert out[key] == pytest.approx(value, rel=1e-9), key


def three_leaf_tree():
    # root 100 rows -> leaf0 (30) | node1 (70) -> leaf1 (50) | leaf2 (20)
    return {"num_leaves": 3,
            "left_child": np.array([~0, ~1]), "right_child": np.array([1, ~2]),
            "internal_count": np.array([100, 70]),
            "leaf_count": np.array([30, 50, 20])}


def test_work_model_by_hand():
    moved, ops = work_model.tree_work(100, 4, three_leaf_tree())
    assert moved == (100 * 16 + 100 * 12 + 100 * 8
                     + 100 * 4 + 30 * 12 + 70 * 4 + 20 * 12) == 4880
    assert ops == 2 * 4 * (100 + 30 + 20) == 1200
    work = work_model.window_work(100, 4, [three_leaf_tree()] * 2,
                                  "TPU v5 lite")
    assert work["bound"] == "hbm_bytes"
    assert work["least_s_per_iter"] == pytest.approx(4880 / 819e9)
    with pytest.raises(KeyError):
        work_model.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work_model.peaks_for("_source")
