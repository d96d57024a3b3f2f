"""The six set-up readers (`find_bin_s`, `bin_data_s`, `bundle_s`,
`learner_build_s`, `trace_lower_s`, `backend_compile_s`) on the CPU rehearsal: each reads the
program's own counters in the run's process, their sums stay inside the
numbers that time the same layer from outside (`dataset_construct_s`,
`compile_s`), and a process without the counter reads nothing. CPU, tiny
size: no second read here is a device number."""
import contextlib
import io
import json

import pytest

from bench_rehearsal import ROOT, tiny_root  # noqa: F401 (a fixture)

from benchmark import run, spec

READERS = ("find_bin_s", "bin_data_s", "bundle_s", "learner_build_s",
           "trace_lower_s", "backend_compile_s")


@pytest.fixture(scope="module")
def traced_line(tiny_root):  # noqa: F811
    """The result line and the phases line of one traced rehearsal, in a
    process whose set-up counters start from nothing (they are sums over
    the process, and a test worker has built other Datasets)."""
    from lightgbm_tpu.telemetry import counters
    counters.reset()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", "tiny-train", "--seed",
                       str(2**31 + 26), "--seconds", "1", "--trace", "1"],
                      root=tiny_root, allow_cpu=True)
    assert rc == 0
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()
             if ln.startswith("{")]
    return lines[-1], lines[-2]["phases"]


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_positive_number(traced_line, name):
    line, _ = traced_line
    assert line["correct"] is True
    assert line["metrics"][name]["unit"] == "s"
    assert line["metrics"][name]["value"] > 0


def test_stage_sums_stay_inside_the_outer_clocks(traced_line):
    line, phases = traced_line
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert value["dataset_construct_s"] == phases["dataset_construct_s"]
    assert (value["find_bin_s"] + value["bin_data_s"] + value["bundle_s"]
            <= value["dataset_construct_s"])
    assert value["learner_build_s"] <= phases["learner_init_s"]
    assert (value["trace_lower_s"] + value["backend_compile_s"]
            <= value["compile_s"] + 1e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_its_counter_reads_nothing(monkeypatch, name):
    """As on a program that has no such counter (the parent commit): the
    reader returns None, never 0, and the line leaves the metric out."""
    from lightgbm_tpu.telemetry import counters
    monkeypatch.setattr(counters, "get", lambda key, default=0: default)
    monkeypatch.setattr(counters, "compile_seconds", dict)
    assert spec.load_layer_metric(name).read({}) is None
