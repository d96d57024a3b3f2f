"""CPU rehearsal of the harness at a tiny size, and the shape of
`BENCHMARK.json`."""
import json
import subprocess
import sys

import pytest

import bench_rehearsal
from bench_rehearsal import ROOT, tiny_root  # noqa: F401 (a fixture)

from benchmark import run, spec

BENCH = bench_rehearsal.load_bench(ROOT)


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), [json.loads(ln) for ln in out[:-1]
                                 if ln.startswith("{")]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(tiny_root, capsys, trace):
    rc = run.main(["--workload", "tiny-train", "--seed", str(2**31 + 11),
                   "--seconds", "1", "--trace", str(trace)],
                  root=tiny_root, allow_cpu=True)
    assert rc == 0
    line, earlier = last_line(capsys)
    assert list(line)[-1] == "checks"
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert line["device"]["platform"] == "cpu"      # and says so
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    for check in line["checks"].values():
        assert set(check) == {"value", "limit"}
    # the tiny cell is in no metric's `workloads` list
    wanted = BENCH["per_layer"] if trace else BENCH["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted if "workloads" not in m}
    assert set(line["metrics"]) <= set(units)
    if not trace:
        assert set(line["metrics"]) == set(units)
    else:
        # no device trace on the CPU: those readers return nothing
        assert {"grow_dispatches_per_tree", "compile_s",
                "dataset_construct_s"} <= set(line["metrics"])
    for name, m in line["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    phases = earlier[-1]["phases"]
    assert {"datagen_s", "dataset_construct_s", "learner_init_s",
            "compile_s", "warmup_s"} <= set(phases)


def test_no_accelerator_is_an_error_and_prints_no_line(tiny_root):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "higgs-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "LGBM_TPU_NO_COMP_CACHE": "1"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr


def test_names_units_and_key_sets():
    bench_rehearsal.check_names_units_and_key_sets(ROOT)


def test_every_file_is_found_by_name():
    bench_rehearsal.check_every_file_is_found_by_name(ROOT)
    # the harness loads the readers the check read, from the same files
    for name in spec.layer_metric_names():
        assert spec.load_layer_metric(name).__file__ == \
            bench_rehearsal.reader_module(ROOT, name).__file__
