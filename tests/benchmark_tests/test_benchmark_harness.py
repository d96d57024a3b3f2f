"""CPU rehearsal of the harness at a tiny size, and the shape of
`BENCHMARK.json`."""
import json
import re
import subprocess
import sys

import pytest

from bench_rehearsal import ROOT, tiny_root  # noqa: F401 (a fixture)

from benchmark import drive, run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


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


def names_and_units():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[section]:
            yield section, entry


def test_names_units_and_key_sets():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    seen = set()
    for section, entry in names_and_units():
        assert NAME.match(entry["name"]), entry
        assert (section, entry["name"]) not in seen
        seen.add((section, entry["name"]))
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry
            assert entry["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_file_is_found_by_name():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert hasattr(drive.load_traffic(cell["traffic"]["kind"]), "run")
        if "generator" in cell["config"]:
            assert hasattr(spec.load_generator(
                cell["config"]["generator"]), "generate")
        conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
        assert conf["reduced"] == cell["config"]["reduced"]
        assert conf["source"] == cell["config"]["source"]
        assert cell["workload"]["limits"]
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    assert set(spec.layer_metric_names()) == set(declared)
    for name, m in declared.items():
        mod = spec.load_layer_metric(name)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])
