"""`traffic/train.py` takes a table with Dataset fields and the
configuration's own reference (PR 33): one window loop for every
objective. A stub generator and a stub reference module are put in where
`spec.load_generator` / `spec.load_reference` would find a
configuration's own. CPU, tiny sizes: control flow and counts only."""
import json
import types

import numpy as np
import pytest

from bench_rehearsal import run_cell, write_tiny_root

from benchmark import spec
from benchmark.datagen import higgs_like
from benchmark.reference import gbdt_reference
from benchmark.traffic import train

SEED = 2**31 + 33
ALWAYS = ("steps_missing", "compiles_in_window", "nonfinite_score")
QUERY = 20


def stub_root(tmp, rows, params=None, limits=None):
    """The tiny `higgs` root with the configuration naming a generator
    and a reference of its own."""
    root = write_tiny_root(tmp, rows=rows)
    path = root / "benchmark/configs/tiny.json"
    conf = json.loads(path.read_text())
    conf.update(generator="stub_table", reference="stub_reference",
                bins_rows=rows)
    conf["params"].update(params or {})
    path.write_text(json.dumps(conf))
    if limits is not None:
        path = root / "benchmark/workloads/tiny-train.json"
        work = json.loads(path.read_text())
        work["limits"] = limits
        path.write_text(json.dumps(work))
    return root


def put_in(monkeypatch, generate, reference):
    """`generate` and `reference` under the names the stub root's
    configuration gives; every other name is found as before."""
    real_gen, real_ref = spec.load_generator, spec.load_reference
    monkeypatch.setattr(spec, "load_generator", lambda name: (
        types.SimpleNamespace(generate=generate) if name == "stub_table"
        else real_gen(name)))
    monkeypatch.setattr(spec, "load_reference", lambda name: (
        reference if name == "stub_reference" else real_ref(name)))


def watch_datasets(monkeypatch):
    """The keyword arguments of every `lgb.Dataset` the run builds."""
    import lightgbm_tpu as lgb
    seen = []

    class Watched(lgb.Dataset):
        def __init__(self, *args, **kwargs):
            seen.append(kwargs)
            super().__init__(*args, **kwargs)

    monkeypatch.setattr(lgb, "Dataset", Watched)
    return seen


def run_tiny(root):
    return run_cell(root, "tiny-train", SEED)


def test_a_two_tuple_has_no_fields():
    x, y = np.zeros((4, 2), np.float32), np.zeros(4, np.float32)
    got = train.table_and_fields((x, y))
    assert got[0] is x and got[1] is y and got[2] == {}
    fields = {"weight": np.ones(4, np.float32)}
    assert train.table_and_fields((x, y, fields))[2] == fields


def test_weight_reaches_both_datasets_and_the_reference(tmp_path,
                                                        monkeypatch):
    """Unit weights: the plain reference's own readings hold, so the
    run is held to the real limits, through a reference module that
    takes `fields`."""
    built = []

    class Reference(gbdt_reference.Reference):
        def __init__(self, x, y, params, seed, fields):
            built.append(fields)
            assert (fields["weight"] == 1).all()
            super().__init__(x, y, params, seed)

    def generate(seed, rows, features, params):
        x, y = higgs_like.generate(seed, rows, features, params)
        return x, y, {"weight": np.ones(rows, np.float32)}

    put_in(monkeypatch, generate, types.SimpleNamespace(
        Reference=Reference, Outputs=gbdt_reference.Outputs,
        TREE_KEYS=gbdt_reference.TREE_KEYS))
    seen = watch_datasets(monkeypatch)
    line = run_tiny(stub_root(tmp_path, rows=20_000))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) > set(ALWAYS)
    assert len(seen) == 2 and "reference" in seen[1]
    assert all(len(kw["weight"]) == 20_000 for kw in seen)
    assert len(built) == 1 and set(built[0]) == {"weight"}


def test_group_trains_lambdarank_through_the_same_loop(tmp_path,
                                                       monkeypatch):
    """Queries of a fixed short length: the booster the window drives
    is a `lambdarank` one, and the stub reference gives no reading of
    its own, so the line compares what the loop itself counts."""
    built = []

    class Reference:
        def __init__(self, x, y, params, seed, fields):
            built.append((params["objective"], fields))

        def follow(self, outputs):
            assert len(outputs.trees) == len(outputs.scores) == 3
            assert set(outputs.trees[0]) == {"num_leaves", "leaf_value"}
            return {}

    def generate(seed, rows, features, params):
        x, y = higgs_like.generate(seed, rows, features, params)
        grade = (x[:, 0] > 0).astype(np.float32) + y * 2
        return x, grade, {"group": np.full(rows // QUERY, QUERY)}

    put_in(monkeypatch, generate, types.SimpleNamespace(
        Reference=Reference, Outputs=gbdt_reference.Outputs,
        TREE_KEYS=("num_leaves", "leaf_value")))
    seen = watch_datasets(monkeypatch)
    line = run_tiny(stub_root(
        tmp_path, rows=4_000, params={"objective": "lambdarank"},
        limits=dict.fromkeys(ALWAYS, 0)))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["checks"]) == set(ALWAYS)
    assert all(kw["group"].sum() == 4_000 for kw in seen)
    assert [b[0] for b in built] == ["lambdarank"]
    assert set(built[0][1]) == {"group"}


def test_default_reference_is_built_without_fields(tmp_path, monkeypatch):
    """A 2-tuple and no `reference` key: `gbdt_reference.Reference` with
    the four arguments it has always had."""
    calls = []
    real = gbdt_reference.Reference.__init__

    def init(self, *args, **kwargs):
        calls.append((len(args), kwargs))
        real(self, *args, **kwargs)

    monkeypatch.setattr(gbdt_reference.Reference, "__init__", init)
    root = write_tiny_root(tmp_path)
    assert "reference" not in json.loads(
        (root / "benchmark/configs/tiny.json").read_text())
    cell = spec.load_cell(root, "tiny-train")
    state = train.first_steps(cell, SEED, {})
    assert state["fields"] == {} and state["ref"] is gbdt_reference
    del state["booster"]
    _, readings = train.check_first_steps(state, SEED)
    assert calls == [(4, {})]
    limits = cell["workload"]["limits"]
    assert all(readings[k] <= limits[k] for k in readings)


def test_reading_without_a_limit_is_refused(tmp_path, monkeypatch):
    """A reference's reading the workload file states no limit for is an
    error, not a pass (`run.judge`)."""
    def generate(seed, rows, features, params):
        return higgs_like.generate(seed, rows, features, params)

    class Reference(gbdt_reference.Reference):
        def follow(self, outputs):
            return {"a_new_number": 0.0}

    put_in(monkeypatch, generate, types.SimpleNamespace(
        Reference=Reference, Outputs=gbdt_reference.Outputs,
        TREE_KEYS=gbdt_reference.TREE_KEYS))
    with pytest.raises(SystemExit, match="a_new_number"):
        run_tiny(stub_root(tmp_path, rows=20_000))
