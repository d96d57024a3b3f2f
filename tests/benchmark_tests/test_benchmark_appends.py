"""A PR that adds appends, and edits no file that stands (PR 33;
`benchmark/README.md`). A copy of the benchmark's data files with a
made-up configuration, a cell on it, a per-layer metric that lists that
cell and a second one that lists a cell that stands appended, and nothing
else changed, passes every check the real checkout passes: the
structural ones of `bench_rehearsal.STRUCTURE` and every `check_*(root)`
function of every `test_benchmark_*.py` of this directory, found by file
(PR 36: PR 34's last-place pin sat in a per-configuration file that read
the checkout alone, `test_benchmark_msltr.py:40-41`). A check that pins a
length, a last place or the absence of a key fails here, in the PR that
writes it; and a scan of the files' source fails on an index from the
end or a compared length of one of `BENCHMARK.json`'s lists wherever it
is written."""
import ast
import importlib
import inspect
import json
from pathlib import Path
import shutil

import pytest

import bench_rehearsal
from bench_rehearsal import ROOT, STANDS, STRUCTURE

from benchmark import spec

FILES = sorted(Path(__file__).resolve().parent.glob("test_benchmark_*.py"))
SECTIONS = ("configs", "workloads", "end_to_end", "per_layer")
READER = '''"""A made-up reader that finds nothing to read."""
LAYER = "tree program"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_row_trees_per_s"


def read(ctx):
    return None
'''


def file_checks():
    """Every module-level `check_*(root)` a test file of this directory
    defines, by `<file>-<function>`; nobody registers one."""
    found = {}
    for path in FILES:
        if path.stem == __name__:
            continue
        mod = importlib.import_module(path.stem)
        found.update({f"{path.stem}-{name}": fn
                      for name, fn in vars(mod).items()
                      if name.startswith("check_") and inspect.isfunction(fn)
                      and fn.__module__ == mod.__name__})
    return found


FILE_CHECKS = file_checks()


@pytest.fixture(scope="module", params=STANDS["workloads"])
def appended_root(request, tmp_path_factory):
    """The appended copy; the second appended metric lists the cell that
    stands which the parameter names, so each cell's own file meets a
    listed metric of its cell that it did not bring."""
    tmp = tmp_path_factory.mktemp("appended")
    for sub in ("configs", "workloads", "traffic", "layer_metrics"):
        shutil.copytree(ROOT / "benchmark" / sub, tmp / "benchmark" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = bench_rehearsal.load_bench(ROOT)
    conf = json.loads((ROOT / "benchmark/configs/higgs.json").read_text())
    conf.update(name="appended", reference="gbdt_reference")
    work = json.loads((ROOT / "benchmark/workloads/higgs-train.json")
                      .read_text())
    work["config"] = "appended"
    bench["configs"].append({
        "name": "appended", "source": conf["source"],
        "file": "benchmark/configs/appended.json",
        "reduced": conf["reduced"], "why": "made up"})
    bench["workloads"].append({
        "name": "appended-train", "config": "appended",
        "traffic": "train_window", "chips": 1, "why": "made up"})
    for name, cell in (("appended.metric", "appended-train"),
                       ("appended.listed", request.param)):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "tree program",
            "moves": "train_row_trees_per_s", "workloads": [cell]})
        (tmp / "benchmark/layer_metrics"
         / f"{name.replace('.', '__')}.py").write_text(READER)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/appended.json").write_text(json.dumps(conf))
    (tmp / "benchmark/workloads/appended-train.json").write_text(
        json.dumps(work))
    return tmp


@pytest.mark.parametrize("check", sorted(STRUCTURE))
def test_structure_holds_with_entries_appended(appended_root, check):
    STRUCTURE[check](appended_root)


@pytest.mark.parametrize("check", sorted(FILE_CHECKS))
def test_file_checks_hold_with_entries_appended(appended_root, check):
    FILE_CHECKS[check](appended_root)


def test_the_files_that_check_a_real_cell_are_found():
    """The glob finds the files and their checks: the five that state a
    check of `BENCHMARK.json` today, by name, and whatever a later PR
    brings."""
    assert {check.split("-")[0] for check in FILE_CHECKS} >= {
        "test_benchmark_criteo", "test_benchmark_msltr",
        "test_benchmark_rung_rows", "test_benchmark_table_readers",
        "test_benchmark_tiled_partition"}
    for check, fn in FILE_CHECKS.items():
        assert list(inspect.signature(fn).parameters) == ["root"], check


def test_listed_metric_is_its_cells_alone(appended_root):
    def names(root, cell):
        return [m["name"] for m in spec.load_cell(root, cell)["per_layer"]]

    per_layer = bench_rehearsal.load_bench(appended_root)["per_layer"]
    unlisted = [m["name"] for m in per_layer if "workloads" not in m]
    listed, = per_layer[at(per_layer, "appended.listed")]["workloads"]
    assert names(appended_root, "appended-train") \
        == unlisted + ["appended.metric"]
    # what the checkout's own cells report is what they report without
    # the appended entries, and the one a new metric lists reports that
    for cell in STANDS["workloads"]:
        assert names(appended_root, cell) == names(ROOT, cell) + (
            ["appended.listed"] if cell == listed else [])


def at(entries, name):
    return next(i for i, e in enumerate(entries) if e["name"] == name)


def change(section, name, **keys):
    return lambda b: b[section][at(b[section], name)].update(keys)


@pytest.mark.parametrize("break_it", [
    lambda b: b["configs"].insert(0, b["configs"].pop()),
    lambda b: b["workloads"].pop(0),
    lambda b: b["per_layer"].insert(3, b["per_layer"].pop()),
    lambda b: b["per_layer"][0].update(workloads=["higgs-train"]),
    # the entries PR 36 brought under the pin: moved, dropped, re-listed
    lambda b: b["configs"].pop(at(b["configs"], "msltr")),
    change("workloads", "msltr-train", config="higgs"),
    lambda b: b["per_layer"].insert(
        at(b["per_layer"], "table_bytes_per_row"),
        b["per_layer"].pop(at(b["per_layer"], "rung_row_inflation"))),
    lambda b: b["per_layer"].pop(at(b["per_layer"], "rank_pair_fill_share")),
    change("per_layer", "missing_split_share",
           workloads=["criteo-train", "msltr-train"]),
    lambda b: b["per_layer"][at(b["per_layer"], "objective_init_s")].pop(
        "workloads"),
    change("per_layer", "rung_row_inflation",
           workloads=["higgs-train", "criteo-train", "msltr-train"]),
    change("per_layer", "table_bytes_per_row", workloads=["higgs-train"]),
], ids=["config-moved", "cell-dropped", "metric-moved", "sixteen-listed",
        "msltr-dropped", "msltr-train-on-another-config",
        "rung_row_inflation-moved", "rank_pair_fill_share-dropped",
        "missing_split_share-relisted", "objective_init_s-unlisted",
        "rung_row_inflation-listed", "table_bytes_per_row-listed"])
def test_what_stands_is_still_pinned(appended_root, tmp_path, break_it):
    """The prefix check fails when an entry that stands moves, goes, or
    takes, loses or changes its list."""
    bench = bench_rehearsal.load_bench(appended_root)
    break_it(bench)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(AssertionError):
        bench_rehearsal.check_what_stands_is_a_prefix(tmp_path)


# -- the scan: no index from the end, no compared length -----------------
def mentions(node, tainted):
    """Whether `node` subscripts one of `BENCHMARK.json`'s lists by its
    key, or names something made from one."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Subscript) \
                and isinstance(sub.slice, ast.Constant) \
                and sub.slice.value in SECTIONS:
            return True
        if isinstance(sub, ast.Name) and sub.id in tainted:
            return True
    return False


def taint(nodes, tainted):
    """`tainted` and the names that `nodes` assign (or bind by `for`, a
    comprehension or `with`) from an expression that `mentions` a list:
    the list itself, an entry of it, a list of its entries' names."""
    def bound(target):
        return {n.id for n in ast.walk(target) if isinstance(n, ast.Name)}

    tainted, before = set(tainted), None
    while len(tainted) != before:
        before = len(tainted)
        for node in nodes:
            pairs = []
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                                 ast.NamedExpr)) and node.value is not None:
                for target in getattr(node, "targets", None) or [node.target]:
                    if isinstance(target, ast.Tuple) \
                            and isinstance(node.value, ast.Tuple) \
                            and len(target.elts) == len(node.value.elts):
                        pairs += zip(target.elts, node.value.elts)
                    else:
                        pairs.append((target, node.value))
            elif isinstance(node, (ast.For, ast.comprehension)):
                pairs.append((node.target, node.iter))
            elif isinstance(node, ast.withitem) and node.optional_vars:
                pairs.append((node.optional_vars, node.context_expr))
            for target, value in pairs:
                if mentions(value, tainted):
                    tainted |= bound(target)
    return tainted


def pins(nodes, tainted):
    def negative(node):
        return isinstance(node, ast.UnaryOp) \
            and isinstance(node.op, ast.USub)

    found = []
    for node in nodes:
        if isinstance(node, ast.Subscript) and mentions(node.value, tainted):
            s = node.slice
            if negative(s) or (isinstance(s, ast.Slice)
                               and (negative(s.lower) or negative(s.upper))):
                found.append((node.lineno, "an index from the end"))
        if isinstance(node, ast.Compare):
            for side in [node.left] + node.comparators:
                # `len(x)` itself or arithmetic on it, not a slice by it
                if isinstance(side, (ast.Call, ast.BinOp)) and any(
                        isinstance(c, ast.Call)
                        and getattr(c.func, "id", None) == "len"
                        and mentions(c, tainted) for c in ast.walk(side)):
                    found.append((node.lineno, "a compared length"))
    return found


def pins_in(source):
    """`(line, what)` for every place in `source` that takes an element
    or a slice from the END of one of `BENCHMARK.json`'s lists, or
    compares such a list's length: by the key (`bench["configs"][-1]`)
    or through a name made from one (`names[-1]`, `len(names) == 16`),
    followed function by function, each with the module's own names."""
    tree = ast.parse(source)
    defs = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
    top = [n for stmt in tree.body if not isinstance(stmt, defs)
           for n in ast.walk(stmt)]
    shared = taint(top, set())
    found = pins(top, shared)
    for stmt in tree.body:
        if isinstance(stmt, defs):
            nodes = list(ast.walk(stmt))
            found += pins(nodes, taint(nodes, shared))
    return sorted(set(found))


@pytest.mark.parametrize("path", FILES, ids=[p.stem for p in FILES])
def test_no_file_pins_a_last_place_or_a_length(path):
    """Every test file of this directory but `bench_rehearsal.py` (which
    holds the prefix check and is a `benchmark` PR's to extend)."""
    assert pins_in(path.read_text()) == [], path.name


@pytest.mark.parametrize("source, what", [
    # PR 34's two (`test_benchmark_msltr.py:40-41` as it was)
    ('assert BENCH["configs"][-1] == entry', "an index from the end"),
    ('assert BENCH["workloads"][-1]["name"] == "msltr-train"',
     "an index from the end"),
    # PR 27's and PR 29's, which PR 33 re-pinned
    ('names = [m["name"] for m in bench["per_layer"]]\n'
     'assert len(names) == 16 and names[-1] == "x"', "a compared length"),
    ('declared = [m["name"] for m in bench["per_layer"]]\n'
     'assert declared[-1] == NAME', "an index from the end"),
    ('entry = bench["per_layer"][-1]', "an index from the end"),
    ('for w in bench["workloads"][-2:]:\n    pass', "an index from the end"),
    ('cell = spec.load_cell(ROOT, "x")\n'
     'assert 22 == len(cell["per_layer"])', "a compared length"),
])
def test_the_scan_finds_a_pin(source, what):
    assert what in [w for _, w in pins_in(source)]


@pytest.mark.parametrize("source", [
    'entry = next(c for c in BENCH["configs"] if c["name"] == "msltr")',
    'names = [m["name"] for m in bench["per_layer"]]\n'
    'assert names.index("a") < names.index("b")',
    'assert [e["name"] for e in bench[section]][:len(names)] == names',
    'line = json.loads(out[-1])\nassert len(calls) >= 3',
    'assert len(conf["source"]) <= 200',
])
def test_the_scan_lets_a_prefix_and_a_name_through(source):
    assert pins_in(source) == []
