"""What the benchmark's tests share: a temporary checkout with one tiny
cell. They run on the CPU at tiny sizes (`tests/conftest.py` holds JAX
to the CPU and keeps the compile cache off), so no time, rate or share
read here is a device number. This directory has no `conftest.py`: 27
tier-1 files do `from conftest import ...`, and a second module of that
name would shadow theirs."""
import json
from pathlib import Path
import sys

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

TINY_ROWS = 20_000
TINY_LEAVES = 15


def write_tiny_root(tmp, rows=TINY_ROWS, leaves=TINY_LEAVES):
    """A temporary checkout's data files: the `higgs` configuration cut
    to a tiny size and one cell on it, with the real traffic mix, limits
    and metric lists."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "benchmark/configs/higgs.json").read_text())
    conf.update(name="tiny", rows=rows)
    conf["params"]["num_leaves"] = leaves
    work = json.loads((ROOT / "benchmark/workloads/higgs-train.json")
                      .read_text())
    work["config"] = "tiny"
    bench["configs"] = [{"name": "tiny", "source": conf["source"],
                         "file": "benchmark/configs/tiny.json",
                         "reduced": [], "why": "tiny rehearsal"}]
    bench["workloads"] = [{"name": "tiny-train", "config": "tiny",
                           "traffic": "train_window", "chips": 1,
                           "why": "tiny rehearsal"}]
    for sub in ("configs", "workloads", "traffic"):
        (tmp / "benchmark" / sub).mkdir(parents=True)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "benchmark/configs/tiny.json").write_text(json.dumps(conf))
    (tmp / "benchmark/workloads/tiny-train.json").write_text(
        json.dumps(work))
    (tmp / "benchmark/traffic/train_window.json").write_text(
        (ROOT / "benchmark/traffic/train_window.json").read_text())
    return tmp


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny"))
