"""`correct` has to be able to fail. The control (the reference in the
program's place, gradients in bfloat16) and each planted fault read over
the limits at a size a test can hold; and a run of the harness with the
timed path broken underneath prints `correct: false`."""
import json

import numpy as np
import pytest

from bench_rehearsal import ROOT, tiny_root, write_tiny_root  # noqa: F401

from benchmark import control, run, spec
from benchmark.traffic import train

SEED = 2**31 + 77
LIMITS = json.loads((ROOT / "benchmark/workloads/higgs-train.json")
                    .read_text())["limits"]


def failed_checks(readings):
    return {k for k, v in readings.items() if v > LIMITS[k]}


@pytest.fixture(scope="module")
def variants(tmp_path_factory):
    # leaves of ~2,000 rows, as the cells have tens of thousands: the
    # control's gap shrinks with the leaf, so a toy leaf would flatter it
    root = write_tiny_root(tmp_path_factory.mktemp("ctl"), rows=60_000,
                           leaves=31)
    cell = spec.load_cell(root, "tiny-train")
    state = train.first_steps(cell, SEED, {})
    del state["booster"]
    reference, _ = train.check_first_steps(state, SEED)
    return control.variants(reference, state["outputs"], SEED)


def test_sound_first_steps_pass(variants):
    assert failed_checks(variants["sound"]) == set()


@pytest.mark.parametrize("variant, must_fail", [
    ("control", "leaf_value_gap"),
    ("half_rows", "leaf_count_mismatch"),
    ("frozen", "update_norm_gap"),
    ("altered", "leaf_value_gap"),
])
def test_control_and_faults_fail(variants, variant, must_fail):
    assert must_fail in failed_checks(variants[variant])


def run_broken(root, capsys):
    rc = run.main(["--workload", "tiny-train", "--seed", str(SEED),
                   "--seconds", "0.5", "--trace", "0"],
                  root=root, allow_cpu=True)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def failing(line):
    return {k for k, c in line["checks"].items() if c["value"] > c["limit"]}


def test_step_that_returns_its_state_unchanged(tiny_root, capsys,
                                               monkeypatch):
    import lightgbm_tpu as lgb
    real, calls = lgb.Booster.update, []

    def update(self, *a, **kw):
        calls.append(1)
        if len(calls) >= 3:
            return False          # nothing trained, nothing said
        return real(self, *a, **kw)

    monkeypatch.setattr(lgb.Booster, "update", update)
    line = run_broken(tiny_root, capsys)
    assert line["correct"] is False
    assert "steps_missing" in failing(line) and line["failed"] > 0


def test_half_of_the_batch_left_out(tiny_root, capsys, monkeypatch):
    from lightgbm_tpu.objectives.objective import BinaryLogloss
    real = BinaryLogloss.get_gradients

    def get_gradients(self, score):
        g, h = real(self, score)
        keep = np.arange(g.shape[-1]) < g.shape[-1] // 2
        return g * keep, h * keep   # the sums, and so the means, of half

    monkeypatch.setattr(BinaryLogloss, "get_gradients", get_gradients)
    line = run_broken(tiny_root, capsys)
    assert line["correct"] is False
    assert "leaf_value_gap" in failing(line)


def test_answer_altered_where_it_is_produced(tiny_root, capsys,
                                             monkeypatch):
    from lightgbm_tpu.models.tree import Tree
    real = Tree.apply_shrinkage

    def apply_shrinkage(self, rate):
        real(self, rate)
        self.leaf_value[1] *= 1.01

    monkeypatch.setattr(Tree, "apply_shrinkage", apply_shrinkage)
    line = run_broken(tiny_root, capsys)
    assert line["correct"] is False
    assert failing(line) == {"leaf_value_gap"}
