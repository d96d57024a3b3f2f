"""Distributed bin finding over real multi-process jax.distributed
(2 local CPU processes), mirroring what the reference leaves manual
(reference: src/io/dataset_loader.cpp:573-722 distributed FindBin +
Allgather; examples/parallel_learning is a hand-run recipe only).

The workers each hold HALF the rows, cooperatively find bins, and must
produce BinMappers identical to a single-process run over the full data.
"""
import os
import pickle
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import os, pickle, sys
import numpy as np
import jax

rank = int(sys.argv[1])
port = sys.argv[2]
out = sys.argv[3]
jax.distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                           num_processes=2, process_id=rank)

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.distributed import (distributed_find_bins,
                                         rank_row_range, load_distributed)

r = np.random.RandomState(123)
n, f = 600, 6
data = r.randn(n, f)
data[r.rand(n, f) < 0.05] = np.nan
data[:, 3] = np.round(np.abs(data[:, 3]) * 3)        # categorical-ish
lo, hi = rank_row_range(n, rank, 2)
cfg = Config({"max_bin": 31, "min_data_in_bin": 1, "verbosity": -1})
mappers = distributed_find_bins(data[lo:hi], cfg, categorical=[3])

# also exercise the full load path (bin local rows with shared mappers)
y = (np.nan_to_num(data[:, 0]) > 0).astype(float)
ds = load_distributed(data[lo:hi], cfg, label_local=y[lo:hi],
                      categorical=[3])
assert ds.num_data == hi - lo

payload = [(m.bin_type, m.num_bin, m.missing_type, m.is_trivial,
            [repr(b) for b in m.bin_upper_bound],   # repr: nan == 'nan'
            dict(m.categorical_2_bin))
           for m in mappers]
with open(out, "wb") as fh:
    pickle.dump(payload, fh)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_distributed_bin_finding_matches_single_process(tmp_path):
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # each worker is its own process domain; no virtual device mesh here
    env["XLA_FLAGS"] = ""
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    outs = [tmp_path / f"mappers_{r}.pkl" for r in range(2)]
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), str(port), str(outs[r])],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for r in range(2)]
    for p in procs:
        _, err = p.communicate(timeout=300)
        assert p.returncode == 0, err.decode()[-2000:]

    with open(outs[0], "rb") as fh:
        m0 = pickle.load(fh)
    with open(outs[1], "rb") as fh:
        m1 = pickle.load(fh)
    assert m0 == m1, "ranks disagree on the mapper list"

    # single-process oracle over the full data
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Dataset

    r = np.random.RandomState(123)
    n, f = 600, 6
    data = r.randn(n, f)
    data[r.rand(n, f) < 0.05] = np.nan
    data[:, 3] = np.round(np.abs(data[:, 3]) * 3)
    cfg = Config({"max_bin": 31, "min_data_in_bin": 1, "verbosity": -1})
    ds = Dataset(data, config=cfg,
                 label=(np.nan_to_num(data[:, 0]) > 0).astype(float),
                 categorical_feature=[3])
    single = [(m.bin_type, m.num_bin, m.missing_type, m.is_trivial,
               [repr(b) for b in m.bin_upper_bound],
               dict(m.categorical_2_bin))
              for m in ds.bin_mappers]
    assert m0 == single, "distributed mappers differ from single-process"
