"""lightgbm_tpu: TPU-native gradient boosting framework.

A from-scratch reimplementation of the LightGBM (v2.3.1) feature surface,
designed TPU-first: binned data as device arrays, histogram construction on
the MXU, split search as vectorized bin scans, distribution via
jax.sharding meshes + XLA collectives. Drop-in Python API:

    import lightgbm_tpu as lgb
    bst = lgb.train(params, lgb.Dataset(X, label=y))
"""
import os as _os

import jax as _jax

CHECKOUT_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_compile_cache")


def _configure_compile_cache() -> None:
    """The one rule for the persistent XLA compile cache (the growth
    ladder compiles for minutes cold, so a cache that misses is most of
    a cold run): where JAX_COMPILATION_CACHE_DIR is set JAX reads it
    itself and no directory is set in code; otherwise the cache lives at
    a fixed path inside the checkout — the path is part of the cache
    key, so it must never move. LGBM_TPU_NO_COMP_CACHE is the test
    suite's opt-out (tests/conftest.py)."""
    if _os.environ.get("LGBM_TPU_NO_COMP_CACHE"):
        return
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)


_configure_compile_cache()


def compile_cache_dir():
    """The persistent compile cache directory in effect (None = off)."""
    return _jax.config.jax_compilation_cache_dir

from . import telemetry
from .basic import Booster, Dataset
from .callback import (EarlyStopException, early_stopping, print_evaluation,
                       record_evaluation, record_telemetry, reset_parameter)
from .engine import CVBooster, cv, train
from .sklearn import (LGBMClassifier, LGBMModel, LGBMRanker, LGBMRegressor)
from .utils.log import LightGBMError

try:
    from .plotting import (plot_importance, plot_metric, plot_split_value_histogram,
                           plot_tree, create_tree_digraph)
except ImportError:  # matplotlib/graphviz absent
    pass

__version__ = "2.3.1.tpu1"

__all__ = [
    "Dataset", "Booster", "CVBooster",
    "train", "cv",
    "LGBMModel", "LGBMRegressor", "LGBMClassifier", "LGBMRanker",
    "early_stopping", "print_evaluation", "record_evaluation",
    "record_telemetry", "reset_parameter", "EarlyStopException",
    "LightGBMError", "telemetry", "compile_cache_dir",
    "plot_importance", "plot_split_value_histogram", "plot_metric",
    "plot_tree", "create_tree_digraph",
]
