"""Test configuration: run everything on a virtual 8-device CPU mesh.

Distributed learners are validated the way SURVEY.md §4 prescribes: the CPU
backend with xla_force_host_platform_device_count gives N devices without N
chips; the driver's dryrun separately compile-checks the multi-chip path.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent XLA compile cache: DISABLED for the suite, so a run never
# depends on what an earlier run left on disk. The jaxlib-0.4.37 reason
# (a cached executable from a previous process segfaulted the
# interpreter) no longer reproduces under jaxlib 0.9.0 — test_binning +
# test_bundling twice against one cache directory pass warm — but every
# XLA:CPU reload there logs "Machine type used for XLA:CPU compilation
# doesn't match the machine type for execution ... could lead to
# execution errors such as SIGILL" (cpu_aot_loader.cc), and the driver's
# checkout starts with no cache anyway. LGBM_TPU_NO_COMP_CACHE is the
# package's opt-out (lightgbm_tpu/__init__.py holds the one cache rule).
os.environ["LGBM_TPU_NO_COMP_CACHE"] = "1"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# The suite runs under a watchdog timeout that ends it with SIGTERM.
# In-process CLI tests would otherwise install the graceful-preemption
# handlers (resilience/preempt.py) into the PYTEST process — the
# watchdog's SIGTERM would then be swallowed, arm the preempt flag, and
# turn every subsequent training test into an exit-76 cascade. Tests
# that exercise the handlers delete this var via monkeypatch.
os.environ["LGBM_TPU_NO_SIGNAL_HANDLERS"] = "1"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(42)


def make_binary(n=2000, f=10, seed=7):
    r = np.random.RandomState(seed)
    x = r.randn(n, f)
    logit = x[:, 0] * 1.5 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
    y = (logit + r.randn(n) * 0.5 > 0).astype(np.float64)
    return x, y


def make_regression(n=2000, f=10, seed=7):
    r = np.random.RandomState(seed)
    x = r.randn(n, f)
    y = x[:, 0] * 2.0 + np.sin(x[:, 1]) + 0.1 * r.randn(n)
    return x, y


def make_multiclass(n=2000, f=10, k=4, seed=7):
    r = np.random.RandomState(seed)
    centers = r.randn(k, f) * 2.5
    y = r.randint(0, k, n)
    x = centers[y] + r.randn(n, f)
    return x, y.astype(np.float64)


def make_ranking(nq=60, docs_per_q=20, f=8, seed=7):
    r = np.random.RandomState(seed)
    n = nq * docs_per_q
    x = r.randn(n, f)
    rel = np.clip((x[:, 0] + r.randn(n) * 0.5) * 1.2 + 1.5, 0, 4)
    y = np.floor(rel).astype(np.float64)
    group = np.full(nq, docs_per_q)
    return x, y, group
