"""Environment knobs shared across modules (single parse, single name)."""
from __future__ import annotations

import os

_TRUE = ("1", "true", "yes", "on")


def flag(name: str, default: str = "0") -> bool:
    return os.environ.get(name, default).strip().lower() in _TRUE


def use_pallas_env() -> bool:
    """Opt-in to the Pallas histogram kernels (both learners honor both
    spellings; default off). Mosaic compiles them for a TPU only, so the
    request anywhere else is an error, not a quiet switch to XLA."""
    want = flag("LGBM_TPU_PALLAS") or flag("LGBM_TPU_PALLAS_HIST")
    if want:
        import jax
        if jax.default_backend() != "tpu":
            from .log import LightGBMError
            raise LightGBMError(
                "LGBM_TPU_PALLAS=1 asks for the Pallas histogram kernels, "
                "which compile only for a TPU; this backend is %r"
                % jax.default_backend())
    return want


def pipeline_env() -> bool:
    """LGBM_TPU_PIPELINE: overlap the fused iteration's split-record
    D2H fetch + host tree replay with the NEXT iteration's device
    program (models materialize lazily through GBDT.models). Default on
    for TPU, where the record fetch is a device round trip per iteration
    (its cost on today's code: not measured), and off elsewhere (on CPU
    the fetch is free and the synchronous path keeps step-debugging
    simple)."""
    v = os.environ.get("LGBM_TPU_PIPELINE", "").strip().lower()
    if v:
        return v in _TRUE
    import jax
    return jax.default_backend() == "tpu"


def strategy_env(default: str = "auto") -> str:
    """LGBM_TPU_STRATEGY: auto | masked | compact | chunk — the ONE
    read shared by the device learner's resolve_strategy and the
    sharded learners' chunk opt-in."""
    return os.environ.get("LGBM_TPU_STRATEGY", default).strip().lower()


def dp_reduce_mode_env() -> str:
    """LGBM_TPU_DP_REDUCE: 'scatter' (reference comm pattern, default) or
    'psum' (replicated histograms) for the data-parallel device learner."""
    return os.environ.get("LGBM_TPU_DP_REDUCE", "scatter").strip().lower()
