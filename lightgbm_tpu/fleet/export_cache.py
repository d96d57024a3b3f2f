"""Persistent compiled-predictor cache: zero-compile process restarts.

The in-memory PredictorCache makes the first request after warm-up a
pure cache hit — but every process start pays the full warm-up compile
bill again. For a fleet rollout ("restart 200 replicas") that bill is
the difference between a zero-error rolling restart and minutes of cold
replicas. This module persists warm executables on disk, next to the
model file, so a restart skips the compiles entirely.

Every entry carries TWO serialization layers:

* **native** — the XLA executable itself
  (`jax.experimental.serialize_executable`). Loading it is pure
  deserialization: zero trace, zero lower, zero backend compile — the
  `telemetry.counters.compile_events` listener records NOTHING on a
  cache-hit restart (the acceptance property). Valid only when the
  environment fingerprint (jax + jaxlib version, backend, donation
  flag) matches exactly.
* **stablehlo** — the `jax.export` serialized StableHLO module. Survives
  a jaxlib upgrade (the native layer's main invalidation): restoring
  from it skips the Python retrace but pays one backend compile per
  bucket ("rebuilt", counted separately from hits).

Entry identity (the file name) is the sha256 of the executable family —
the registry's ensemble shape signature, feature count, objective
convert key, placement device — plus the batch bucket. The environment
fingerprint deliberately lives INSIDE the entry, not in the key: a
jaxlib bump overwrites entries in place instead of stranding stale
files.

Writes are atomic (tmp + os.replace) and torn/corrupt entries are
treated as misses, mirroring the checkpoint discipline of
resilience/checkpoint.py.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import struct
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from ..telemetry import counters as telem_counters
from ..utils import log

__all__ = ["ExportCache", "cache_dir_for_model", "env_fingerprint"]

_MAGIC = b"LGBMTPUXC1\n"
_registered = {"done": False}


def _register_pytrees() -> None:
    """jax.export serializes the argument pytree structure; custom
    NamedTuples must be registered once per process or export() refuses
    the whole function (the stablehlo layer would silently vanish)."""
    if _registered["done"]:
        return
    try:
        from jax import export as jax_export
        from ..ops.predict import EnsembleArrays
        jax_export.register_namedtuple_serialization(
            EnsembleArrays,
            serialized_name="lightgbm_tpu.ops.predict.EnsembleArrays")
    except Exception as exc:   # noqa: BLE001 — double-register / old jax
        log.debug("export cache: pytree registration skipped: %s", exc)
    _registered["done"] = True


def _jaxlib_version() -> str:
    try:
        import jaxlib
        return getattr(jaxlib, "__version__", "") or \
            getattr(getattr(jaxlib, "version", None), "__version__", "?")
    except Exception:                      # pragma: no cover - no jaxlib
        return "?"


def env_fingerprint(donate: bool) -> Dict[str, str]:
    """The native layer's validity domain: an executable deserializes
    safely only into the exact runtime that serialized it."""
    import jax
    return {"jax": jax.__version__,
            "jaxlib": _jaxlib_version(),
            "backend": jax.default_backend(),
            "donate": "1" if donate else "0"}


def cache_dir_for_model(model_file: str) -> str:
    """The on-disk location convention: `<model_file>.xcache/` — the
    cache travels with the model artifact through a rollout."""
    return str(model_file) + ".xcache"


class ExportCache:
    """One on-disk directory of serialized predictor executables."""

    def __init__(self, cache_dir: str):
        self.cache_dir = str(cache_dir)
        self.last_restore: Dict[str, int] = {}

    # -- keys -----------------------------------------------------------
    @staticmethod
    def entry_name(family: Tuple, bucket: int) -> str:
        digest = hashlib.sha256(
            repr((family, int(bucket))).encode()).hexdigest()[:32]
        return f"{digest}.xc"

    def _path(self, family: Tuple, bucket: int) -> str:
        return os.path.join(self.cache_dir, self.entry_name(family, bucket))

    # -- write ----------------------------------------------------------
    def save(self, model, predictor, overwrite: bool = False) -> int:
        """Serialize every warm executable belonging to `model` (matched
        by ensemble shape signature + device) into the cache dir.
        Returns the number of entries written; existing entries are kept
        unless `overwrite` (their native layer is already valid here —
        this process just loaded them)."""
        entries = [(fam, bucket, compiled)
                   for fam, bucket, compiled in predictor.entries()
                   if fam[0] == model.shape_sig
                   and fam[6] == model.device_key]
        if not entries:
            return 0
        os.makedirs(self.cache_dir, exist_ok=True)
        written = 0
        for family, bucket, compiled in entries:
            path = self._path(family, bucket)
            if not overwrite and os.path.exists(path):
                continue
            try:
                self._write_entry(path, family, bucket, model, predictor,
                                  compiled)
                written += 1
                telem_counters.incr("export_cache_saves")
            except Exception as exc:   # noqa: BLE001 — cache is best-effort
                log.warning("export cache: serialize bucket=%d failed: %s",
                            bucket, exc)
        if written:
            log.info("export cache: wrote %d executable(s) to %s",
                     written, self.cache_dir)
        return written

    def _write_entry(self, path, family, bucket, model, predictor,
                     compiled) -> None:
        from jax.experimental import serialize_executable
        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        trees = pickle.dumps((in_tree, out_tree))
        hlo = self._export_stablehlo(family, bucket, model, predictor)
        header = json.dumps({
            "env": env_fingerprint(predictor.donate_input),
            "bucket": int(bucket),
            "n_features": int(family[1]),
            "raw_score": bool(family[4]),
            "device": family[6],
            "version": model.version,
            "created_unix": round(time.time(), 3),
            "native_len": len(payload),
            "trees_len": len(trees),
            "hlo_len": len(hlo),
        }).encode()
        tmp = path + ".tmp"
        with open(tmp, "wb") as fh:
            fh.write(_MAGIC)
            fh.write(struct.pack(">I", len(header)))
            fh.write(header)
            fh.write(payload)
            fh.write(trees)
            fh.write(hlo)
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)

    def _export_stablehlo(self, family, bucket, model, predictor) -> bytes:
        """The portable layer: re-export the same scoring function as
        serialized StableHLO. Best-effort — an export failure degrades
        the entry to native-only."""
        try:
            from jax import export as jax_export
            import jax
            _register_pytrees()
            fn = predictor._make_fn(model, raw_score=bool(family[4]))
            x_ex = np.zeros((int(bucket), int(family[1])), dtype=np.float32)
            exp = jax_export.export(jax.jit(fn))(
                x_ex, model.arrays, model.tree_class, model.denom)
            return exp.serialize()
        except Exception as exc:   # noqa: BLE001 — optional layer
            log.debug("export cache: stablehlo export failed: %s", exc)
            return b""

    # -- read -----------------------------------------------------------
    def restore(self, model, predictor, buckets: Sequence[int],
                raw_flags: Sequence[bool] = (False,)) -> Dict[str, int]:
        """Install cached executables for every (bucket, raw_score) pair
        into `predictor`. Exact-environment entries load natively (zero
        compiles); stale-environment entries rebuild from StableHLO (one
        backend compile, no Python retrace); anything else is a miss the
        caller warms the ordinary way. Returns {restored, rebuilt,
        missed} and remembers it in `last_restore`."""
        from ..ops.predict import _bucket_up
        stats = {"restored": 0, "rebuilt": 0, "missed": 0}
        want_env = env_fingerprint(predictor.donate_input)
        for raw in raw_flags:
            family = predictor.family(model, model.num_features, bool(raw))
            for bucket_rows in buckets:
                bucket = min(_bucket_up(max(1, int(bucket_rows))),
                             predictor.max_batch_rows)
                entry = self._read_entry(self._path(family, bucket))
                if entry is None:
                    stats["missed"] += 1
                    telem_counters.incr("export_cache_misses")
                    continue
                header, payload, trees, hlo = entry
                if header["env"] == want_env and self._install_native(
                        predictor, model, family, bucket, payload, trees):
                    stats["restored"] += 1
                    telem_counters.incr("export_cache_hits")
                elif hlo and self._install_rebuilt(
                        predictor, model, family, bucket, hlo):
                    stats["rebuilt"] += 1
                    telem_counters.incr("export_cache_rebuilds")
                else:
                    stats["missed"] += 1
                    telem_counters.incr("export_cache_misses")
        self.last_restore = dict(stats)
        telem_counters.set_gauge(
            "export_cache_last_restored", stats["restored"])
        return stats

    def _read_entry(self, path: str):
        try:
            with open(path, "rb") as fh:
                if fh.read(len(_MAGIC)) != _MAGIC:
                    return None
                (hlen,) = struct.unpack(">I", fh.read(4))
                header = json.loads(fh.read(hlen))
                payload = fh.read(header["native_len"])
                trees = fh.read(header["trees_len"])
                hlo = fh.read(header["hlo_len"])
                if (len(payload), len(trees), len(hlo)) != (
                        header["native_len"], header["trees_len"],
                        header["hlo_len"]):
                    return None                     # torn write
                return header, payload, trees, hlo
        except (OSError, ValueError, KeyError, struct.error):
            return None

    def _install_native(self, predictor, model, family, bucket, payload,
                        trees) -> bool:
        try:
            import jax
            from jax.experimental import serialize_executable
            in_tree, out_tree = pickle.loads(trees)
            # a single-device executable must be told its device, or the
            # loader assumes one shard per local device
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[model.device or jax.devices()[0]])
            predictor.install(family, bucket, compiled)
            return True
        except Exception as exc:   # noqa: BLE001 — fall through to hlo
            log.warning("export cache: native load bucket=%d failed: %s",
                        bucket, exc)
            return False

    def _install_rebuilt(self, predictor, model, family, bucket,
                         hlo: bytes) -> bool:
        try:
            from jax import export as jax_export
            import jax
            _register_pytrees()
            exp = jax_export.deserialize(hlo)
            x_ex = np.zeros((int(bucket), int(family[1])), dtype=np.float32)
            compiled = jax.jit(exp.call).lower(
                x_ex, model.arrays, model.tree_class,
                model.denom).compile()
            predictor.install(family, bucket, compiled)
            return True
        except Exception as exc:   # noqa: BLE001 — degrade to a miss
            log.warning("export cache: stablehlo rebuild bucket=%d "
                        "failed: %s", bucket, exc)
            return False

    # -- introspection ---------------------------------------------------
    def info(self) -> Dict[str, object]:
        try:
            files = [f for f in os.listdir(self.cache_dir)
                     if f.endswith(".xc")]
            size = sum(os.path.getsize(os.path.join(self.cache_dir, f))
                       for f in files)
        except OSError:
            files, size = [], 0
        return {"dir": self.cache_dir, "entries": len(files),
                "bytes": size, "last_restore": dict(self.last_restore)}
