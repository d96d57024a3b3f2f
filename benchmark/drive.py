"""The one general driver of traffic. A traffic mix is a data file under
`traffic/`; its `kind` names the module `traffic/<kind>.py` that holds
the loop, found by that name, and its other keys are the loop's
parameters. What every kind of traffic needs of the machine is here: the
look for the chip, the device's record, the clock round a set-up phase.

A traffic module has `run(cell, seed, seconds, trace, t0, root,
allow_cpu)` and returns a dict with `attempted`, `failed`, `end_to_end`
(every end-to-end metric the mix measures, by its name in
`BENCHMARK.json`), `readings` (every number `correct` compares), `ctx`
(what the per-layer readers read), `phases` and
`compile_events_in_window`.
"""
from contextlib import contextmanager
import importlib
import time


class NoAccelerator(SystemExit):
    pass


def find_devices(chips, allow_cpu):
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not allow_cpu:
        raise NoAccelerator(f"no accelerator: JAX reports platform "
                            f"{platform!r}")
    if platform == "tpu" and len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX reports "
                            f"{len(devices)}")
    return devices


def device_record(devices, chips):
    peaks = []
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def load_traffic(kind):
    """The module of one kind of traffic, found by its name."""
    try:
        return importlib.import_module(f"benchmark.traffic.{kind}")
    except ModuleNotFoundError as exc:
        if exc.name != f"benchmark.traffic.{kind}":
            raise
        raise SystemExit(f"traffic kind {kind!r} has no module "
                         f"benchmark/traffic/{kind}.py") from None


def run(cell, seed, seconds, trace, t0, root, allow_cpu=False):
    return load_traffic(cell["traffic"]["kind"]).run(
        cell, seed, seconds, trace, t0, root, allow_cpu)


@contextmanager
def timed(phases, name):
    start = time.perf_counter()
    yield
    phases[name] = time.perf_counter() - start
