"""Process-group bootstrap: config surface -> jax.distributed -> Mesh.

Maps the reference's cluster bring-up (reference:
src/network/linkers_socket.cpp:80 — rank = index of the local address
in the ``machines`` list, full-mesh TCP handshake) onto
``jax.distributed.initialize``: entry 0 of the machine list is the
coordinator, every process dials it, and the platform runtime owns the
transport from there. Collectives never run in userspace — they are XLA
ops inside the jitted tree programs — so the only host-side state this
module keeps is the process identity and the global `Mesh`.

Env-var overrides (launchers like SLURM/k8s indexed jobs set these
instead of editing configs):

* ``LGBM_TPU_COORDINATOR``   — ``host:port`` of process 0
* ``LGBM_TPU_NUM_PROCESSES`` — world size
* ``LGBM_TPU_PROCESS_ID``    — this process's rank

On the CPU backend, cross-process collectives need an explicit
implementation (gloo); `_enable_cpu_collectives` flips the jax config
flag BEFORE the first backend touch — after the CPU client exists the
flag is ignored and every multi-process computation fails with
"Multiprocess computations aren't implemented on the CPU backend".
TPU/GPU need nothing: the fabric is the implementation.
"""
from __future__ import annotations

import os
from typing import Optional

from ..utils import log

_state = {"initialized": False, "num_processes": 1, "rank": 0,
          "mesh": None, "mesh_axis": None}


def _enable_cpu_collectives() -> None:
    """Select gloo for CPU cross-process collectives. Must run before
    jax creates the CPU client; harmless (and skipped) elsewhere."""
    import jax
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        # jaxlib without the flag (or a backend that doesn't need it):
        # leave the default; TPU/GPU transports are built in
        pass


def resolve_rank(entries, explicit_rank: int = -1) -> Optional[int]:
    """Rank of this host in the machine list. ``machine_rank >= 0``
    short-circuits hostname detection (containers often don't resolve
    their external address; the reference has the same escape via
    ``local_listen_port`` disambiguation, linkers_socket.cpp:80)."""
    if explicit_rank >= 0:
        return explicit_rank
    import socket
    my_names = {socket.gethostname(), "localhost", "127.0.0.1"}
    try:
        my_names.add(socket.gethostbyname(socket.gethostname()))
    except OSError:
        pass
    for i, e in enumerate(entries):
        if e.split(":")[0] in my_names:
            return i
    return None


def _initialize_supervised(coordinator_address: str, num_processes: int,
                           process_id: int) -> None:
    """Join the group with the platform coordination service made INERT.

    The stock ``jax.distributed.initialize`` arms the coordination
    service's own heartbeat: when a rank dies, the service tears down
    every *survivor* (hard process abort from a C++ polling thread) —
    the opposite of elastic recovery. So the supervised path builds the
    same service/client pair manually with an effectively-infinite
    heartbeat timeout: the service degenerates to the bootstrap KV
    store the backends need, while OUR supervision
    (distributed/supervisor.py) owns liveness with a clean Python-side
    failure path. ``shutdown_on_destruction=False`` keeps the client
    destructor from joining threads blocked on dead peers during
    shrink."""
    from jax._src import distributed as _jd
    from jax._src.lib import _jax

    # seconds; push the service's death verdict past any plausible job
    inert_s = 10_000_000
    if int(process_id) == 0 and _jd.global_state.service is None:
        port = coordinator_address.rsplit(":", 1)[1]
        _jd.global_state.service = _jax.get_distributed_runtime_service(
            f"[::]:{port}", int(num_processes), heartbeat_timeout=inert_s)
    # init_timeout doubles as the elastic-rejoin wait: a replacement
    # process blocks here until the existing members reach their
    # re-form boundary and rank 0 starts the new service
    init_timeout = int(os.environ.get("LGBM_TPU_INIT_TIMEOUT_S", 60))
    client = _jax.get_distributed_runtime_client(
        coordinator_address, int(process_id), init_timeout=init_timeout,
        heartbeat_timeout=inert_s, shutdown_on_destruction=False,
        use_compression=True)
    client.connect()
    _jd.global_state.client = client
    _jd.global_state.num_processes = int(num_processes)
    _jd.global_state.process_id = int(process_id)
    _jd.global_state.coordinator_address = coordinator_address


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, supervise: bool = False) -> None:
    """Join the process group (idempotent). Bootstrap is a host
    collective boundary: joining retries transient failures with the
    same bounded backoff as in-training collectives
    (resilience/faults.py). ``supervise=True`` (or env
    ``LGBM_TPU_SUPERVISE=1``) routes through the supervised bring-up so
    rank death is OUR layer's to detect, not the platform's to abort
    on."""
    if _state["initialized"]:
        return
    import jax
    from ..resilience import faults
    from ..telemetry import counters
    _enable_cpu_collectives()
    if supervise or os.environ.get("LGBM_TPU_SUPERVISE", "") == "1":
        join = lambda: _initialize_supervised(  # noqa: E731
            coordinator_address, num_processes, process_id)
    else:
        join = lambda: jax.distributed.initialize(  # noqa: E731
            coordinator_address=coordinator_address,
            num_processes=int(num_processes),
            process_id=int(process_id))
    faults.run_collective(join, site="bootstrap")
    _state["initialized"] = True
    _state["num_processes"] = int(num_processes)
    _state["rank"] = int(process_id)
    counters.set_gauge("dist_process_count", int(num_processes))
    counters.set_gauge("dist_rank", int(process_id))
    # trace events carry pid=rank from here on, so per-rank dumps load
    # side-by-side in Perfetto and rank 0 can merge them
    from ..telemetry import spans
    spans.set_pid(int(process_id))
    log.info("jax.distributed initialized: rank %d of %d (coordinator %s)",
             process_id, num_processes, coordinator_address)


def initialize_from_env() -> bool:
    """Bring-up purely from LGBM_TPU_* env vars. Returns True if the
    trio was present and the group was joined."""
    coord = os.environ.get("LGBM_TPU_COORDINATOR", "").strip()
    nproc = os.environ.get("LGBM_TPU_NUM_PROCESSES", "").strip()
    pid = os.environ.get("LGBM_TPU_PROCESS_ID", "").strip()
    if not (coord and nproc and pid):
        return False
    initialize(coord, int(nproc), int(pid))
    return True


def initialize_from_config(machines: str = "", local_listen_port: int = 12400,
                           num_machines: int = 1, machine_rank: int = -1,
                           coordinator: str = "",
                           supervise: bool = False) -> None:
    """The reference's config surface -> process group. Precedence:
    env-var trio > explicit ``coordinator`` + ``machine_rank`` >
    ``machines`` list with hostname rank detection. ``supervise``
    (set from ``dist_heartbeat_ms > 0``) selects the supervised
    bring-up."""
    if _state["initialized"]:
        return
    if initialize_from_env():
        return
    if coordinator and num_machines > 1:
        if machine_rank < 0:
            log.fatal("coordinator=%s requires machine_rank>=0 "
                      "(hostname detection needs the machines list)",
                      coordinator)
        initialize(coordinator, num_machines, machine_rank,
                   supervise=supervise)
        return
    if isinstance(machines, (list, tuple)):
        machines = ",".join(machines)
    entries = [m.strip() for m in str(machines).split(",") if m.strip()]
    if len(entries) <= 1:
        return                       # single machine: nothing to join
    rank_ = resolve_rank(entries, machine_rank)
    if rank_ is None:
        log.fatal("Could not find local machine in machine list: %s "
                  "(set machine_rank=<idx> to override)", machines)
    initialize(entries[0], len(entries), rank_, supervise=supervise)


def _external_group():
    """(num_processes, rank) of a process group brought up OUTSIDE this
    module (a harness calling jax.distributed.initialize directly), or
    None. Inspects jax.distributed's own state object rather than
    calling jax.process_count(), which would instantiate the backend —
    and freeze the CPU client before gloo could be selected."""
    import sys
    if "jax" not in sys.modules:
        return None
    try:
        from jax._src import distributed as _jd
        st = _jd.global_state
        if getattr(st, "client", None) is None:
            return None
        return int(st.num_processes), int(st.process_id)
    except Exception:  # pragma: no cover - jax internals moved
        return None


def is_distributed() -> bool:
    """True once a REAL multi-process group is up (the virtual
    single-process mesh never counts)."""
    return process_count() > 1


def process_count() -> int:
    if _state["initialized"]:
        return _state["num_processes"]
    ext = _external_group()
    return ext[0] if ext else 1


def rank() -> int:
    if _state["initialized"]:
        return _state["rank"]
    ext = _external_group()
    return ext[1] if ext else 0


def global_mesh(axis_name: str = "data"):
    """The one mesh the learners consume: 1-D over ALL devices in the
    process group (jax.devices() is global under jax.distributed, so
    the same code serves the virtual and the real topology). Cached —
    learners, ingest, and checkpoints must agree on the axis."""
    if _state["mesh"] is not None and _state["mesh_axis"] == axis_name:
        return _state["mesh"]
    import jax
    import numpy as np
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()), (axis_name,))
    _state["mesh"] = mesh
    _state["mesh_axis"] = axis_name
    return mesh


def barrier(name: str = "lgbm_tpu_barrier") -> None:
    """Cross-host rendezvous (checkpoint durability, resume gating).
    No-op single-process; a real collective dispatch otherwise, counted
    and retried like every other host collective."""
    if not is_distributed():
        return
    from jax.experimental import multihost_utils
    from ..resilience import faults
    faults.run_collective(
        lambda: multihost_utils.sync_global_devices(name),
        site=f"barrier:{name}")


def shutdown() -> None:
    if _state["initialized"]:
        import jax
        try:
            # teardown must not retry or respect the collective deadline:
            # by here peers may already be gone, and the bare except is
            # the whole failure policy. lint: disable=collective-discipline
            jax.distributed.shutdown()
        except Exception:  # pragma: no cover - already torn down
            pass
    _state["initialized"] = False
    _state["num_processes"] = 1
    _state["rank"] = 0
    _state["mesh"] = None
    _state["mesh_axis"] = None
