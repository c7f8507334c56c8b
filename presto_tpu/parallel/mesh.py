"""Device mesh runtime: the distributed execution substrate.

Reference parity: the coordinator/worker topology + HTTP exchanges
(SURVEY.md §2.6) re-based on jax.sharding.Mesh + shard_map supersteps:
- P1 hash repartition (FIXED_HASH_DISTRIBUTION / PartitionedOutputOperator)
  -> lax.all_to_all over the 'x' mesh axis (parallel/exchange.py)
- P2 broadcast (BroadcastOutputBuffer) -> lax.all_gather
- P5 gather to coordinator (SINGLE_DISTRIBUTION) -> psum / device_get
- partial->final aggregation (AddExchanges.java:239) -> per-shard segment
  reduce + psum tree-combine.

Round 21 adds the MULTI-HOST lane: this module is the single home (lint:
tests/test_lint.py confines `jax.distributed` here) for standing one
worker process up as member k of an N-process `jax.distributed` mesh, so
cross-host exchange edges can lower to DCN collectives (all_to_all /
all_gather) instead of the HTTP data plane.  HTTP stays the control
plane, result-delivery path, and fallback.
"""

from __future__ import annotations

import os

import jax
from jax.sharding import Mesh


AXIS = "x"

# process-topology facts, frozen once init_multihost() succeeds
_MULTIHOST = {"on": False, "coordinator": "", "num_processes": 1,
              "process_id": 0}

#: env opt-in mirrored by the WorkerServer CLI flags: set
#: PRESTO_TPU_MULTIHOST="coordinator_addr,num_processes,process_id"
MULTIHOST_ENV = "PRESTO_TPU_MULTIHOST"


def init_multihost(coordinator_address: str, num_processes: int,
                   process_id: int) -> None:
    """Join this process to the global `jax.distributed` mesh.  MUST run
    before any other jax backend use (device queries, jit, device_put):
    the distributed runtime can only attach to an uninitialized backend.
    On CPU the collectives run over gloo loopback — the CI stand-in for
    the TPU DCN fabric."""
    if _MULTIHOST["on"]:
        return
    if os.environ.get("JAX_PLATFORMS", "") == "cpu" \
            or os.environ.get("PRESTO_TPU_PLATFORM", "") == "cpu":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=int(num_processes), process_id=int(process_id))
    _MULTIHOST.update(on=True, coordinator=coordinator_address,
                      num_processes=int(num_processes),
                      process_id=int(process_id))


def init_multihost_from_env() -> bool:
    """PRESTO_TPU_MULTIHOST="addr:port,nproc,pid" -> init_multihost."""
    spec = os.environ.get(MULTIHOST_ENV, "")
    if not spec:
        return False
    addr, nproc, pid = (p.strip() for p in spec.split(","))
    init_multihost(addr, int(nproc), int(pid))
    return True


def is_multihost() -> bool:
    return _MULTIHOST["on"]


def process_count() -> int:
    return _MULTIHOST["num_processes"] if _MULTIHOST["on"] else 1


def process_index() -> int:
    return _MULTIHOST["process_id"] if _MULTIHOST["on"] else 0


def multihost_spec() -> dict:
    """The /v1/info declaration block a mesh-member worker serves, from
    which the coordinator assembles gang groups (same coordinator addr +
    complete process-id set = one fusible cross-host mesh)."""
    return {"distCoordinator": _MULTIHOST["coordinator"],
            "distProcessId": _MULTIHOST["process_id"],
            "distNumProcesses": _MULTIHOST["num_processes"],
            "globalDevices": len(jax.devices()) if _MULTIHOST["on"]
            else 0}


def refuse_cpu_children(launcher: str) -> None:
    """The cluster, fleet and multi-process launchers start their
    children on the CPU backend (a chip belongs to one process, so a
    child of a parent that holds it could not have it anyway).  Under a
    TPU parent that would time CPU work and stamp the parent's platform
    on the record, so the launch is refused; run it with
    JAX_PLATFORMS=cpu, where it is the CPU topology it says it is."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{launcher} starts CPU child processes and this process "
            "runs on a TPU: these launchers are CPU topologies today — "
            "run them under JAX_PLATFORMS=cpu (one process drives the "
            "chips: distributed=True / mesh_devices=N)")


def make_mesh(n_devices: int | None = None) -> Mesh:
    """Mesh over the first `n_devices` devices of the default backend
    (all of them when None).  Too few devices is an error: a mesh never
    moves to another backend than the one the process runs on."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, the {devs[0].platform} "
                f"backend has {len(devs)} (a CPU rehearsal sets XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before jax "
                "starts)")
        devs = devs[:n_devices]
    import numpy as np

    return Mesh(np.asarray(devs), (AXIS,))
