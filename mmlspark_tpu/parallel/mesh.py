"""TPU topology discovery and mesh construction.

TPU-native replacement for ``ClusterUtil`` (``core/utils/ClusterUtil.scala:13-177``)
and the driver socket rendezvous (``lightgbm/LightGBMUtils.scala:117-186``):
instead of discovering executor cores and exchanging host:port lists over a
``ServerSocket``, we discover the chip topology from the JAX runtime and build
a ``jax.sharding.Mesh``. Rendezvous/collective bring-up is the JAX runtime's
job (``jax.distributed`` + ICI); the "driver" only decides the mesh shape and
the partition→device assignment.

Axis convention (used across the framework):
- ``data``  — data parallel (batch/rows; the LightGBM ``data_parallel`` axis)
- ``model`` — tensor/feature parallel (feature-parallel histograms, TP matmuls)
- ``seq``   — sequence/context parallel (ring attention)
- ``pipe``  — pipeline parallel stages
- ``expert``— expert parallel (MoE)
Axes of size 1 cost nothing under XLA, so a single config covers 1 chip → pods.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"
AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"

ALL_AXES = (AXIS_DATA, AXIS_MODEL, AXIS_SEQ, AXIS_PIPE, AXIS_EXPERT)


@dataclasses.dataclass(frozen=True)
class Topology:
    """What ``ClusterUtil`` discovered on Spark, re-expressed for TPU."""

    num_devices: int
    num_hosts: int
    devices_per_host: int
    platform: str
    device_kind: str

    @property
    def multi_host(self) -> bool:
        return self.num_hosts > 1


def get_topology() -> Topology:
    import jax

    devices = jax.devices()
    hosts = {d.process_index for d in devices}
    return Topology(
        num_devices=len(devices),
        num_hosts=len(hosts),
        devices_per_host=len(devices) // max(1, len(hosts)),
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
    )


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. -1 on ``data`` means 'absorb remaining devices'."""

    data: int = -1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    def resolve(self, num_devices: int) -> Dict[str, int]:
        fixed = self.model * self.seq * self.pipe * self.expert
        if num_devices % fixed != 0:
            raise ValueError(
                f"{num_devices} devices not divisible by model*seq*pipe*expert={fixed}"
            )
        data = self.data if self.data != -1 else num_devices // fixed
        if data * fixed != num_devices:
            raise ValueError(
                f"mesh {data}x{fixed} != {num_devices} devices"
            )
        return {
            AXIS_DATA: data,
            AXIS_MODEL: self.model,
            AXIS_SEQ: self.seq,
            AXIS_PIPE: self.pipe,
            AXIS_EXPERT: self.expert,
        }


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[Any]] = None,
    axis_names: Optional[Sequence[str]] = None,
):
    """Build a ``jax.sharding.Mesh`` over all (or given) devices.

    Device order follows ``jax.devices()``, which JAX already orders for ICI
    locality; inner-most mesh axes therefore get the tightest rings, so put
    the heavy-traffic axis (``model``/``seq``) last when customizing.
    """
    import jax
    from jax.sharding import Mesh

    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    sizes = config.resolve(len(devices))
    names = tuple(axis_names or ALL_AXES)
    shape = tuple(sizes[n] for n in names)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def best_mesh(num_devices: Optional[int] = None):
    """A sensible default: everything on the data axis (the reference's only
    distribution mode is data parallel — SURVEY.md §5)."""
    import jax

    devices = jax.devices()
    if num_devices is not None:
        devices = devices[:num_devices]
    return make_mesh(MeshConfig(), devices=devices)


def data_sharding(mesh):
    """NamedSharding that shards dim 0 over the ``data`` axis only, replicating
    across model/seq/pipe/expert groups and all other dims."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(AXIS_DATA))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def pad_to_multiple(
    n: int, multiple: int
) -> Tuple[int, int]:
    """Rows to pad so n divides the mesh/data axis. Returns (padded_n, pad)."""
    padded = int(math.ceil(n / multiple) * multiple)
    return padded, padded - n


def distributed_init(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    executor_ids: Optional[Sequence[str]] = None,
    local_executor_id: Optional[str] = None,
    initialization_timeout: Optional[float] = None,
) -> Topology:
    """Multi-host bootstrap — the surviving driver-rendezvous role.

    The reference's driver collects executor host:port lines over a
    ``ServerSocket`` and broadcasts the worker list
    (``lightgbm/LightGBMUtils.scala:117-186``, ``ClusterUtil.scala:107-177``);
    on TPU the collective mesh is the JAX runtime's job and the driver's
    only duty is numbering the processes. Two calling conventions:

    - explicit: ``coordinator_address`` (driver host:port), ``num_processes``,
      ``process_id`` — forwarded to :func:`jax.distributed.initialize`;
    - executor-keyed: pass the full sorted-stable list of ``executor_ids``
      plus this host's ``local_executor_id``; the process id is the
      executor's rank in the list (deterministic across hosts, no extra
      coordination round).

    No-ops (returning the current topology) when running single-process,
    or when the process group is already initialized AND no explicit
    rendezvous was requested. An explicit multi-process rendezvous while a
    prior client is still up (a worker re-forming its gang after a member
    died) first tears the old client down via
    :func:`distributed_shutdown` — silently keeping the stale group would
    rendezvous iteration state against a dead membership.

    ``initialization_timeout`` (seconds) bounds how long the rendezvous
    waits for stragglers; a gang member that never shows up surfaces as an
    exception here instead of a five-minute default hang.
    """
    import jax

    if executor_ids is not None:
        if local_executor_id is None:
            raise ValueError("local_executor_id required with executor_ids")
        ordered = sorted(set(map(str, executor_ids)))
        if str(local_executor_id) not in ordered:
            raise ValueError(
                f"local executor {local_executor_id!r} not in executor_ids"
            )
        num_processes = len(ordered)
        process_id = ordered.index(str(local_executor_id))

    if num_processes is not None and num_processes > 1:
        if coordinator_address is None:
            raise ValueError(
                f"{num_processes} processes derived but no coordinator_address "
                "— pass the driver's host:port (the one piece of rendezvous "
                "the runtime cannot discover itself)"
            )
        if process_id is None:
            raise ValueError(
                f"{num_processes} processes requested but no process_id — "
                "pass it explicitly or use the executor_ids convention"
            )
        already = getattr(jax.distributed, "global_state", None)
        if already is not None and getattr(already, "client", None) is not None:
            # Re-initialization (second gang epoch in one process): the old
            # client must go down before a new rendezvous can form. The old
            # behavior — no-opping on global_state — left the process wired
            # to a dead coordinator.
            distributed_shutdown()
        kwargs = {}
        if initialization_timeout is not None:
            kwargs["initialization_timeout"] = int(max(1, initialization_timeout))
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kwargs,
        )
    return get_topology()


def distributed_shutdown(timeout_s: float = 5.0, clear_backends: bool = False) -> bool:
    """Tear down this process's ``jax.distributed`` client/service so a new
    group can form (the gang-recovery teardown half of
    :func:`distributed_init`).

    The clean path is :func:`jax.distributed.shutdown`; it can block
    indefinitely when the coordinator died first, so it runs on a reaper
    thread bounded by ``timeout_s`` and on overrun the global state is
    force-cleared — the orphaned client leaks, but the process regains the
    ability to rendezvous, which is the property gang recovery needs.

    ``clear_backends=True`` additionally drops already-initialized XLA
    backends and compiled caches (the :func:`force_platform` teardown):
    required before re-initializing, because a backend created under the
    old group bakes in its process count/device topology. Returns True on
    a clean shutdown, False when state had to be force-cleared.
    """
    import threading

    import jax
    from jax._src import distributed as _dist
    from jax._src import xla_bridge

    state = getattr(_dist, "global_state", None)
    clean = True
    if state is not None and (
        getattr(state, "client", None) is not None
        or getattr(state, "service", None) is not None
    ):
        done = threading.Event()

        def _shutdown():
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 - a dead coordinator is expected here
                pass
            finally:
                done.set()

        t = threading.Thread(
            target=_shutdown, name="mmlspark-tpu-dist-shutdown", daemon=True
        )
        t.start()
        if not done.wait(timeout_s):
            clean = False
        if getattr(state, "client", None) is not None or not clean:
            # force-clear whatever the (possibly wedged) clean path left
            for attr, value in (
                ("client", None), ("service", None),
                ("preemption_sync_manager", None),
                ("process_id", 0), ("num_processes", 0),
                ("coordinator_address", None),
            ):
                try:
                    setattr(state, attr, value)
                except AttributeError:
                    pass
    if clear_backends:
        if getattr(xla_bridge, "_backends", None) and hasattr(
            xla_bridge, "_clear_backends"
        ):
            xla_bridge._clear_backends()
            if hasattr(xla_bridge.get_backend, "cache_clear"):
                xla_bridge.get_backend.cache_clear()
            jax.clear_caches()
    return clean


def partition_assignment(num_partitions: int, mesh) -> Dict[int, Tuple[int, ...]]:
    """Map data-partition ids onto mesh coordinates — the partition→chip
    assignment that replaces ``ClusterUtil``'s executor/core bookkeeping.

    Partitions are assigned round-robin over the ``data`` axis (a partition's
    rows land on every device in that data-slice's model/seq/... subgroup,
    which replicates or shards them per the program's NamedShardings).
    Returns {partition_id: mesh coordinates of its data slice}.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_size = sizes.get(AXIS_DATA, 1)
    if num_partitions < data_size:
        raise ValueError(
            f"{num_partitions} partitions cannot cover data axis of {data_size} "
            "(repartition up, or shrink the mesh — empty mesh slices would "
            "deadlock collectives, the 'empty partition' hazard of "
            "LightGBMUtils.scala:144-161)"
        )
    data_axis_pos = (
        mesh.axis_names.index(AXIS_DATA) if AXIS_DATA in mesh.axis_names else None
    )
    out: Dict[int, Tuple[int, ...]] = {}
    for pid in range(num_partitions):
        coord = [0] * len(mesh.axis_names)
        if data_axis_pos is not None:
            coord[data_axis_pos] = pid % data_size
        out[pid] = tuple(coord)  # no data axis: one slice takes everything
    return out


def feature_parallel_sharding(mesh):
    """NamedSharding for a (rows, features) matrix sharded rows-over-``data``
    AND features-over-``model`` — LightGBM's ``feature_parallel`` data layout
    (vertical partitioning), expressed as a sharding annotation: XLA then
    partitions histogram build + split search across the model axis and
    inserts the small best-split argmax collectives itself."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(AXIS_DATA, AXIS_MODEL))


def force_platform(platform: str, min_devices: int = 1) -> None:
    """Re-point JAX at a platform mid-process, tearing down already-initialized
    backends. CPU-mesh test machinery: a process that wants the chip never
    calls this (a fresh process sets ``JAX_PLATFORMS``/``XLA_FLAGS`` before
    importing jax instead). For ``cpu`` with
    ``min_devices > 1`` the host-platform device-count flag is injected —
    it must be set before the first CPU client is created.

    WARNING: only reliable before the first jit execution in the process;
    after real compute has run, dispatch can silently stick to the old
    backend. Use a fresh subprocess to benchmark a second platform."""
    import os
    import re

    if platform == "cpu" and min_devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            flags = (flags + f" --xla_force_host_platform_device_count={min_devices}").strip()
        elif int(m.group(1)) < min_devices:
            flags = flags.replace(
                m.group(0), f"--xla_force_host_platform_device_count={min_devices}"
            )
        os.environ["XLA_FLAGS"] = flags

    import jax
    from jax._src import xla_bridge

    # Inspect only already-initialized backends — querying jax.devices() here
    # would instantiate the CURRENT platform's client (claiming the chip,
    # the very thing this function exists to avoid).
    initialized = dict(getattr(xla_bridge, "_backends", {}) or {})
    current_ok = (
        platform in initialized
        and xla_bridge._default_backend is not None
        and xla_bridge._default_backend.platform == platform
        and len(initialized[platform].devices()) >= min_devices
    )
    if current_ok:
        return
    if initialized:
        if not hasattr(xla_bridge, "_clear_backends"):
            raise RuntimeError(
                "jax backends already initialized and this jax version has no "
                "_clear_backends hook; restart the process with "
                f"JAX_PLATFORMS={platform}"
            )
        xla_bridge._clear_backends()
        if hasattr(xla_bridge.get_backend, "cache_clear"):
            xla_bridge.get_backend.cache_clear()
        # Compiled-executable caches survive the backend teardown and can be
        # REUSED against the new client: a program traced on the old
        # single-device backend then silently misexecutes collectives on the
        # new multi-device one (observed as wrong ring-attention output after
        # an entry()-style warm-up preceded the platform switch).
        jax.clear_caches()
    jax.config.update("jax_platforms", platform)
    if len(jax.devices()) < min_devices:
        raise RuntimeError(
            f"could not materialize {min_devices} {platform} devices; "
            f"got {jax.devices()}"
        )
