"""Process-group supervisor: real OS processes, rendezvous, gang recovery.

Everything below ``runtime/`` so far exercised fault tolerance against
worker *threads*; the reference framework's failure domain is the worker
*process* (a lost JVM executor). This module closes that gap: the driver
supervises N genuine child processes that rendezvous through
``jax.distributed`` (the executor-keyed convention of
``parallel/mesh.py``), exchange histograms over a LightGBM-style socket
allreduce, heartbeat through the group workdir, and — the point — survive
one of their number being SIGKILL'd mid-collective.

Roles:

- :func:`pick_port` — seeded, bind-probed port picker (deterministic
  chaos runs need reproducible rendezvous addresses; TOCTOU losers are
  healed by the epoch retry loop);
- :class:`AllreduceGroup` — star-topology sum-allreduce over TCP
  (rank 0 accumulates and broadcasts; LightGBM's socket collective
  reduced to the one op GBDT fit needs). Round counters in the frame
  header catch desynchronized members; any socket failure raises
  :class:`GroupRevokedError`;
- :func:`worker_main` — the child-process entry loop: wait for an epoch
  spec naming this member -> rendezvous -> form the socket group ->
  *release the jax.distributed client while everyone is alive* -> run the
  payload -> commit barrier -> report. On revocation, clear XLA backends
  and wait for the next epoch spec;
- :class:`ProcessGroup` — the driver: spawn/respawn members, watch
  heartbeats and exit statuses, translate deaths into
  ``ProcessLost``/``GroupReformed`` events and
  :class:`~mmlspark_tpu.runtime.health.HealthTracker` bookings, and
  re-form the gang with a respawned (or, when quarantined, dropped)
  membership.

Why the client release (step between group formation and payload): the
CPU coordination service fatally aborts any process whose peer dies while
the distributed client is live — gang recovery is impossible with the
client up. On this backend the client's only job is rendezvous, so each
epoch uses it for exactly that and then shuts it down cleanly; peer death
afterwards surfaces as a catchable socket error in the allreduce.

Protocol files in the group workdir (all JSON, atomically renamed in):

====================  =======================================================
``epoch-<k>.json``    driver -> workers: membership, ports, entry, payload
``hb-<m>``            worker heartbeat (driver checks mtime staleness)
``ready-<k>-<m>``     member m formed epoch k (rendezvous + group + release)
``done-<k>-<m>.json`` member m's payload finished; carries the result
``revoked-<k>-<m>``   member m observed epoch k revoked (peer loss/timeout)
``failed-<k>-<m>``    member m's payload raised (a bug, not a fault)
``log-<m>-<g>.txt``   stdout/stderr of member m, generation g
``stop``              driver -> workers: exit cleanly
====================  =======================================================
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import traceback
import zlib
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from mmlspark_tpu.core.profiling import get_logger
from mmlspark_tpu.runtime.faults import FaultPlan, current_faults
from mmlspark_tpu.runtime.health import HealthTracker
from mmlspark_tpu.runtime.journal import _atomic_write

logger = get_logger("mmlspark_tpu.runtime.procgroup")

#: env vars that must not leak into CPU worker processes (a chip belongs
#: to one process; TPU runtime settings would send the child after it)
_SCRUB_PREFIXES = ("TPU_",)
_SCRUB_EXACT = ("XLA_FLAGS",)


class GroupRevokedError(RuntimeError):
    """The current gang epoch is dead: a peer was lost mid-collective (or
    the rendezvous timed out). Not a payload bug — the worker reports the
    revocation and waits for the re-formed epoch.

    ``suspect`` (when the collective could attribute the failure) is the
    stable member id of the peer this process blames — a non-root always
    blames the star center, rank 0 blames the member on the failed
    connection. ``stats`` is the collective's retransmit/CRC/slow-peer
    tally at death. Both ride the worker's revoked report so the driver
    can pick the victim by vote."""

    suspect: Optional[int] = None
    stats: Optional[Dict[str, Any]] = None


class GangFailedError(RuntimeError):
    """The supervisor ran out of recovery options: no live membership
    left, or the epoch budget was exhausted without a successful fit."""


def scrub_env(env: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """A child-process environment with accelerator settings stripped and
    the backend pinned to CPU. Every launcher in the tree goes through
    this (``ProcessGroup``, ``ReplicaSupervisor``), so a ``procfit`` gang
    or a replica fleet never touches the chip: the parent process holds
    it, and a chip belongs to one process at a time."""
    base = dict(os.environ if env is None else env)
    out = {
        k: v for k, v in base.items()
        if k not in _SCRUB_EXACT and not k.startswith(_SCRUB_PREFIXES)
    }
    out["JAX_PLATFORMS"] = "cpu"
    # children run with cwd=workdir; make this package importable even
    # when it is used from a source checkout rather than installed
    pkg_root = str(Path(__file__).resolve().parents[2])
    parts = [pkg_root] + [
        p for p in out.get("PYTHONPATH", "").split(os.pathsep) if p
    ]
    out["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    return out


def pick_port(
    seed: Optional[int] = None,
    attempts: int = 64,
    low: int = 20001,
    high: int = 59999,
    exclude: Optional[Sequence[int]] = None,
) -> int:
    """Seeded, bind-probed free-port picker.

    ``random.randint`` port pickers make chaos runs unreproducible and
    bare ``bind(0)`` pickers hand back ports that another picker grabs in
    the gap — this draws candidates from a seeded RNG and *proves* each by
    binding it before returning. The TOCTOU window between probe and the
    worker's real bind still exists; callers heal a lost race by retrying
    with the next epoch/attempt (which advances the seed).
    """
    rng = np.random.default_rng(seed)
    skip = set(int(p) for p in (exclude or ()))
    last_err: Optional[OSError] = None
    for _ in range(attempts):
        port = int(rng.integers(low, high))
        if port in skip:
            continue
        probe = socket.socket()
        try:
            probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            probe.bind(("127.0.0.1", port))
        except OSError as e:
            last_err = e
            continue
        finally:
            probe.close()
        return port
    raise OSError(
        f"no free port in [{low}, {high}] after {attempts} seeded probes"
    ) from last_err


# -- socket allreduce ---------------------------------------------------------


class AllreduceGroup:
    """Star-topology float32 sum-allreduce over localhost TCP.

    Rank 0 binds ``port``, accepts ``world - 1`` connections, sums the
    incoming buffers and broadcasts the total; other ranks send and
    receive. Every frame is ``(round, nbytes, crc32)`` + payload and is
    acknowledged: the receiver verifies the payload CRC and answers ACK,
    or NAK for a wire-corrupted frame, which the sender answers with a
    bounded retransmit (``max_retransmits``) of the clean bytes — a
    flipped bit degrades to one extra round trip instead of a corrupt
    histogram. A round-counter mismatch means the members desynchronized
    (one resumed a different iteration) and revokes the group rather
    than silently mixing histograms from different trees.

    Deadlines, not hangs: formation runs under ``timeout`` and every
    per-round socket op under ``io_timeout``, so a partitioned or
    alive-but-silent peer surfaces as ``socket.timeout`` within one io
    window — including a dead star center, which every non-root notices
    the same way (the coordinator-stall watchdog is nothing more than
    this deadline plus blame: a non-root's ``suspect`` is always the
    coordinator). Any socket error — peer SIGKILL'd, accept/connect
    timeout, short read, retransmit exhaustion — raises
    :class:`GroupRevokedError` carrying the suspected member and the
    collective's stats, and marks the group ``revoked``.

    ``member``/``members`` carry the *stable* supervisor ids (rank order)
    so blame and chaos directives survive re-formation renumbering; a
    hello frame after connect tells rank 0 which member each accepted
    connection belongs to. ``chaos`` (a
    :class:`~mmlspark_tpu.runtime.netchaos.NetChaos`) filters every
    outgoing frame; ``slow_peer_s`` is the soft detection threshold — a
    successful round that made rank 0 wait at least this long books the
    peer into ``stats["slow_peers"]`` (the driver turns that into health
    straggle bookings and ``PeerSlow`` events).
    """

    _HDR = struct.Struct(">QQI")
    _HELLO = struct.Struct(">Q")
    _ACK, _NAK = b"\x06", b"\x15"

    def __init__(
        self,
        rank: int,
        world: int,
        port: int,
        timeout: float = 30.0,
        io_timeout: Optional[float] = None,
        member: Optional[int] = None,
        members: Optional[Sequence[int]] = None,
        chaos=None,
        slow_peer_s: Optional[float] = None,
        max_retransmits: int = 2,
    ):
        self.rank, self.world, self.port = int(rank), int(world), int(port)
        self.timeout = float(timeout)
        self.io_timeout = float(io_timeout if io_timeout is not None
                                else timeout)
        self.member = int(member if member is not None else rank)
        self.members = [int(m) for m in (
            members if members is not None else range(world)
        )]
        self.chaos = chaos
        self.slow_peer_s = float(
            slow_peer_s if slow_peer_s is not None else self.io_timeout / 2.0
        )
        self.max_retransmits = int(max_retransmits)
        self.revoked = False
        self.rounds = 0
        #: member id this process blames for the revocation, when known
        self.suspect: Optional[int] = None
        self.stats: Dict[str, Any] = {
            "retransmits": 0, "crc_drops": 0, "slow_peers": {},
        }
        self._conns: List[socket.socket] = []
        #: stable member id behind each entry of ``_conns`` (rank 0 learns
        #: them from the hello frames; a non-root's single peer is the
        #: coordinator)
        self._peers: List[int] = []
        if self.world <= 1:
            return
        coordinator = self.members[0]
        try:
            if self.rank == 0:
                srv = socket.socket()
                srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                srv.bind(("127.0.0.1", self.port))
                srv.listen(self.world - 1)
                srv.settimeout(self.timeout)
                try:
                    for _ in range(self.world - 1):
                        conn, _ = srv.accept()
                        conn.settimeout(self.timeout)
                        hello, = self._HELLO.unpack(
                            self._recv_exact(conn, self._HELLO.size)
                        )
                        conn.settimeout(self.io_timeout)
                        self._conns.append(conn)
                        self._peers.append(int(hello))
                finally:
                    srv.close()
            else:
                deadline = time.monotonic() + self.timeout
                while True:
                    try:
                        conn = socket.create_connection(
                            ("127.0.0.1", self.port), timeout=1.0
                        )
                        break
                    except OSError:
                        if time.monotonic() >= deadline:
                            raise
                        time.sleep(0.05)
                conn.settimeout(self.timeout)
                conn.sendall(self._HELLO.pack(self.member))
                conn.settimeout(self.io_timeout)
                self._conns.append(conn)
                self._peers.append(coordinator)
        except (OSError, ConnectionError, struct.error) as e:
            if self.suspect is None and self.rank != 0:
                self.suspect = coordinator
            self._die(f"group formation failed (rank {self.rank}): {e}")

    def _die(self, why: str) -> None:
        self.revoked = True
        self.close()
        err = GroupRevokedError(why)
        err.suspect = self.suspect
        err.stats = dict(self.stats)
        raise err

    def _send(self, conn: socket.socket, peer: int, buf: bytes) -> None:
        """One acknowledged frame to ``peer``: CRC over the clean bytes,
        chaos applied after (so injected corruption is a genuine wire
        flip), retransmit the clean copy on NAK up to
        ``max_retransmits`` times."""
        hdr = self._HDR.pack(
            self.rounds, len(buf), zlib.crc32(buf) & 0xFFFFFFFF
        )
        for _ in range(self.max_retransmits + 1):
            wire = buf
            if self.chaos is not None:
                wire = self.chaos.on_send(peer, self.rounds, buf)
                if wire is None:
                    # swallowed (partition/drop): nothing on the wire,
                    # nothing to wait for — the peer's io deadline and
                    # ours end this round
                    return
            conn.sendall(hdr + wire)
            ack = self._recv_exact(conn, 1)
            if ack == self._ACK:
                return
            self.stats["retransmits"] += 1
        raise ConnectionError(
            f"peer {peer} rejected frame {self.rounds} "
            f"{self.max_retransmits + 1} times (CRC)"
        )

    def _recv(self, conn: socket.socket, peer: int) -> bytes:
        """One verified frame from ``peer``: NAK + re-read on CRC
        mismatch, bounded like the send side."""
        for _ in range(self.max_retransmits + 1):
            hdr = self._recv_exact(conn, self._HDR.size)
            rnd, nbytes, want = self._HDR.unpack(hdr)
            if rnd != self.rounds:
                raise ConnectionError(
                    f"round mismatch: peer {peer} at {rnd}, "
                    f"local at {self.rounds}"
                )
            payload = self._recv_exact(conn, nbytes)
            if zlib.crc32(payload) & 0xFFFFFFFF == want:
                conn.sendall(self._ACK)
                return payload
            self.stats["crc_drops"] += 1
            conn.sendall(self._NAK)
        raise ConnectionError(
            f"frame from peer {peer} failed CRC "
            f"{self.max_retransmits + 1} times"
        )

    def _timed_recv(self, conn: socket.socket, peer: int) -> bytes:
        """A receive that also feeds the soft slow-peer detector: waits
        that clear ``slow_peer_s`` (but still succeed) are remembered as
        the peer's worst observed lag."""
        t0 = time.monotonic()
        data = self._recv(conn, peer)
        wait = time.monotonic() - t0
        if self.slow_peer_s > 0 and wait >= self.slow_peer_s:
            slow = self.stats["slow_peers"]
            slow[str(peer)] = max(float(slow.get(str(peer), 0.0)), wait)
        return data

    @staticmethod
    def _recv_exact(conn: socket.socket, n: int) -> bytes:
        out = bytearray()
        while len(out) < n:
            chunk = conn.recv(min(1 << 20, n - len(out)))
            if not chunk:
                raise ConnectionError("peer closed")
            out += chunk
        return bytes(out)

    def allreduce(self, arr: np.ndarray) -> np.ndarray:
        """Element-wise float32 sum across all members (identity when
        ``world == 1``). Raises :class:`GroupRevokedError` on any wire
        failure — the caller's signal to start gang recovery."""
        if self.world <= 1:
            return np.ascontiguousarray(arr, dtype=np.float32)
        if self.revoked:
            raise GroupRevokedError("allreduce on a revoked group")
        a = np.ascontiguousarray(arr, dtype=np.float32)
        peer = -1
        try:
            if self.rank == 0:
                total = a.copy()
                for conn, peer in zip(self._conns, self._peers):
                    total += np.frombuffer(
                        self._timed_recv(conn, peer), np.float32
                    ).reshape(a.shape)
                buf = total.tobytes()
                for conn, peer in zip(self._conns, self._peers):
                    self._send(conn, peer, buf)
                out = total
            else:
                peer = self._peers[0]
                self._send(self._conns[0], peer, a.tobytes())
                out = np.frombuffer(
                    self._timed_recv(self._conns[0], peer), np.float32
                ).reshape(a.shape)
        except (OSError, ConnectionError, struct.error) as e:
            self.suspect = peer if peer >= 0 else None
            kind = "deadline" if isinstance(e, socket.timeout) else "error"
            self._die(
                f"allreduce round {self.rounds} failed "
                f"({kind}, suspect member {self.suspect}): {e}"
            )
        self.rounds += 1
        return out

    def barrier(self) -> None:
        """All members reached this point (sum-allreduce of one scalar)."""
        self.allreduce(np.ones((1,), np.float32))

    def close(self) -> None:
        for conn in self._conns:
            try:
                conn.close()
            except OSError:  # pragma: no cover - close is best-effort
                pass
        self._conns = []
        self._peers = []


# -- worker side --------------------------------------------------------------


@dataclasses.dataclass
class WorkerContext:
    """Everything a payload entry point gets: identity, the epoch spec's
    payload, and the collective. ``rank``/``world`` describe the *current*
    epoch's membership (a survivor of a two-member gang re-forms with
    ``world == 2`` and possibly a different rank); ``member`` is the
    stable supervisor-assigned id."""

    member: int
    rank: int
    world: int
    epoch: int
    workdir: Path
    payload: Dict[str, Any]
    group: Optional[AllreduceGroup]
    fault_directives: List[dict] = dataclasses.field(default_factory=list)

    def allreduce(self, arr: np.ndarray) -> np.ndarray:
        if self.group is None:
            return np.ascontiguousarray(arr, dtype=np.float32)
        return self.group.allreduce(arr)

    def maybe_die(self, iteration: int) -> None:
        """Enact a ``FaultPlan.kill_process`` directive: a real SIGKILL,
        no Python teardown — the failure mode the supervisor exists for."""
        if FaultPlan.should_die(
            self.fault_directives, self.member, iteration, self.epoch
        ):
            logger.warning(
                "member %d enacting kill_process at iteration %d (epoch %d)",
                self.member, iteration, self.epoch,
            )
            sys.stdout.flush()
            sys.stderr.flush()
            os.kill(os.getpid(), signal.SIGKILL)


class _Heartbeat(threading.Thread):
    """Daemon thread bumping ``hb-<member>`` every ``interval`` seconds;
    the driver reads staleness off the file's mtime."""

    def __init__(self, path: Path, interval: float = 0.5):
        super().__init__(name=f"procgroup-hb-{path.name}", daemon=True)
        self.path = path
        self.interval = float(interval)
        self._stop = threading.Event()
        self._seq = 0

    def run(self) -> None:
        while not self._stop.is_set():
            self.beat()
            self._stop.wait(self.interval)

    def beat(self) -> None:
        self._seq += 1
        try:
            self.path.write_text(f"{self._seq} {time.time()}\n")
        except OSError:  # pragma: no cover - workdir vanished mid-shutdown
            pass

    def stop(self) -> None:
        self._stop.set()


def _write_json(path: Path, payload: Dict[str, Any]) -> None:
    _atomic_write(str(path), json.dumps(payload).encode("utf-8"))


def _resolve_entry(entry: str) -> Callable[[WorkerContext], Any]:
    mod_name, _, fn_name = entry.partition(":")
    if not fn_name:
        raise ValueError(f"entry must be 'module:function', got {entry!r}")
    return getattr(importlib.import_module(mod_name), fn_name)


def _clear_backends() -> None:
    """Drop initialized XLA backends + compiled caches so the next epoch's
    rendezvous builds a topology against the new membership."""
    try:
        import jax
        from jax._src import xla_bridge
    except Exception:  # noqa: BLE001 - pragma: no cover - jax-free unit-test workers
        return
    if getattr(xla_bridge, "_backends", None) and hasattr(
        xla_bridge, "_clear_backends"
    ):
        xla_bridge._clear_backends()
        if hasattr(xla_bridge.get_backend, "cache_clear"):
            xla_bridge.get_backend.cache_clear()
        jax.clear_caches()


def _wait_for_spec(
    workdir: Path, member: int, next_epoch: int, poll: float = 0.05
) -> Optional[Dict[str, Any]]:
    """Block until an epoch spec with ``epoch >= next_epoch`` appears (the
    highest wins — stale specs from revoked epochs are skipped), or the
    stop file does. Returns the spec, or None on stop."""
    while True:
        if (workdir / "stop").exists():
            return None
        best: Optional[Tuple[int, Path]] = None
        for path in workdir.glob("epoch-*.json"):
            try:
                k = int(path.stem.split("-", 1)[1])
            except (IndexError, ValueError):
                continue
            if k >= next_epoch and (best is None or k > best[0]):
                best = (k, path)
        if best is not None:
            try:
                return json.loads(best[1].read_text())
            except (OSError, json.JSONDecodeError):
                pass  # mid-rename blip; re-read next tick
        time.sleep(poll)


def _form_epoch(
    spec: Dict[str, Any], member: int, rank: int, world: int
) -> Optional[AllreduceGroup]:
    """The epoch formation sequence: jax.distributed rendezvous (when the
    spec asks for it and the gang spans processes), socket group, then
    *release the distributed client while every member is alive* — after
    this point peer death is a catchable socket error, not a fatal
    coordination-service abort. Any failure revokes the epoch."""
    use_jax = spec.get("rendezvous", "jax") == "jax" and world > 1
    if use_jax:
        from mmlspark_tpu.parallel.mesh import (
            distributed_init,
            distributed_shutdown,
        )

        _clear_backends()
        try:
            distributed_init(
                coordinator_address=f"127.0.0.1:{spec['coordinator_port']}",
                num_processes=world,
                process_id=rank,
                initialization_timeout=spec.get("rendezvous_timeout_s", 60.0),
            )
        except Exception as e:  # noqa: BLE001 - straggler/timeout = revoked
            raise GroupRevokedError(f"rendezvous failed: {e}") from e
        import jax

        if jax.process_count() != world:
            distributed_shutdown(clear_backends=True)
            raise GroupRevokedError(
                f"rendezvous formed {jax.process_count()} processes, "
                f"expected {world}"
            )
    group = None
    if world > 1:
        chaos = None
        net = spec.get("net_faults") or []
        if net:
            from mmlspark_tpu.runtime.netchaos import NetChaos

            chaos = NetChaos(
                net, member, int(spec.get("epoch", 0)),
                seed=int(spec.get("net_seed", 0)),
            )
            if not chaos.active:
                chaos = None
        group = AllreduceGroup(
            rank, world, int(spec["reduce_port"]),
            timeout=float(spec.get("group_timeout_s", 30.0)),
            io_timeout=float(spec.get("io_timeout_s",
                                      spec.get("group_timeout_s", 30.0))),
            member=member,
            members=[int(m) for m in spec["members"]],
            chaos=chaos,
            slow_peer_s=spec.get("slow_peer_s"),
        )
    if use_jax:
        from mmlspark_tpu.parallel.mesh import distributed_shutdown

        distributed_shutdown()
    return group


def worker_main(workdir: str, member: int, start_epoch: int = 0) -> int:
    """Child-process entry loop (spawned as
    ``python -m mmlspark_tpu.runtime.procgroup --worker ...``).

    Runs epochs until dropped from the membership or told to stop. A
    revoked epoch (peer loss) is reported and survived; a payload
    exception is reported and fatal — the supervisor must be able to tell
    "my peer died" from "my code is broken".
    """
    wd = Path(workdir)
    member = int(member)
    hb = _Heartbeat(wd / f"hb-{member}")
    hb.start()
    next_epoch = int(start_epoch)
    try:
        while True:
            spec = _wait_for_spec(wd, member, next_epoch)
            if spec is None:
                return 0
            epoch = int(spec["epoch"])
            members: List[int] = [int(m) for m in spec["members"]]
            if member not in members:
                logger.info("member %d dropped from epoch %d; exiting",
                            member, epoch)
                return 0
            rank, world = members.index(member), len(members)
            group: Optional[AllreduceGroup] = None
            try:
                group = _form_epoch(spec, member, rank, world)
                _write_json(wd / f"ready-{epoch}-{member}.json",
                            {"rank": rank, "world": world, "pid": os.getpid()})
                ctx = WorkerContext(
                    member=member, rank=rank, world=world, epoch=epoch,
                    workdir=wd, payload=dict(spec.get("payload") or {}),
                    group=group,
                    fault_directives=list(spec.get("faults") or []),
                )
                # the epoch spec ships the driver's TraceContext: the
                # gang.worker span (and the payload's children — allreduce,
                # histogram build) land in the driver's trace, tagged with
                # this process's label in the federated event log
                from mmlspark_tpu.observability.tracing import (
                    TraceContext,
                    get_tracer,
                )

                trace_ctx = TraceContext.from_dict(spec.get("trace"))
                with get_tracer().span(
                    "gang.worker", context=trace_ctx,
                    member=member, rank=rank, epoch=epoch,
                ):
                    result = _resolve_entry(spec["entry"])(ctx)
                if group is not None:
                    group.barrier()  # commit: the whole gang finished
                _write_json(wd / f"done-{epoch}-{member}.json",
                            {"ok": True, "result": result,
                             "collective": dict(group.stats)
                             if group is not None else {}})
            except GroupRevokedError as e:
                logger.warning("member %d: epoch %d revoked: %s",
                               member, epoch, e)
                _write_json(wd / f"revoked-{epoch}-{member}.json",
                            {"reason": str(e),
                             "suspect": getattr(e, "suspect", None),
                             "stats": getattr(e, "stats", None) or {}})
            except Exception as e:  # noqa: BLE001 - payload bug: report + die
                _write_json(wd / f"failed-{epoch}-{member}.json",
                            {"error": f"{type(e).__name__}: {e}",
                             "traceback": traceback.format_exc()})
                traceback.print_exc()
                return 1
            finally:
                if group is not None:
                    group.close()
                _clear_backends()
            next_epoch = epoch + 1
    finally:
        hb.stop()


def demo_entry(ctx: WorkerContext) -> Dict[str, Any]:
    """The dryrun/smoke payload: every member contributes ``member + 1``
    over a small grid and checks the allreduced total against the
    closed-form sum — proof the rendezvous numbered the right processes
    and the collective crossed all of them."""
    iters = int(ctx.payload.get("iterations", 3))
    total = 0.0
    for it in range(iters):
        ctx.maybe_die(it)
        local = np.full((4, 8), float(ctx.member + 1), np.float32)
        total = float(ctx.allreduce(local).sum())
    expected = 32.0 * sum(
        float(m + 1) for m in ctx.payload.get("expect_members", [ctx.member])
    )
    if ctx.payload.get("expect_members") and abs(total - expected) > 1e-5:
        raise AssertionError(f"allreduce total {total} != expected {expected}")
    return {"member": ctx.member, "rank": ctx.rank, "world": ctx.world,
            "total": total}


# -- driver side --------------------------------------------------------------


@dataclasses.dataclass
class ExitStatus:
    """Structured record of one member process's demise (or survival)."""

    member: int
    pid: int
    returncode: Optional[int]
    reason: str
    epoch: int

    @property
    def signal(self) -> Optional[int]:
        if self.returncode is not None and self.returncode < 0:
            return -self.returncode
        return None


class _Member:
    """Driver-side handle for one supervised child process."""

    def __init__(self, member: int, proc: subprocess.Popen, log_path: Path,
                 generation: int):
        self.member = member
        self.proc = proc
        self.log_path = log_path
        self.generation = generation

    @property
    def pid(self) -> int:
        return self.proc.pid


class ProcessGroup:
    """Supervised gang of worker processes with heartbeat liveness,
    structured exit-status collection, and epoch-based gang recovery.

    One :meth:`run` call drives the full protocol: write the epoch spec,
    watch for the gang to finish (done files) or fracture (child death,
    heartbeat silence, epoch timeout), and on fracture book the loss with
    the :class:`HealthTracker`, respawn or drop the member, and re-form on
    fresh ports. The payload sees revocation as
    :class:`GroupRevokedError` and is responsible for resuming from its
    own journal — the supervisor guarantees only membership and liveness.
    """

    def __init__(
        self,
        num_members: int,
        entry: str,
        payload: Optional[Dict[str, Any]] = None,
        workdir: Optional[str] = None,
        env: Optional[Dict[str, str]] = None,
        seed: int = 0,
        rendezvous: str = "jax",
        heartbeat_timeout_s: float = 10.0,
        epoch_timeout_s: float = 300.0,
        rendezvous_timeout_s: float = 60.0,
        group_timeout_s: float = 15.0,
        io_timeout_s: Optional[float] = None,
        slow_peer_s: Optional[float] = None,
        revoke_grace_s: float = 2.0,
        respawn: bool = True,
        max_epochs: int = 8,
        health: Optional[HealthTracker] = None,
        faults: Optional[FaultPlan] = None,
    ):
        if num_members < 1:
            raise ValueError(f"num_members must be >= 1, got {num_members}")
        self.num_members = int(num_members)
        self.entry = entry
        self.payload = dict(payload or {})
        if workdir is None:
            import tempfile

            workdir = tempfile.mkdtemp(prefix="mmlspark-tpu-procgroup-")
        self.workdir = Path(workdir)
        self.workdir.mkdir(parents=True, exist_ok=True)
        self.env = scrub_env(env)
        self.seed = int(seed)
        self.rendezvous = rendezvous
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.epoch_timeout_s = float(epoch_timeout_s)
        self.rendezvous_timeout_s = float(rendezvous_timeout_s)
        self.group_timeout_s = float(group_timeout_s)
        #: per-round collective deadline — the bound on how long a
        #: partitioned or silent peer can stall the gang before the
        #: epoch revokes (defaults to the formation timeout)
        self.io_timeout_s = float(
            io_timeout_s if io_timeout_s is not None else group_timeout_s
        )
        self.slow_peer_s = slow_peer_s
        #: how long to wait after the first revoked report for the rest
        #: of the gang to file theirs, so victim selection sees every vote
        self.revoke_grace_s = float(revoke_grace_s)
        self.respawn = bool(respawn)
        self.max_epochs = int(max_epochs)
        self.faults = faults if faults is not None else current_faults()
        self.health = health or HealthTracker(
            threshold=2.0, window_s=600.0, parole_s=600.0
        )
        self._wire_health_events()
        self.epoch = 0
        self.members: List[int] = list(range(self.num_members))
        self._procs: Dict[int, _Member] = {}
        self._generations: Dict[int, int] = {}
        self.exit_statuses: List[ExitStatus] = []
        self._metrics = self._make_metrics()

    # -- observability wiring ------------------------------------------------

    def _wire_health_events(self) -> None:
        from mmlspark_tpu.observability import WorkerQuarantined, get_bus

        def announce(member: int, score: float) -> None:
            bus = get_bus()
            if bus.active:
                bus.publish(WorkerQuarantined(
                    worker=member, score=score,
                    parole_s=self.health.parole_s,
                ))

        if self.health.on_quarantine is None:
            self.health.on_quarantine = announce

    @staticmethod
    def _make_metrics():
        from mmlspark_tpu.observability import get_registry

        reg = get_registry()
        return {
            "members": reg.gauge(
                "procgroup_members", "Live members in the process group"),
            "epoch": reg.gauge(
                "procgroup_epoch", "Current gang epoch"),
            "started": reg.counter(
                "procgroup_processes_started_total",
                "Member processes spawned (including respawns)"),
            "lost": reg.counter(
                "procgroup_processes_lost_total",
                "Member processes lost (exit, signal, or heartbeat silence)"),
            "reforms": reg.counter(
                "procgroup_reforms_total", "Gang recovery re-formations"),
            "partitions": reg.counter(
                "netchaos_partitions_total",
                "Partition-triggered epoch revocations resolved"),
            "retransmits": reg.counter(
                "collective_retransmits_total",
                "Allreduce frames retransmitted after a CRC rejection"),
            "slow_peers": reg.counter(
                "netchaos_slow_peers_total",
                "Slow-peer detections booked from collective stats"),
        }

    def _publish(self, event) -> None:
        from mmlspark_tpu.observability import get_bus

        bus = get_bus()
        if bus.active:
            bus.publish(event)

    # -- spawn/monitor -------------------------------------------------------

    def start(self) -> "ProcessGroup":
        for member in self.members:
            self._spawn(member, start_epoch=0)
        return self

    def _spawn(self, member: int, start_epoch: int) -> None:
        from mmlspark_tpu.observability import ProcessStarted

        gen = self._generations.get(member, -1) + 1
        self._generations[member] = gen
        log_path = self.workdir / f"log-{member}-{gen}.txt"
        log_fh = open(log_path, "wb")
        # per-process event-log federation: the gang member writes its own
        # ``<base>@member-<m>`` segment instead of clobbering the driver's
        # live file (observability.events.collect folds them back)
        env = dict(self.env)
        env["MMLSPARK_TPU_EVENT_LOG_PROCESS"] = f"member-{member}"
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "mmlspark_tpu.runtime.procgroup",
                 "--worker", str(self.workdir), str(member),
                 "--start-epoch", str(start_epoch)],
                env=env, stdout=log_fh, stderr=subprocess.STDOUT,
                cwd=str(self.workdir),
            )
        finally:
            log_fh.close()  # child holds its own descriptor
        self._procs[member] = _Member(member, proc, log_path, gen)
        self._metrics["started"].inc()
        logger.info("spawned member %d pid %d (epoch %d, gen %d)",
                    member, proc.pid, start_epoch, gen)
        self._publish(ProcessStarted(member=member, pid=proc.pid,
                                     epoch=start_epoch))

    def tail_log(self, member: int, max_bytes: int = 4096) -> str:
        """The last ``max_bytes`` of a member's current log — appended to
        failure messages so a worker's stderr reaches the driver's
        exception instead of dying with the temp dir."""
        handle = self._procs.get(member)
        if handle is None or not handle.log_path.exists():
            return ""
        data = handle.log_path.read_bytes()
        return data[-max_bytes:].decode("utf-8", errors="replace")

    def _hb_age(self, member: int) -> Optional[float]:
        path = self.workdir / f"hb-{member}"
        try:
            return time.time() - path.stat().st_mtime
        except OSError:
            return None  # no beat yet — covered by the epoch deadline

    def _check_losses(self, epoch: int, done: Dict[int, Any]) -> List[ExitStatus]:
        """Sweep live members for deaths and heartbeat silence. A member
        that already reported done for this epoch is not a loss regardless
        of its process state (it may be exiting after the stop file)."""
        losses: List[ExitStatus] = []
        for member in self.members:
            if member in done:
                continue
            handle = self._procs.get(member)
            if handle is None:
                continue
            rc = handle.proc.poll()
            if rc is not None:
                reason = f"signal:{-rc}" if rc < 0 else f"exit:{rc}"
                losses.append(ExitStatus(member, handle.pid, rc, reason, epoch))
                continue
            age = self._hb_age(member)
            if age is not None and age > self.heartbeat_timeout_s:
                handle.proc.kill()
                handle.proc.wait(timeout=10)
                losses.append(ExitStatus(
                    member, handle.pid, handle.proc.returncode,
                    "heartbeat", epoch,
                ))
        return losses

    def _read_done(self, epoch: int) -> Dict[int, Any]:
        done: Dict[int, Any] = {}
        for member in self.members:
            path = self.workdir / f"done-{epoch}-{member}.json"
            if path.exists():
                try:
                    done[member] = json.loads(path.read_text())
                except (OSError, json.JSONDecodeError):
                    pass
        return done

    def _read_revoked(self, epoch: int) -> Dict[int, Any]:
        """Members that reported epoch ``epoch`` revoked, with their
        blame (``suspect``) and collective stats."""
        revoked: Dict[int, Any] = {}
        for member in self.members:
            path = self.workdir / f"revoked-{epoch}-{member}.json"
            if path.exists():
                try:
                    revoked[member] = json.loads(path.read_text())
                except (OSError, json.JSONDecodeError):
                    pass
        return revoked

    def _write_spec(self, epoch: int) -> None:
        directives: List[dict] = []
        if self.faults is not None:
            directives = self.faults.process_kill_directives()
        spec = {
            "epoch": epoch,
            "members": list(self.members),
            "coordinator_port": pick_port(seed=self.seed * 1000 + epoch * 2),
            "reduce_port": pick_port(seed=self.seed * 1000 + epoch * 2 + 1),
            "entry": self.entry,
            "payload": self.payload,
            "faults": directives,
            "rendezvous": self.rendezvous,
            "rendezvous_timeout_s": self.rendezvous_timeout_s,
            "group_timeout_s": self.group_timeout_s,
            "io_timeout_s": self.io_timeout_s,
            "slow_peer_s": self.slow_peer_s,
        }
        if self.faults is not None:
            net = self.faults.net_directives(epoch)
            if net:
                spec["net_faults"] = net
                spec["net_seed"] = self.faults.seed
        if spec["reduce_port"] == spec["coordinator_port"]:
            spec["reduce_port"] = pick_port(
                seed=self.seed * 1000 + epoch * 2 + 7,
                exclude=[spec["coordinator_port"]],
            )
        # ship the driver's ambient trace so worker spans (allreduce,
        # histogram build) parent under it in the merged fleet trace
        from mmlspark_tpu.observability.tracing import TraceContext, get_tracer

        span = get_tracer().current()
        if span is not None:
            spec["trace"] = TraceContext.from_span(span).to_dict()
        _write_json(self.workdir / f"epoch-{epoch}.json", spec)

    # -- the gang loop -------------------------------------------------------

    def run(self, poll: float = 0.1) -> Dict[int, Any]:
        """Drive epochs until one completes on every live member. Returns
        ``{member: payload result}`` for the successful epoch. Raises
        :class:`GangFailedError` when recovery options run out and
        ``RuntimeError`` when a payload itself fails (a bug, surfaced with
        the worker's log tail).

        The whole gang runs under one ``procgroup.run`` span whose
        context ships in every epoch spec, so worker-side spans join the
        driver's trace; a :class:`GangFailedError` trips the incident
        flight recorder before it propagates."""
        from mmlspark_tpu.observability.tracing import get_tracer

        with get_tracer().span("procgroup.run", entry=self.entry):
            return self._run_epochs(poll)

    def _gang_failed(self, message: str) -> GangFailedError:
        """Book the incident (when a recorder is installed) and build the
        terminal error — gang death is exactly what the flight recorder
        exists to capture."""
        from mmlspark_tpu.observability.incidents import maybe_record
        from mmlspark_tpu.observability.tracing import get_tracer

        span = get_tracer().current()
        maybe_record(
            "gang_failed",
            trace_id=span.trace_id if span is not None else "",
            detail=message,
        )
        return GangFailedError(message)

    def _harvest_collective(
        self, epoch: int, stats_by_member: Dict[int, dict]
    ) -> None:
        """Fold the gang's per-member collective stats into metrics,
        events, health bookings, and fault-plan acknowledgements:
        retransmits count toward ``collective_retransmits_total`` (and
        consume a ``net_corrupt`` directive — the corruption fired and
        was absorbed); slow peers become ``PeerSlow`` events plus health
        straggle bookings (and consume a ``net_delay`` directive)."""
        from mmlspark_tpu.observability import PeerSlow

        for member in sorted(stats_by_member):
            stats = stats_by_member[member] or {}
            retrans = int(stats.get("retransmits", 0))
            if retrans > 0:
                self._metrics["retransmits"].inc(retrans)
                if self.faults is not None:
                    self.faults.mark_net_fired("corrupt", member, epoch)
                logger.warning(
                    "member %d absorbed %d retransmit(s) in epoch %d",
                    member, retrans, epoch,
                )
            for peer, wait in sorted(
                (stats.get("slow_peers") or {}).items()
            ):
                peer = int(peer)
                self._metrics["slow_peers"].inc()
                self.health.note_straggle(peer)
                if self.faults is not None:
                    self.faults.mark_net_fired("delay", peer, epoch)
                self._publish(PeerSlow(
                    member=peer, epoch=epoch, wait_s=float(wait),
                ))
                logger.warning(
                    "member %d observed peer %d slow (%.3fs) in epoch %d",
                    member, peer, float(wait), epoch,
                )

    def _pick_victim(self, epoch: int, revoked: Dict[int, Any]) -> int:
        """Deterministic blame resolution for a no-corpse revocation:
        every reporter names the peer its collective suspected (non-roots
        always blame the star center, rank 0 blames the member on the
        failed link); members that filed nothing within the grace window
        are suspects by silence. Most votes loses; ties go to the
        highest member id, so the coordinator survives a symmetric
        two-member partition and the journal-holding rank 0 is kept."""
        votes: Dict[int, int] = {}
        for reporter, info in revoked.items():
            suspect = info.get("suspect")
            if suspect is None or int(suspect) == int(reporter):
                continue
            if int(suspect) in self.members:
                votes[int(suspect)] = votes.get(int(suspect), 0) + 1
        silent = [
            m for m in self.members
            if m not in revoked and m not in self._read_done(epoch)
        ]
        for m in silent:  # said nothing while the gang revoked around it
            votes[m] = votes.get(m, 0) + 1
        if not votes:
            return max(self.members)
        top = max(votes.values())
        return max(m for m, n in votes.items() if n == top)

    def _resolve_revocation(
        self, epoch: int, revoked: Dict[int, Any]
    ) -> List[ExitStatus]:
        """Turn a partition-style revocation (every process alive, the
        collective dead) into the loss the existing recovery path knows
        how to handle: pick the blamed member, kill it, and book the
        death with reason ``"partition"``. When a real corpse already
        exists (the revocation was a peer noticing a SIGKILL) the corpse
        is the loss and no extra member is killed."""
        from mmlspark_tpu.observability import NetworkPartitioned
        from mmlspark_tpu.observability.incidents import maybe_record
        from mmlspark_tpu.observability.tracing import get_tracer

        self._harvest_collective(epoch, {
            m: info.get("stats") or {} for m, info in revoked.items()
        })
        losses = self._check_losses(epoch, self._read_done(epoch))
        if losses:
            return losses
        victim = self._pick_victim(epoch, revoked)
        handle = self._procs.get(victim)
        pid, rc = -1, None
        if handle is not None:
            if handle.proc.poll() is None:
                handle.proc.kill()
                handle.proc.wait(timeout=10)
            pid, rc = handle.pid, handle.proc.returncode
        reasons = "; ".join(
            f"m{m}: {info.get('reason', '?')}"
            for m, info in sorted(revoked.items())
        )
        self._metrics["partitions"].inc()
        if self.faults is not None:
            for kind in ("partition", "drop"):
                if self.faults.mark_net_fired(kind, victim, epoch):
                    break
        self._publish(NetworkPartitioned(
            member=victim, epoch=epoch, reason=reasons,
        ))
        span = get_tracer().current()
        maybe_record(
            "network_partitioned",
            trace_id=span.trace_id if span is not None else "",
            detail=f"epoch {epoch} victim {victim}: {reasons}",
        )
        logger.warning(
            "epoch %d revoked without a corpse; victim member %d "
            "(votes from %s)", epoch, victim, sorted(revoked),
        )
        return [ExitStatus(victim, pid, rc, "partition", epoch)]

    def _run_epochs(self, poll: float) -> Dict[int, Any]:
        from mmlspark_tpu.observability import GroupReformed, ProcessLost

        if not self._procs:
            self.start()
        while True:
            if self.epoch >= self.max_epochs:
                raise self._gang_failed(
                    f"no successful epoch within {self.max_epochs} attempts"
                )
            epoch = self.epoch
            self._metrics["epoch"].set(epoch)
            self._metrics["members"].set(len(self.members))
            self._write_spec(epoch)
            outcome, detail = self._monitor_epoch(epoch, poll)
            if outcome == "ok":
                return detail
            if outcome == "failed":
                raise RuntimeError(detail)
            if outcome == "revoked":
                # partition/slow-peer: resolve blame into a loss, then
                # recover exactly as for a corpse
                detail = self._resolve_revocation(epoch, detail)
            # book the dead, decide membership, re-form
            losses: List[ExitStatus] = detail
            survivors = list(self.members)
            for loss in losses:
                self.exit_statuses.append(loss)
                self._metrics["lost"].inc()
                self._publish(ProcessLost(
                    member=loss.member, pid=loss.pid,
                    reason=loss.reason, epoch=epoch,
                ))
                if self.faults is not None:
                    self.faults.mark_process_killed(loss.member)
                self.health.note_failure(loss.member, reason=loss.reason)
                survivors.remove(loss.member)
            next_members = list(survivors)
            for loss in losses:
                # drop the dead handle now: its demise is booked above, and
                # shutdown() must not book the same corpse a second time
                self._procs.pop(loss.member, None)
                if self.respawn and not self.health.is_quarantined(loss.member):
                    self._spawn(loss.member, start_epoch=epoch + 1)
                    next_members.append(loss.member)
                else:
                    logger.warning(
                        "member %d not respawned (quarantined=%s respawn=%s)",
                        loss.member,
                        self.health.is_quarantined(loss.member), self.respawn,
                    )
            if not next_members:
                raise self._gang_failed(
                    "all members lost and none eligible for respawn"
                )
            self.members = sorted(next_members)
            self.epoch = epoch + 1
            self._metrics["reforms"].inc()
            self._publish(GroupReformed(
                epoch=self.epoch, members=len(self.members), lost=len(losses),
            ))
            logger.info("gang re-formed for epoch %d with members %s "
                        "(lost %s)", self.epoch, self.members,
                        [l.member for l in losses])

    def _monitor_epoch(self, epoch: int, poll: float) -> Tuple[str, Any]:
        deadline = time.monotonic() + self.epoch_timeout_s
        while True:
            done = self._read_done(epoch)
            if all(m in done for m in self.members):
                bad = {m: d for m, d in done.items() if not d.get("ok")}
                if bad:
                    return "failed", f"payload reported failure: {bad}"
                self._harvest_collective(epoch, {
                    m: d.get("collective") or {} for m, d in done.items()
                })
                return "ok", {m: d.get("result") for m, d in done.items()}
            for member in self.members:
                path = self.workdir / f"failed-{epoch}-{member}.json"
                if path.exists():
                    try:
                        info = json.loads(path.read_text())
                    except (OSError, json.JSONDecodeError):
                        info = {}
                    return "failed", (
                        f"member {member} payload failed in epoch {epoch}: "
                        f"{info.get('error', '?')}\n"
                        f"{info.get('traceback', '')}\n"
                        f"--- log tail ---\n{self.tail_log(member)}"
                    )
            losses = self._check_losses(epoch, done)
            if losses:
                time.sleep(min(0.5, poll * 2))  # catch simultaneous deaths
                losses = self._check_losses(epoch, self._read_done(epoch))
                if losses:
                    return "lost", losses
            revoked = self._read_revoked(epoch)
            if revoked:
                # a partition/slow-peer revocation with every process
                # still alive: give the rest of the gang a grace window
                # to file their reports so victim selection sees all votes
                grace = min(deadline, time.monotonic() + self.revoke_grace_s)
                while time.monotonic() < grace:
                    done = self._read_done(epoch)
                    revoked = self._read_revoked(epoch)
                    if all(
                        m in done or m in revoked
                        or (self._procs.get(m) is not None
                            and self._procs[m].proc.poll() is not None)
                        for m in self.members
                    ):
                        break
                    time.sleep(poll)
                return "revoked", self._read_revoked(epoch)
            if time.monotonic() >= deadline:
                stuck = [m for m in self.members if m not in done]
                losses = []
                for member in stuck:
                    handle = self._procs.get(member)
                    if handle is None:
                        continue
                    handle.proc.kill()
                    handle.proc.wait(timeout=10)
                    losses.append(ExitStatus(
                        member, handle.pid, handle.proc.returncode,
                        "timeout", epoch,
                    ))
                if losses:
                    return "lost", losses
                return "failed", f"epoch {epoch} timed out with no live member"
            time.sleep(poll)

    # -- teardown ------------------------------------------------------------

    def shutdown(self, grace_s: float = 5.0) -> List[ExitStatus]:
        """Stop the gang: write the stop file, give workers ``grace_s`` to
        exit on their own, then escalate to terminate/kill. Returns the
        final exit status of every member ever spawned."""
        try:
            (self.workdir / "stop").write_text("stop\n")
        except OSError:  # pragma: no cover - workdir already gone
            pass
        deadline = time.monotonic() + grace_s
        for handle in self._procs.values():
            remaining = max(0.0, deadline - time.monotonic())
            try:
                handle.proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                handle.proc.terminate()
                try:
                    handle.proc.wait(timeout=2.0)
                except subprocess.TimeoutExpired:
                    handle.proc.kill()
                    handle.proc.wait(timeout=10)
        final: List[ExitStatus] = []
        for member, handle in sorted(self._procs.items()):
            rc = handle.proc.returncode
            reason = "running" if rc is None else (
                f"signal:{-rc}" if rc < 0 else f"exit:{rc}"
            )
            final.append(ExitStatus(member, handle.pid, rc, reason, self.epoch))
        self._metrics["members"].set(0)
        return final

    def __enter__(self) -> "ProcessGroup":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()


# -- CLI (the spawned worker) -------------------------------------------------


def _main(argv: List[str]) -> int:
    import argparse

    parser = argparse.ArgumentParser(prog="mmlspark_tpu.runtime.procgroup")
    parser.add_argument("--worker", required=True, metavar="WORKDIR",
                        help="group workdir (driver-managed)")
    parser.add_argument("member", type=int)
    parser.add_argument("--start-epoch", type=int, default=0)
    args = parser.parse_args(argv)
    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()  # gang members share compiled programs
    return worker_main(args.worker, args.member, args.start_epoch)


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    # Re-dispatch through the canonically-imported module: under
    # ``python -m`` this file runs as ``__main__``, and exception classes
    # defined here would differ from the ones payload entries import from
    # ``mmlspark_tpu.runtime.procgroup`` — ``except GroupRevokedError``
    # in worker_main must see the SAME class the payload raises.
    from mmlspark_tpu.runtime import procgroup as _canonical

    sys.exit(_canonical._main(sys.argv[1:]))
