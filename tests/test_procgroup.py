"""Process-group supervisor tests (``mmlspark_tpu.runtime.procgroup``).

The fast tests exercise the in-process pieces: the seeded port prober,
the socket star allreduce (threads standing in for processes), the
worker-side fault directive check, and the spec/exit-status plumbing.
The ``slow`` tests spawn REAL worker processes and cover the tentpole
claims: a gang that completes, and a gang whose member is SIGKILL'd
mid-collective yet re-forms and finishes, with the loss booked as
events, health failures, and structured exit statuses.
"""

import json
import os
import socket
import threading

import numpy as np
import pytest

from mmlspark_tpu.runtime.faults import FaultPlan
from mmlspark_tpu.runtime.procgroup import (
    AllreduceGroup,
    ExitStatus,
    GangFailedError,
    GroupRevokedError,
    ProcessGroup,
    pick_port,
    scrub_env,
)


class TestPickPort:
    def test_seeded_is_deterministic(self):
        assert pick_port(seed=42) == pick_port(seed=42)

    def test_exclude_respected(self):
        first = pick_port(seed=7)
        second = pick_port(seed=7, exclude={first})
        assert second != first

    def test_port_is_bindable(self):
        port = pick_port(seed=3)
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", port))


class TestScrubEnv:
    def test_strips_accelerator_vars_and_pins_cpu(self):
        env = scrub_env({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "TPU_Z": "3", "HOME": "/root",
        })
        assert "XLA_FLAGS" not in env
        assert not any(k.startswith("TPU_") for k in env)
        assert env["JAX_PLATFORMS"] == "cpu"
        assert env["HOME"] == "/root"

    def test_repo_root_on_pythonpath(self):
        env = scrub_env({})
        import mmlspark_tpu

        root = os.path.dirname(os.path.dirname(mmlspark_tpu.__file__))
        assert root in env["PYTHONPATH"].split(os.pathsep)


class TestAllreduceGroup:
    def _run_group(self, world, arrays, port):
        results = [None] * world
        errors = []

        def member(rank):
            try:
                g = AllreduceGroup(rank, world, port, timeout=20.0)
                results[rank] = np.asarray(g.allreduce(arrays[rank]))
                g.barrier()
                g.close()
            except Exception as e:  # noqa: BLE001
                errors.append((rank, e))

        threads = [threading.Thread(target=member, args=(r,)) for r in range(world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errors, errors
        return results

    def test_three_member_sum(self):
        world = 3
        arrays = [np.full((2, 4), float(r + 1), np.float32) for r in range(world)]
        port = pick_port(seed=100)
        results = self._run_group(world, arrays, port)
        for r in range(world):
            np.testing.assert_allclose(results[r], np.full((2, 4), 6.0))

    def test_single_member_is_identity(self):
        g = AllreduceGroup(0, 1, 0)
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        np.testing.assert_array_equal(g.allreduce(x), x)
        g.barrier()
        g.close()

    def test_peer_death_revokes_group(self):
        port = pick_port(seed=101)
        ready = threading.Event()
        outcome = {}

        def survivor():
            g = AllreduceGroup(0, 2, port, timeout=10.0)
            ready.set()
            try:
                g.allreduce(np.ones(4, np.float32))
                g.allreduce(np.ones(4, np.float32))  # peer is gone by now
                outcome["error"] = None
            except GroupRevokedError:
                outcome["error"] = "revoked"
                assert g.revoked
            finally:
                g.close()

        t = threading.Thread(target=survivor)
        t.start()
        peer = AllreduceGroup(1, 2, port, timeout=10.0)
        peer.allreduce(np.ones(4, np.float32))
        peer.close()  # vanish without a second round
        t.join(timeout=20.0)
        assert outcome.get("error") == "revoked"


class TestFaultDirectives:
    def test_kill_process_plan_round_trip(self):
        plan = FaultPlan(seed=1).kill_process(2, iteration=5, epoch=0)
        directives = plan.process_kill_directives()
        assert directives == [{"member": 2, "iteration": 5, "epoch": 0}]
        # worker side: only the targeted member at the targeted iteration
        assert FaultPlan.should_die(directives, member=2, iteration=5, epoch=0)
        assert not FaultPlan.should_die(directives, member=1, iteration=5, epoch=0)
        assert not FaultPlan.should_die(directives, member=2, iteration=4, epoch=0)

    def test_mark_killed_is_one_shot(self):
        plan = FaultPlan(seed=1).kill_process(1, iteration=0)
        assert plan.mark_process_killed(1)
        assert not plan.mark_process_killed(1)
        assert ("kill_process", 1, 0) in plan.fired
        assert plan.process_kill_directives() == []

    def test_exit_status_signal(self):
        dead = ExitStatus(member=0, pid=1, returncode=-9, reason="signal:9", epoch=0)
        clean = ExitStatus(member=1, pid=2, returncode=0, reason="exit:0", epoch=0)
        assert dead.signal == 9
        assert clean.signal is None


class TestSpecPlumbing:
    def test_write_spec_ships_fault_directives_once(self, tmp_path):
        plan = FaultPlan(seed=2).kill_process(0, iteration=1)
        pg = ProcessGroup(
            2, "mmlspark_tpu.runtime.procgroup:demo_entry",
            workdir=str(tmp_path), rendezvous="none", faults=plan,
        )
        pg._write_spec(0)
        spec = json.loads((tmp_path / "epoch-0.json").read_text())
        assert spec["members"] == [0, 1]
        assert spec["faults"] == [{"member": 0, "iteration": 1, "epoch": 0}]
        assert spec["entry"] == "mmlspark_tpu.runtime.procgroup:demo_entry"
        # after the driver books the kill, the NEXT spec ships no directive
        plan.mark_process_killed(0)
        pg._write_spec(1)
        spec1 = json.loads((tmp_path / "epoch-1.json").read_text())
        assert spec1["faults"] == []

    def test_spec_ports_differ_per_epoch(self, tmp_path):
        pg = ProcessGroup(
            2, "mmlspark_tpu.runtime.procgroup:demo_entry",
            workdir=str(tmp_path), rendezvous="none", seed=5,
        )
        pg._write_spec(0)
        pg._write_spec(1)
        s0 = json.loads((tmp_path / "epoch-0.json").read_text())
        s1 = json.loads((tmp_path / "epoch-1.json").read_text())
        assert s0["coordinator_port"] != s1["coordinator_port"]
        assert s0["reduce_port"] != s0["coordinator_port"]


@pytest.mark.slow
class TestProcessGroupLive:
    """Real spawned worker processes."""

    def test_happy_path_allreduce(self, tmp_path):
        with ProcessGroup(
            3, "mmlspark_tpu.runtime.procgroup:demo_entry",
            payload={"iterations": 2, "expect_members": [0, 1, 2]},
            workdir=str(tmp_path), rendezvous="none", epoch_timeout_s=120.0,
        ) as pg:
            results = pg.run()
        assert sorted(results) == [0, 1, 2]
        for res in results.values():
            assert res["total"] == 32.0 * 6  # (1+2+3) * 4*8 grid
        assert pg.epoch == 0

    def test_sigkill_reform_and_complete(self, tmp_path):
        plan = FaultPlan(seed=9).kill_process(1, iteration=1)
        with ProcessGroup(
            2, "mmlspark_tpu.runtime.procgroup:demo_entry",
            payload={"iterations": 3},
            workdir=str(tmp_path), rendezvous="none",
            epoch_timeout_s=120.0, faults=plan,
        ) as pg:
            results = pg.run()
        assert sorted(results) == [0, 1]
        assert pg.epoch == 1  # one re-formation
        assert [s.reason for s in pg.exit_statuses] == ["signal:9"]
        assert pg.exit_statuses[0].member == 1
        assert plan.fired == [("kill_process", 1, 0)]
        assert pg.health.score(1) > 0

    def test_payload_failure_surfaces_worker_log(self, tmp_path):
        with ProcessGroup(
            1, "mmlspark_tpu.runtime.procgroup:no_such_entry",
            workdir=str(tmp_path), rendezvous="none", epoch_timeout_s=60.0,
        ) as pg:
            with pytest.raises(RuntimeError, match="no_such_entry"):
                pg.run()

    def test_no_respawn_exhausts_gang(self, tmp_path):
        plan = FaultPlan(seed=3).kill_process(0, iteration=0)
        with ProcessGroup(
            1, "mmlspark_tpu.runtime.procgroup:demo_entry",
            payload={"iterations": 2}, workdir=str(tmp_path),
            rendezvous="none", epoch_timeout_s=60.0, respawn=False,
            faults=plan,
        ) as pg:
            with pytest.raises(GangFailedError):
                pg.run()
