"""Two-process ``jax.distributed`` bootstrap smoke.

Executes :func:`mmlspark_tpu.parallel.mesh.distributed_init` for REAL: a
coordinator and a worker process rendezvous over localhost (the surviving
driver-rendezvous role of the reference's ``LightGBMUtils.scala:117-186``
socket collect/broadcast), then run one cross-process collective and
check both sides observe the global sum.

The collective has two layers, matching how the process-parallel fit
actually works (``runtime/procgroup.py``): an XLA ``psum`` when the
backend supports multi-process computation, else the host-level socket
allreduce — the analogue of LightGBM's own ``Network::Allreduce``, which
likewise never runs inside the accelerator program. jax's CPU backend
raises ``Multiprocess computations aren't implemented`` for the former,
so on CPU the socket path is the one under test; the rendezvous
assertions (process_count/process_index/topology) run either way.

Hardening baked in here: worker ports come from the seeded
``pick_port`` prober with a bounded retry on bind races, and a failing
worker's full output (stderr is merged into stdout) is propagated into
the assertion message instead of a bare exit code.
"""

import os
import subprocess
import sys
import textwrap

from mmlspark_tpu.runtime.procgroup import pick_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent(
    """
    import os, sys, traceback

    sys.path.insert(0, sys.argv[3])

    pid, port, reduce_port = int(sys.argv[1]), sys.argv[2], int(sys.argv[4])

    from mmlspark_tpu.parallel.mesh import distributed_init

    # executor-keyed convention: process ids derive from the sorted
    # executor list, exactly how a driver would number its workers.
    topo = distributed_init(
        coordinator_address=f"127.0.0.1:{port}",
        executor_ids=["exec-b", "exec-a"],
        local_executor_id=["exec-a", "exec-b"][pid],
    )
    import jax
    import jax.numpy as jnp

    assert jax.process_count() == 2, jax.process_count()
    assert jax.process_index() == pid, (jax.process_index(), pid)
    assert topo.num_devices == 2, topo.num_devices

    # one real cross-process collective: the global sum of (pid + 1) over
    # both processes must be 3 on BOTH sides. Try the XLA layer first;
    # backends without multi-process computation (CPU) fall back to the
    # host-level socket allreduce — the layer the process-parallel fit
    # rides (procgroup.AllreduceGroup over jax.pure_callback).
    layer = "psum"
    try:
        local = jnp.full((jax.local_device_count(), 1), float(pid + 1))
        total = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(local)
        value = float(total[0, 0])
    except RuntimeError as e:
        if "Multiprocess computations" not in str(e):
            raise
        layer = "socket"
        from mmlspark_tpu.parallel.mesh import distributed_shutdown
        from mmlspark_tpu.runtime.procgroup import AllreduceGroup

        # release the distributed client BEFORE host collectives: a live
        # coordination-service poller aborts survivors on peer exit
        distributed_shutdown()
        import numpy as np

        group = AllreduceGroup(pid, 2, reduce_port, timeout=60.0)
        value = float(group.allreduce(np.full((1,), float(pid + 1)))[0])
        group.close()
    assert value == 3.0, (layer, value)
    print(f"OK {pid} via {layer}", flush=True)
    """
)


def _run_pair(script, port, reduce_port, env):
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), str(port), REPO,
             str(reduce_port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    return procs, outs


def test_two_process_collective(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)

    # Scrub the child env: no XLA_FLAGS (one CPU device per process), no
    # TPU_* runtime variables, backend pinned to the CPU — a chip belongs
    # to one process, and these two must form their group without it.
    env = {
        k: v
        for k, v in os.environ.items()
        if k != "XLA_FLAGS" and not k.startswith("TPU_")
    }
    env["JAX_PLATFORMS"] = "cpu"

    # Seeded bind-probed ports; the probe releases before the coordinator
    # child rebinds — a TOCTOU window another process can steal. Retry on
    # fresh ports rather than flaking.
    for attempt in range(3):
        port = pick_port(seed=7000 + attempt)
        reduce_port = pick_port(seed=8000 + attempt, exclude={port})
        procs, outs = _run_pair(script, port, reduce_port, env)
        if all(p.returncode == 0 for p in procs):
            break
        bind_lost = any(
            "Failed to bind" in out or "address already in use" in out.lower()
            for out in outs
        )
        if not (bind_lost and attempt < 2):
            break
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"OK {pid}" in out, out
