"""Serving latency artifact — BASELINE config 5 (p50 < 5 ms target).

Measures the two components of a served single-row prediction and their
end-to-end composition:

1. HTTP edge + micro-batch loop overhead (trivial model, local socket);
2. warm jitted device forward of a real zoo model (ResNet-18, batch 1..8);
3. end-to-end: the ResNet served through ServingServer.

(1) and (2) are per-component numbers, (2) timed with an on-device loop;
their sum is reported as a composition next to the measured end-to-end (3).

Run: ``python benchmarks/serving_latency.py`` (single chip).
"""

import json
import os
import sys
import time
import urllib.request

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), method="POST"
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _percentiles(times):
    times = sorted(times)
    n = len(times)
    return {
        "p50_ms": times[n // 2] * 1e3,
        "p90_ms": times[int(n * 0.9)] * 1e3,
        "p99_ms": times[min(n - 1, int(n * 0.99))] * 1e3,
    }


def http_edge_keepalive_latency(n=500):
    """One persistent HTTP/1.1 connection, n sequential requests — the
    steady-state client shape (no TCP setup per call)."""
    import http.client

    from mmlspark_tpu.core.pipeline import Transformer
    from mmlspark_tpu.serving import ServingServer

    class Doubler(Transformer):
        def transform(self, table):
            x = np.asarray(table.column("input"), dtype=np.float64)
            return table.with_column("prediction", x * 2)

    with ServingServer(Doubler(), max_latency_ms=0.2) as srv:
        conn = http.client.HTTPConnection("127.0.0.1", srv.info.port)
        conn.connect()
        import socket

        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        body = json.dumps({"input": 1.0}).encode()

        def call():
            conn.request("POST", "/", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            assert resp.status == 200

        for _ in range(20):
            call()
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            call()
            times.append(time.perf_counter() - t0)
        conn.close()
    return _percentiles(times)


def http_edge_latency(n=200):
    from mmlspark_tpu.core.pipeline import Transformer
    from mmlspark_tpu.serving import ServingServer

    class Doubler(Transformer):
        def transform(self, table):
            x = np.asarray(table.column("input"), dtype=np.float64)
            return table.with_column("prediction", x * 2)

    with ServingServer(Doubler(), max_latency_ms=0.5) as srv:
        for _ in range(10):
            _post(srv.info.url, {"input": 1.0})
        times = []
        for i in range(n):
            t0 = time.perf_counter()
            _post(srv.info.url, {"input": float(i)})
            times.append(time.perf_counter() - t0)
    return _percentiles(times)


def device_forward_latency(
    batch=1, iters=200, variant="resnet18", size=32, dtype="float32"
):
    """Warm jitted ResNet forward, timed with an on-device loop (one
    dispatch for all iters, so per-call dispatch cost amortizes out; the
    closing sync fetch is subtracted via an empty-loop floor — at fewer
    reps it silently inflates every per-iter number)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mmlspark_tpu.models import init_resnet, resnet_apply

    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    params = jax.tree.map(
        lambda a: jnp.asarray(a, dt),
        init_resnet(
            variant=variant, num_classes=10, small_inputs=(size <= 64)
        ),
    )  # pin weights on device ONCE — numpy leaves re-upload per dispatch
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(batch, 3, size, size)), dt
    )

    @jax.jit
    def loop(params, x):
        def body(i, acc):
            out = resnet_apply(params, x * (1.0 + i.astype(dt) * dt(1e-9)))
            return acc + out.ravel()[0].astype(jnp.float32)

        return lax.fori_loop(0, iters, body, jnp.float32(0.0))

    @jax.jit
    def floor_loop(x):
        def body(i, acc):
            return acc + x.ravel()[0].astype(jnp.float32) * 0

        return lax.fori_loop(0, iters, body, jnp.float32(0.0))

    float(loop(params, x))  # compile
    float(floor_loop(x))
    # The sync fetch swings run to run — a single floor/loop pair can
    # even go negative. Median of 5 each.
    floors, runs = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        float(floor_loop(x))
        floors.append(time.perf_counter() - t0)
    for _ in range(5):
        t0 = time.perf_counter()
        float(loop(params, x))
        runs.append(time.perf_counter() - t0)
    per_call = (float(np.median(runs)) - float(np.median(floors))) / iters
    return per_call * 1e3


def served_resnet_latency(n=30):
    import jax.numpy as jnp

    from mmlspark_tpu.core.pipeline import Transformer
    from mmlspark_tpu.models import init_resnet, resnet_apply
    from mmlspark_tpu.serving import ServingServer

    import jax

    params = jax.tree.map(
        jnp.asarray,
        init_resnet(variant="resnet18", num_classes=10, small_inputs=True),
    )
    fwd = jax.jit(resnet_apply)

    class ResNetModel(Transformer):
        def transform(self, table):
            col = table.column("input")
            x = jnp.asarray(np.stack(list(col)), jnp.float32)
            out = np.asarray(fwd(params, x))
            outcol = np.empty(len(out), dtype=object)
            for i in range(len(out)):
                outcol[i] = out[i].tolist()
            return table.with_column("prediction", outcol)

    img = np.random.default_rng(0).normal(size=(3, 32, 32)).tolist()
    with ServingServer(ResNetModel(), max_latency_ms=1.0) as srv:
        for _ in range(3):
            _post(srv.info.url, {"input": img})
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            _post(srv.info.url, {"input": img})
            times.append(time.perf_counter() - t0)
    return _percentiles(times)


def concurrent_load_latency(
    num_servers=3, num_clients=16, reqs_per_client=25, kill_worker=True
):
    """END-TO-END measured latency distribution under concurrent load —
    ``num_clients`` threads hammering a :class:`DistributedServingServer`
    (the ``HTTPv2Suite.scala:315-387`` shape). Midway through, one listener
    dies; its clients fail over to the surviving endpoints (the
    registry-discovery story), and the distribution INCLUDES the failed
    attempts' wall time. This is one measured pipeline number (HTTP parse →
    shared queue → micro-batch → model → cross-listener reply), not a
    composition."""
    import threading

    from mmlspark_tpu.core.pipeline import Transformer
    from mmlspark_tpu.serving import DistributedServingServer

    class Doubler(Transformer):
        def transform(self, table):
            x = np.asarray(table.column("input"), dtype=np.float64)
            return table.with_column("prediction", x * 2)

    results = {"times": [], "failovers": 0, "errors": 0}
    lock = threading.Lock()
    srv = DistributedServingServer(
        Doubler(), num_servers=num_servers, max_latency_ms=1.0
    ).start()
    urls = [info.url for info in srv.service_info]
    kill_after = num_clients * reqs_per_client // 2
    done = {"count": 0}

    def client(cid):
        for i in range(reqs_per_client):
            want = float(cid * 1000 + i)
            t0 = time.perf_counter()
            ok = False
            for attempt in range(len(urls)):
                url = urls[(cid + attempt) % len(urls)]
                try:
                    out = _post(url, {"input": want})
                    assert out["prediction"] == want * 2, out
                    ok = True
                    break
                except AssertionError:
                    raise
                except Exception:
                    with lock:
                        results["failovers"] += 1
            dt = time.perf_counter() - t0
            with lock:
                results["times"].append(dt)
                if not ok:
                    results["errors"] += 1
                done["count"] += 1

    def killer():
        # worker death mid-stream: stop one listener once half the requests
        # have completed (the shared batch loop keeps serving the others)
        while True:
            with lock:
                if done["count"] >= kill_after:
                    break
            time.sleep(0.002)
        srv.servers[0].stop()

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(num_clients)
    ]
    if kill_worker:
        threads.append(threading.Thread(target=killer))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    srv.stop()
    out = _percentiles(results["times"])
    out["requests"] = len(results["times"])
    out["failovers"] = results["failovers"]
    out["errors"] = results["errors"]
    return out


def main():
    import jax

    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()

    edge = http_edge_latency()
    edge_ka = http_edge_keepalive_latency()
    dev1 = device_forward_latency(batch=1)
    dev8 = device_forward_latency(batch=8)
    # BASELINE config 5 names ResNet-50 — measure THAT model at serving
    # shape (224x224, batch 1, bf16), not a stand-in. Long loops (device
    # work >> the closing sync fetch) keep the per-call number stable even
    # on a loaded host.
    r50_1 = device_forward_latency(
        batch=1, iters=2000, variant="resnet50", size=224, dtype="bfloat16"
    )
    r50_8 = device_forward_latency(
        batch=8, iters=500, variant="resnet50", size=224, dtype="bfloat16"
    )
    served = served_resnet_latency()
    load = concurrent_load_latency()
    report = {
        "backend": jax.default_backend(),
        "http_edge": edge,
        "http_edge_keepalive": edge_ka,
        "resnet18_forward_ms": {"batch1": dev1, "batch8": dev8},
        "resnet50_224_bf16_forward_ms": {"batch1": r50_1, "batch8": r50_8},
        "served_resnet18_end_to_end": served,
        "concurrent_load_distributed": load,
        "composed_locally_attached_p50_ms": edge["p50_ms"] + dev1,
        "composed_resnet50_p50_ms": edge_ka["p50_ms"] + r50_1,
        "note": (
            "composed = HTTP edge p50 + warm on-device forward (a sum of "
            "two harnesses, not a measurement); concurrent_load_distributed "
            "is a single MEASURED pipeline distribution (16 clients, 3 "
            "listeners, one killed mid-stream) with a host model"
        ),
    }
    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
