"""BASELINE config 2: ImageFeaturizer ResNet-50 images/sec/chip.

Warm on-device forward loop at 224x224 (the reference's ImageNet input),
input perturbed per iteration, synced by a small fetch — the same
measurement discipline as the other kernel benches. Weights do not affect
throughput; the trained-artifact flow is examples/zoo_transfer_learning.py.

    python benchmarks/image_featurizer_bench.py [batch] [reps]
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mmlspark_tpu.models import init_resnet, resnet_apply

BATCH = int(sys.argv[1]) if len(sys.argv) > 1 else 64
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 50


def main():
    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()
    params = init_resnet(variant="resnet50", num_classes=1000)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BATCH, 3, 224, 224)).astype(np.float32))
    pdev = jax.tree_util.tree_map(jnp.asarray, params)

    results = {}
    for dtype, name in ((jnp.bfloat16, "bf16"), (None, "f32")):
        @jax.jit
        def loop(p, xb):
            def body(i, acc):
                feats = resnet_apply(
                    p, xb * (1 + i.astype(jnp.float32) * 1e-9), cut=1,
                    dtype=dtype,
                )
                return acc + feats[0, 0].astype(jnp.float32)

            return lax.fori_loop(0, REPS, body, jnp.float32(0.0))

        np.asarray(loop(pdev, x))  # compile
        t0 = time.perf_counter()
        np.asarray(loop(pdev, x))
        dt = time.perf_counter() - t0
        ips = BATCH * REPS / dt
        results[name] = round(ips, 1)
        print(f"resnet50 224x224 b{BATCH} {name}: {ips:,.0f} images/sec/chip")

    out = {
        "metric": f"imagefeaturizer_resnet50_images_per_sec_{jax.default_backend()}",
        "value": results.get("bf16"),
        "unit": "images/sec/chip",
        "batch": BATCH,
        "f32": results.get("f32"),
    }
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__),
                           "image_featurizer_bench.json"), "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
