"""Driver: one job is ``ImageFeaturizer(...).transform(Table)`` over uint8
host images: the autoResize stage, batch stacking, upload, forward, fetch,
column assembly, and whatever tracing or cache load the call itself causes.
"""

from __future__ import annotations

import numpy as np

from chipbench.reference import resnet as ref

SAMPLE = 128  # images of a job compared with the reference


def conv_flops(blocks, stem_kernel: int, image_size: int) -> int:
    """Multiply-adds x 2 of every convolution of one image's forward pass,
    from the layout and the input size alone (SAME padding: a stride halves
    the side, rounding up). Batch norm, ReLU and pooling are not counted."""
    side = image_size
    if stem_kernel == 7:
        side = -(-side // 2)  # the stem's stride
    total = 2 * side * side * 64 * 3 * stem_kernel * stem_kernel
    if stem_kernel == 7:
        side = -(-side // 2)  # max pool
    c_in = 64
    for s, (n, width) in enumerate(zip(blocks, ref.STAGE_WIDTHS)):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            out_side = -(-side // stride)
            c_out = width * ref.EXPANSION
            total += 2 * side * side * width * c_in  # conv1, 1x1
            total += 2 * out_side * out_side * width * width * 9  # conv2, 3x3
            total += 2 * out_side * out_side * c_out * width  # conv3, 1x1
            if b == 0:
                total += 2 * out_side * out_side * c_out * c_in  # projection
            side, c_in = out_side, c_out
    return total


def work(config: dict, traffic: dict) -> dict:
    per_image = conv_flops(config["blocks"], config["stem_kernel"], config["image_size"])
    return {"flops": traffic["images"] * per_image, "bytes": 0}


def key_of(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def setup(config: dict, traffic: dict, seed: int) -> dict:
    from mmlspark_tpu.data.table import Table

    params = ref.init_params(
        key_of(seed), blocks=tuple(config["blocks"]), stem_kernel=config["stem_kernel"]
    )
    rng = np.random.default_rng(seed)
    n, side = traffic["images"], config["image_size"]
    pixels = rng.integers(0, 256, size=(n, side, side, 3), dtype=np.uint8)
    column = np.empty(n, dtype=object)
    for i in range(n):
        column[i] = pixels[i]
    sample = np.sort(rng.choice(n, size=min(SAMPLE, n), replace=False))
    return {
        "params": params, "pixels": pixels, "table": Table({"image": column}),
        "sample": sample, "images": n, "side": side,
        "batch": traffic["batchSize"], "limits": traffic["limits"],
        "products": config["products"],
    }


def job(state: dict) -> dict:
    from mmlspark_tpu.image import ImageFeaturizer

    featurizer = ImageFeaturizer(
        inputCol="image", outputCol="features", modelParams=state["params"],
        inputHeight=state["side"], inputWidth=state["side"],
        batchSize=state["batch"], **state.get("featurizer", {}),
    )
    feats = np.asarray(featurizer.transform(state["table"])["features"])
    return {
        "shape": feats.shape, "finite": bool(np.isfinite(feats).all()),
        "sample": feats[state["sample"]].copy() if feats.ndim == 2 else None,
    }


def fault(state: dict, out: dict):
    if out["shape"] != (state["images"], 512 * ref.EXPANSION):
        return f"features of shape {out['shape']}"
    if not out["finite"]:
        return "non-finite features"
    return None


def end_to_end(state: dict, window_s: float, jobs: int) -> dict:
    return {"featurize_img_per_s": (jobs * state["images"] / window_s, "img/s")}


def reference_features(state: dict) -> np.ndarray:
    """The reference over the sampled images, in blocks that fit."""
    import jax

    fn = jax.jit(lambda p, x: ref.features(p, x, state["products"]))
    images = state["pixels"][state["sample"]]
    return np.concatenate([
        np.asarray(fn(state["params"], images[i : i + 32]))
        for i in range(0, len(images), 32)
    ])


def checks(state: dict, outputs: list) -> dict:
    want = reference_features(state)
    gap = max(float(ref.relative_gaps(o["sample"], want).max()) for o in outputs)
    return {"feature_gap_max": {"value": gap, "limit": state["limits"]["feature_gap_max"]}}


def compare(state: dict, outputs: list, seed: int) -> dict:
    """Every job's sampled rows against the reference's forward of the same
    weights at the precision the configuration states; the widest relative
    gap of any image of any job."""
    state.pop("table")  # the program's inputs go before the reference runs
    return checks(state, outputs)


def control(state: dict) -> dict:
    """{side: the comparison's numbers with that side in the program's
    place}. The control is the program's own path one precision below the
    float32 weights and activations the configuration states:
    ``resnet_apply(dtype=bfloat16)`` through the featurizer's public
    ``applyFn``, one whole job at the cell's size."""
    import functools

    from mmlspark_tpu.models.resnet import resnet_apply

    low = functools.partial(resnet_apply, dtype="bfloat16")
    return {"control": checks(state, [job(dict(state, featurizer={"applyFn": low}))])}
