"""Functional ResNet in plain JAX — the ImageFeaturizer backbone.

The reference's ``ImageFeaturizer`` wraps a downloaded CNTK ResNet and cuts
``cutOutputLayers`` layers off the top (``image/ImageFeaturizer.scala:40-86``).
Here the backbone is defined natively: a ``(params, x, cut) -> array``
function whose ``cut`` argument selects the same "featurize vs classify"
behavior, and whose body is pure lax ops so the whole forward pass jits into
one XLA program (convs on the MXU, bf16-friendly).

Layout NCHW to match :mod:`mmlspark_tpu.image` unrolled tensors; weights are
float32 at rest and can be cast to bfloat16 at apply time (``dtype`` arg).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

VARIANTS: Dict[str, Tuple[Tuple[int, ...], bool]] = {
    # name -> (blocks per stage, bottleneck?)
    "resnet18": ((2, 2, 2, 2), False),
    "resnet34": ((3, 4, 6, 3), False),
    "resnet50": ((3, 4, 6, 3), True),
}

_STAGE_WIDTHS = (64, 128, 256, 512)


def _he(rng: np.random.Generator, shape: Tuple[int, ...]) -> np.ndarray:
    fan_in = int(np.prod(shape[1:])) if len(shape) > 1 else shape[0]
    return (rng.standard_normal(shape) * np.sqrt(2.0 / fan_in)).astype(np.float32)


def _conv_params(rng, c_out, c_in, k) -> Dict[str, np.ndarray]:
    return {"w": _he(rng, (c_out, c_in, k, k))}


def _bn_params(c) -> Dict[str, np.ndarray]:
    return {
        "gamma": np.ones(c, np.float32),
        "beta": np.zeros(c, np.float32),
        "mean": np.zeros(c, np.float32),
        "var": np.ones(c, np.float32),
    }


def init_resnet(
    seed: int = 0,
    variant: str = "resnet18",
    num_classes: int = 1000,
    in_channels: int = 3,
    small_inputs: bool = False,
) -> Dict[str, Any]:
    """Random-init parameter pytree. ``small_inputs`` uses the CIFAR stem
    (3x3 stride-1 conv, no maxpool) instead of the ImageNet 7x7 stride-2."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; choose from {sorted(VARIANTS)}")
    blocks, bottleneck = VARIANTS[variant]
    rng = np.random.default_rng(seed)
    expansion = 4 if bottleneck else 1
    # Architecture is encoded in the pytree structure itself (stem kernel
    # size ⇒ small_inputs, conv3 presence ⇒ bottleneck) so the params dict
    # stays a pure array pytree — jit-able with no static side channel.
    params: Dict[str, Any] = {
        "stem": {
            "conv": _conv_params(rng, 64, in_channels, 3 if small_inputs else 7),
            "bn": _bn_params(64),
        },
    }
    c_in = 64
    stages: List[List[Dict[str, Any]]] = []
    for stage_i, (n_blocks, width) in enumerate(zip(blocks, _STAGE_WIDTHS)):
        stage: List[Dict[str, Any]] = []
        for block_i in range(n_blocks):
            stride = 2 if (stage_i > 0 and block_i == 0) else 1
            c_out = width * expansion
            block: Dict[str, Any] = {}
            if bottleneck:
                block["conv1"] = _conv_params(rng, width, c_in, 1)
                block["bn1"] = _bn_params(width)
                block["conv2"] = _conv_params(rng, width, width, 3)
                block["bn2"] = _bn_params(width)
                block["conv3"] = _conv_params(rng, c_out, width, 1)
                block["bn3"] = _bn_params(c_out)
            else:
                block["conv1"] = _conv_params(rng, width, c_in, 3)
                block["bn1"] = _bn_params(width)
                block["conv2"] = _conv_params(rng, width, width, 3)
                block["bn2"] = _bn_params(width)
            if stride != 1 or c_in != c_out:
                block["down_conv"] = _conv_params(rng, c_out, c_in, 1)
                block["down_bn"] = _bn_params(c_out)
            stage.append(block)
            c_in = c_out
        stages.append(stage)
    params["stages"] = stages
    params["fc"] = {
        "w": _he(rng, (num_classes, c_in)),
        "b": np.zeros(num_classes, np.float32),
    }
    return params


def _conv(x, p, stride=1, padding="SAME"):
    from jax import lax

    return lax.conv_general_dilated(
        x,
        p["w"].astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )


def _bn(x, p):
    shape = (1, -1, 1, 1)
    inv = (p["var"] + 1e-5) ** -0.5
    return (
        x * (p["gamma"] * inv).astype(x.dtype).reshape(shape)
        + (p["beta"] - p["mean"] * p["gamma"] * inv).astype(x.dtype).reshape(shape)
    )


def _block(x, p, stride, bottleneck):
    import jax

    identity = x
    if bottleneck:
        out = jax.nn.relu(_bn(_conv(x, p["conv1"], 1), p["bn1"]))
        out = jax.nn.relu(_bn(_conv(out, p["conv2"], stride), p["bn2"]))
        out = _bn(_conv(out, p["conv3"], 1), p["bn3"])
    else:
        out = jax.nn.relu(_bn(_conv(x, p["conv1"], stride), p["bn1"]))
        out = _bn(_conv(out, p["conv2"], 1), p["bn2"])
    if "down_conv" in p:
        identity = _bn(_conv(x, p["down_conv"], stride), p["down_bn"])
    return jax.nn.relu(out + identity)


def resnet_apply(params: Dict[str, Any], x, cut: int = 0, dtype: Any = None):
    """Forward pass. ``cut=0`` → logits; ``cut=1`` → pooled features (the
    reference's ``cutOutputLayers=1`` transfer-learning default);
    ``cut=2`` → pre-pool feature map."""
    import jax
    from jax import lax

    small_inputs = params["stem"]["conv"]["w"].shape[-1] == 3
    bottleneck = "conv3" in params["stages"][0][0]
    if dtype is not None:
        x = x.astype(dtype)
    stride = 1 if small_inputs else 2
    # the scope names are what an xprof trace groups the forward's ops by
    with jax.named_scope("resnet_stem"):
        x = jax.nn.relu(
            _bn(_conv(x, params["stem"]["conv"], stride), params["stem"]["bn"])
        )
        if not small_inputs:
            x = lax.reduce_window(
                x, -np.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                ((0, 0), (0, 0), (1, 1), (1, 1)),
            )
    for stage_i, stage in enumerate(params["stages"]):
        with jax.named_scope(f"resnet_stage{stage_i + 1}"):
            for block_i, block in enumerate(stage):
                s = 2 if (stage_i > 0 and block_i == 0) else 1
                x = _block(x, block, s, bottleneck)
    if cut >= 2:
        return x
    with jax.named_scope("head"):
        feats = x.mean(axis=(2, 3))
        if cut >= 1:
            return feats
        fc = params["fc"]
        return feats @ fc["w"].astype(feats.dtype).T + fc["b"].astype(feats.dtype)
