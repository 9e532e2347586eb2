"""Plain reference for a bottleneck ResNet featurizer (He et al. 2015,
torchvision's ResNet-50 layout): weights from a key, and the forward pass
from uint8 NHWC images to the pooled feature vector.

Imports nothing of the program. The parameter tree has the shape the
program's zoo documents (stem / stages / fc, OIHW kernels, inference-mode
batch norm with running statistics), because the program takes its weights
from the caller; everything else here is written from the paper. The batch
norm statistics are drawn away from (0, 1) so that a forward pass that
skipped or misread them would not agree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

STAGE_WIDTHS = (64, 128, 256, 512)
EXPANSION = 4
BN_EPS = 1e-5


def _layout(blocks, stem_kernel, in_channels=3, num_classes=1000):
    """[(path, kind, shape)] in a fixed order; kind is 'conv', 'bn' or 'fc'."""
    out = [(("stem", "conv"), "conv", (64, in_channels, stem_kernel, stem_kernel)),
           (("stem", "bn"), "bn", (64,))]
    c_in = 64
    for s, (n, width) in enumerate(zip(blocks, STAGE_WIDTHS)):
        for b in range(n):
            c_out = width * EXPANSION
            at = ("stages", s, b)
            out += [
                (at + ("conv1",), "conv", (width, c_in, 1, 1)),
                (at + ("bn1",), "bn", (width,)),
                (at + ("conv2",), "conv", (width, width, 3, 3)),
                (at + ("bn2",), "bn", (width,)),
                (at + ("conv3",), "conv", (c_out, width, 1, 1)),
                (at + ("bn3",), "bn_last", (c_out,)),
            ]
            if b == 0:  # the first block of a stage changes width or stride
                out += [(at + ("down_conv",), "conv", (c_out, c_in, 1, 1)),
                        (at + ("down_bn",), "bn", (c_out,))]
            c_in = c_out
    out.append((("fc",), "fc", (num_classes, c_in)))
    return out


@functools.partial(jax.jit, static_argnames=("blocks", "stem_kernel"))
def init_params(key, blocks=(3, 4, 6, 3), stem_kernel=7):
    """Float32 weights on the device, one jitted call. He-normal kernels;
    batch norm with gamma in [0.5, 1.5) (the last of a block in [0.1, 0.3),
    which keeps sixteen residual sums in range), beta and mean in
    [-0.2, 0.2), variance in [0.5, 1.5)."""
    layout = _layout(blocks, stem_kernel)
    keys = jax.random.split(key, len(layout))
    params = {"stem": {}, "stages": [[{} for _ in range(n)] for n in blocks]}
    for k, (path, kind, shape) in zip(keys, layout):
        if kind == "conv":
            fan_in = shape[1] * shape[2] * shape[3]
            leaf = {"w": jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5}
        elif kind == "fc":
            leaf = {"w": jax.random.normal(k, shape) * (2.0 / shape[1]) ** 0.5,
                    "b": jnp.zeros(shape[0])}
        else:
            kg, kb, km, kv = jax.random.split(k, 4)
            lo, hi = (0.1, 0.3) if kind == "bn_last" else (0.5, 1.5)
            leaf = {
                "gamma": jax.random.uniform(kg, shape, minval=lo, maxval=hi),
                "beta": jax.random.uniform(kb, shape, minval=-0.2, maxval=0.2),
                "mean": jax.random.uniform(km, shape, minval=-0.2, maxval=0.2),
                "var": jax.random.uniform(kv, shape, minval=0.5, maxval=1.5),
            }
        node = params
        for step in path[:-1]:
            node = node[step]
        if path == ("fc",):
            params["fc"] = leaf
        else:
            node[path[-1]] = leaf
    return params


def _conv(x, w, stride, products):
    """A convolution at the precision the configuration states for its
    products: ``bfloat16`` rounds the input and the kernel to bfloat16
    (their products are then exact in float32) and sums in float32, which
    is the TPU's default precision for float32 tensors; ``float32`` keeps
    both as they are."""
    if products == "bfloat16":
        x, w = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in (x, w))
    elif products != "float32":
        raise ValueError(f"products in {products!r}")
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=lax.Precision.HIGHEST,
    )


def _bn(x, p):
    inv = p["gamma"] / jnp.sqrt(p["var"] + BN_EPS)
    shift = p["beta"] - p["mean"] * inv
    return x * inv.reshape(1, -1, 1, 1) + shift.reshape(1, -1, 1, 1)


def _bottleneck(x, p, stride, products):
    out = jax.nn.relu(_bn(_conv(x, p["conv1"]["w"], 1, products), p["bn1"]))
    out = jax.nn.relu(_bn(_conv(out, p["conv2"]["w"], stride, products), p["bn2"]))
    out = _bn(_conv(out, p["conv3"]["w"], 1, products), p["bn3"])
    if "down_conv" in p:
        x = _bn(_conv(x, p["down_conv"]["w"], stride, products), p["down_bn"])
    return jax.nn.relu(out + x)


def features(params, images_u8, products):
    """uint8 NHWC images at the model's input size -> (n, 2048) pooled
    features: scale by 1/255, NCHW, 7x7/2 stem, 3x3/2 max pool, the
    bottleneck stages (stride 2 on the 3x3 of each later stage's first
    block), global average pool. Weights and activations are float32
    throughout; ``products`` is the precision the configuration states for
    a convolution's products (``_conv``)."""
    x = images_u8.astype(jnp.float32) * (1.0 / 255.0)
    x = x.transpose(0, 3, 1, 2)
    small = params["stem"]["conv"]["w"].shape[-1] == 3
    x = _conv(x, params["stem"]["conv"]["w"], 1 if small else 2, products)
    x = jax.nn.relu(_bn(x, params["stem"]["bn"]))
    if not small:
        x = lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
            ((0, 0), (0, 0), (1, 1), (1, 1)),
        )
    for s, stage in enumerate(params["stages"]):
        for b, block in enumerate(stage):
            x = _bottleneck(x, block, 2 if (s > 0 and b == 0) else 1, products)
    return x.mean(axis=(2, 3))


def relative_gaps(got, want):
    """Per image: ||got - want|| / ||want||."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=1) / np.linalg.norm(want, axis=1)
