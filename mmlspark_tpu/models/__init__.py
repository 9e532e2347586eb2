"""Built-in model zoo (reference ``downloader/`` model zoo role, SURVEY.md §2.14).

The reference downloads pre-trained CNTK graphs from a CDN; in the TPU build
the zoo is constructive — model families are defined here in JAX and their
weights are produced by training or loaded from checkpoints via
:mod:`mmlspark_tpu.downloader`.
"""

from mmlspark_tpu.models.afmoe import afmoe_apply, init_afmoe
from mmlspark_tpu.models.lfm2_moe import init_lfm2_moe, lfm2_moe_apply
from mmlspark_tpu.models.mla_moe import init_mla_moe, mla_moe_apply
from mmlspark_tpu.models.nemotron_h import init_nemotron_h, nemotron_h_apply
from mmlspark_tpu.models.resnet import init_resnet, resnet_apply
from mmlspark_tpu.models.zoo import (
    load_zoo_params,
    params_from_bytes,
    params_to_bytes,
    publish_model,
    train_resnet_classifier,
)

__all__ = [
    "init_resnet", "resnet_apply", "init_afmoe", "afmoe_apply", "init_mla_moe", "mla_moe_apply",
    "init_nemotron_h", "nemotron_h_apply", "init_lfm2_moe", "lfm2_moe_apply",
    "publish_model", "load_zoo_params",
    "params_to_bytes", "params_from_bytes", "train_resnet_classifier",
]
