"""Model registry.

``create_model(cfg, mesh=None)`` dispatches on ``ModelConfig.name``:
the reference's one model (MobileNetV2, cifar10_mpi_mobilenet_224.py:
137-139) plus tpunet's attention-based ViT family. ``init_variables``
is model-agnostic — some models carry BatchNorm statistics (MobileNetV2)
and some do not (ViT); callers use ``variables.get("batch_stats", {})``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpunet.config import ModelConfig
from tpunet.models import mobilenetv2, vit
from tpunet.models.convert import convert_torch_state_dict, load_pretrained  # noqa: F401
from tpunet.models.mobilenetv2 import MobileNetV2  # noqa: F401
from tpunet.models.vit import ViT, VIT_PRESETS  # noqa: F401


def create_model(cfg: ModelConfig, mesh=None):
    """Build the configured model. ``mesh`` is needed only by models
    that run shard_map internally (ring attention, pipeline)."""
    if cfg.name == "mobilenet_v2":
        return mobilenetv2.create_model(cfg)
    if cfg.name == "vit_pp":
        from tpunet.models import vit_pp
        return vit_pp.create_model(cfg, mesh=mesh)
    if cfg.name == "lm":
        from tpunet.models import lm
        return lm.create_model(cfg, mesh=mesh)
    if cfg.name == "lm_pp":
        from tpunet.models import lm_pp
        return lm_pp.create_model(cfg, mesh=mesh)
    if cfg.name == "latent_lm":
        from tpunet.models import latent_lm
        return latent_lm.create_model(cfg, mesh=mesh)
    if cfg.name == "vit" or cfg.name in VIT_PRESETS:
        return vit.create_model(cfg, mesh=mesh)
    raise ValueError(f"unknown model {cfg.name!r}")


def init_variables(model, rng: jax.Array, image_size: int = 224,
                   batch_size: int = 1, seq_len: int = 16) -> dict:
    """Initialize model variables with a dummy batch — NHWC images, or
    int32 tokens for models declaring ``input_kind = "tokens"``.

    ``batch_size`` (and ``seq_len`` for token models) matters only for
    models whose attention runs under shard_map (ring): the init batch
    must divide the mesh's batch/seq axes. A token model none of whose
    parameters' shapes depend on the sequence names a short
    ``init_seq_len`` of its own.
    """
    if getattr(model, "input_kind", "image") == "tokens":
        seq_len = getattr(model, "init_seq_len", seq_len)
        dummy = jnp.zeros((batch_size, seq_len), jnp.int32)
    else:
        dummy = jnp.zeros((batch_size, image_size, image_size, 3),
                          jnp.float32)
    return model.init({"params": rng}, dummy, train=False)


def num_params(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
