"""Model zoo. Each model is a pair of pure functions over a params pytree:

    init(key, cfg) -> params
    apply(params, inputs) -> outputs

plus a ``param_specs(cfg, axes)`` function mapping the params pytree to
``jax.sharding.PartitionSpec``s for FSDP/tensor sharding. No framework
classes — pytrees compose directly with ``jit``/``shard_map``/optax.

A model module may declare ``CONFIG_FIELDS``: the optional fields of
``ModelConfig`` that it reads and that mean nothing to a model which does
not declare them too; and ``check_config(cfg)``: what it needs of its own
fields. ``model_for(cfg)`` holds a config to both.
"""

import dataclasses

from tpudist.config import ModelConfig
from tpudist.models import (cohere2moe, longcatflash, mlp, moe, sdarmoe,
                            transformer)

_REGISTRY = {"mlp": mlp, "transformer": transformer, "moe": moe,
             "cohere2moe": cohere2moe, "sdarmoe": sdarmoe,
             "longcatflash": longcatflash}


def get_model(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(_REGISTRY)}") from None


def model_for(cfg: ModelConfig):
    """``get_model(cfg.name)``, after the config is held to that model: a
    field that some model declares its own (``CONFIG_FIELDS``) and this one
    does not, set away from its default, is refused in words (it would be
    read by nothing, or by shared code that keys on it: ``kv_lora_rank``
    makes the serve cache latent); then the model's own ``check_config``."""
    model = get_model(cfg.name)
    mine = getattr(model, "CONFIG_FIELDS", ())
    owners = {f: name for name, m in _REGISTRY.items()
              for f in getattr(m, "CONFIG_FIELDS", ())}
    default = {f.name: f.default for f in dataclasses.fields(ModelConfig)}
    given = [f for f in owners
             if f not in mine and getattr(cfg, f) != default[f]]
    if given:
        raise ValueError(
            f"{given} belong to the model(s) "
            f"{sorted({owners[f] for f in given})}; model {cfg.name!r} "
            f"reads none of them")
    check = getattr(model, "check_config", None)
    if check is not None:
        check(cfg)
    return model
