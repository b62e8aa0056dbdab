"""Model zoo. Each model is a pair of pure functions over a params pytree:

    init(key, cfg) -> params
    apply(params, inputs) -> outputs

plus a ``param_specs(cfg, axes)`` function mapping the params pytree to
``jax.sharding.PartitionSpec``s for FSDP/tensor sharding. No framework
classes — pytrees compose directly with ``jit``/``shard_map``/optax.
"""

from tpudist.models import cohere2moe, mlp, moe, sdarmoe, transformer

_REGISTRY = {"mlp": mlp, "transformer": transformer, "moe": moe,
             "cohere2moe": cohere2moe, "sdarmoe": sdarmoe}


def get_model(name: str):
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; available: {sorted(_REGISTRY)}") from None
