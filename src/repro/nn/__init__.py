"""Pure-JAX neural-network substrate (no flax/optax dependency).

Conventions
-----------
* Parameters are nested dicts of jnp arrays ("param trees").
* Every ``init_*`` returns ``(params, specs)`` where ``specs`` mirrors the
  param tree with logical :class:`ShardSpec` leaves used by
  ``repro.runtime.sharding`` to produce concrete ``PartitionSpec``s.
* ``apply`` functions are pure: ``f(params, *inputs, cfg) -> outputs``.
* Compute dtype is taken from the config (bf16 by default); params are
  stored in ``param_dtype`` (fp32 master copies) and cast at use sites.

The language-model layers (``layers``, ``rope``, ``attention``, ``moe``,
``ssm``, ``transformer``) are imported by name where they are used, so
importing the SimNet predictor, which needs only ``init``, loads none of
them.
"""
from repro.nn.init import ShardSpec, dense_init, embed_init, scalar_init

__all__ = [
    "ShardSpec",
    "dense_init",
    "embed_init",
    "scalar_init",
]
