"""Mesh-aware serving: tensor-parallel model runner over shard_map.

The engine (``serving/engine.py``) drives admission and scheduling but
no longer owns its jitted programs — it calls a
:class:`~paddle_tpu.serving.parallel.runner.ModelRunner`, which owns
the ``jax.sharding.Mesh`` (a single ``tp`` axis), places the weights
with ``NamedSharding`` (attention heads and the FFN hidden dim sharded
on ``tp``; embeddings, norms, and the LM head replicated), shards the
paged KV pool along the head axis, and runs decode / prefill /
cached-prefill / CoW-copy as ``shard_map`` computations with an
all-reduce only at the attention and FFN output projections.  The layer
bodies those programs run are ``decode_layer`` / ``prefill_layer`` of
``models/generation.py`` (one chip and a mesh shard alike: they take the
mesh axis), over the ``PagedKV`` cache of
``ops/pallas/paged_attention.py``.

``tp=1`` builds the same programs with no mesh, no ``device_put`` and no
``shard_map``; ``tp>1`` is CPU-testable via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from .mesh import mesh_devices, parse_mesh, validate_tp
from .runner import ModelRunner

__all__ = ["ModelRunner", "mesh_devices", "parse_mesh", "validate_tp"]
