"""Mesh, collectives, and the distributed lookup engine."""

from . import wire
from .lookup_engine import (
    Bucket,
    DedupRouted,
    DistributedLookup,
    class_buckets,
    class_param_name,
    pack_mp_inputs,
    padded_rows,
    ragged_to_padded,
)
from .mesh import (
    DEFAULT_AXIS,
    batch_sharding,
    create_mesh,
    device_summary,
    initialize_multihost,
    replicated,
    require_tpu,
    table_sharding,
)

__all__ = [
    "Bucket",
    "DedupRouted",
    "DistributedLookup",
    "wire",
    "class_buckets",
    "class_param_name",
    "pack_mp_inputs",
    "padded_rows",
    "ragged_to_padded",
    "DEFAULT_AXIS",
    "batch_sharding",
    "create_mesh",
    "device_summary",
    "initialize_multihost",
    "replicated",
    "require_tpu",
    "table_sharding",
]
