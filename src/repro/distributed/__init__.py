"""Distribution utilities: mesh construction, partition specs, collectives."""
from repro.distributed.mesh_utils import (
    corpus_mesh,
    make_mesh,
    make_production_mesh,
    mesh_device_count,
    named_sharding,
)
from repro.distributed.partition import ShardingPolicy
