"""Device mesh, shardings, and collectives.

This module is the single owner of distribution concerns, mirroring how
everything in the reference bottoms out in Spark ``treeReduce`` /
``treeAggregate`` / ``broadcast`` (SURVEY.md §2.9).  The TPU-native
translation:

  ====================================  =====================================
  reference (Spark)                     keystone_tpu (JAX/XLA)
  ====================================  =====================================
  RDD partitions across executors       batch axis sharded over mesh 'data'
  treeReduce / treeAggregate            lax.psum / jnp.einsum + auto all-reduce
  broadcast of weights                  replicated sharding (free over ICI)
  driver-side solve                     replicated on-device solve
  feature blocks solved in time         feature axis sharded over mesh 'model'
  ====================================  =====================================

Everything above this module uses only this API.
"""

from keystone_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS,
    MODEL_AXIS,
    MeshContext,
    current_mesh,
    data_sharding,
    default_mesh,
    device_count,
    local_mesh,
    replicated,
    set_mesh,
    shard_batch,
    use_mesh,
)
from keystone_tpu.parallel.collectives import (  # noqa: F401
    gram_panels,
    pmean,
    psum,
    sharded_gram,
    sharded_matmul,
    tree_psum,
)
from keystone_tpu.parallel.multihost import (  # noqa: F401
    SickHostError,
    health_barrier,
    maybe_health_barrier,
)
