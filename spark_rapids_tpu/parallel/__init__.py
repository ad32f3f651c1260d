"""Multi-chip parallelism: device meshes and collective exchanges.

TPU-native replacement for the reference's shuffle transport layer
(ref: shuffle-plugin/.../ucx/UCX.scala point-to-point RDMA): partitioned
exchanges become XLA `all_to_all` collectives over a `jax.sharding.Mesh`,
riding ICI within a pod slice (DCN across slices) with no explicit
endpoint/bounce-buffer management — the compiler owns the transport.
"""

from spark_rapids_tpu.parallel.mesh import (  # noqa: F401
    make_mesh,
    mesh_key,
)
from spark_rapids_tpu.parallel.pipeline import (  # noqa: F401
    device_read,
    device_read_int,
    device_read_many,
    pipelined,
    prefetch,
    stage_snapshot,
)
