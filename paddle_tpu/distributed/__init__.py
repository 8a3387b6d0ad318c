"""paddle_tpu.distributed — the Fleet-parity distributed stack, TPU-native.

Layer map (SURVEY.md §2.3): collectives are XLA collectives over ICI/DCN
named by mesh axes; groups are mesh slices; hybrid parallelism is one named
mesh [dp, pp, sharding, sep, mp]; ZeRO is placement; pipeline is a compiled
collective-permute schedule; auto-parallel is the native execution model.
"""
from __future__ import annotations

from . import mesh  # noqa: F401
from .mesh import (  # noqa: F401
    build_mesh,
    get_global_mesh,
    global_mesh,
    set_global_mesh,
    sharding_constraint,
)
from .env import (  # noqa: F401
    Group,
    ParallelEnv,
    destroy_process_group,
    get_rank,
    get_world_size,
    init_parallel_env,
    is_initialized,
    new_group,
)
from .collective import (  # noqa: F401
    ReduceOp,
    all_gather,
    all_gather_object,
    all_reduce,
    alltoall,
    alltoall_single,
    barrier,
    broadcast,
    broadcast_object_list,
    gather,
    get_backend,
    irecv,
    isend,
    ppermute,
    recv,
    scatter_object_list,
    reduce,
    reduce_scatter,
    scatter,
    send,
    stream,
    wait,
)
from .parallel import DataParallel, spawn  # noqa: F401
from . import fleet  # noqa: F401
from . import sharding as sharding_api  # noqa: F401
from . import auto_parallel  # noqa: F401
from .auto_parallel import (  # noqa: F401
    Partial,
    Placement,
    ProcessMesh,
    Replicate,
    Shard,
    dtensor_from_fn,
    get_mesh,
    reshard,
    set_mesh,
    shard_layer,
    shard_tensor,
)
from .fleet.utils.recompute_helper import recompute  # noqa: F401


def get_group(gid=None):
    from .env import _default_group, _groups

    if gid is None:
        return _default_group
    for g in _groups:
        if g.id == gid:
            return g
    return None


# `shard_map` convenience re-export: the explicit-SPMD escape hatch
# (reference analogue: writing custom collective ops).
def shard_map(f, mesh=None, in_specs=None, out_specs=None, **kwargs):
    import jax

    from .mesh import require_global_mesh

    return jax.shard_map(
        f,
        mesh=mesh or require_global_mesh(),
        in_specs=in_specs,
        out_specs=out_specs,
        **kwargs,
    )


QueueDataset = None  # PS-mode datasets: deliberate non-goal (SURVEY.md §2.3 PS)

from .collective import P2POp, batch_isend_irecv  # noqa: E402,F401
from . import launch  # noqa: E402,F401  (paddle.distributed.launch module)
from . import rpc  # noqa: E402,F401  (paddle.distributed.rpc module)
from . import utils  # noqa: E402,F401  (paddle.distributed.utils module)
from . import communication  # noqa: E402,F401  (reference package path)
from . import checkpoint  # noqa: E402,F401
from .auto_parallel import shard_dataloader  # noqa: E402,F401
from .parallelize import (  # noqa: E402,F401
    ColWiseParallel,
    RowWiseParallel,
    SequenceParallelBegin,
    SequenceParallelEnd,
    parallelize,
    to_distributed,
)
from .checkpoint import (  # noqa: E402,F401  (paddle.distributed.* parity)
    load_state_dict,
    save_state_dict,
)
all_to_all = alltoall  # reference alias



def split(x, size, operation, axis=0, num_partitions=None, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """paddle.distributed.split parity: build a tensor-parallel embedding or
    linear whose weight is partitioned over the mp axis (reference:
    python/paddle/distributed/collective.py::split). Under SPMD the
    partitioning is a sharding annotation on the parallel layer."""
    from .fleet import meta_parallel as mp_layers

    if operation == "embedding":
        layer = mp_layers.VocabParallelEmbedding(size[0], size[1])
        return layer(x)
    if operation == "linear":
        if axis == 0:
            layer = mp_layers.RowParallelLinear(
                size[0], size[1], input_is_parallel=False
            )
        else:
            layer = mp_layers.ColumnParallelLinear(
                size[0], size[1], gather_output=gather_out
            )
        return layer(x)
    raise ValueError(f"unsupported split operation {operation!r}")
