"""Global device-mesh context — the TPU-native "communicator" layer.

Reference capability replaced here (SURVEY.md §2.3): Paddle manages NCCL
communicators per process subgroup (`ProcessGroupNCCL`, `NCCLCommContext`,
unique-id rendezvous over TCPStore). On TPU there are no user-managed
communicators: collectives are compiled into the XLA program and ride the
ICI/DCN fabric. The analogue of "creating communicators" is *constructing a
named device mesh* (`jax.sharding.Mesh`) whose axes map onto the physical
topology; every collective is then named by mesh axis instead of by
communicator handle.

Axis order convention (mirrors the reference's HybridCommunicateGroup order
[dp, pp, sharding, sep, mp] — `fleet/base/topology.py`): the *last* axes are
the fastest-varying over devices, so `mp` (the most bandwidth-hungry axis)
lands on adjacent devices / same-host ICI, `dp` on the slowest links — the
same locality goal the reference encodes in its topology ordering.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

P = PartitionSpec

_state = threading.local()

# Canonical hybrid axis names, outermost → innermost.
HYBRID_AXES = ("dp", "pp", "sharding", "sep", "mp")


def build_mesh(
    axis_dims: Sequence[int],
    axis_names: Sequence[str],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh over `devices` (default: all) with the given axis shape.

    Degenerate (size-1) axes are kept so sharding specs can always name any
    hybrid axis regardless of the configured degree.
    """
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(axis_dims))
    if n != len(devices):
        raise ValueError(
            f"mesh axis dims {tuple(axis_dims)} require {n} devices, "
            f"got {len(devices)}"
        )
    dev_array = np.array(devices).reshape(tuple(axis_dims))
    return Mesh(dev_array, tuple(axis_names))


def _device_slice_ids(devices, num_slices: Optional[int]):
    """Slice id per device. Real multi-slice TPU devices expose
    `.slice_index`; `num_slices` (or PADDLE_TPU_NUM_SLICES) overrides with a
    contiguous split for simulation/testing."""
    import os

    if num_slices is None:
        env = os.environ.get("PADDLE_TPU_NUM_SLICES")
        if env:
            num_slices = int(env)
    if num_slices is not None and num_slices > 1:
        if len(devices) % num_slices != 0:
            raise ValueError(
                f"{len(devices)} devices not divisible by "
                f"num_slices={num_slices}"
            )
        per = len(devices) // num_slices
        return [i // per for i in range(len(devices))]
    return [getattr(d, "slice_index", 0) or 0 for d in devices]


def _ici_device_array(dims, devices) -> np.ndarray:
    """Arrange `devices` (one slice) into `dims` honoring the physical ICI
    torus when coords are available (TPU); plain reshape otherwise (CPU)."""
    try:
        from jax.experimental import mesh_utils

        return np.asarray(
            mesh_utils.create_device_mesh(
                tuple(dims), devices=list(devices),
                allow_split_physical_axes=True,
            )
        )
    except Exception:
        return np.array(devices).reshape(tuple(dims))


# Axes allowed to cross DCN (slice boundaries), in preference order. The
# reference encodes the same rule by axis ordering in
# `fleet/base/topology.py`: gradient-sync (dp) tolerates the slow fabric,
# pipeline stage hops tolerate it next, ZeRO gathers after that; sep/mp
# collectives are per-layer and must stay on ICI.
DCN_CAPABLE_AXES = ("dp", "pp", "sharding")


def build_hybrid_mesh(
    axis_dims: Sequence[int],
    axis_names: Sequence[str],
    devices: Optional[Sequence[jax.Device]] = None,
    num_slices: Optional[int] = None,
) -> Mesh:
    """ICI/DCN-topology-aware hybrid mesh (SURVEY.md §2.3 "Hybrid topology":
    "ICI-aware axis assignment is the key added value").

    Single slice: devices are arranged so the innermost axes (mp, sep) land
    on physically adjacent chips of the ICI torus.

    Multi-slice (slice_index present, or simulated): the slice count is
    factored into the outermost DCN-capable axes ([dp, pp, sharding] in that
    order) so ONLY those axes' collectives cross DCN; each slice internally
    holds a contiguous ICI-arranged sub-mesh for the remaining axis extents.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    dims = [int(d) for d in axis_dims]
    if int(np.prod(dims)) != len(devices):
        raise ValueError(
            f"mesh axis dims {tuple(dims)} require {int(np.prod(dims))} "
            f"devices, got {len(devices)}"
        )
    slice_ids = _device_slice_ids(devices, num_slices)
    uniq = sorted(set(slice_ids))
    n_slices = len(uniq)
    if n_slices <= 1:
        return Mesh(_ici_device_array(dims, devices), tuple(axis_names))

    by_slice = {s: [] for s in uniq}
    for d, sid in zip(devices, slice_ids):
        by_slice[sid].append(d)
    per_slice_n = len(devices) // n_slices
    if any(len(g) != per_slice_n for g in by_slice.values()):
        raise ValueError(
            f"uneven slices: {[len(by_slice[s]) for s in uniq]} devices per "
            "slice; hybrid mesh needs equal slice sizes"
        )

    # factor n_slices into the outer DCN-capable axes, in order
    import math

    dcn = [1] * len(dims)
    rem = n_slices
    for i, (name, dim) in enumerate(zip(axis_names, dims)):
        if rem == 1:
            break
        if name in DCN_CAPABLE_AXES:
            f = math.gcd(dim, rem)
            dcn[i] = f
            rem //= f
    if rem != 1:
        raise ValueError(
            f"cannot place {n_slices} slices onto DCN-capable axes "
            f"{DCN_CAPABLE_AXES} with degrees "
            f"{dict(zip(axis_names, dims))}: the slice count must divide "
            "their product (dp/pp/sharding are the axes allowed to span DCN)"
        )
    per_dims = [d // f for d, f in zip(dims, dcn)]

    # per-slice ICI sub-meshes, composed so dcn coords are the OUTER part of
    # each axis: axis i index = dcn_i * per_dims[i] + ici_i
    subs = np.stack(
        [_ici_device_array(per_dims, by_slice[s]) for s in uniq]
    )  # [n_slices, *per_dims]
    k = len(dims)
    subs = subs.reshape(tuple(dcn) + tuple(per_dims))
    perm = [j for i in range(k) for j in (i, k + i)]
    arr = subs.transpose(perm).reshape(tuple(dims))
    return Mesh(arr, tuple(axis_names))


def set_global_mesh(mesh: Optional[Mesh]):
    _state.mesh = mesh


def get_global_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def require_global_mesh() -> Mesh:
    m = get_global_mesh()
    if m is None:
        raise RuntimeError(
            "no global device mesh: call paddle_tpu.distributed.fleet.init() "
            "or init_parallel_env() first"
        )
    return m


@contextlib.contextmanager
def global_mesh(mesh: Mesh):
    prev = get_global_mesh()
    set_global_mesh(mesh)
    try:
        yield mesh
    finally:
        set_global_mesh(prev)


def named_sharding(spec: PartitionSpec, mesh: Optional[Mesh] = None) -> NamedSharding:
    return NamedSharding(mesh or require_global_mesh(), spec)


def global_device_put(value, spec: PartitionSpec, mesh: Optional[Mesh] = None):
    """Place host data onto the (possibly multi-host) global mesh.

    Single-process: a plain ``jax.device_put``. Multi-process SPMD
    (``jax.process_count() > 1``): ``device_put`` would fail on the
    non-addressable remote devices, so build the global array from a
    callback — every process holds the SAME full-value host copy (model
    init and batch loading are same-seeded on each host, the reference's
    `test_dist_base` contract) and contributes just its addressable
    shards. This is the TPU-native stand-in for the reference's
    per-rank scatter in `DistributedDataParallel` / data loaders.
    """
    m = mesh or require_global_mesh()
    sh = NamedSharding(m, spec)
    if jax.process_count() == 1:
        return jax.device_put(value, sh)
    arr = np.asarray(value)
    return jax.make_array_from_callback(arr.shape, sh, lambda idx: arr[idx])


def _sanitize_spec(spec: PartitionSpec, shape, mesh: Mesh) -> PartitionSpec:
    """Drop axis names from dims they don't divide evenly (correctness first:
    an indivisible dim stays replicated rather than erroring)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, e in zip(shape, entries):
        names = e if isinstance(e, tuple) else ((e,) if e is not None else ())
        size = 1
        for n in names:
            size *= mesh.shape.get(n, 1)
        if size > 1 and dim % size != 0:
            out.append(None)
        else:
            out.append(e)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def sharding_constraint(value, spec: PartitionSpec, mesh: Optional[Mesh] = None):
    """Pin `value`'s layout to `spec` on the (global) mesh.

    Inside a jit trace this becomes an XLA sharding annotation (GSPMD inserts
    whatever collectives are needed to honor it — the TPU-native equivalent of
    the reference's explicit c_allgather/c_reducescatter ops). Eagerly it is a
    device_put (a real resharding transfer).
    """
    m = mesh or get_global_mesh()
    if m is None or m.empty:
        return value
    spec = _sanitize_spec(spec, tuple(value.shape), m)
    # Inside a shard_map/pmap region the bound axes are MANUAL for this
    # trace: data is already rank-local along them, so a GSPMD hint naming
    # them is moot — and rejected at LOWERING time (too late for a
    # try/except here). Strip them from the spec up front.
    manual = manual_axis_names()
    if manual:
        entries = [
            None
            if e is not None and any(
                n in manual for n in (e if isinstance(e, tuple) else (e,))
            )
            else e
            for e in spec
        ]
        while entries and entries[-1] is None:
            entries.pop()
        spec = PartitionSpec(*entries)
    try:
        from jax import lax

        return lax.with_sharding_constraint(value, NamedSharding(m, spec))
    except Exception:
        return jax.device_put(value, NamedSharding(m, spec))


def manual_axis_names() -> frozenset:
    """Axis names bound in the CURRENT trace (shard_map/pmap/vmap regions):
    manual here, so not GSPMD's to partition."""
    from jax._src import core as _core

    return frozenset(_core.unsafe_get_axis_names())


def mesh_axis_size(name: str, mesh: Optional[Mesh] = None) -> int:
    m = mesh or get_global_mesh()
    if m is None or name not in m.shape:
        return 1
    return m.shape[name]
