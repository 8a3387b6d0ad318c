"""Gradient-communication optimization layer: bucketing, wire quantization,
and ZeRO weight-update sharding primitives.

Reference capability (SURVEY.md §2.2 "Data parallel"): the reference fuses
per-parameter NCCL allreduces into size-targeted coalesced buffers
(`fused_allreduce_gradients`, `comm_buffer_size_MB`) so gradient exchange
overlaps with backward compute, and GroupSharded decomposes the weight
update into reduce-scatter(grad) → rank-local update → all-gather(param)
(`group_sharded_stage{2,3}.py`). DGC-style compressed exchange is the
closest reference analogue of the wire-quantized collectives here.

TPU-native design: there is no eager NCCL loop to fuse — every collective
is compiled into the step program. On this jax the building block is the
*fully-manual* `jax.shard_map` region, whose boundary
autodiff gives exactly the mechanics we need (all verified empirically on
the CPU mesh backend):

* a replicated region input receives ONE boundary `psum` over the
  unmentioned mesh axes for its cotangent — so CONCATENATING N parameter
  leaves into one flat fusion buffer merges N per-tensor all-reduces into
  one per-bucket all-reduce, and splitting the gradient exchange into
  several buckets lets the XLA scheduler start early buckets' collectives
  while the backward of earlier layers is still running;
* an input entering SHARDED (its in_spec names the `sharding` axis) that is
  `all_gather`-ed inside the region transposes to `reduce_scatter` — the
  gradient leaves the region sharded, the optimizer update runs on the
  shard, and only the updated parameter is all-gathered: the
  "Automatic Cross-Replica Sharding of Weight Update" decomposition
  (arxiv 2004.13336), which also keeps ZeRO-3 parameter shards sharded
  *inside* pipeline regions;
* a `custom_vjp` identity whose backward round-trips the cotangent through
  the wire dtype implements precision-reduced collectives (bf16; int8 with
  per-bucket scales + error-feedback residuals, cf. EQuARX,
  arxiv 2506.17615) while accumulation stays f32-safe
  (`collective.psum_f32safe` semantics).

Config: `DistributedStrategy.grad_comm` / `grad_comm_configs`, overridden
by the `PADDLE_TPU_GRAD_COMM` env var (see `resolve_config`). Wire/payload
visibility: `comm_analysis.bucket_traffic` + the `grad_comm_*` metrics
registered in `observability/catalog.py` (recorded ONLY from this module —
`scripts/check_observability.py` enforces that ownership).
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass, replace
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import observability as _obs
from .collective import psum_f32safe as _psum_f32safe

WIRE_DTYPES = ("f32", "bf16", "int8")

# int8 symmetric range: per-bucket absmax scale maps onto [-127, 127]
_INT8_LEVELS = 127.0


@dataclass(frozen=True)
class GradCommConfig:
    """Resolved gradient-communication knobs.

    `enable` gates the *optimization* features (bucketed fusion buffers,
    wire quantization, the explicit data-parallel step). `zero_update` and
    `pipeline_batch_shard` default on independently: the first is the
    ZeRO weight-update decomposition (a memory/traffic correctness fix for
    sharded state inside pipeline regions), the second reverses the
    batch-compute replication of the fully-manual pipeline region — both
    are numerics-preserving and carry their own kill switches.
    """

    enable: bool = False
    bucket_mb: float = 32.0
    wire_dtype: str = "f32"
    error_feedback: bool = False
    zero_update: bool = True
    pipeline_batch_shard: bool = True
    # issue each tail bucket's collective INSIDE the backward chain, as its
    # cotangents finalize, instead of after the full backward (docs/
    # PIPELINE.md §4). ZeRO buckets (shard-shaped scatter result) and
    # error-feedback (residual state can't escape a vjp) keep the
    # post-backward issue regardless.
    overlap: bool = True

    @property
    def quantized(self) -> bool:
        return self.wire_dtype != "f32"

    @property
    def bucket_bytes(self) -> int:
        return max(int(self.bucket_mb * (1 << 20)), 1)

    @property
    def wire_itemsize(self) -> int:
        return {"f32": 4, "bf16": 2, "int8": 1}[self.wire_dtype]


_TRUE = {"1", "on", "true", "yes"}
_FALSE = {"0", "off", "false", "no"}


def _strategy_config(strategy) -> GradCommConfig:
    cfg = GradCommConfig()
    if strategy is None:
        return cfg
    enable = bool(getattr(strategy, "grad_comm", False))
    sub = dict(getattr(strategy, "grad_comm_configs", {}) or {})
    wire = str(sub.get("wire_dtype", cfg.wire_dtype)).lower()
    if wire not in WIRE_DTYPES:
        raise ValueError(
            f"grad_comm_configs.wire_dtype={wire!r} not in {WIRE_DTYPES}")
    # the reference's comm_buffer_size_MB lives on DistributedStrategy as
    # fuse_grad_size_in_MB — honor it as the bucket-size default
    default_mb = float(getattr(strategy, "fuse_grad_size_in_MB", cfg.bucket_mb)
                       or cfg.bucket_mb)
    return replace(
        cfg,
        enable=enable,
        bucket_mb=float(sub.get("bucket_mb", default_mb)),
        wire_dtype=wire,
        error_feedback=bool(sub.get("error_feedback", cfg.error_feedback)),
        zero_update=bool(sub.get("zero_update", cfg.zero_update)),
        pipeline_batch_shard=bool(
            sub.get("pipeline_batch_shard", cfg.pipeline_batch_shard)),
        overlap=bool(sub.get("overlap", cfg.overlap)),
    )


def _parse_bool(env_var: str, key: str, v: str) -> bool:
    """Strict boolean values: anything outside the on/off vocabulary is a
    hard error — ``ef=maybe`` must never silently parse as False."""
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    raise ValueError(
        f"{env_var}: {key}={v!r} is not a boolean "
        f"(want one of {tuple(sorted(_TRUE | _FALSE))})")


def _bool_key(env_var: str, field: str):
    def apply(cfg, v):
        return replace(cfg, **{field: _parse_bool(env_var, field, v)})
    return apply


def parse_wire_env(env_var: str, cfg, extra_keys=None):
    """The shared ``off/on/f32/bf16/int8`` + ``k=v`` comm-wire env grammar
    — ONE implementation behind both prefixes (``PADDLE_TPU_GRAD_COMM``
    here, ``PADDLE_TPU_MP_COMM`` in ``mp_comm``).

    ``cfg`` is any frozen dataclass with ``enable`` and ``wire_dtype``
    fields; ``extra_keys`` maps prefix-specific key names to
    ``f(cfg, value) -> cfg`` appliers. Unknown bare tokens, unknown keys,
    and non-boolean values for boolean keys are all hard errors — a typo
    must never silently run the f32 wire."""
    raw = os.environ.get(env_var, "").strip().lower()
    if not raw:
        return cfg
    extra_keys = extra_keys or {}
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            # bare mode tokens compose with k=v ones: "on,bucket_mb=8"
            if part in _FALSE:
                cfg = replace(cfg, enable=False)
            elif part in _TRUE or part == "f32":
                cfg = replace(cfg, enable=True, wire_dtype="f32")
            elif part in ("bf16", "int8"):
                cfg = replace(cfg, enable=True, wire_dtype=part)
            else:
                raise ValueError(
                    f"{env_var}: bad token {part!r} (want k=v, or "
                    f"a mode from {('off', 'on', 'f32', 'bf16', 'int8')})")
            continue
        k, v = (s.strip() for s in part.split("=", 1))
        if k in ("wire", "wire_dtype"):
            if v not in WIRE_DTYPES:
                raise ValueError(
                    f"{env_var} wire={v!r} not in {WIRE_DTYPES}")
            cfg = replace(cfg, wire_dtype=v, enable=True)
        elif k == "enable":
            cfg = replace(cfg, enable=_parse_bool(env_var, k, v))
        elif k in extra_keys:
            cfg = extra_keys[k](cfg, v)
        else:
            raise ValueError(f"{env_var}: unknown key {k!r}")
    return cfg


def resolve_config(strategy=None) -> GradCommConfig:
    """Strategy knobs overridden by ``PADDLE_TPU_GRAD_COMM``.

    Env grammar (case-insensitive, shared with ``PADDLE_TPU_MP_COMM`` —
    see :func:`parse_wire_env`):
      ``off``/``0``            disable bucketing/quantization (the
                               zero_update / batch-shard fixes keep their
                               defaults; use explicit keys to kill them)
      ``on``/``1``/``f32``     enable with f32 wire
      ``bf16`` / ``int8``      enable with that wire dtype
      comma list of ``k=v``    fine-grained: ``wire=int8,bucket_mb=8,``
                               ``error_feedback=1,zero=0,batch_shard=0,``
                               ``overlap=0,enable=1``
    """
    if strategy is None:
        from . import fleet as _fleet

        strategy = _fleet.fleet_strategy()
    cfg = _strategy_config(strategy)
    var = "PADDLE_TPU_GRAD_COMM"
    return parse_wire_env(var, cfg, {
        "bucket_mb": lambda c, v: replace(c, bucket_mb=float(v), enable=True),
        "ef": _bool_key(var, "error_feedback"),
        "error_feedback": _bool_key(var, "error_feedback"),
        "zero": _bool_key(var, "zero_update"),
        "zero_update": _bool_key(var, "zero_update"),
        "batch_shard": _bool_key(var, "pipeline_batch_shard"),
        "pipeline_batch_shard": _bool_key(var, "pipeline_batch_shard"),
        "overlap": _bool_key(var, "overlap"),
    })


# --------------------------------------------------------------- bucketing --
def build_buckets(sizes_bytes: Sequence[int], target_bytes: int) -> List[List[int]]:
    """Greedy, order-preserving grouping of tensor indices into buckets of
    ~``target_bytes``. Order preservation matters: backward visits
    parameters roughly last-to-first, so keeping construction order keeps
    each bucket's members adjacent in the backward schedule — the property
    that lets its collective start while earlier layers still compute."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, sz in enumerate(sizes_bytes):
        if cur and cur_bytes + int(sz) > target_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += int(sz)
    if cur:
        buckets.append(cur)
    return buckets


@dataclass(frozen=True)
class BucketLayout:
    """Static layout of one flat fusion buffer: which leaves, where."""

    indices: Tuple[int, ...]          # leaf indices (into the caller's list)
    shapes: Tuple[Tuple[int, ...], ...]
    offsets: Tuple[int, ...]          # flat element offsets
    sizes: Tuple[int, ...]            # flat element counts
    total: int                        # bucket length in elements


def make_layouts(shapes: Sequence[Tuple[int, ...]], itemsizes: Sequence[int],
                 target_bytes: int, *, lead_dims: int = 0,
                 indices: Optional[Sequence[int]] = None) -> List[BucketLayout]:
    """Bucket a list of tensors into flat-buffer layouts. With ``lead_dims``
    the leading dims are preserved by pack/unpack and offsets/sizes count
    elements PER lead-slice (grouping still targets full-tensor bytes).
    ``indices`` relabels position j in ``shapes`` to a caller index."""
    full = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = [int(np.prod(s[lead_dims:])) if s[lead_dims:] else 1 for s in shapes]
    groups = build_buckets(
        [n * it for n, it in zip(full, itemsizes)], target_bytes)
    out = []
    for g in groups:
        offs, off = [], 0
        for j in g:
            offs.append(off)
            off += flat[j]
        out.append(BucketLayout(
            indices=tuple(indices[j] if indices is not None else j for j in g),
            shapes=tuple(tuple(shapes[j]) for j in g),
            offsets=tuple(offs),
            sizes=tuple(flat[j] for j in g),
            total=off,
        ))
    return out


def pack_bucket(leaves, layout: BucketLayout, *, lead_dims: int = 0):
    """Concatenate ``leaves[i]`` for i in the layout into one flat buffer.
    ``lead_dims`` leading dims (e.g. the stacked layer dim of a pipeline
    leaf) are preserved; the rest flattens."""
    parts = []
    for i in layout.indices:
        v = leaves[i]
        lead = v.shape[:lead_dims]
        parts.append(v.reshape(lead + (-1,)))
    return jnp.concatenate(parts, axis=lead_dims)


def unpack_bucket(bucket, layout: BucketLayout, *, lead_dims: int = 0):
    """Inverse of :func:`pack_bucket`: list of (index, leaf) pairs."""
    out = []
    lead = bucket.shape[:lead_dims]
    for i, off, n, shape in zip(
            layout.indices, layout.offsets, layout.sizes, layout.shapes):
        sl = lax.slice_in_dim(bucket, off, off + n, axis=lead_dims)
        out.append((i, sl.reshape(lead + tuple(shape[lead_dims:]))))
    return out


# ------------------------------------------------------- wire quantization --
def quantize_absmax(v, axis=None):
    """Symmetric int8 quantization with an absmax scale over ``axis``
    (``None`` = one scale for the whole array, the gradient-wire shape;
    the serving KV cache passes the head_dim axis for per-head scales).
    Returns ``(q_int8, scale_f32)`` with ``scale`` keeping reduced dims."""
    scale = jnp.maximum(
        jnp.max(jnp.abs(v.astype(jnp.float32)), axis=axis, keepdims=True)
        / _INT8_LEVELS,
        jnp.finfo(jnp.float32).tiny)
    q = jnp.clip(jnp.round(v.astype(jnp.float32) / scale),
                 -_INT8_LEVELS, _INT8_LEVELS)
    return q.astype(jnp.int8), scale


def dequantize_absmax(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_absmax` (scale broadcasts over the
    reduced axes it kept)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_roundtrip(v, wire_dtype: str):
    """Project ``v`` onto what the wire dtype can represent (f32-safe
    accumulation semantics: the payload is quantized once, the reduction
    itself accumulates in f32 via psum_f32safe — see docs/GRAD_COMM.md for
    why this is numerics-faithful to a native low-precision collective)."""
    if wire_dtype == "bf16":
        return v.astype(jnp.bfloat16).astype(v.dtype)
    if wire_dtype == "int8":
        q, scale = quantize_absmax(v)
        return dequantize_absmax(q, scale, v.dtype)
    return v


def quantize_with_feedback(v, residual, wire_dtype: str):
    """Error-feedback compression: send quant(v + residual), carry the
    quantization error to the next step (residual lives in optimizer
    state; see HybridParallelOptimizer)."""
    c = v + residual.astype(v.dtype)
    q = quantize_roundtrip(c, wire_dtype)
    return q, (c - q).astype(residual.dtype)


def wire_cast(v, wire_dtype: str):
    """Identity whose COTANGENT is round-tripped through the wire dtype.

    Placed on a fusion buffer just inside a shard_map region, the boundary
    psum of that buffer's cotangent carries exactly the quantized payload —
    the trick that wire-compresses a collective jax itself inserts."""
    if wire_dtype == "f32":
        return v
    return _wire_cast_vjp(v, wire_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _wire_cast_vjp(v, wire_dtype):
    return v


def _wire_cast_fwd(v, wire_dtype):
    return v, None


def _wire_cast_bwd(wire_dtype, _res, ct):
    return (quantize_roundtrip(ct, wire_dtype),)


_wire_cast_vjp.defvjp(_wire_cast_fwd, _wire_cast_bwd)


# ------------------------------------------------- sharded (ZeRO) layouts --
@dataclass(frozen=True)
class ShardLayout:
    """Shard-major flat layout for psum_scatter / all_gather round trips.

    Leaves are split into ``nshards`` static slices along ``dims[i]``; the
    flat buffer concatenates [shard 0 of every leaf, shard 1 of every
    leaf, ...] so a tiled dim-0 ``psum_scatter`` hands rank s exactly its
    contiguous shard block, and a tiled ``all_gather`` of updated shard
    blocks reassembles in the same order."""

    indices: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    dims: Tuple[int, ...]             # shard dim per leaf
    nshards: int
    shard_sizes: Tuple[int, ...]      # per-leaf elements in ONE shard slice
    block: int                        # elements per shard block

    @property
    def total(self) -> int:
        return self.block * self.nshards


def make_shard_layout(indices: Sequence[int],
                      shapes: Sequence[Tuple[int, ...]],
                      dims: Sequence[int], nshards: int) -> ShardLayout:
    shard_sizes = []
    for shape, d in zip(shapes, dims):
        if shape[d] % nshards != 0:
            raise ValueError(
                f"shape {shape} dim {d} not divisible by {nshards} shards")
        shard_sizes.append(int(np.prod(shape)) // nshards)
    return ShardLayout(
        indices=tuple(indices),
        shapes=tuple(tuple(s) for s in shapes),
        dims=tuple(int(d) for d in dims),
        nshards=int(nshards),
        shard_sizes=tuple(shard_sizes),
        block=int(sum(shard_sizes)),
    )


def pack_shard_major(leaves, layout: ShardLayout):
    """Full leaves -> one flat shard-major buffer (layout.total elements)."""
    split = [jnp.split(leaves[i], layout.nshards, axis=d)
             for i, d in zip(layout.indices, layout.dims)]
    blocks = []
    for s in range(layout.nshards):
        blocks.extend(parts[s].reshape(-1) for parts in split)
    return jnp.concatenate(blocks)


def unpack_shard_block(block, layout: ShardLayout):
    """One rank's shard block -> list of (index, shard-slice) pairs, each
    shaped like the leaf with ``dims[i]`` divided by nshards."""
    out, off = [], 0
    for i, shape, d, n in zip(layout.indices, layout.shapes, layout.dims,
                              layout.shard_sizes):
        sshape = list(shape)
        sshape[d] //= layout.nshards
        out.append((i, lax.slice_in_dim(block, off, off + n).reshape(sshape)))
        off += n
    return out


def unpack_gathered(flat, layout: ShardLayout):
    """Tiled all_gather output (shard-major, layout.total elements) -> list
    of (index, full leaf) pairs."""
    blocks = [lax.slice_in_dim(flat, s * layout.block, (s + 1) * layout.block)
              for s in range(layout.nshards)]
    per_shard = [unpack_shard_block(b, layout) for b in blocks]
    out = []
    for j, (i, _) in enumerate(per_shard[0]):
        out.append((i, jnp.concatenate(
            [per_shard[s][j][1] for s in range(layout.nshards)],
            axis=layout.dims[j])))
    return out


def gather_leaves(local_leaves, layout: ShardLayout, axis_name: str,
                  wire_dtype: Optional[str] = None,
                  act_wire: Optional[str] = None):
    """Inside a manual region: one tiled all_gather reassembling the full
    leaves from every rank's shard block (ZeRO-3 parameter gather; its
    autodiff transpose is the reduce_scatter that keeps gradients
    sharded). ``local_leaves`` are this rank's shard slices, in layout
    order. ``wire_dtype`` wire-casts the gathered buffer so the transposed
    reduce_scatter carries a quantized cotangent payload.

    ``act_wire`` (mp_comm activation wire) additionally quantizes the
    FORWARD payload itself — per-leaf absmax scales, a REAL
    reduced-precision all_gather in the compiled HLO, not just the
    cotangent cast (``collective.all_gather_quantized``)."""
    flat = jnp.concatenate([v.reshape(-1) for v in local_leaves])
    if act_wire in ("bf16", "int8"):
        from .collective import all_gather_quantized

        gathered = all_gather_quantized(
            flat, axis_name, wire_dtype=act_wire,
            segments=tuple(int(np.prod(v.shape)) if v.shape else 1
                           for v in local_leaves),
            grad_wire=wire_dtype)
        return unpack_gathered(gathered, layout)
    gathered = lax.all_gather(flat, axis_name, axis=0, tiled=True)
    if wire_dtype is not None:
        gathered = wire_cast(gathered, wire_dtype)
    return unpack_gathered(gathered, layout)


# ----------------------------------------------------------- mesh helpers --
def data_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("dp", "sharding")
                 if a in mesh.shape and mesh.shape[a] > 1)


def is_pure_data_mesh(mesh) -> bool:
    """True when every non-trivial mesh axis is a data axis (dp/sharding):
    the whole step can run in one fully-manual region with no model
    parallelism or pipeline schedule inside."""
    if mesh is None or mesh.size <= 1:
        return False
    extent = 1
    for a in data_axes(mesh):
        extent *= mesh.shape[a]
    return extent == mesh.size


def spec_mentions(spec, axis_name: str) -> bool:
    for e in (spec or ()):
        if e == axis_name or (isinstance(e, (tuple, list)) and axis_name in e):
            return True
    return False


def sharded_dim(spec, axis_name: str) -> Optional[int]:
    """Dim index that ``spec`` shards over ``axis_name``, or None."""
    for i, e in enumerate(spec or ()):
        if e == axis_name or (isinstance(e, (tuple, list)) and axis_name in e):
            return i
    return None


# ------------------------------------------- explicit data-parallel step --
@dataclass(frozen=True)
class DpPlan:
    """Static exchange plan for the explicit data-parallel step: which
    trainable parameters ride shard-major ZeRO buckets (psum_scatter →
    shard-local update → all_gather) and which ride plain flat fusion
    buckets (psum → full update)."""

    axes: Tuple[str, ...]
    group: int
    nshards: int                      # extent of the `sharding` axis
    zero_layouts: Tuple[ShardLayout, ...]
    tail_layouts: Tuple[BucketLayout, ...]
    bytes_f32: int                    # one direction, f32 payload
    bytes_wire: int                   # same payload at the wire dtype
    overlap_tail: bool = False        # tail buckets issue in-backward

    @property
    def n_buckets(self) -> int:
        return len(self.zero_layouts) + len(self.tail_layouts)


def plan_dp_exchange(cfg: GradCommConfig, mesh, param_shapes,
                     param_itemsizes, trainable,
                     state_shard_dims) -> Optional[DpPlan]:
    """Build the bucket/shard plan, or None when the explicit path does not
    apply to this mesh. ``state_shard_dims[i]`` is the dim the committed
    optimizer state of param i is sharded over (None = replicated state)."""
    if not is_pure_data_mesh(mesh):
        return None
    axes = data_axes(mesh)
    group = int(np.prod([mesh.shape[a] for a in axes]))
    S = mesh.shape.get("sharding", 1)
    zero = cfg.zero_update and S > 1
    if S > 1 and not cfg.zero_update:
        # sharded optimizer states but no shard-local update: the explicit
        # path would have to gather states — strictly worse than GSPMD
        return None

    shardable, tail = [], []
    for i, (shape, tr, k) in enumerate(
            zip(param_shapes, trainable, state_shard_dims)):
        if not tr:
            continue
        if zero and k is not None and shape[k] % S == 0:
            shardable.append(i)
        else:
            tail.append(i)

    target = cfg.bucket_bytes
    zero_layouts = []
    if shardable:
        sizes = [int(np.prod(param_shapes[i])) * param_itemsizes[i]
                 for i in shardable]
        for g in build_buckets(sizes, target):
            idx = [shardable[j] for j in g]
            zero_layouts.append(make_shard_layout(
                idx, [param_shapes[i] for i in idx],
                [state_shard_dims[i] for i in idx], S))
    tail_layouts = []
    if tail:
        shapes = [param_shapes[i] for i in tail]
        its = [param_itemsizes[i] for i in tail]
        tail_layouts = list(make_layouts(shapes, its, target, indices=tail))

    n_elems = sum(l.total for l in zero_layouts) + sum(
        l.total for l in tail_layouts)
    return DpPlan(
        axes=axes, group=group, nshards=S,
        zero_layouts=tuple(zero_layouts), tail_layouts=tuple(tail_layouts),
        bytes_f32=n_elems * 4, bytes_wire=n_elems * cfg.wire_itemsize,
        overlap_tail=bool(cfg.overlap and tail_layouts
                          and not (cfg.quantized and cfg.error_feedback)),
    )


SUPPORTED_CLIPS = ("ClipGradByValue", "ClipGradByNorm", "ClipGradByGlobalNorm")


def clip_supported(clip) -> bool:
    return clip is None or type(clip).__name__ in SUPPORTED_CLIPS


def _clip_sharded(clip, shard_pairs, tail_pairs, have_sharding: bool):
    """Apply a grad clip to (param_idx, shard_grad) + (param_idx, full_grad)
    pairs inside the manual region. Norms over sharded grads close over the
    `sharding` axis with a scalar/vector psum; full (tail) grads are
    replicated across the group so their norm contribution is added once."""
    kind = type(clip).__name__
    if kind == "ClipGradByValue":
        f = lambda g: jnp.clip(g, clip.min, clip.max)
        return ([(i, f(g)) for i, g in shard_pairs],
                [(i, f(g)) for i, g in tail_pairs])
    if kind == "ClipGradByNorm":
        out_s = []
        if shard_pairs:
            sq = jnp.stack([jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for _, g in shard_pairs])
            if have_sharding:
                sq = _psum_f32safe(sq, "sharding")
            norms = jnp.sqrt(sq)
            for j, (i, g) in enumerate(shard_pairs):
                scale = jnp.minimum(
                    clip.clip_norm / jnp.maximum(norms[j], 1e-12), 1.0)
                out_s.append((i, g * scale.astype(g.dtype)))
        out_t = []
        for i, g in tail_pairs:
            n = jnp.sqrt(jnp.sum(jnp.square(g)))
            scale = jnp.minimum(clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
            out_t.append((i, g * scale))
        return out_s, out_t
    # ClipGradByGlobalNorm
    sq_sh = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                for _, g in shard_pairs)
    if shard_pairs and have_sharding:
        sq_sh = _psum_f32safe(sq_sh, "sharding")
    sq_t = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
               for _, g in tail_pairs)
    gnorm = jnp.sqrt(sq_sh + sq_t)
    scale = clip.clip_norm / jnp.maximum(gnorm, clip.clip_norm)
    fix = lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype)
    return ([(i, fix(g)) for i, g in shard_pairs],
            [(i, fix(g)) for i, g in tail_pairs])


RESIDUAL_KEY = "__grad_comm__"


def init_residuals(cfg: GradCommConfig, plan: DpPlan, mesh):
    """Error-feedback residual buffers, one per bucket, committed sharded
    over the data axes (each group rank carries its own quantization
    error). NOT serialized with optimizer state — after restore the first
    step quantizes with a zero residual (documented in GRAD_COMM.md)."""
    from . import mesh as _mesh

    out = {}
    for b, lay in enumerate(tuple(plan.zero_layouts) + tuple(plan.tail_layouts)):
        z = jnp.zeros((plan.group, lay.total), jnp.float32)
        out[f"residual_{b}"] = _mesh.global_device_put(
            z, P(plan.axes if len(plan.axes) > 1 else plan.axes[0]), mesh)
    return out


def build_explicit_dp_step(cfg: GradCommConfig, plan: DpPlan, mesh, *,
                           loss_of, opt, trainable, state_specs_tree,
                           batch_spec_fn, buffer_changed_cell,
                           use_residuals: bool):
    """The explicit data-parallel train step: one fully-manual shard_map
    over the whole fwd+bwd+update, with the gradient exchange bucketed,
    optionally wire-quantized (+error feedback), and — when the `sharding`
    axis is live — decomposed into psum_scatter → shard-local optimizer
    update → all_gather of updated params (ZeRO weight-update sharding).

    Returns a ``step(p_vals, b_vals, opt_states, batch_vals, lr, rng_key)``
    with the same signature/state-layout contract as TrainStep._build_step
    (opt_states may carry a trailing {RESIDUAL_KEY: ...} entry)."""
    axes = plan.axes
    S = plan.nshards
    have_sh = S > 1 and "sharding" in axes
    group = plan.group
    ef = use_residuals
    clip = getattr(opt, "_grad_clip", None)
    all_layouts = tuple(plan.zero_layouts) + tuple(plan.tail_layouts)
    # Backward-overlapped exchange (docs/PIPELINE.md §4): tail buckets wrap
    # their params in a custom_vjp identity whose backward packs the
    # bucket's cotangents, quantizes, and issues the group psum RIGHT THERE
    # — the returned "cotangent" IS the exchanged gradient, so XLA must
    # schedule the collective before any earlier layer's backward that
    # consumes nothing from it, i.e. it runs concurrently with the
    # remaining backward instead of after all of it. ZeRO buckets can't
    # ride this (psum_scatter yields shard-shaped grads, but a cotangent
    # must match the full param) and error feedback can't either (the
    # residual update is state escaping a vjp) — both keep the
    # post-backward issue.
    overlap_tail = plan.overlap_tail and not ef
    # mp_comm activation wire: the ZeRO parameter all-gather is a forward
    # payload, so it rides the quantized gather (floored at bf16 — see
    # MpCommConfig.param_gather_wire) when the activation wire is on
    from . import mp_comm as _mp_comm

    param_gather_wire = _mp_comm.resolve_config().param_gather_wire

    def _overlapped(shapes):
        @jax.custom_vjp
        def ident(*leaves):
            return leaves

        def fwd(*leaves):
            return leaves, None

        def bwd(_, cts):
            flat = jnp.concatenate(
                [c.astype(jnp.float32).reshape(-1) for c in cts])
            if cfg.quantized:
                flat = quantize_roundtrip(flat, cfg.wire_dtype)
            flat = lax.psum(flat, axes) / group
            out, off = [], 0
            for shp in shapes:
                n = int(np.prod(shp)) if shp else 1
                out.append(lax.dynamic_slice_in_dim(
                    flat, off, n, 0).reshape(shp))
                off += n
            return tuple(out)

        ident.defvjp(fwd, bwd)
        return ident

    def body(p_vals, b_vals, states, residuals, batch_vals, lr, rng_key):
        # decorrelate per-rank randomness (dropout) across the group
        ridx = jnp.int32(0)
        for a in axes:
            ridx = ridx * mesh.shape[a] + lax.axis_index(a)
        rng_local = jax.random.fold_in(rng_key, ridx)
        loss_fn = loss_of
        if overlap_tail:
            def loss_fn(p_list, aux):
                p_list = list(p_list)
                for lay in plan.tail_layouts:
                    ident = _overlapped(
                        [tuple(p_list[i].shape) for i in lay.indices])
                    for i, w in zip(lay.indices,
                                    ident(*[p_list[i] for i in lay.indices])):
                        p_list[i] = w
                return loss_of(p_list, aux)
        (loss, new_b), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            list(p_vals), (list(b_vals), list(batch_vals), rng_local))
        loss = lax.psum(loss.astype(jnp.float32), axes) / group
        # sync only buffers the model actually mutated (running stats):
        # identity of unchanged buffers survives the trace (see
        # DistTrainStep); untouched buffers stay replicated for free
        changed = buffer_changed_cell[0] if buffer_changed_cell else ()
        new_b = [
            _psum_f32safe(v, axes) / group
            if (ch and jnp.issubdtype(v.dtype, jnp.floating)) else v
            for v, ch in zip(new_b, changed or (False,) * len(new_b))
        ]

        shard_pairs, tail_pairs, new_res = [], [], {}
        for b, lay in enumerate(all_layouts):
            is_zero = b < len(plan.zero_layouts)
            if not is_zero and overlap_tail:
                # exchanged in-backward by the custom_vjp identity above:
                # grads[i] already carries the reduced (and, if quantized,
                # wire-round-tripped) group average
                tail_pairs.extend(
                    (i, grads[i].astype(jnp.float32)) for i in lay.indices)
                continue
            if is_zero:
                flat = pack_shard_major(grads, lay)
            else:
                flat = pack_bucket(grads, lay)
            flat32 = flat.astype(jnp.float32)
            if cfg.quantized:
                if ef:
                    flat32, res = quantize_with_feedback(
                        flat32, residuals[f"residual_{b}"][0], cfg.wire_dtype)
                    new_res[f"residual_{b}"] = res[None]
                else:
                    flat32 = quantize_roundtrip(flat32, cfg.wire_dtype)
            elif ef:
                new_res[f"residual_{b}"] = residuals[f"residual_{b}"]
            if is_zero:
                blk = flat32
                if have_sh:
                    blk = lax.psum_scatter(
                        blk, "sharding", scatter_dimension=0, tiled=True)
                if "dp" in axes:
                    blk = lax.psum(blk, "dp")
                blk = blk / group
                shard_pairs.extend(unpack_shard_block(blk, lay))
            else:
                flat32 = lax.psum(flat32, axes) / group
                tail_pairs.extend(unpack_bucket(flat32, lay))

        if clip is not None:
            shard_pairs, tail_pairs = _clip_sharded(
                clip, shard_pairs, tail_pairs, have_sh)

        # assemble aligned per-param lists for the (clip-free) update rule
        glist = [None] * len(p_vals)
        plist = list(p_vals)
        shard_dim = {}
        for lay in plan.zero_layouts:
            for i, k in zip(lay.indices, lay.dims):
                shard_dim[i] = k
        sidx = lax.axis_index("sharding") if have_sh else None
        for i, g in shard_pairs:
            k = shard_dim[i]
            glist[i] = g.astype(p_vals[i].dtype)
            chunk = p_vals[i].shape[k] // S
            plist[i] = lax.dynamic_slice_in_dim(
                p_vals[i], sidx * chunk, chunk, k)
        for i, g in tail_pairs:
            glist[i] = g.astype(p_vals[i].dtype)
        new_p, new_st = opt.functional_update(plist, glist, list(states), lr)

        # gather updated shards back to full params, one collective/bucket
        new_p = list(new_p)
        for lay in plan.zero_layouts:
            local = [new_p[i] for i in lay.indices]
            for i, full in gather_leaves(local, lay, "sharding",
                                         act_wire=param_gather_wire):
                new_p[i] = full
        return loss, tuple(new_p), tuple(new_b), list(new_st), new_res

    p_specs = [P()] * len(trainable)

    def step(p_vals, b_vals, opt_states, batch_vals, lr, rng_key):
        states, residuals = opt_states, {}
        if states and isinstance(states[-1], dict) and RESIDUAL_KEY in states[-1]:
            residuals = states[-1][RESIDUAL_KEY]
            states = states[:-1]
        b_specs = [P()] * len(b_vals)
        batch_specs = tuple(
            batch_spec_fn(tuple(v.shape)) for v in batch_vals)
        res_spec = P(axes if len(axes) > 1 else axes[0])
        res_specs = {k: res_spec for k in residuals}
        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(tuple(p_specs), tuple(b_specs), state_specs_tree,
                      res_specs, tuple(batch_specs), P(), P()),
            out_specs=(P(), tuple(p_specs), tuple(b_specs),
                       state_specs_tree, res_specs),
            axis_names=frozenset(axes), check_vma=False,
        )
        loss, new_p, new_b, new_st, new_res = mapped(
            tuple(p_vals), tuple(b_vals), list(states), residuals,
            tuple(batch_vals), lr, rng_key)
        new_st = list(new_st)
        if residuals:
            new_st.append({RESIDUAL_KEY: new_res})
        return loss, list(new_p), list(new_b), new_st

    return step


# ------------------------------------------------------------- metrics ----
def record_build_stats(n_buckets: int, payload_bytes_f32: int,
                       payload_bytes_wire: int) -> None:
    """Gauges describing the compiled gradient-exchange structure. Called
    at trace/build time (values are static Python numbers, never tracers).

    overlap_ratio: share of exchanged bytes NOT in the final-issued bucket.
    Buckets are built in parameter order and backward reaches bucket 0
    last, so everything outside bucket 0 can overlap remaining backward
    compute — 0.0 for a monolithic exchange, ->1 for many buckets."""
    _obs.set_gauge("grad_comm_buckets", float(n_buckets))
    if payload_bytes_f32 > 0:
        _obs.set_gauge("grad_comm_quantized_fraction",
                       1.0 - payload_bytes_wire / payload_bytes_f32)
    # instant marker span (dur 0): the build happens inside tracing, so
    # wall time is not separable here — the attrs are what matters
    _obs.record_span("grad_comm_exchange", dur_s=0.0, buckets=n_buckets,
                     wire_bytes=payload_bytes_wire)


def record_overlap_ratio(first_bucket_bytes: int, total_bytes: int) -> None:
    if total_bytes > 0:
        _obs.set_gauge("grad_comm_overlap_ratio",
                       1.0 - first_bucket_bytes / total_bytes)


def record_step_bytes(wire_bytes: int) -> None:
    """Per-executed-step wire payload (both directions of the exchange are
    counted by the caller)."""
    if wire_bytes > 0:
        _obs.inc("grad_comm_bytes_total", float(wire_bytes))
