"""Grouped (per-expert) matmul with DYNAMIC group sizes — Pallas TPU kernel
(megablox-style).

Reference capability (SURVEY.md §2.3 "EP / MoE": grouped expert FFN over
`global_scatter`/`global_gather`; §7 step 8 "MoE grouped matmul + ragged
all_to_all"). The reference's experts run as separate CUDA GEMMs per expert;
the TPU-native design is ONE kernel over group-sorted rows:

    out[r] = lhs[r] @ rhs[group_of(r)]    lhs: [M, K], rhs: [G, K, N]

`group_sizes` is a RUNTIME array (routing is data-dependent — this is what
makes dropless MoE possible): rows are sorted by group, groups are ragged,
and a row tile may span several group boundaries. The kernel runs over a
precomputed *visit schedule*: each visit is (row-tile, group) with the
group's row-range inside the tile; boundary tiles get one visit per
overlapping group, with rows outside the visit's range masked before the
MXU dot. The schedule (int32 [V, 8]) is computed in-graph from group_sizes
and rides the scalar-prefetch channel, so the expert-weight BlockSpec index
map can select rhs[group] per visit without any HBM gather.

Rows past sum(group_sizes) are padding: their tiles are visited with an
empty row-range and emit zeros.

Backward with the SAME schedule (visits are simultaneously consecutive in
row-tile AND in group, because rows are group-sorted):
  dlhs = gmm(dout, rhs^T)            (same forward kernel)
  drhs[g] = lhs_g^T @ dout_g         (accumulate per group, emit at each
                                      group's last visit)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 128
# N auto-pads to a block multiple inside grouped_matmul, so a wide default
# is safe for any n; measured on v5e it is ~6% faster than 128 at MoE-FFN
# shapes (the lhs block is reused across the whole N sweep)
DEFAULT_BLOCK_N = 1024

# schedule columns
_MTILE, _GID, _RS, _RE, _FIRST_OUT, _LAST_OUT, _FIRST_G, _LAST_G = range(8)


def _build_schedule(group_sizes, m, block_m, num_groups):
    """int32 [V, 8] visit table; V = nt + G + 1 static (worst case: every
    group adds one boundary visit, plus one virtual padding-tail group)."""
    nt = m // block_m
    sizes = jnp.asarray(group_sizes, jnp.int32)
    total = jnp.sum(sizes)
    # virtual tail group absorbs padding rows [total, m) with an EMPTY
    # row-range (those tiles emit zeros)
    sizes_ext = jnp.concatenate([sizes, (m - total)[None]])
    start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes_ext)[:-1]]
    )
    end = start + sizes_ext
    ts = start // block_m
    te = jnp.maximum(-(-end // block_m), ts + 1)  # >= 1 visit even if empty
    vg = te - ts  # visits per group
    voff = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(vg)[:-1]])
    n_visits = voff[-1] + vg[-1]

    v = jnp.arange(nt + num_groups + 1, dtype=jnp.int32)
    gid = jnp.searchsorted(jnp.cumsum(vg), v, side="right").astype(jnp.int32)
    gid = jnp.minimum(gid, num_groups)  # incl. virtual tail
    m_tile = jnp.clip(ts[gid] + (v - voff[gid]), 0, max(nt - 1, 0))
    valid = v < n_visits
    # row range of this visit's group inside its tile (tile-relative)
    rs = jnp.clip(start[gid] - m_tile * block_m, 0, block_m)
    re = jnp.clip(end[gid] - m_tile * block_m, 0, block_m)
    is_tail = gid >= num_groups
    rs = jnp.where(valid & ~is_tail, rs, 0)
    re = jnp.where(valid & ~is_tail, re, 0)
    # padding visits (v >= n_visits) chain onto the last real tile/group so
    # the first/last flags below stay consistent
    m_tile = jnp.where(valid, m_tile, max(nt - 1, 0))
    gid_sched = jnp.where(valid, jnp.minimum(gid, num_groups - 1),
                          num_groups - 1)

    prev_tile = jnp.concatenate([m_tile[:1] - 1, m_tile[:-1]])
    next_tile = jnp.concatenate([m_tile[1:], m_tile[-1:] + 1])
    prev_g = jnp.concatenate([gid_sched[:1] - 1, gid_sched[:-1]])
    next_g = jnp.concatenate([gid_sched[1:], gid_sched[-1:] + 1])
    first_out = (m_tile != prev_tile).astype(jnp.int32)
    last_out = (m_tile != next_tile).astype(jnp.int32)
    first_g = (gid_sched != prev_g).astype(jnp.int32)
    last_g = (gid_sched != next_g).astype(jnp.int32)
    return jnp.stack(
        [m_tile, gid_sched, rs, re, first_out, last_out, first_g, last_g],
        axis=1,
    )


def _mask_rows(x, rs, re):
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where((rows >= rs) & (rows < re), x, jnp.zeros_like(x))


def _fwd_kernel(sched_ref, lhs_ref, rhs_ref, out_ref, acc):
    v = pl.program_id(1)

    @pl.when(sched_ref[v, _FIRST_OUT] == 1)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = _mask_rows(lhs_ref[...], sched_ref[v, _RS], sched_ref[v, _RE])
    acc[...] += jax.lax.dot_general(
        x, rhs_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(sched_ref[v, _LAST_OUT] == 1)
    def _emit():
        out_ref[...] = acc[...].astype(out_ref.dtype)


def _gmm_forward(lhs, rhs, sched, block_m, block_n, interpret):
    m, k = lhs.shape
    _, k2, n = rhs.shape
    assert k == k2, (lhs.shape, rhs.shape)
    assert m % block_m == 0, f"M={m} must be a block_m={block_m} multiple"
    block_n = min(block_n, n)
    assert n % block_n == 0, f"N={n} must be a block_n={block_n} multiple"
    grid = (n // block_n, sched.shape[0])  # visits innermost

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, v, s: (s[v, _MTILE], 0)),
            pl.BlockSpec((1, k, block_n), lambda j, v, s: (s[v, _GID], 0, j)),
        ],
        out_specs=pl.BlockSpec(
            (block_m, block_n), lambda j, v, s: (s[v, _MTILE], j)
        ),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        _fwd_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=interpret,
        name="grouped_matmul_fwd",
    )(sched, lhs, rhs)


def _drhs_kernel(sched_ref, lhs_ref, dout_ref, drhs_ref, acc):
    v = pl.program_id(1)

    @pl.when(sched_ref[v, _FIRST_G] == 1)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = _mask_rows(lhs_ref[...], sched_ref[v, _RS], sched_ref[v, _RE])
    acc[...] += jax.lax.dot_general(
        x, dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(sched_ref[v, _LAST_G] == 1)
    def _emit():
        drhs_ref[0] = acc[...].astype(drhs_ref.dtype)


# VMEM the drhs kernel plans its f32 blocks in: Mosaic's scoped limit on a
# v5e is 16 MiB, and the bf16 lhs/dout input blocks need their share of it
_DRHS_VMEM_BUDGET = 12 << 20


def _drhs_block_n(k, n, block_n):
    """Widest column block, from ``block_n`` down by halves, whose (k, bn)
    f32 accumulator plus double-buffered (k, bn) f32 output block fit the
    budget. At K=2048 the forward's 1024-wide block would need 24 MiB."""
    bn = min(block_n, n)
    while 3 * k * bn * 4 > _DRHS_VMEM_BUDGET and bn % 256 == 0:
        bn //= 2
    return bn


def _gmm_drhs(lhs, dout, sched, num_groups, block_m, block_n, interpret):
    m, k = lhs.shape
    n = dout.shape[1]
    block_n = _drhs_block_n(k, n, block_n)
    grid = (n // block_n, sched.shape[0])
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, v, s: (s[v, _MTILE], 0)),
            pl.BlockSpec((block_m, block_n),
                         lambda j, v, s: (s[v, _MTILE], j)),
        ],
        out_specs=pl.BlockSpec(
            (1, k, block_n), lambda j, v, s: (s[v, _GID], 0, j)
        ),
        scratch_shapes=[pltpu.VMEM((k, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), jnp.float32),
        interpret=interpret,
        name="grouped_matmul_drhs",
    )(sched, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm(lhs, rhs, sched, num_groups, block_m, block_n, interpret):
    return _gmm_forward(lhs, rhs, sched, block_m, block_n, interpret)


def _gmm_fwd(lhs, rhs, sched, num_groups, block_m, block_n, interpret):
    out = _gmm_forward(lhs, rhs, sched, block_m, block_n, interpret)
    return out, (lhs, rhs, sched)


def _gmm_bwd(num_groups, block_m, block_n, interpret, res, dout):
    lhs, rhs, sched = res
    rhs_t = jnp.swapaxes(rhs, 1, 2)  # [G, N, K]
    dlhs = _gmm_forward(dout, rhs_t, sched, block_m, block_n, interpret)
    drhs = _gmm_drhs(
        lhs, dout, sched, rhs.shape[0], block_m, block_n, interpret
    )
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, block_m=DEFAULT_BLOCK_M,
                   block_n=DEFAULT_BLOCK_N, interpret=None):
    """out[rows of group g] = lhs[rows of group g] @ rhs[g], ragged groups.

    Args:
      lhs: [M, K] rows sorted by group (group-contiguous); M must be a
        block_m multiple. Rows past sum(group_sizes) are padding and
        produce zero rows in the output.
      rhs: [G, K, N] per-group weights.
      group_sizes: [G] int array — may be a traced (data-dependent) value;
        sum(group_sizes) <= M.
    Returns out: [M, N].
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    m = lhs.shape[0]
    n = rhs.shape[2]
    num_groups = int(rhs.shape[0])
    # pick the widest block that divides N (wide blocks measured faster on
    # v5e), falling back to 128-col padding at most — padding all the way
    # to a 1024 multiple would compute up to ~78% throwaway columns for
    # N like 1152. The slice below routes the cotangent back through the
    # zero-padding in backward automatically.
    bn = min(block_n, n)
    if n % bn:
        for cand in (512, 256, 128):
            if cand < bn and n % cand == 0:
                bn = cand
                break
        else:
            bn = min(128, bn)
    pad_n = (-n) % bn
    if pad_n:
        rhs = jnp.pad(rhs, ((0, 0), (0, 0), (0, pad_n)))
    sched = _build_schedule(group_sizes, m, block_m, num_groups)
    out = _gmm(
        lhs, rhs, sched, num_groups, int(block_m), int(bn), bool(interpret),
    )
    return out[:, :n] if pad_n else out
