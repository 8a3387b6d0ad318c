"""Mixture-of-Experts with expert parallelism.

Reference capability (SURVEY.md §2.3 "Expert parallel (EP/MoE)"):
`python/paddle/incubate/distributed/models/moe/moe_layer.py` — gshard/switch
gating with capacity, `global_scatter`/`global_gather` all-to-all dispatch
ops (CUDA), per-rank expert FFNs.

TPU-native design (GShard formulation): gating produces dispatch/combine
tensors; dispatch is an einsum into a dense [experts, capacity, hidden]
buffer, experts run as ONE batched matmul over the expert dim (MXU-friendly,
no ragged loops), combine is the transpose einsum. The expert dim is sharded
over a mesh axis, so GSPMD emits the token all-to-all that the reference's
global_scatter/global_gather implement by hand. Static capacity keeps shapes
XLA-compatible; dropped tokens (over capacity) pass through the residual,
exactly like capacity-factor semantics in the reference.
"""
from __future__ import annotations

import contextlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..framework.core import Tensor
from ..framework.op import defop, raw
from ..distributed import mesh as _mesh
from ..profiler import scope as _scope


def _expert_axis() -> Optional[str]:
    """Mesh axis carrying the expert dim: prefer a dedicated data axis."""
    m = _mesh.get_global_mesh()
    if m is None:
        return None
    for name in ("sharding", "dp", "sep"):
        if name in m.shape and m.shape[name] > 1:
            return name
    return None


def _aux_loss(probs, e, k):
    """gshard load-balancing loss: E^2/k * Σ_e density_e · mean-prob_e
    (shared by the capacity and dropless routing paths)."""
    density = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32), 0
    )
    return jnp.sum(density * jnp.mean(probs, 0)) * (e * e) / max(k, 1)


@defop(name="moe_gate_dispatch")
def _gshard_gating(logits, key, k, capacity, use_aux_noise):
    """Top-k gating with static capacity (gshard/switch).

    logits: [G, E] (G tokens). Returns (combine [G,E,C], dispatch bool
    [G,E,C], aux_loss scalar).
    """
    g, e = logits.shape
    if use_aux_noise and key is not None:
        logits = logits + jax.random.gumbel(key, logits.shape) * 0.01
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    combine = jnp.zeros((g, e), jnp.float32)
    remaining = probs
    position_in_expert = jnp.zeros((g, e), jnp.int32)
    fill = jnp.zeros((e,), jnp.int32)
    masks = []
    gates = []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)  # [G]
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        gates.append((probs * onehot).sum(-1))
        # position of each token within its chosen expert queue
        pos = jnp.cumsum(onehot, axis=0) - 1.0 + fill[None, :].astype(jnp.float32)
        pos = (pos * onehot).sum(-1).astype(jnp.int32)  # [G]
        keep = pos < capacity
        masks.append((onehot, pos, keep))
        fill = fill + onehot.sum(0).astype(jnp.int32)
        remaining = remaining * (1.0 - onehot)

    aux = _aux_loss(probs, e, k)

    denom = sum(gt * m[2] for gt, m in zip(gates, masks))
    denom = jnp.maximum(denom, 1e-9)
    dispatch = jnp.zeros((g, e, capacity), bool)
    combine3 = jnp.zeros((g, e, capacity), jnp.float32)
    for gt, (onehot, pos, keep) in zip(gates, masks):
        w = (gt / denom) * keep.astype(jnp.float32)
        sel = onehot.astype(bool) & keep[:, None]
        oh_cap = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [G, C]
        combine3 = combine3 + w[:, None, None] * onehot[:, :, None] * oh_cap[:, None, :]
        dispatch = dispatch | (sel[:, :, None] & (oh_cap[:, None, :] > 0))
    return combine3, dispatch, aux


class MoELayer(nn.Layer):
    """GShard-style MoE FFN (paddle.incubate MoELayer parity).

    experts: number of expert FFNs (global). Weights are stored stacked
    [E, ...] with the expert dim sharded over the expert-parallel mesh axis.
    """

    def __init__(
        self,
        d_model: int,
        d_hidden: int,
        num_experts: int,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        gate: str = "gshard",
        aux_loss_weight: float = 1e-2,
        activation=None,
        drop_tokens: bool = True,
    ):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.top_k = 1 if gate == "switch" else top_k
        self.capacity_factor = capacity_factor
        self.aux_loss_weight = aux_loss_weight
        self.act = activation or F.gelu
        # drop_tokens=False → DROPLESS routing over the Pallas grouped-matmul
        # kernel (megablox-style): no capacity, no dropped tokens; experts
        # see exactly their routed tokens (ragged groups). Currently runs
        # with replicated expert weights (the capacity path carries the
        # EP-sharded all-to-all).
        self.drop_tokens = drop_tokens
        self.gate = nn.Linear(d_model, num_experts)
        init = I.XavierNormal()
        self.w_in = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=init
        )
        self.b_in = self.create_parameter([num_experts, 1, d_hidden], is_bias=True)
        self.w_out = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=init
        )
        self.b_out = self.create_parameter([num_experts, 1, d_model], is_bias=True)
        ax = _expert_axis()
        if (drop_tokens and ax is not None
                and num_experts % _mesh.mesh_axis_size(ax) == 0):
            # EP sharding only for the capacity path; the dropless grouped-
            # matmul kernel runs with replicated expert weights
            for p in (self.w_in, self.b_in, self.w_out, self.b_out):
                p.dist_spec = P(ax)
                p.is_distributed = True
        self.last_aux_loss = None

    def forward(self, x):
        b, t, h = x.shape
        g = b * t
        flat = x.reshape([g, h])
        logits = self.gate(flat)
        if not self.drop_tokens:
            out, aux = _moe_apply_dropless(
                flat, logits, self.w_in, self.b_in, self.w_out, self.b_out,
                self.act, self.top_k,
            )
            self.last_aux_loss = aux * self.aux_loss_weight
            return out.reshape([b, t, h])
        capacity = max(
            self.top_k, int(math.ceil(self.top_k * self.capacity_factor * g / self.num_experts))
        )
        from ..framework import rng as _rng

        key = _rng.next_key() if self.training else None
        combine, dispatch, aux = _gshard_gating(
            logits, key, self.top_k, capacity, self.training
        )
        self.last_aux_loss = aux * self.aux_loss_weight
        out = _moe_apply(
            flat, combine, dispatch, self.w_in, self.b_in, self.w_out, self.b_out,
            self.act,
        )
        return out.reshape([b, t, h])


@defop(name="moe_apply")
def _moe_apply(flat, combine, dispatch, w_in, b_in, w_out, b_out, act):
    # dispatch tokens into per-expert buffers: [E, C, h]
    expert_in = jnp.einsum("gec,gh->ech", dispatch.astype(flat.dtype), flat)
    spec = None
    m = _mesh.get_global_mesh()
    ax = _expert_axis()
    if m is not None and ax is not None and expert_in.shape[0] % m.shape[ax] == 0:
        # pin the expert buffers to the expert axis — this is the all-to-all
        expert_in = _mesh.sharding_constraint(expert_in, P(ax))
    hidden = raw(act(jnp.einsum("ech,ehf->ecf", expert_in, w_in) + b_in))
    expert_out = jnp.einsum("ecf,efh->ech", hidden, w_out) + b_out
    if m is not None and ax is not None and expert_out.shape[0] % m.shape[ax] == 0:
        expert_out = _mesh.sharding_constraint(expert_out, P(ax))
    # combine back to tokens
    return jnp.einsum("gec,ech->gh", combine.astype(flat.dtype), expert_out)


@defop(name="moe_apply_dropless")
def _moe_apply_dropless(flat, logits, w_in, b_in, w_out, b_out, act, top_k):
    """Dropless MoE FFN over the Pallas grouped-matmul kernel.

    Token copies are sorted by routed expert; the two expert GEMMs run as
    ragged grouped matmuls with data-dependent group sizes (no capacity, no
    dropped tokens — the reference needs `global_scatter` + per-expert GEMM
    loops for this; megablox-style kernels are the TPU-native equivalent).
    Returns (out [G, H], aux_loss).
    """
    from ..ops.pallas.grouped_matmul import grouped_matmul

    g, h = flat.shape
    e = w_in.shape[0]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)  # [G, k]
    gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    aux = _aux_loss(probs, e, top_k)

    gk = g * top_k
    expert_ids = topi.reshape(-1)  # [gk]
    order = jnp.argsort(expert_ids)  # stable: ties keep token order
    sizes = jnp.bincount(expert_ids, length=e)  # dynamic group sizes
    row_gid = expert_ids[order]
    xs = flat[order // top_k].astype(flat.dtype)  # [gk, H] sorted copies

    block_m = _gmm_block_m(gk)
    pad = (-gk) % block_m
    xs_p = jnp.pad(xs, ((0, pad), (0, 0)))

    h1 = grouped_matmul(xs_p, w_in, sizes, block_m=block_m)[:gk]
    h1 = h1 + b_in[row_gid, 0]
    a = raw(act(h1)).astype(flat.dtype)
    a_p = jnp.pad(a, ((0, pad), (0, 0)))
    y = grouped_matmul(a_p, w_out, sizes, block_m=block_m)[:gk]
    y = y + b_out[row_gid, 0]

    inv = jnp.argsort(order)  # unsort copies back to (token, slot) order
    y_tok = y[inv].reshape(g, top_k, h)
    out = jnp.sum(gates[..., None].astype(flat.dtype) * y_tok, axis=1)
    return out, aux


def _gmm_block_m(gk: int) -> int:
    """Row block of the grouped products over ``gk`` sorted token copies.
    Measured on v5e (8k tokens, 1024->4096, 8 experts): 512-row blocks are
    ~6% faster than 128 (less per-visit overhead). Use them only once the
    padding tail is amortized (gk >= 2048 keeps the tail under 25%; at gk
    just above 512 it would nearly double the row tiles); tiny inputs keep
    a pow2 block so the tail stays bounded."""
    if gk >= 2048:
        return 512
    if gk >= 128:
        return 128
    return max(8, 1 << (gk - 1).bit_length())


#: the traced counts that ``routed_experts`` leaves while ``count_experts``
#: is open: one int32 a layer traced, the experts it sent tokens to
_EXPERT_COUNTS: Optional[list] = None


@contextlib.contextmanager
def count_experts():
    """Collect, while open, how many of its experts each ``routed_experts``
    traced inside sent at least one token to (a traced int32 a layer): a
    compiled program sums the list into an output of its own, so that the
    count comes from the routing on the device."""
    global _EXPERT_COUNTS
    prev, _EXPERT_COUNTS = _EXPERT_COUNTS, []
    try:
        yield _EXPERT_COUNTS
    finally:
        _EXPERT_COUNTS = prev


@defop(name="moe_routed_experts")
def routed_experts(x, router_w, w_gate_up, w_down, top_k: int,
                   expert_range: Optional[Tuple[int, int]] = None):
    """A sparse SwiGLU expert layer, dropless, as one expert-parallel rank
    computes it (Qwen3-MoE's block): ``x [G, H]`` routed over ALL experts,
    the part of the result that the experts held here give.

        r = softmax_f32(x Wr)                  Wr [H, E]; products in f32
        S = top_k(r), w_e = r_e / sum_S r      (norm_topk_prob)
        y = sum_{e in S, held} w_e (silu(x G_e) * (x U_e)) D_e

    ``w_gate_up [E_held, H, 2F]`` holds each expert's G and U side by side
    (one grouped product for both), ``w_down [E_held, F, H]``;
    ``expert_range = (lo, hi)`` names the experts held (all when None).
    Token copies routed to a held expert are sorted by expert and run as
    the two grouped products of ``ops/pallas/grouped_matmul.py``; copies
    routed elsewhere sort past the groups, where the kernel gives zero
    rows. Over every range the parts add up to the whole layer (no
    exchange is written: on one chip the layer holds every expert).
    Returns y [G, H] in x's dtype."""
    from ..ops.pallas.grouped_matmul import grouped_matmul

    g, h = x.shape
    e = router_w.shape[1]
    lo, hi = expert_range or (0, e)
    held = w_gate_up.shape[0]
    if hi - lo != held:
        raise ValueError(f"expert_range {(lo, hi)} does not match the "
                         f"{held} experts held")
    f = w_down.shape[1]
    gk = g * top_k
    with _scope("moe_route"):
        probs = jax.nn.softmax(jnp.dot(
            x, router_w, preferred_element_type=jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(probs, top_k)  # [G, k]
        gates = topv / topv.sum(-1, keepdims=True)
        local = topi.reshape(-1) - lo  # [gk]
        # copies of experts held elsewhere sort last, past every group
        group = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(group)  # stable: ties keep token order
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(
            jnp.int32)
        if _EXPERT_COUNTS is not None:
            _EXPERT_COUNTS.append(jnp.sum(sizes > 0, dtype=jnp.int32))
        block_m = _gmm_block_m(gk)
        pad = (-gk) % block_m
        xs = jnp.pad(x[order // top_k], ((0, pad), (0, 0)))
    with _scope("moe_experts"):
        gu = grouped_matmul(xs, w_gate_up, sizes, block_m=block_m)
        gu = gu.astype(jnp.float32)
        a = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(x.dtype)
        y = grouped_matmul(a, w_down, sizes, block_m=block_m)[:gk]
        y = y[jnp.argsort(order)].reshape(g, top_k, h)  # (token, choice)
        # an elementwise product: a contraction would take one bf16 term
        # of the float32 gates on a TPU
        out = jnp.sum(gates[..., None] * y.astype(jnp.float32), axis=1)
    return out.astype(x.dtype)


# ------------------------------------------------- global_scatter / gather --
def global_scatter(x, local_count=None, global_count=None, group=None):
    """Reference `global_scatter` op parity: the token all-to-all. Under SPMD
    this is a resharding of the expert-major buffer onto the expert axis."""
    ax = _expert_axis()
    if ax is None:
        return x
    return Tensor(_mesh.sharding_constraint(raw(x), P(ax)))


def global_gather(x, local_count=None, global_count=None, group=None):
    ax = _expert_axis()
    if ax is None:
        return x
    return Tensor(_mesh.sharding_constraint(raw(x), P()))
