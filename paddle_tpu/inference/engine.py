"""Paged-KV decode engine: continuous batching, prefix sharing, speculation.

Reference capability: Paddle Inference's generation serving stack (fused
attention-with-cache kernels updating an in-place ``cache_kv`` per layer)
and PaddleNLP's ``llm/predictor.py`` batched serving loop, extended with
the vLLM-style block-granular cache discipline. TPU-native design (the
static-shape serving discipline on XLA):

* **Static shapes only.** Three compiled program families serve every
  request mix: one prefill per power-of-two *tail* bucket (batch 1,
  written through a page table), ONE single-token decode step over all
  ``num_slots`` slots, and (when ``speculate_k > 0``) ONE multi-token
  verify step. Nothing recompiles per request, per length, or per step.
* **Paged KV cache.** The cache is a page pool
  ``[L, num_pages, Hkv, page_size, D]`` plus a per-slot page table
  ``[S, max_pages]`` (host-maintained int32). Page 0 is a reserved trash
  page; a free-list allocator hands out the rest. A request holds only
  ``ceil(total_len / page_size)`` pages instead of a full ``max_length``
  ring, so short requests stop stranding HBM and the pool can serve far
  more concurrent requests per GB (``scripts/bench_serving.py`` churn
  scenario). ``F.paged_attention`` gathers K/V through the table; int8
  scales are paged identically.
* **Prefix caching.** Full prompt blocks are chain-hashed
  (``h_j = H(h_{j-1} || tokens_j)``) and registered in a bounded-LRU
  page registry with refcounts. A new prompt whose leading blocks hit
  the registry shares those pages (incref, never rewritten — decode and
  tail writes only touch pages past ``cached_len``, which is the
  copy-on-write discipline) and prefills ONLY the unique tail: an
  80 %-shared-prefix workload skips 80 % of its prefill FLOPs.
* **Speculative / multi-token decode.** A host-side prompt-lookup
  (n-gram) draft proposes ``k`` tokens per slot; one compiled verify
  program scores current + k draft tokens in a single target-model pass
  and per-position target tokens are accepted while they agree with the
  draft, emitting up to ``k + 1`` tokens per step. Acceptance compares
  against the SAME position-keyed sample streams the decode step uses
  (``fold_in(request_key, position)``), so greedy output stays bit-equal
  and sampled streams stay scheduling-invariant with speculation on or
  off.
* **Disaggregated prefill.** ``prefill_export`` runs a prompt's prefill
  here and returns its content KV pages as host arrays;
  ``try_import_prefill`` adopts them on a decode engine, seating the
  request straight into the decode batch. Raw transfer with a matching
  ``kv_dtype`` is bit-equal to a local prefill (the serving worker
  streams the payload through ``serving/transport.py``'s KV codec).
* **Continuous batching / on-device sampling / int8 KV** as before
  (PR 5): pure-Python scheduler admits into free slots between compiled
  steps, one int32 per slot per step host transfer (``k+1`` for verify),
  absmax-scaled int8 via grad_comm's quantize/dequantize helpers.

* **Block diffusion.** A model whose adapter states ``block_length``
  (text/models/sdar.py) writes a block of positions at a time: a step is
  ONE compiled block pass over every slot's current block (all of its
  positions in place, masked ones as the mask token, under a block-causal
  horizon), which draws a token at each masked position and unmasks some
  by the model's rule, on device; tokens are emitted as they become final.
  The batch moves in rounds of one block: when no slot has a masked
  position left, ONE compiled commit pass writes every finished block's
  final keys and values, and only then are new requests admitted.

A model plugs in through ``model.decode_adapter()`` (text/models/gpt.py,
llama.py): ``embed``, its own block as ``layer`` (calling the engine's
paged ``attend(q, k, v)`` where full attention stood), ``head``, and the
pool's geometry. The pool's format is ``kv_pool.KVPool``'s alone. See
docs/SERVING.md for "Serving a new model", the page-table invariants and
the accept/reject rule.
"""
from __future__ import annotations

import functools
import hashlib
import math
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..distributed import mesh as _mesh
from ..runtime import compile_cache as _compile_cache
from ..framework.core import Tensor, no_grad
from ..framework.op import raw
from ..nn import functional as F
from ..profiler import scope as _scope
from .kv_pool import KV_DTYPES, TRASH_PAGE, KVPool, active_mp_mesh

__all__ = [
    "DecodeEngine",
    "EngineConfig",
    "PagePool",
    "PrefixRegistry",
    "SamplingParams",
    "pow2_bucket",
]

#: PRNG of the request sample streams, pinned so that sampled tokens do not
#: depend on the process-wide default: on a TPU that default is ``rbg``
#: (framework/rng.py), whose bits change under ``vmap`` and so with the
#: slot a request happens to decode in
_KEY_IMPL = "threefry2x32"


def _program_kind(name: str) -> Tuple[str, int]:
    """A program's family and size: ``("prefill", 512)`` of "prefill_b512",
    ``("verify", 4)`` of "verify_k4", ``("decode", 0)``, ``("block", 4)`` of
    "block_b4" and ``("commit", 4)`` of "commit_b4"."""
    kind, _, size = name.partition("_")
    return kind, int(size[1:] or 0)


def _pack_fields(layout, arrays) -> np.ndarray:
    """Host: ``arrays`` (arrays or scalars, one a field of ``layout``, a
    tuple of ``(shape, dtype)``) laid end to end in ONE int32 buffer, so
    that a pass's inputs reach the device in one transfer: int32 as it is,
    float32 and uint32 (threefry key data) bit for bit, bool as 0/1."""
    buf = np.empty(sum(math.prod(shape) for shape, _ in layout), np.int32)
    o = 0
    for (shape, dt), a in zip(layout, arrays, strict=True):
        a = np.asarray(a, dt)
        if a.shape != shape:
            raise ValueError(f"packed field {shape} {dt} given {a.shape}")
        n = a.size
        buf[o:o + n] = (a if dt == np.bool_ else a.view(np.int32)).reshape(n)
        o += n
    return buf


def _unpack_fields(layout, packed):
    """Device (traced): the fields of ``layout`` cut back out of the one
    int32 array ``_pack_fields`` made, each in its own dtype and shape, bit for
    bit: static slices, a bit-cast for float32 and uint32, ``!= 0`` for
    bool."""
    out, o = [], 0
    for shape, dt in layout:
        n = math.prod(shape)
        seg = packed[o:o + n]
        o += n
        if dt == np.bool_:
            seg = seg != 0
        elif dt != np.int32:
            seg = jax.lax.bitcast_convert_type(seg, dt)
        out.append(seg.reshape(shape))
    return out


def pow2_bucket(n: int, lo: int = 16, hi: Optional[int] = None) -> int:
    """Smallest power-of-two >= n (floored at `lo`, capped at `hi`)."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi) if hi is not None else b


@dataclass
class EngineConfig:
    """Engine geometry + cache policy (see docs/SERVING.md for tuning)."""

    num_slots: int = 8
    max_length: int = 512
    kv_dtype: str = "f32"  # f32 | bf16 | int8
    #: explicit prompt buckets; None = powers of two from min_bucket up to
    #: max_length. Only buckets a prompt tail actually lands in get
    #: compiled.
    prompt_buckets: Optional[Tuple[int, ...]] = None
    min_bucket: int = 16
    #: KV page size in tokens. A request holds ceil(total/page_size)
    #: pages; prefix sharing works at full-page granularity.
    page_size: int = 16
    #: total pages in the pool INCLUDING the reserved trash page 0.
    #: None = 1 + num_slots * ceil(max_length / page_size) (the same
    #: capacity the PR 5 contiguous cache reserved); set it lower to
    #: overcommit — admission blocks when the free list runs dry.
    num_pages: Optional[int] = None
    #: hash full prompt blocks and share hit pages across requests
    prefix_cache: bool = True
    #: bounded LRU capacity of the prefix registry, in blocks.
    #: None = num_pages (every page could be registered).
    prefix_registry_blocks: Optional[int] = None
    #: draft tokens per speculative step; 0 disables speculation
    speculate_k: int = 0
    #: longest n-gram the prompt-lookup draft matches on
    ngram: int = 3
    #: self-tuning speculation: track EMAs of decode/verify step wall time
    #: and draft acceptance, and only run the verify program when its
    #: expected tokens/s beats plain decode (verify is ~free on memory-
    #: bound TPU decode, ~(k+1)x on compute-bound CPU). Acceptance is
    #: timing-INDEPENDENT, so output stays bit-equal either way — the
    #: gate only changes how many tokens one step emits. False = always
    #: speculate when a draft exists (deterministic step pattern, what
    #: the bit-equality tests pin).
    spec_adaptive: bool = True
    #: while speculation is suppressed, re-probe with one verify step
    #: every this many decode steps (acceptance drifts with the workload)
    spec_probe_every: int = 32
    #: None = donate cache buffers on tpu/gpu only (CPU XLA cannot alias
    #: them and would warn on every step)
    donate: Optional[bool] = None
    #: base seed for requests that don't carry their own
    seed: int = 0
    #: wire dtype of the mp-sharded logit recombination (docs/SERVING.md
    #: §5): None resolves from the mp_comm activation-wire config
    #: (PADDLE_TPU_MP_COMM / DistributedStrategy.mp_comm), "off"/"f32"
    #: pins today's exact f32 all-gather byte-for-byte, "bf16"/"int8"
    #: quantize the replication payload while a per-shard (max, argmax)
    #: exchange keeps greedy decode bit-equal to the single-device
    #: engine. Ignored (always exact) when the mesh has no mp axis.
    logit_wire: Optional[str] = None
    #: paged-attention kernel for the decode/verify/prefill programs:
    #: None inherits PADDLE_TPU_ATTN_KERNEL (default "auto"), "pallas"
    #: pins the fused Pallas kernel (page gather + online softmax + int8
    #: dequant in one pass, docs/SERVING.md §kernel plane), "einsum" pins
    #: the XLA reference oracle, "auto" picks pallas on TPU. An mp-
    #: sharded pool serves einsum under "auto" (the GSPMD annotations
    #: live there; counted in attn_kernel_fallback_total) and rejects an
    #: explicit "pallas".
    attn_kernel: Optional[str] = None
    #: jax.sharding.Mesh to run the compiled programs on. An ``mp`` axis
    #: with degree > 1 shards the KV pools (and int8 scales) over kv
    #: heads — GQA groups stay whole per shard, so mp must divide
    #: num_kv_heads — while page tables, sampling, and everything outside
    #: attention stay replicated (greedy output is bit-equal to the
    #: single-device engine; docs/SERVING.md §mp sharding). Give each
    #: engine its OWN mesh slice: a dp axis here replicates the pools,
    #: engine replicas belong behind serving.Router instead.
    mesh: Optional[object] = None

    def resolved_buckets(self) -> List[int]:
        if self.prompt_buckets:
            bs = sorted({min(int(b), self.max_length)
                         for b in self.prompt_buckets})
        else:
            bs, b = [], self.min_bucket
            while b < self.max_length:
                bs.append(b)
                b *= 2
            bs.append(min(b, self.max_length))
        return bs

    @property
    def max_pages(self) -> int:
        """Page-table width: pages a max_length request spans."""
        return -(-self.max_length // self.page_size)

    def resolved_num_pages(self) -> int:
        if self.num_pages is not None:
            return int(self.num_pages)
        return 1 + self.num_slots * self.max_pages


@dataclass
class SamplingParams:
    max_new_tokens: int = 32
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: Optional[int] = None

    def fields(self):
        """(temperature, top_k, top_p, greedy) in device form."""
        greedy = (not self.do_sample) or self.temperature <= 0.0
        return (max(float(self.temperature), 1e-6), int(self.top_k),
                float(self.top_p), bool(greedy))


@dataclass
class BlockState:
    """One slot's block in a block-diffusion engine: its positions from
    ``start``, which of them are still masked (kept by position: a prompt
    may hold the mask token's id), the tokens of the others, how many were
    masked when it began and the passes run on it."""
    start: int
    tokens: np.ndarray  # [B] int32; a masked position's entry is unused
    masked: np.ndarray  # [B] bool
    to_unmask: int
    passes: int = 0


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray
    params: SamplingParams
    key_np: np.ndarray
    tokens: List[int] = field(default_factory=list)
    status: str = "waiting"  # waiting | running | done
    slot: int = -1
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    #: every page id this request holds a reference on (shared prefix
    #: pages first, then private pages), in virtual-sequence order
    page_ids: List[int] = field(default_factory=list)
    #: tokens served from the prefix registry (multiple of page_size)
    cached_len: int = 0
    #: distributed-trace context from the router wire record (spans are
    #: emitted only when trace_id is set — standalone engines stay quiet)
    trace_id: Optional[str] = None
    trace_parent: Optional[str] = None
    resubmitted: bool = False
    #: phase accounting (perf_counter stamps) behind the enriched
    #: serving_request_done event; maintained regardless of tracing
    prefill_t0: Optional[float] = None
    prefill_s: float = 0.0
    decode_t0: Optional[float] = None
    decode_steps_n: int = 0
    verify_steps_n: int = 0
    spec_accepted_n: int = 0
    #: cost-attribution key (observability/accounting.py): "-" = the
    #: untagged default; slo mirrors the router's class for the ledger
    tenant: str = "-"
    slo: str = "standard"
    #: True when this request's prefill (and first token) ran on another
    #: engine (try_import_prefill) — its prefill/first-token usage was
    #: attributed there, so _finish must not count them again
    imported: bool = False
    #: pro-rata KV page occupancy charged to this request so far, in
    #: integer page-microseconds (PageSecondsMeter)
    acct_page_us: int = 0
    #: weight epoch this request was admitted under (per-slot epoch pin):
    #: the request decodes against these weights until it finishes, even
    #: if the engine promotes a newer epoch mid-flight — the per-epoch
    #: greedy bit-equal contract rides on this
    epoch: int = 0
    #: a block-diffusion engine's current block of this request
    block: Optional[BlockState] = None
    #: generated position -> the pass of its block that unmasked it (0 is
    #: the block's first), recorded by a block-diffusion engine
    unmask_pass: Dict[int, int] = field(default_factory=dict)


@dataclass
class StepReport:
    """What one ``DecodeEngine.step()`` did, by request id
    (``engine.last_step``): what the step already knows, not a
    measurement, so it is kept whether or not anybody is tracing."""
    #: requests whose prefill ran in this step, in admission order
    admitted: List[int] = field(default_factory=list)
    #: requests that finished in this step
    finished: List[int] = field(default_factory=list)
    #: the tokens each request emitted in this step (an admitted request's
    #: first token, then what the decode or verify pass added)
    tokens: Dict[int, List[int]] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# host-side page accounting: free-list allocator + prefix registry
# ---------------------------------------------------------------------------


class PagePool:
    """Free-list page allocator with refcounts.

    Page ``TRASH_PAGE`` (0) is reserved and never allocated. A page is
    free iff its refcount is 0; ``alloc`` hands it out at refcount 1,
    sharing increfs, and the last ``decref`` returns it to the free
    list — so the invariant ``available() + pages_referenced == num_pages
    - 1`` holds at every step and a double-allocation is structurally
    impossible (allocated pages are not on the free list).
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (trash page + 1)")
        self.num_pages = int(num_pages)
        # pop() hands out low page ids first
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._ref = np.zeros(self.num_pages, np.int64)

    def available(self) -> int:
        return len(self._free)

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def shared_pages(self) -> int:
        """Pages currently referenced by more than one owner."""
        return int((self._ref[1:] >= 2).sum())

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages at refcount 1, or None (never partial)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, page: int):
        if page == TRASH_PAGE or self._ref[page] <= 0:
            raise ValueError(f"incref of unallocated page {page}")
        self._ref[page] += 1

    def decref(self, page: int):
        if self._ref[page] <= 0:
            raise ValueError(f"decref of free page {page}")
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)


class PrefixRegistry:
    """Bounded LRU of full prompt blocks: chain hash -> page id.

    Each registered page carries one registry reference, so pages stay
    resident (and shareable) after their request finishes until LRU
    capacity or an explicit ``evict_unused`` reclaims them. Entries whose
    page is still used by a running request can drop OUT of the registry
    (no longer discoverable) without freeing the page — the refcount
    keeps it alive until the request finishes.
    """

    def __init__(self, pool: PagePool, capacity: int):
        self.pool = pool
        self.capacity = int(capacity)
        self._lru: "OrderedDict[bytes, int]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self):
        return len(self._lru)

    @staticmethod
    def block_keys(prompt: np.ndarray, page_size: int) -> List[bytes]:
        """Chain hashes of the prompt's FULL blocks: block j's key folds
        in its parent's key, so equal keys imply equal whole prefixes,
        not just equal blocks."""
        keys, parent = [], b"paddle_tpu/prefix"
        t0 = int(prompt.shape[0])
        for j in range(t0 // page_size):
            blk = np.ascontiguousarray(
                prompt[j * page_size:(j + 1) * page_size], dtype=np.int64)
            parent = hashlib.blake2b(
                parent + blk.tobytes(), digest_size=16).digest()
            keys.append(parent)
        return keys

    def lookup_chain(self, keys: List[bytes]) -> List[int]:
        """Pages for the longest registered prefix of `keys`, each
        increfed for the caller (release with pool.decref)."""
        pages = []
        for key in keys:
            page = self._lru.get(key)
            if page is None:
                self.misses += 1
                break
            self._lru.move_to_end(key)
            self.pool.incref(page)
            pages.append(page)
            self.hits += 1
        return pages

    def register(self, key: bytes, page: int):
        if key in self._lru:
            self._lru.move_to_end(key)
            return
        self.pool.incref(page)
        self._lru[key] = page
        while len(self._lru) > self.capacity:
            _, old = self._lru.popitem(last=False)
            self.pool.decref(old)

    def evict_unused(self, want: int) -> int:
        """Drop up to `want` LRU entries whose page only the registry
        still references (freeing the page); returns pages freed."""
        freed = 0
        for key in list(self._lru):
            if freed >= want:
                break
            page = self._lru[key]
            if self.pool.refcount(page) == 1:
                del self._lru[key]
                self.pool.decref(page)
                freed += 1
        return freed

    def clear(self):
        for page in self._lru.values():
            self.pool.decref(page)
        self._lru.clear()


# ---------------------------------------------------------------------------
# sharding hints and sampling (pure jnp; traced inside the compiled programs)
# ---------------------------------------------------------------------------


def _shard_kv_heads(kv):
    """Constraint hint sharding a fresh K/V projection [..., Hkv, D] over
    the mp axis on its head dim (axis -2), so the page-pool scatter that
    follows stays shard-local instead of gathering the pool. No-op
    without an active mp mesh or when mp doesn't divide Hkv."""
    m = active_mp_mesh()
    if m is None:
        return kv
    spec = [None] * kv.ndim
    spec[-2] = "mp"
    return _mesh.sharding_constraint(kv, _mesh.P(*spec), m)


def _replicate_out(x):
    """Constraint hint forcing a program output replicated (sampled
    tokens, logits) so the one-int32-per-slot host transfer reads the
    same bits on every shard. No-op without an active mesh."""
    m = active_mp_mesh()
    return x if m is None else _mesh.sharding_constraint(x, _mesh.P(), m)


def _bisect_max(holds, n, bits):
    """The largest uint32 ``c`` [n, 1] below ``2**bits`` for which
    ``holds(c)`` (a row-wise predicate that holds up to some value and not
    above it), set bit by bit from the top: ``bits`` masked reductions.
    0 where it never holds."""
    def step(i, c):
        cand = c | jnp.left_shift(jnp.uint32(1),
                                  (bits - 1 - i).astype(jnp.uint32))
        return jnp.where(holds(cand), cand, c)
    return jax.lax.fori_loop(0, bits, step, jnp.zeros((n, 1), jnp.uint32))


def _kth_largest(x, k):
    """[N, 1]: the ``k``-th largest of each row of ``x`` [N, V] f32, ties
    counted (``jnp.sort(x)[:, ::-1][:, k - 1]``), ``k`` [N] in [1, V]."""
    # float32 -> uint32 in the float order, -inf first: the answer is the
    # largest key that k keys reach. The order is finer than jnp.sort's
    # (-0.0 below 0.0), so the k-th falls in the sort's class of equals
    # and ``x < kth`` reads the same
    flip = jnp.int32(-2**31)
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    u = jax.lax.bitcast_convert_type(
        jnp.where(b < 0, b ^ 0x7FFFFFFF, b) ^ flip, jnp.uint32)
    ku = _bisect_max(lambda c: jnp.sum(u >= c, axis=-1, keepdims=True,
                                       dtype=jnp.int32) >= k[:, None],
                     x.shape[0], 32)
    kb = jax.lax.bitcast_convert_type(ku, jnp.int32) ^ flip
    return jax.lax.bitcast_convert_type(
        jnp.where(kb < 0, kb ^ 0x7FFFFFFF, kb), jnp.float32)


def _nucleus_floor(probs, top_p):
    """[N, 1]: the largest ``t`` for which the tokens of ``probs`` [N, V]
    at ``t`` or above hold ``top_p`` [N] of the row's mass, which is the
    smallest probability of the nucleus; 0 (every token) where even the
    whole row's float sum falls short of ``top_p``."""
    # probabilities in [0, 1]: their bit patterns order as the values, and
    # lie below 2.0's (2**30). A step compares the values with the
    # candidate's float, so the loop carries and reads the one array
    def holds(c):
        t = jax.lax.bitcast_convert_type(c, jnp.float32)
        return jnp.sum(jnp.where(probs >= t, probs, 0.0), axis=-1,
                       keepdims=True) >= top_p[:, None]
    return jax.lax.bitcast_convert_type(
        _bisect_max(holds, probs.shape[0], 30), jnp.float32)


def _filter_logits(logits, temperature, top_k, top_p):
    """The temperature-scaled logits [N, V] with every token outside a
    row's top-k and top-p set to -inf; top_k <= 0 and top_p >= 1.0
    disable their filters. Both cut-offs are exact thresholds found by
    bisection, with no sort of the vocabulary, each skipped where no row
    asks for it. The nucleus is every token at or above the smallest
    probability a sort's rule keeps (a token is kept while the mass before
    it is below top_p); the sort sums in another order, which can move
    one token at the nucleus's exact edge."""
    n, v = logits.shape
    x = logits / temperature[:, None]
    kth = jax.lax.cond(
        jnp.any(top_k > 0), lambda x: _kth_largest(x, jnp.clip(top_k, 1, v)),
        lambda x: jnp.full((n, 1), -jnp.inf, x.dtype), x)
    x = jnp.where((top_k[:, None] > 0) & (x < kth), -jnp.inf, x)
    probs = jax.nn.softmax(x, axis=-1)
    thr = jax.lax.cond(
        jnp.any(top_p < 1.0), lambda p: _nucleus_floor(p, top_p),
        lambda p: jnp.zeros((n, 1), p.dtype), probs)
    return jnp.where((top_p[:, None] < 1.0) & (probs < thr), -jnp.inf, x)


def _sample_tokens(logits, keys, temperature, top_k, top_p, greedy,
                   exact_argmax=None):
    """On-device sampling for N rows: logits [N, V] f32, keys [N] typed,
    temperature/top_p f32 [N], top_k i32 [N], greedy bool [N]. Per-row
    keys keep every request's sample stream independent of co-scheduling.
    The filters are ``_filter_logits``'s. ``exact_argmax`` [N] i32, when
    given, replaces the local argmax for greedy rows — the quantized logit
    wire passes the verify exchange's exact winner here so greedy output
    never sees quantization (docs/SERVING.md §5)."""
    x = _filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.vmap(lambda xr, kr: jax.random.categorical(kr, xr))(x, keys)
    arg = (jnp.argmax(logits, axis=-1) if exact_argmax is None
           else exact_argmax)
    return jnp.where(greedy, arg, sampled).astype(jnp.int32)


def _unmask(drawn, conf, tokens, masked, count, rule, threshold):
    """Which masked positions of each slot's block a pass unmasks, on
    device: ``drawn``, ``conf`` [S, B] the tokens drawn at every position
    and their probabilities, ``tokens``, ``masked`` [S, B] the block's
    state, ``count`` [S] how many to unmask. ``sequential`` takes the
    leftmost masked positions, ``low_confidence_static`` the most probable
    drawn tokens, ``low_confidence_dynamic`` every one above ``threshold``
    where there are at least ``count``, else as static. Returns the new
    tokens and mask."""
    b = masked.shape[1]
    if rule == "sequential":
        score = -jnp.broadcast_to(jnp.arange(b, dtype=jnp.float32),
                                  masked.shape)
    else:
        score = conf
    score = jnp.where(masked, score, -jnp.inf)
    # rank of each position among its slot's, best first (ties: leftmost)
    rank = jnp.argsort(jnp.argsort(-score, axis=1, stable=True), axis=1)
    if rule == "low_confidence_dynamic":
        count = jnp.maximum(count, jnp.sum(masked & (conf > threshold),
                                           axis=1, dtype=count.dtype))
    chosen = masked & (rank < count[:, None])
    return (jnp.where(chosen, drawn, tokens).astype(jnp.int32),
            masked & ~chosen)


class DecodeEngine:
    """Continuous-batching serving engine over a decoder-only LM.

    Usage::

        eng = DecodeEngine(model, num_slots=8, max_length=512,
                           speculate_k=4)
        rid = eng.submit(prompt_ids, max_new_tokens=64, eos_token_id=2)
        eng.run()                     # or step() from your own loop
        out = eng.result(rid)         # np.ndarray prompt + generated

    or the batch front end ``eng.generate_batch(ids, ...)`` which
    ``text.generation.generate`` rides on.
    """

    def __init__(self, model, config: Optional[EngineConfig] = None,
                 **overrides):
        self.config = config or EngineConfig(**overrides)
        cfg = self.config
        if cfg.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {tuple(KV_DTYPES)}, got "
                f"{cfg.kv_dtype!r}")
        if cfg.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {cfg.page_size}")
        self.model = model
        model.eval()
        self.adapter = model.decode_adapter()
        ad = self.adapter
        if cfg.max_length > ad.max_positions:
            raise ValueError(
                f"max_length={cfg.max_length} exceeds the model's "
                f"max_positions={ad.max_positions}")
        #: the two depths, stated apart: ``num_layers`` layers of weights
        #: run ``loops`` times (1 unless the adapter says otherwise), each
        #: run ended by ``close_loop``; a (loop, layer) has its own cache
        #: entry, so the pool is ``cache_layers`` deep
        self._loops = int(getattr(ad, "loops", 1))
        self._close_loop = getattr(ad, "close_loop", None)
        self.cache_layers = self._loops * ad.num_layers
        #: a block-diffusion model's block length (0: one token a pass);
        #: its block pass and commit pass take the place of decode
        self._block = int(getattr(ad, "block_length", 0) or 0)
        if self._block:
            if cfg.page_size % self._block:
                raise ValueError(
                    f"block_length={self._block} must divide page_size="
                    f"{cfg.page_size}: a registered prompt page must hold "
                    "whole blocks, whose keys no later position changes")
            if cfg.speculate_k:
                raise ValueError("speculate_k must be 0 for a block-"
                                 "diffusion model: its pass is the block")
        self.buckets = cfg.resolved_buckets()
        self._mp = cfg.max_pages
        self._num_pages = cfg.resolved_num_pages()
        self._mesh = cfg.mesh
        self._mp_degree = 1
        if self._mesh is not None:
            from ..distributed.mesh import mesh_axis_size

            self._mp_degree = mesh_axis_size("mp", self._mesh)
            if (self._mp_degree > 1
                    and ad.num_kv_heads % self._mp_degree != 0):
                raise ValueError(
                    f"mp={self._mp_degree} must divide num_kv_heads="
                    f"{ad.num_kv_heads}: the KV pool shards by whole kv "
                    "heads (GQA groups stay intact per shard)")
        # resolve the logit-recombination wire (docs/SERVING.md §5): the
        # explicit config wins; None inherits the ambient mp_comm
        # activation wire. f32 keeps the exact all-gather byte-for-byte.
        from ..distributed import mp_comm as _mp_comm

        lw, self._logit_verify = cfg.logit_wire, True
        if lw is None:
            wcfg = _mp_comm.resolve_config()
            lw = wcfg.wire_dtype if wcfg.quantized else "f32"
            self._logit_verify = wcfg.logit_verify
        elif lw in ("off", "f32"):
            lw = "f32"
        elif lw not in ("bf16", "int8"):
            raise ValueError(
                f"logit_wire must be one of (None, 'off', 'f32', 'bf16', "
                f"'int8'), got {cfg.logit_wire!r}")
        if self._mp_degree <= 1:
            lw = "f32"
        self._logit_wire = lw
        # resolve the paged-attention kernel once — it shapes every
        # compiled program (and so belongs in the AOT cache key). The
        # fused Pallas kernel cannot express the mp GSPMD sharding: under
        # mp "auto" serves the einsum oracle and counts it, while a
        # kernel that was asked for by name and cannot run is an error,
        # never a quiet switch.
        asked = F.asked_attn_kernel(cfg.attn_kernel)
        self._attn_kernel = F.resolve_attn_kernel(asked)
        if self._attn_kernel == "pallas" and self._mp_degree > 1:
            if asked == "pallas":
                raise ValueError(
                    f"attn_kernel='pallas' cannot serve an mp-sharded KV "
                    f"pool (mp={self._mp_degree}); use 'auto' or 'einsum'")
            self._attn_kernel = "einsum"
            _obs.inc("attn_kernel_fallback_total")
        _obs.set_gauge("attn_kernel_active",
                       1.0 if self._attn_kernel == "pallas" else 0.0)
        #: the pool and its format (kv_pool.py); committed kv-head-sharded
        #: ONCE on a mesh, like the replicated model state below: a
        #: per-call device_put would re-place them every step
        self.kv = KVPool.zeros(
            self.cache_layers, self._num_pages, ad.num_kv_heads, cfg.page_size,
            ad.head_dim, cfg.kv_dtype, mesh=self._mesh)
        # einsum + int8 materializes both dequantized [N, Hkv, P, D] f32
        # pools per layer per step; the fused path never does — account
        # the avoided traffic per decode/verify step
        self._fused_dequant_bytes_step = (
            self.kv.dequantized_bytes if self._attn_kernel == "pallas" else 0)
        try:  # price the choice in the auto-planner's cost model
            from ..distributed.auto_parallel.planner import plan_attn_kernel

            plan_attn_kernel(
                num_slots=cfg.num_slots, max_pages=self._mp,
                kv_heads=ad.num_kv_heads, query_heads=ad.num_heads,
                page_size=cfg.page_size, head_dim=ad.head_dim,
                layers=self.cache_layers, kv_dtype=cfg.kv_dtype,
                selected=self._attn_kernel)
        except Exception:  # noqa: BLE001 — pricing never gates serving
            pass
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._replicated_sharding = NamedSharding(
                self._mesh, PartitionSpec())
        self.pool = PagePool(self._num_pages)
        cap = (cfg.prefix_registry_blocks
               if cfg.prefix_registry_blocks is not None
               else self._num_pages)
        self.registry = (PrefixRegistry(self.pool, cap)
                         if cfg.prefix_cache else None)
        #: per-slot page tables, uploaded to every decode/verify step;
        #: freed slots are zeroed so their writes/gathers hit trash
        self._tables = np.zeros((cfg.num_slots, self._mp), np.int32)
        # stable state ordering for the compiled-call state swap (the
        # TracedLayer idiom): dedup'd params first, then buffers. Names
        # ride along (first name wins on dedup) so the online weight
        # plane can address leaves by name over the wire.
        self._state, self._state_names, seen = [], [], set()
        for name, p in model.named_parameters():
            if id(p) not in seen:
                seen.add(id(p))
                self._state.append(p)
                self._state_names.append(name)
        for name, b in model.named_buffers():
            if id(b) not in seen:
                seen.add(id(b))
                self._state.append(b)
                self._state_names.append(name)
        self._state_index = {n: i for i, n in enumerate(self._state_names)}
        #: versioned weight-epoch plane (serving/online.py): the live
        #: epoch, value snapshots pinned for in-flight old-epoch
        #: requests, and the double-buffered shadow set an in-progress
        #: wt stream stages into
        self._epoch = 0
        self._epoch_vals: Dict[int, List] = {}
        self._shadow: Optional[dict] = None
        if self._mesh is not None:
            for t in self._state:
                t._value = jax.device_put(t._value,
                                          self._replicated_sharding)
        donate = cfg.donate
        if donate is None:
            donate = jax.default_backend() in ("tpu", "gpu")
        self._donate = bool(donate)
        #: name -> jitted program ("decode", "verify_k4", "prefill_b512")
        self._jit: Dict[str, object] = {}
        self._compiled = set()
        #: name -> (jitted fn, abstract args) of every program that ran
        self._programs: Dict[str, tuple] = {}
        self._aot: Dict[str, object] = {}  # persistent-cache Compiled objects
        self.aot_cache_hits = 0
        self.compile_count = 0
        self.total_tokens = 0
        self.decode_steps = 0
        self.verify_steps = 0
        #: slots advanced, summed over the decode and verify passes: over
        #: ``decode_steps * num_slots`` it is how full the batch has been
        self.slot_steps = 0
        #: page slots that the decode and verify passes' attention had to
        #: read, ``(position + T - 1) // page_size + 1`` a slot (an idle
        #: slot reads one): over ``kv_pages_capacity`` it is how far the
        #: paged kernel's work is below what the tables hold
        self.kv_pages_live = 0
        #: page slots of the blocks that the paged kernel's walk takes for
        #: those passes, ``ceil(live / ppb) * ppb`` a slot at the kernel's
        #: own pages a block for the pass's shapes, never past the table:
        #: ``kv_pages_live`` over it is how full the blocks run
        self.kv_block_pages = 0
        self._pages_per_block: Dict[int, int] = {}  # rows a slot -> ppb
        self.spec_proposed = 0
        self.spec_accepted = 0
        #: a block-diffusion engine's running totals: commit passes; rows
        #: its passes computed for live slots (prefill rows included); and
        #: over the block and commit passes, the experts that the routing
        #: sent tokens to, summed over layers (counted on the device) and
        #: the experts held, passes x layers x experts a layer
        self.commit_passes = 0
        self.rows_computed = 0
        #: host-to-device transfers made by passes and prefills: ONE
        #: packed int32 array each (``_pack_fields``)
        self.host_uploads = 0
        self._layouts: Dict[str, tuple] = {}
        self.experts_touched = 0
        self.experts_capacity = 0
        self._t_decode_ema = None
        self._t_verify_ema = None
        self._tok_verify_ema = None
        self._steps_since_probe = 0
        self.prefix_hit_tokens = 0
        self.peak_pages_in_use = 0
        self.peak_running = 0
        self.admission_waits = 0
        self.admission_wait_s = 0.0
        #: untagged prompt tokens prefilled on THIS engine (the
        #: independent integer the per-tenant ledger reconciles against)
        self.prompt_tokens_total = 0
        #: per-tenant metering (observability/accounting.py), created
        #: lazily on the first submit with accounting enabled; the hot
        #: paths pay one None check when it is off
        self._acct = None
        self._pg_meter = None
        self._backoff_s = 0.0
        self._base_key = jax.random.key(cfg.seed, impl=_KEY_IMPL)
        self._zero_key = np.asarray(jax.random.key_data(self._base_key))
        self._waiting: deque = deque()
        self._running: Dict[int, Request] = {}
        self._free = list(range(cfg.num_slots))[::-1]  # pop() -> slot 0
        self._requests: Dict[int, Request] = {}
        self._next_id = 0
        #: the newest ``step()``'s report; ``_report`` is the one being
        #: written, None outside ``step()`` (prefill_export and
        #: try_import_prefill emit tokens that belong to no step)
        self.last_step = StepReport()
        self._report: Optional[StepReport] = None

    # -- scheduler ----------------------------------------------------------

    def accounting_ledger(self, create: bool = False):
        """This engine's per-tenant metering ledger (accounting.py), or
        None while accounting is disabled. ``create=True`` instantiates
        it when accounting is enabled (one env lookup — the µs-scale
        disabled-path contract)."""
        if self._acct is None and create:
            from ..observability import accounting as _acct

            if _acct.enabled():
                self._acct = _acct.TenantLedger()
                self._pg_meter = _acct.PageSecondsMeter(self._acct)
        return self._acct

    def _acct_tick(self, now: float):
        """Charge KV page occupancy since the last tick to the running
        set, shared pages split pro rata (accounting.PageSecondsMeter)."""
        self._pg_meter.tick(now, self._running.values(),
                            self.pool.refcount,
                            self._num_pages - 1 - self.pool.available())

    def _acct_wire_bytes(self, active, vocab: int, rows_per_slot: int):
        """Attribute one step's sharded-decode logit-recombination wire
        bytes per tenant. The compiled program all-gathers every slot's
        logit rows regardless of occupancy, so active requests get their
        rows and the padded remainder lands on the unattributed cell.
        Zero when the engine is not mp-sharded (single-device wire-free
        decode — the bench conservation gate covers this shape too)."""
        if self._mp_degree <= 1:
            return
        from ..observability import accounting as _acct

        itemsize = {"f32": 4, "bf16": 2, "int8": 1}[self._logit_wire]
        row_bytes = vocab * itemsize * rows_per_slot
        for _slot, req in active:
            self._acct.add(req.tenant, req.slo, wire_bytes=row_bytes)
        pad = self.config.num_slots - len(active)
        if pad > 0:
            self._acct.add(_acct.DEFAULT_TENANT, _acct.UNATTRIBUTED_SLO,
                           wire_bytes=row_bytes * pad)

    def submit(self, prompt, params: Optional[SamplingParams] = None,
               *, trace: Optional[dict] = None, tenant: Optional[str] = None,
               slo: Optional[str] = None, **kw) -> int:
        """Queue one request; returns its id. `prompt` is a 1-D int array
        (Tensor/np/list); keyword args build a SamplingParams. ``trace``
        is the router's propagated span context (protocol.py ``trace``
        field): when given, the engine's prefill/decode/verify spans join
        that request tree. ``tenant``/``slo`` label the request for the
        per-tenant cost ledger (absent -> the "-" default)."""
        if params is None:
            params = SamplingParams(**kw)
        ids = np.asarray(raw(prompt), dtype=np.int32).reshape(-1)
        t0 = int(ids.shape[0])
        if t0 < 1:
            raise ValueError("empty prompt")
        if t0 > self.buckets[-1]:
            raise ValueError(
                f"prompt length {t0} exceeds the largest prompt bucket "
                f"{self.buckets[-1]}")
        if t0 + params.max_new_tokens > self.config.max_length:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({params.max_new_tokens}) "
                f"exceeds max_length={self.config.max_length}")
        total_pages = -(-(t0 + params.max_new_tokens)
                        // self.config.page_size)
        if total_pages > self._num_pages - 1:
            raise ValueError(
                f"request needs {total_pages} KV pages but the pool only "
                f"has {self._num_pages - 1}")
        rid = self._next_id
        self._next_id += 1
        req = Request(req_id=rid, prompt=ids, params=params,
                      key_np=self._request_key(params, rid),
                      submit_time=time.perf_counter())
        if trace:
            req.trace_id = trace.get("trace_id")
            req.trace_parent = trace.get("parent_id")
            req.resubmitted = int(trace.get("resubmits", 0) or 0) > 0
        if tenant is not None or slo is not None:
            from ..observability import accounting as _acct

            req.tenant = _acct.normalize_tenant(tenant)
            if slo:
                req.slo = str(slo)
        self.accounting_ledger(create=True)
        self._requests[rid] = req
        self._waiting.append(req)
        _obs.inc("serving_requests_total")
        _obs.set_gauge("serving_queue_depth", float(len(self._waiting)))
        return rid

    def step(self) -> bool:
        """Admit waiting requests into free slots (one compiled tail
        prefill each), then advance every occupied slot: ONE compiled
        decode step, or — when speculation is on and a prompt-lookup
        draft exists — ONE compiled verify step emitting up to
        ``speculate_k + 1`` tokens per slot. Returns False when the
        engine is fully idle. Leaves ``last_step`` (what it did, always)
        and, while somebody is tracing, one ``eng_step`` span tree."""
        report = self._report = StepReport()
        try:
            if not (self._running or self._waiting):
                # an idle poll (serving/worker.py makes 200 a second) is no
                # step: it leaves no tree
                return self._step(None)
            with _obs.span("eng_step", num_slots=self.config.num_slots,
                           loops=self._loops,
                           cache_layers=self.cache_layers) as sp:
                busy = self._step(sp)
                if sp:
                    sp.attrs.update(
                        emitted={rid: len(t)
                                 for rid, t in report.tokens.items()},
                        # running totals since the engine was built
                        slot_steps=self.slot_steps,
                        slot_capacity=(self.decode_steps
                                       * self.config.num_slots),
                        kv_pages_live=self.kv_pages_live,
                        kv_block_pages=self.kv_block_pages,
                        kv_pages_capacity=self.kv_pages_capacity)
                    if self._block:
                        sp.attrs.update(
                            block_length=self._block,
                            commit_passes=self.commit_passes,
                            rows_computed=self.rows_computed,
                            experts_touched=self.experts_touched,
                            experts_capacity=self.experts_capacity)
        finally:
            self._report = None
            self.last_step = report
        return busy

    def _step(self, sp) -> bool:
        if self._block:
            # a round of one block ends when no slot has a masked position
            # left: commit, then admit into the fresh round
            self._end_block_round()
        if not self._block or self._at_round_start():
            self._admit()
        if sp:
            # the batch that the decode pass below runs with
            sp.attrs.update(
                running=len(self._running), waiting=len(self._waiting),
                context_tokens=sum(len(r.prompt) + len(r.tokens)
                                   for r in self._running.values()))
        if not self._running:
            if self._waiting:
                self._admission_backoff()
            return bool(self._waiting)
        self._backoff_s = 0.0
        epochs = sorted({r.epoch for r in self._running.values()})
        if self._block:
            for e in epochs:  # one pass a weight epoch, as decode below
                self._step_block(epoch=e)
            return True
        if len(epochs) > 1:
            # mixed-epoch flip window: one masked decode per epoch group
            # (excluded slots' table rows are zeroed, so their KV writes
            # land on the trash page and their sampled tokens are
            # ignored). Speculation is skipped for the window — verify
            # and decode sample identical position-keyed streams, so
            # forcing plain decode costs throughput, never bits.
            for e in epochs:
                self._step_decode(epoch=e)
            return True
        k = self.config.speculate_k
        if k > 0 and self._spec_worthwhile(k):
            drafts, any_real = self._collect_drafts(k)
            if any_real and self._verify_headroom(k):
                self._step_verify(drafts, k, epoch=epochs[0])
                return True
        self._step_decode(epoch=epochs[0])
        return True

    def _admission_backoff(self):
        """Every waiting request is blocked on free KV pages (or slots
        pinned by an external holder) and no slot is decoding: sleep a
        bounded exponentially-growing backoff instead of hot-spinning —
        run() would otherwise busy-loop _admit at 100% CPU until another
        actor releases pages. Reset the moment any slot runs again."""
        self._backoff_s = min(max(self._backoff_s * 2, 1e-3), 0.05)
        self.admission_waits += 1
        self.admission_wait_s += self._backoff_s
        _obs.observe("serving_admission_wait_seconds", self._backoff_s)
        time.sleep(self._backoff_s)

    def _spec_worthwhile(self, k: int) -> bool:
        """Adaptive gate: speculate when the measured step-time and
        acceptance EMAs predict verify emits more tokens/s than decode
        (always True with spec_adaptive=False). With no verify estimate
        yet — or a stale one — probe."""
        if not self.config.spec_adaptive:
            return True
        if self._t_decode_ema is None:
            return False  # measure the decode baseline first
        if self._t_verify_ema is None:
            return True
        if self._steps_since_probe >= self.config.spec_probe_every:
            return True
        if self._tok_verify_ema is None:
            return True
        # measured tokens/s comparison: one decode step yields exactly 1
        # token per slot; a verify step yields what acceptance actually
        # delivered (fallback-draft slots and budget truncation included)
        return (self._tok_verify_ema * self._t_decode_ema
                > self._t_verify_ema)

    @staticmethod
    def _ema(prev, x, alpha=0.3):
        return x if prev is None else (1 - alpha) * prev + alpha * x

    def _step_decode(self, epoch: Optional[int] = None):
        if self._acct is not None:
            self._acct_tick(time.perf_counter())
        if epoch is None:
            epoch = self._epoch
        with _obs.span("eng_decode_prep"):
            active, host = self._decode_inputs(epoch)
            packed = self._pack("decode", host)
        warm = "decode" in self._compiled
        t0 = time.perf_counter()
        with _obs.span("eng_decode_upload") as sp:
            dev = self._upload(packed, sp)
        with _obs.span("eng_decode_dispatch"):
            nxt, logits = self._run(
                "decode", self._state_vals(epoch), self.kv, dev)
        with _obs.span("eng_decode_readback"):
            # the per-token host transfer, [S] int32: waits for the step
            nxt_host = np.asarray(nxt)
        dt = time.perf_counter() - t0
        _obs.observe("serving_decode_step_seconds", dt)
        if self._fused_dequant_bytes_step:
            _obs.inc("attn_kernel_fused_dequant_bytes_total",
                     self._fused_dequant_bytes_step)
        if warm:  # a compile-laden first step would poison the estimate
            self._t_decode_ema = self._ema(self._t_decode_ema, dt)
        with _obs.span("eng_decode_append"):
            self._steps_since_probe += 1
            self.decode_steps += 1
            self.slot_steps += len(active)
            self._count_kv_pages(host[1], 1)
            self._last_logits = logits
            if self._acct is not None:
                self._acct_wire_bytes(active, int(logits.shape[-1]), 1)
            for slot, req in active:
                if req.decode_t0 is None:
                    req.decode_t0 = t0  # first batched step it joined
                req.decode_steps_n += 1
                self.total_tokens += 1
                self._append_token(req, int(nxt_host[slot]))
            _obs.inc("serving_tokens_total", len(active))
            self._update_gauges()

    # -- block diffusion: the block pass, the commit pass and emission -----

    def _at_round_start(self) -> bool:
        """No running slot has run a pass on its current block: the
        moment a block-diffusion engine admits."""
        return all(r.block.passes == 0 for r in self._running.values())

    def _end_block_round(self):
        """When no running slot has a masked position left, commit every
        slot's finished block (one commit pass a weight epoch) and start
        its next block, all masked."""
        run = list(self._running.items())
        if not run or any(r.block.masked.any() for _, r in run):
            return
        b = self._block
        for e in sorted({r.epoch for _, r in run}):
            self._step_commit([(s, r) for s, r in run if r.epoch == e], e)
        for _, req in run:
            start = req.block.start + b
            req.block = BlockState(start, np.zeros(b, np.int32),
                                   np.ones(b, bool), to_unmask=b)

    def _unmask_count(self, blk: BlockState) -> int:
        """``k_s`` of the block's next pass: its masked count at its start
        split evenly over ``denoise_steps`` passes, the remainder to the
        earliest; past them, whatever is still masked."""
        steps = int(self.adapter.denoise_steps)
        if blk.passes >= steps:
            return int(blk.masked.sum())
        base, rem = divmod(blk.to_unmask, steps)
        return base + (blk.passes < rem)

    def _block_inputs(self, active):
        """The block pass's host arrays, in the program's argument order:
        tokens, masked [S, B], positions [S] (each block's start), tables,
        keys, the sampling fields, and how many to unmask [S]. A slot that
        sits the pass out has no masked position and unmasks none."""
        s, b = self.config.num_slots, self._block
        tokens = np.zeros((s, b), np.int32)
        masked = np.zeros((s, b), bool)
        positions = np.zeros(s, np.int32)
        count = np.zeros(s, np.int32)
        temp = np.ones(s, np.float32)
        top_k = np.zeros(s, np.int32)
        top_p = np.ones(s, np.float32)
        greedy = np.ones(s, bool)
        keys = np.array(np.broadcast_to(self._zero_key,
                                        (s,) + self._zero_key.shape))
        for slot, req in active:
            blk = req.block
            tokens[slot], masked[slot] = blk.tokens, blk.masked
            positions[slot] = blk.start
            count[slot] = self._unmask_count(blk)
            t_, k_, p_, g_ = req.params.fields()
            temp[slot], top_k[slot], top_p[slot], greedy[slot] = t_, k_, p_, g_
            keys[slot] = req.key_np
        tables = self._tables
        ids = {slot for slot, _ in active}
        if len(ids) < len(self._running):
            tables = self._tables.copy()
            tables[[sl for sl in self._running if sl not in ids]] = 0
        return (tokens, masked, positions, tables, keys, temp, top_k, top_p,
                greedy, count)

    def _step_block(self, epoch: int):
        """ONE block pass over every running slot of ``epoch``: each masked
        position gets a token drawn, the model's rule unmasks some, and the
        tokens that are now final and follow the request's last emitted one
        are emitted."""
        if self._acct is not None:
            self._acct_tick(time.perf_counter())
        active = [(slot, req) for slot, req in self._running.items()
                  if req.epoch == epoch]
        b, name = self._block, f"block_b{self._block}"
        with _obs.span("eng_block_pass", live=len(active),
                       rows=len(active) * b) as sp:
            with _obs.span("eng_block_prep"):
                host = self._block_inputs(active)
                packed = self._pack(name, host)
            t0 = time.perf_counter()
            with _obs.span("eng_block_upload") as up:
                dev = self._upload(packed, up)
            with _obs.span("eng_block_dispatch"):
                out, logits = self._run(
                    name, self._state_vals(epoch), self.kv, dev)
            with _obs.span("eng_block_readback"):
                new_tokens, new_masked, touched = (np.asarray(a) for a in out)
            _obs.observe("serving_decode_step_seconds",
                         time.perf_counter() - t0)
            with _obs.span("eng_block_append"):
                self._last_logits = logits
                self.decode_steps += 1
                self.slot_steps += len(active)
                self.rows_computed += len(active) * b
                self.experts_touched += int(touched)
                self.experts_capacity += self.adapter.num_layers * int(
                    self.adapter.num_experts)
                live = self.kv_pages_live
                self._count_kv_pages(host[2], b)
                final = 0
                for slot, req in active:
                    blk = req.block
                    now = blk.masked & ~new_masked[slot]
                    for i in np.flatnonzero(now):
                        req.unmask_pass[blk.start + int(i)] = blk.passes
                    blk.tokens = np.where(now, new_tokens[slot], blk.tokens)
                    blk.masked = new_masked[slot].copy()
                    blk.passes += 1
                    if req.decode_t0 is None:
                        req.decode_t0 = t0
                    req.decode_steps_n += 1
                    final += self._emit_final(req)
                _obs.inc("serving_tokens_total", final)
                if sp:
                    sp.attrs.update(final=final, experts_touched=int(touched),
                                    kv_pages=self.kv_pages_live - live)
                self._update_gauges()

    def _emit_final(self, req: Request) -> int:
        """Emit, in order, the tokens of the request's block that are final
        and follow its last emitted one; returns how many."""
        blk, n = req.block, 0
        while req.status == "running":
            i = len(req.prompt) + len(req.tokens) - blk.start
            if i >= len(blk.masked) or blk.masked[i]:
                break
            if req.first_token_time is None:
                req.first_token_time = time.perf_counter()
                _obs.observe("serving_ttft_seconds",
                             req.first_token_time - req.submit_time)
            self.total_tokens += 1
            n += 1
            self._append_token(req, int(blk.tokens[i]))
        return n

    def _step_commit(self, group, epoch: int):
        """ONE commit pass: the final tokens of the finished blocks of
        ``group`` [(slot, request)] through every layer, writing their keys
        and values over what the block passes left there. Other slots ride
        along with zeroed table rows (their writes land on the trash page)
        at position 0."""
        s, b = self.config.num_slots, self._block
        name = f"commit_b{b}"
        with _obs.span("eng_block_commit", slots=len(group),
                       rows=len(group) * b) as sp:
            with _obs.span("eng_commit_prep"):
                tokens = np.zeros((s, b), np.int32)
                positions = np.zeros(s, np.int32)
                tables = np.zeros_like(self._tables)
                for slot, req in group:
                    tokens[slot] = req.block.tokens
                    positions[slot] = req.block.start
                    tables[slot] = self._tables[slot]
                packed = self._pack(name, (tokens, positions, tables))
            with _obs.span("eng_commit_upload") as up:
                dev = self._upload(packed, up)
            with _obs.span("eng_commit_dispatch"):
                touched, _ = self._run(
                    name, self._state_vals(epoch), self.kv, dev)
            with _obs.span("eng_commit_readback"):
                touched = int(np.asarray(touched))  # waits for the pass
            self.commit_passes += 1
            self.rows_computed += len(group) * b
            self.experts_touched += touched
            # the last layer's experts do not run: nothing reads its output
            self.experts_capacity += (self.adapter.num_layers - 1) * int(
                self.adapter.num_experts)
            if sp:
                sp.attrs.update(experts_touched=touched,
                                kv_pages=int(self._live_pages(
                                    positions, b).sum()))

    def _live_pages(self, positions, t: int):
        """The page slots a pass of ``t`` rows a slot reads, a slot, from
        the positions it was called with (0 for a slot that sat it out)."""
        return np.minimum(
            (positions + (t - 1)) // self.config.page_size + 1, self._mp)

    def _count_kv_pages(self, positions, t: int):
        """One pass's live page slots (``_live_pages``), counted."""
        live = self._live_pages(positions, t)
        self.kv_pages_live += int(live.sum())
        ppb = self._pages_per_block.get(t)
        if ppb is None:
            from ..ops.pallas.paged_attention import block_shape

            ad = self.adapter
            ppb = self._pages_per_block[t] = block_shape(
                t, ad.num_heads, ad.num_kv_heads, ad.head_dim,
                self.config.page_size, self.kv.k.dtype.itemsize,
                self.kv.k_scales is not None)[1]
        self.kv_block_pages += int(np.minimum(
            -(-live // ppb) * ppb, self._mp).sum())

    @property
    def kv_pages_capacity(self) -> int:
        """Page slots that the tables of all decode and verify passes
        held: passes x ``num_slots`` x the table's width."""
        return self.decode_steps * self.config.num_slots * self._mp

    def _decode_inputs(self, epoch: int):
        """The decode program's host arrays for one epoch group:
        (active [(slot, request)], (tokens, positions, tables, keys, temp,
        top_k, top_p, greedy) in the program's argument order)."""
        active = [(slot, req) for slot, req in self._running.items()
                  if req.epoch == epoch]
        s = self.config.num_slots
        tokens = np.zeros(s, np.int32)
        positions = np.zeros(s, np.int32)
        temp = np.ones(s, np.float32)
        top_k = np.zeros(s, np.int32)
        top_p = np.ones(s, np.float32)
        greedy = np.ones(s, bool)
        keys = np.broadcast_to(self._zero_key, (s,) + self._zero_key.shape)
        keys = np.array(keys)
        for slot, req in active:
            tokens[slot] = req.tokens[-1]
            positions[slot] = len(req.prompt) + len(req.tokens) - 1
            t_, k_, p_, g_ = req.params.fields()
            temp[slot], top_k[slot], top_p[slot], greedy[slot] = t_, k_, p_, g_
            keys[slot] = req.key_np
        tables = self._tables
        excluded = [slot for slot, req in self._running.items()
                    if req.epoch != epoch]
        if excluded:
            # other epoch groups ride along this call as masked slots:
            # zeroed table rows route their KV writes to the trash page,
            # exactly the warmup mechanism — their real pages are
            # untouched and their tokens below are never applied
            tables = self._tables.copy()
            tables[excluded] = 0
        return active, (tokens, positions, tables, keys, temp, top_k, top_p,
                        greedy)

    def _step_verify(self, drafts: Dict[int, np.ndarray], k: int,
                     epoch: Optional[int] = None):
        """One multi-token speculative step: score cur + k drafts in a
        single target pass; accept target tokens while the draft agrees
        (position-keyed streams, so acceptance never changes WHAT is
        sampled — only how many tokens one step emits). Only runs when
        every running slot shares ``epoch`` (step() forces plain decode
        during mixed-epoch flip windows)."""
        cfg = self.config
        if epoch is None:
            epoch = self._epoch
        if self._acct is not None:
            self._acct_tick(time.perf_counter())
        s, k1, name = cfg.num_slots, k + 1, f"verify_k{k}"
        with _obs.span("eng_verify_prep"):
            tokens = np.zeros((s, k1), np.int32)
            positions = np.zeros(s, np.int32)
            temp = np.ones(s, np.float32)
            top_k = np.zeros(s, np.int32)
            top_p = np.ones(s, np.float32)
            greedy = np.ones(s, bool)
            keys = np.array(np.broadcast_to(
                self._zero_key, (s,) + self._zero_key.shape))
            for slot, req in self._running.items():
                tokens[slot, 0] = req.tokens[-1]
                tokens[slot, 1:] = drafts[slot]
                positions[slot] = len(req.prompt) + len(req.tokens) - 1
                t_, k_, p_, g_ = req.params.fields()
                temp[slot], top_k[slot], top_p[slot], greedy[slot] = (
                    t_, k_, p_, g_)
                keys[slot] = req.key_np
            packed = self._pack(name, (
                tokens, positions, self._tables, keys, temp, top_k, top_p,
                greedy))
        warm = name in self._compiled
        t0 = time.perf_counter()
        with _obs.span("eng_verify_upload") as sp:
            dev = self._upload(packed, sp)
        with _obs.span("eng_verify_dispatch"):
            targets, logits = self._run(
                name, self._state_vals(epoch), self.kv, dev)
        with _obs.span("eng_verify_readback"):
            targets_host = np.asarray(targets)  # [S, k+1] int32
        dt = time.perf_counter() - t0
        _obs.observe("serving_decode_step_seconds", dt)
        if self._fused_dequant_bytes_step:
            _obs.inc("attn_kernel_fused_dequant_bytes_total",
                     self._fused_dequant_bytes_step)
        if warm:
            self._t_verify_ema = self._ema(self._t_verify_ema, dt)
        with _obs.span("eng_verify_append"):
            self._steps_since_probe = 0
            self.decode_steps += 1
            self.verify_steps += 1
            self._last_logits = logits
            emitted = 0
            active_slots = len(self._running)
            self.slot_steps += active_slots
            self._count_kv_pages(positions, k1)
            if self._acct is not None:
                self._acct_wire_bytes(list(self._running.items()),
                                      int(logits.shape[-1]), k1)
            for slot, req in list(self._running.items()):
                tgt = targets_host[slot]
                m = 0
                while m < k and int(drafts[slot][m]) == int(tgt[m]):
                    m += 1
                self.spec_proposed += k
                self.spec_accepted += m
                if req.decode_t0 is None:
                    req.decode_t0 = t0
                req.decode_steps_n += 1
                req.verify_steps_n += 1
                req.spec_accepted_n += m
                for tok in tgt[:m + 1]:
                    if req.status != "running":
                        break  # budget/eos hit mid-emission
                    self.total_tokens += 1
                    emitted += 1
                    self._append_token(req, int(tok))
            if active_slots:
                self._tok_verify_ema = self._ema(
                    self._tok_verify_ema, emitted / active_slots)
            _obs.inc("serving_tokens_total", emitted)
            _obs.set_gauge("serving_spec_accept_ratio",
                           self.spec_accepted / max(self.spec_proposed, 1))
            self._update_gauges()

    def _collect_drafts(self, k: int):
        """Prompt-lookup drafts per running slot; slots with no n-gram
        recurrence fall back to repeating their last token (still a
        legitimate draft — acceptance decides)."""
        from ..text.generation import prompt_lookup_draft

        drafts: Dict[int, np.ndarray] = {}
        any_real = False
        for slot, req in self._running.items():
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
            d = prompt_lookup_draft(ctx, k, max_ngram=self.config.ngram)
            if d is not None:
                any_real = True
            else:
                d = np.full(k, req.tokens[-1], np.int32)
            drafts[slot] = d
        return drafts, any_real

    def _verify_headroom(self, k: int) -> bool:
        """The verify step writes KV at positions p .. p+k; require them
        all inside the cache for every running slot (else this round
        falls back to the single-token decode program)."""
        limit = self.config.max_length - 1
        return all(
            len(r.prompt) + len(r.tokens) - 1 + k <= limit
            for r in self._running.values())

    def run(self) -> Dict[int, np.ndarray]:
        """Drive step() until every submitted request finished; returns
        {req_id: prompt + generated} for requests completed in this
        drain."""
        t0 = time.perf_counter()
        before = self.total_tokens
        finished = [r.req_id for r in self._requests.values()
                    if r.status == "done"]
        seen_done = set(finished)
        while self._waiting or self._running:
            self.step()
        emitted = self.total_tokens - before
        dt = max(time.perf_counter() - t0, 1e-9)
        if emitted:
            _obs.set_gauge("serving_tokens_per_second", emitted / dt)
        return {rid: self.result(rid) for rid, r in self._requests.items()
                if r.status == "done" and rid not in seen_done}

    def result(self, rid: int) -> np.ndarray:
        req = self._requests[rid]
        if req.status != "done":
            raise RuntimeError(f"request {rid} is {req.status}, not done")
        return np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])

    def generate_batch(self, input_ids, max_new_tokens: int = 32,
                       do_sample: bool = False, top_k: int = 0,
                       top_p: float = 1.0, temperature: float = 1.0,
                       eos_token_id=None, pad_token_id=None, seed=None):
        """Batch front end with text.generation.generate semantics: every
        row becomes a request, rows that finish early are padded with
        pad_token_id (else eos, else 0). Returns a Tensor [B, T0 + n]."""
        ids = np.asarray(raw(input_ids))
        b, t0 = ids.shape
        rids = [
            self.submit(ids[i], SamplingParams(
                max_new_tokens=max_new_tokens, do_sample=do_sample,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id,
                seed=None if seed is None else seed * 1000003 + i))
            for i in range(b)
        ]
        self.run()
        reqs = [self._requests[r] for r in rids]
        width = max(len(r.tokens) for r in reqs)
        filler = pad_token_id if pad_token_id is not None else (
            eos_token_id if eos_token_id is not None else 0)
        out = np.full((b, t0 + width), filler, dtype=ids.dtype)
        out[:, :t0] = ids
        for i, r in enumerate(reqs):
            out[i, t0:t0 + len(r.tokens)] = r.tokens
        return Tensor(jnp.asarray(out))

    def release_prefix_cache(self):
        """Drop every registry reference (running requests keep theirs);
        afterwards a drained engine holds zero pages."""
        if self.registry is not None:
            self.registry.clear()
        self._update_gauges()

    def warmup(self) -> dict:
        """Pre-build every compiled program before traffic arrives: one
        prefill per prompt bucket, the single-token decode, and (when
        ``speculate_k > 0``) the verify program. Synthetic inputs use
        all-zero page tables, so every KV write lands on the inert trash
        page 0 — pool, scheduler, and prefix registry are untouched. With
        ``PADDLE_TPU_COMPILE_CACHE`` set, each build is served from the
        persistent AOT cache when fingerprints match; ``cache_hits`` in
        the returned dict counts those.

        Idempotent: programs already compiled by THIS engine (a prior
        warmup, or live traffic) are skipped and counted as cache hits
        instead of re-executed — so a warmup after a weight flip is a
        cheap no-op rather than a second full sweep."""
        hits0, n0 = self.aot_cache_hits, self.compile_count
        k = self.config.speculate_k
        passes = ([f"block_b{self._block}", f"commit_b{self._block}"]
                  if self._block else ["decode"])
        for name in ([f"prefill_b{tb}" for tb in self.buckets] + passes
                     + ([f"verify_k{k}"] if k > 0 else [])):
            if name in self._compiled:
                self.aot_cache_hits += 1
            else:
                self._run(name, *self._example_args(name))
        return {"buckets": len(self.buckets), "decode": True,
                "verify": k > 0,
                "programs": self.compile_count - n0,
                "cache_hits": self.aot_cache_hits - hits0}

    def _example_args(self, name: str) -> tuple:
        """The arguments of program ``name`` ("decode", "verify_k4",
        "prefill_b512"): the live state and pool, then its synthetic
        inputs packed (``_example_inputs``). What ``warmup`` runs, and
        what a compile for a described chip takes its shapes from."""
        return (self._state_vals(), self.kv,
                self._pack(name, self._example_inputs(name)))

    def _example_inputs(self, name: str) -> tuple:
        """Program ``name``'s synthetic inputs, field by field, with
        all-zero page tables, so every KV write lands on the inert trash
        page."""
        s, key = self.config.num_slots, self._zero_key
        one, zero, yes = np.float32(1.0), np.int32(0), np.asarray(True)
        kind, n = _program_kind(name)
        if kind == "prefill":
            inputs = (np.full((1, n), 1, np.int32), zero, np.int32(n),
                      np.zeros(self._mp, np.int32), key, one, zero, one, yes)
        elif kind == "block":
            inputs = list(self._block_inputs(()))
            inputs[3] = np.zeros_like(self._tables)
        elif kind == "commit":
            inputs = (np.zeros((s, n), np.int32), np.zeros(s, np.int32),
                      np.zeros_like(self._tables))
        else:
            t = () if kind == "decode" else (n + 1,)
            inputs = (np.zeros((s,) + t, np.int32), np.zeros(s, np.int32),
                      np.zeros_like(self._tables),
                      np.array(np.broadcast_to(key, (s,) + key.shape)),
                      np.full(s, one), np.full(s, zero), np.full(s, one),
                      np.full(s, yes))
        return tuple(inputs)

    def stats(self) -> dict:
        return {
            "compile_count": self.compile_count,
            "compile_cache_hits": self.aot_cache_hits,
            "compiled": sorted(self._compiled),
            "buckets": list(self.buckets),
            "decode_steps": self.decode_steps,
            "verify_steps": self.verify_steps,
            "slot_steps": self.slot_steps,
            "kv_pages_live": self.kv_pages_live,
            "kv_block_pages": self.kv_block_pages,
            "kv_pages_capacity": self.kv_pages_capacity,
            "total_tokens": self.total_tokens,
            "prompt_tokens_total": self.prompt_tokens_total,
            "running": len(self._running),
            "waiting": len(self._waiting),
            "page_size": self.config.page_size,
            "num_pages": self._num_pages,
            "pages_free": self.pool.available(),
            "pages_shared": self.pool.shared_pages(),
            "peak_pages_in_use": self.peak_pages_in_use,
            "peak_running": self.peak_running,
            "prefix_blocks_registered": (
                len(self.registry) if self.registry is not None else 0),
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "admission_waits": self.admission_waits,
            "admission_wait_s": self.admission_wait_s,
            "attn_kernel": self._attn_kernel,
            "loops": self._loops,
            "cache_layers": self.cache_layers,
            "block_length": self._block,
            "commit_passes": self.commit_passes,
            "host_uploads": self.host_uploads,
            "rows_computed": self.rows_computed,
            "experts_touched": self.experts_touched,
            "experts_capacity": self.experts_capacity,
            "kv_bytes_per_token": self.kv.bytes_per_token,
            "weight_epoch": int(self._epoch),
            "pinned_epochs": sorted(self._epoch_vals),
        }

    def occupancy(self) -> dict:
        """Scheduler-load snapshot for the serving router: the numbers
        serving/worker.py publishes to the coordination store each poll
        (least-outstanding-tokens dispatch reads outstanding_tokens;
        slots_free/pages_free gate admission-side throttling)."""
        outstanding = sum(r.params.max_new_tokens - len(r.tokens)
                          for r in self._running.values())
        outstanding += sum(len(r.prompt) + r.params.max_new_tokens
                           for r in self._waiting)
        return {
            "outstanding_tokens": int(outstanding),
            "running": len(self._running),
            "waiting": len(self._waiting),
            "slots_free": len(self._free),
            "pages_free": self.pool.available(),
            "prefix_hit_tokens": int(self.prefix_hit_tokens),
            "decode_steps": int(self.decode_steps),
            "total_tokens": int(self.total_tokens),
            "compile_cache_hits": int(self.aot_cache_hits),
            "weight_epoch": int(self._epoch),
        }

    # -- disaggregated prefill: KV-page export / import ---------------------

    def prefill_export(self, prompt, params: Optional[SamplingParams] = None,
                       *, trace: Optional[dict] = None,
                       tenant: Optional[str] = None,
                       slo: Optional[str] = None, **kw):
        """Run one prompt's prefill HERE and hand its KV pages to a decode
        engine (disaggregated serving; serving/worker.py streams the
        result over transport.encode_kv).

        Only the ``ceil(t0 / page_size)`` content pages are exported — the
        decode side allocates its own generation pages — and the slabs are
        bit-equal to what a local prefill leaves in this pool (padding
        rows past ``true_len`` included), so a raw-wire import decodes
        bit-equal to a unified engine. Sampled streams additionally need
        an explicit ``params.seed`` (the router always sets one); without
        it the two engines derive different request keys and only greedy
        output matches.

        Returns ``None`` when no slot (or pages) are free right now — the
        caller retries next poll; ``{"done": prompt+tokens}`` when the
        request finished at prefill (1-token budget / instant EOS); else
        ``{"first_token", "true_len", "prefill_s", "pool_dtype", "k", "v"
        [, "ks", "vs"]}`` with k/v ``[L, n_pages, Hkv, P, D]`` host arrays
        (plus the int8 scale slabs when this pool is int8). Raises
        ValueError on the same bad-request conditions as ``submit``.
        """
        if self._block:
            raise NotImplementedError(
                "a block-diffusion engine hands no prefill over: its first "
                "token comes from a block pass, not from the prefill")
        if params is None:
            params = SamplingParams(**kw)
        ids = np.asarray(raw(prompt), dtype=np.int32).reshape(-1)
        t0 = int(ids.shape[0])
        if t0 < 1:
            raise ValueError("empty prompt")
        if t0 > self.buckets[-1]:
            raise ValueError(
                f"prompt length {t0} exceeds the largest prompt bucket "
                f"{self.buckets[-1]}")
        if t0 + params.max_new_tokens > self.config.max_length:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({params.max_new_tokens}) "
                f"exceeds max_length={self.config.max_length}")
        p = self.config.page_size
        content_pages = -(-t0 // p)
        if content_pages > self._num_pages - 1:
            raise ValueError(
                f"prompt needs {content_pages} KV pages but the pool only "
                f"has {self._num_pages - 1}")
        if not self._free:
            return None
        slot = self._free[-1]
        keys: List[bytes] = []
        shared: List[int] = []
        if self.registry is not None:
            keys = PrefixRegistry.block_keys(ids, p)
            shareable = min(len(keys), (t0 - 1) // p)
            shared = self.registry.lookup_chain(keys[:shareable])
        need = content_pages - len(shared)
        if self.pool.available() < need and self.registry is not None:
            self.registry.evict_unused(need - self.pool.available())
        pages = self.pool.alloc(need)
        if pages is None:
            for pg in shared:
                self.pool.decref(pg)
            return None
        rid = self._next_id
        self._next_id += 1
        cached_len = len(shared) * p
        row = np.zeros(self._mp, np.int32)
        row[:len(shared)] = shared
        row[len(shared):content_pages] = pages
        self._tables[slot] = row
        req = Request(req_id=rid, prompt=ids, params=params,
                      key_np=self._request_key(params, rid),
                      submit_time=time.perf_counter())
        if trace:
            req.trace_id = trace.get("trace_id")
            req.trace_parent = trace.get("parent_id")
            req.resubmitted = int(trace.get("resubmits", 0) or 0) > 0
        if tenant is not None or slo is not None:
            from ..observability import accounting as _acct

            req.tenant = _acct.normalize_tenant(tenant)
            if slo:
                req.slo = str(slo)
        self.accounting_ledger(create=True)
        req.page_ids = shared + pages
        req.cached_len = cached_len
        self.prefix_hit_tokens += cached_len
        if cached_len:
            _obs.inc("serving_prefix_hit_tokens", cached_len)
        if self.registry is not None:
            for j in range(len(shared), t0 // p):
                self.registry.register(keys[j], int(row[j]))
        self._requests[rid] = req
        self._prefill(req, slot, row, cached_len)
        self._free.pop()  # _finish may have re-appended it; net correct
        if req.status == "done":
            return {"done": self.result(rid)}
        out = {
            "first_token": int(req.tokens[0]),
            "true_len": t0,
            "prefill_s": float(req.prefill_s),
            **self.kv.export_pages(row[:content_pages]),
        }
        if req.tenant != "-":
            # label the handoff so the decode engine's ledger keys match
            # (absent tenant adds zero wire bytes, like the trace dict)
            out["tenant"] = req.tenant
            out["slo"] = req.slo
        if self._acct is not None:
            # the prefill engine's half of the request: prompt + first
            # token here, KV-stream wire bytes to the decode engine; the
            # occupancy tail is charged while the pages are still held
            self._acct_tick(time.perf_counter())
            self._acct.add(
                req.tenant, req.slo, prefill_tokens=int(t0),
                decode_tokens=1,
                queue_seconds=max(req.prefill_t0 - req.submit_time, 0.0),
                wire_bytes=sum(int(out[kk].nbytes)
                               for kk in ("k", "v", "ks", "vs")
                               if kk in out))
        # detach: the decode engine owns the request from its first token
        # on. The registry's +1 refs keep this prompt's full blocks
        # resident for future prefix hits; the request's own refs drop.
        del self._running[slot]
        self._tables[slot] = 0
        self._free.append(slot)
        req.slot = -1
        for page in req.page_ids:
            self.pool.decref(page)
        req.page_ids = []
        req.status = "done"
        self._update_gauges()
        return out

    def try_import_prefill(self, prompt, params: SamplingParams, kv: dict,
                           *, trace: Optional[dict] = None,
                           tenant: Optional[str] = None,
                           slo: Optional[str] = None) -> Optional[int]:
        """Adopt a prefill computed on ANOTHER engine: write its exported
        content pages into this pool and seat the request directly in
        decode (no local prefill program runs). `kv` is a
        ``prefill_export`` payload (after any wire codec round trip).

        With a raw wire and matching ``kv_dtype`` the imported pages are
        bit-identical to a local prefill, so greedy decode matches a
        unified engine exactly; an int8 wire over a float pool dequantizes
        on import (trajectory-tolerance territory). Returns the new
        request id, or ``None`` when no slot or pages are free right now
        (the caller retries next poll). Raises ValueError on bad requests
        or a prompt/payload length mismatch.
        """
        if self._block:
            raise NotImplementedError(
                "a block-diffusion engine takes no prefill from another")
        ids = np.asarray(raw(prompt), dtype=np.int32).reshape(-1)
        t0 = int(ids.shape[0])
        if t0 < 1:
            raise ValueError("empty prompt")
        if int(kv["true_len"]) != t0:
            raise ValueError(
                f"KV payload prefilled {int(kv['true_len'])} tokens but the "
                f"prompt has {t0}")
        if t0 + params.max_new_tokens > self.config.max_length:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({params.max_new_tokens}) "
                f"exceeds max_length={self.config.max_length}")
        p = self.config.page_size
        total_pages = -(-(t0 + params.max_new_tokens) // p)
        if total_pages > self._num_pages - 1:
            raise ValueError(
                f"request needs {total_pages} KV pages but the pool only "
                f"has {self._num_pages - 1}")
        if not self._free:
            return None
        if self.pool.available() < total_pages and self.registry is not None:
            self.registry.evict_unused(total_pages - self.pool.available())
        pages = self.pool.alloc(total_pages)
        if pages is None:
            return None
        slot = self._free.pop()
        content_pages = -(-t0 // p)
        row = np.zeros(self._mp, np.int32)
        row[:total_pages] = pages
        self._tables[slot] = row
        self.kv = self.kv.import_pages(pages[:content_pages], kv)
        rid = self._next_id
        self._next_id += 1
        now = time.perf_counter()
        req = Request(req_id=rid, prompt=ids, params=params,
                      key_np=self._request_key(params, rid), submit_time=now,
                      status="running", slot=slot, epoch=self._epoch)
        req.page_ids = list(pages)
        req.prefill_t0 = now
        req.prefill_s = float(kv.get("prefill_s", 0.0))
        req.first_token_time = now
        if trace:
            req.trace_id = trace.get("trace_id")
            req.trace_parent = trace.get("parent_id")
            req.resubmitted = int(trace.get("resubmits", 0) or 0) > 0
        req.imported = True
        tenant = tenant if tenant is not None else kv.get("tenant")
        slo = slo if slo is not None else kv.get("slo")
        if tenant is not None or slo is not None:
            from ..observability import accounting as _acct

            req.tenant = _acct.normalize_tenant(tenant)
            if slo:
                req.slo = str(slo)
        self.accounting_ledger(create=True)
        if self.registry is not None:
            keys = PrefixRegistry.block_keys(ids, p)
            for j in range(t0 // p):
                self.registry.register(keys[j], int(row[j]))
        self._requests[rid] = req
        self._running[slot] = req
        _obs.inc("serving_requests_total")
        # the first token was sampled (and counted) on the prefill engine;
        # _append_token handles the instant-EOS / 1-token budget edge
        self._append_token(req, int(kv["first_token"]))
        self._update_gauges()
        return rid

    # -- internals ----------------------------------------------------------

    def _request_key(self, params: SamplingParams, rid: int) -> np.ndarray:
        """Host-side key data of one request's sample stream: its own seed
        when it carries one, else the engine's base key folded with the
        request id."""
        key = (jax.random.key(params.seed, impl=_KEY_IMPL)
               if params.seed is not None
               else jax.random.fold_in(self._base_key, rid))
        return np.asarray(jax.random.key_data(key))

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(f"no prompt bucket holds length {n}")

    def _state_vals(self, epoch: Optional[int] = None):
        """Weight/buffer value list for one compiled call. ``epoch``
        selects a pinned old-epoch snapshot during a mixed-epoch flip
        window; None (or the live epoch) reads the live tensors. The
        value list is jit argument #0 and excluded from the AOT cache
        key, which is exactly why an epoch flip never recompiles."""
        if epoch is None or epoch == self._epoch:
            return [t._value for t in self._state]
        return list(self._epoch_vals[epoch])

    # -- versioned weight epochs (serving/online.py) ------------------------

    @property
    def weight_epoch(self) -> int:
        """The epoch new admissions are pinned to."""
        return self._epoch

    def state_keys(self) -> List[str]:
        """Leaf names in compiled-call state order (dedup'd params then
        buffers; first name wins) — the wt-stream address space."""
        return list(self._state_names)

    def begin_weight_epoch(self, epoch: int) -> bool:
        """Open the shadow param set for ``epoch``: a copy-on-stage view
        of the live values that ``stage_weight`` overwrites leaf by leaf
        while decoding continues on the live set. False (no-op) when
        ``epoch`` is not newer than the live one — a replayed wt stream
        after crash recovery must not reopen a committed epoch."""
        epoch = int(epoch)
        if epoch <= self._epoch:
            return False
        self._shadow = {"epoch": epoch,
                        "vals": [t._value for t in self._state],
                        "staged": set()}
        return True

    def stage_weight(self, name: str, value) -> None:
        """Stage one leaf's new-epoch value into the shadow set (host or
        device array; cast to the live leaf's dtype, replicated onto the
        serving mesh). The live set — and every in-flight request — is
        untouched until ``promote_epoch``."""
        if self._shadow is None:
            raise RuntimeError("stage_weight with no open shadow epoch "
                               "(begin_weight_epoch first)")
        i = self._state_index[name]
        cur = self._state[i]._value
        val = jnp.asarray(value, jnp.asarray(cur).dtype)
        if tuple(val.shape) != tuple(cur.shape):
            raise ValueError(
                f"staged weight {name!r} shape {tuple(val.shape)} != "
                f"live {tuple(cur.shape)}")
        if self._mesh is not None:
            val = jax.device_put(val, self._replicated_sharding)
        self._shadow["vals"][i] = val
        self._shadow["staged"].add(name)

    def discard_shadow(self, epoch: Optional[int] = None) -> bool:
        """Drop an un-promoted shadow set (weight-transaction rollback).
        ``epoch`` narrows the discard to that epoch's shadow; None drops
        whatever is open. Idempotent."""
        if self._shadow is None:
            return False
        if epoch is not None and self._shadow["epoch"] != int(epoch):
            return False
        self._shadow = None
        return True

    def promote_epoch(self, epoch: int) -> bool:
        """Flip the live weights to the staged shadow set by pointer
        swap — the request-boundary epoch flip. No compiled program is
        touched (the AOT cache key carries only shapes/mesh), no slot is
        drained: in-flight requests keep decoding against their pinned
        epoch (the pre-swap values are snapshotted for them), new
        admissions read the promoted set. Exactly-once by construction:
        an ``epoch`` at/below the live one, or with no matching staged
        shadow, is a False no-op — crash recovery re-sends swap orders
        freely. This is the ONLY method that rebinds ``_state`` values
        (check_robustness.py rule 9 pins its callers to the journaled
        weight transaction)."""
        epoch = int(epoch)
        if epoch <= self._epoch:
            return False
        if self._shadow is None or self._shadow["epoch"] != epoch:
            return False
        if any(r.epoch == self._epoch for r in self._running.values()):
            # pin the outgoing epoch's values for its in-flight slots
            self._epoch_vals[self._epoch] = [t._value for t in self._state]
        for t, v in zip(self._state, self._shadow["vals"]):
            t._value = v
        self._epoch = epoch
        self._shadow = None
        # drop pins whose last request already finished
        live = {r.epoch for r in self._running.values()}
        for e in [e for e in self._epoch_vals if e not in live]:
            del self._epoch_vals[e]
        return True

    def _admit(self):
        while self._free and self._waiting:
            req = self._waiting[0]
            with _obs.span("eng_admit", rid=req.req_id,
                           prompt_len=int(req.prompt.shape[0])) as sp:
                admitted = self._try_prefill(req, self._free[-1])
                if sp:
                    sp.attrs["admitted"] = admitted
                    if admitted:
                        sp.attrs.update(
                            cached_len=req.cached_len,
                            bucket=self._bucket_for(
                                len(req.prompt) - req.cached_len),
                            queue_s=req.prefill_t0 - req.submit_time)
                    if req.trace_id is not None:
                        sp.attrs["request_trace_id"] = req.trace_id
            if not admitted:
                break  # head request can't get pages yet; keep FIFO order
            self._waiting.popleft()
            self._free.pop()
        _obs.set_gauge("serving_queue_depth", float(len(self._waiting)))
        self._update_gauges()

    def _try_prefill(self, req: Request, slot: int) -> bool:
        """Reserve pages (sharing registry hits), run the tail prefill,
        register the request's own full prompt blocks. False = not enough
        free pages even after evicting unused registry entries."""
        cfg = self.config
        p = cfg.page_size
        t0 = int(req.prompt.shape[0])
        total_pages = -(-(t0 + req.params.max_new_tokens) // p)
        keys: List[bytes] = []
        shared: List[int] = []
        if self.registry is not None:
            keys = PrefixRegistry.block_keys(req.prompt, p)
            # never share ALL of the prompt: the prefill needs >= 1 tail
            # token to produce the first logits (the last block is
            # recomputed instead — copy-on-write by recompute)
            shareable = min(len(keys), (t0 - 1) // p)
            shared = self.registry.lookup_chain(keys[:shareable])
        need = total_pages - len(shared)
        if self.pool.available() < need and self.registry is not None:
            self.registry.evict_unused(need - self.pool.available())
        pages = self.pool.alloc(need)
        if pages is None:
            for pg in shared:  # retry next round with a fresh lookup
                self.pool.decref(pg)
            return False
        cached_len = len(shared) * p
        row = np.zeros(self._mp, np.int32)
        row[:len(shared)] = shared
        row[len(shared):total_pages] = pages
        self._tables[slot] = row
        req.page_ids = shared + pages
        req.cached_len = cached_len
        self.prefix_hit_tokens += cached_len
        if cached_len:
            _obs.inc("serving_prefix_hit_tokens", cached_len)
        # register BEFORE the prefill runs: the prefill can finish the
        # request outright (1-token budget / instant EOS), and _finish
        # drops the request's page refs — the registry's +1 must already
        # be in place so the blocks survive. No reader can race ahead of
        # the KV write: the next admission only happens after this
        # prefill has executed.
        if self.registry is not None:
            for j in range(len(shared), t0 // p):
                self.registry.register(keys[j], int(row[j]))
        self._prefill(req, slot, row, cached_len)
        return True

    def _prefill(self, req: Request, slot: int, row: np.ndarray,
                 cached_len: int):
        if self._block:
            return self._prefill_blocks(req, slot, row, cached_len)
        t0 = int(req.prompt.shape[0])
        rid = req.req_id
        with _obs.span("eng_prefill_prep", rid=rid) as sp:
            tail = req.prompt[cached_len:]
            tb = self._bucket_for(len(tail))
            ids = np.zeros((1, tb), np.int32)
            ids[0, :len(tail)] = tail
            tp0 = time.perf_counter()  # the upload counts as prefill
            name = f"prefill_b{tb}"
            dev = self._upload(self._pack(name, (
                ids, cached_len, t0, row, req.key_np,
                *req.params.fields())), sp)
        with _obs.span("eng_prefill_dispatch", rid=rid, bucket=int(tb)):
            nxt, _ = self._run(name, self._state_vals(), self.kv, dev)
        with _obs.span("eng_prefill_readback", rid=rid):
            token = int(nxt)  # waits for the prefill
        now = time.perf_counter()
        req.first_token_time = now
        req.prefill_t0 = tp0
        req.prefill_s = now - tp0
        _obs.observe("serving_ttft_seconds", now - req.submit_time)
        if req.trace_id is not None:
            _obs.record_span(
                "srv_prefill", trace_id=req.trace_id,
                parent_id=req.trace_parent, dur_s=req.prefill_s,
                rid=req.req_id, bucket=int(tb), cached_len=int(cached_len),
                kernel=self._attn_kernel)
        req.slot = slot
        req.status = "running"
        req.epoch = self._epoch  # admission pins the epoch it prefilled on
        self._running[slot] = req
        if self._report is not None:
            self._report.admitted.append(rid)
        self.total_tokens += 1
        self.prompt_tokens_total += t0
        _obs.inc("serving_tokens_total")
        self._append_token(req, token)

    def _prefill_blocks(self, req: Request, slot: int, row: np.ndarray,
                        cached_len: int):
        """A block-diffusion admission: prefill the prompt's whole blocks
        past the shared prefix (none, where the prefix covers them), then
        seat the request with its first block, the prompt's tail known and
        the rest masked. Its first token comes from the block pass."""
        t0, b = int(req.prompt.shape[0]), self._block
        start = t0 // b * b
        rid = req.req_id
        tp0 = time.perf_counter()
        if start > cached_len:
            with _obs.span("eng_prefill_prep", rid=rid) as sp:
                tail = req.prompt[cached_len:start]
                tb = self._bucket_for(len(tail))
                ids = np.zeros((1, tb), np.int32)
                ids[0, :len(tail)] = tail
                name = f"prefill_b{tb}"
                dev = self._upload(self._pack(name, (
                    ids, cached_len, start, row, req.key_np, 1.0, 0, 1.0,
                    True)), sp)
            with _obs.span("eng_prefill_dispatch", rid=rid, bucket=int(tb)):
                nxt, _ = self._run(name, self._state_vals(), self.kv, dev)
            with _obs.span("eng_prefill_readback", rid=rid):
                jax.block_until_ready(nxt)  # its token is not used
            self.rows_computed += start - cached_len
        req.prefill_t0 = tp0
        req.prefill_s = time.perf_counter() - tp0
        tokens = np.zeros(b, np.int32)
        tokens[:t0 - start] = req.prompt[start:]
        req.block = BlockState(start, tokens, np.arange(b) >= t0 - start,
                               to_unmask=b - (t0 - start))
        req.slot = slot
        req.status = "running"
        req.epoch = self._epoch
        self._running[slot] = req
        if self._report is not None:
            self._report.admitted.append(rid)
        self.prompt_tokens_total += t0

    def _append_token(self, req: Request, token: int):
        req.tokens.append(token)
        if self._report is not None:
            self._report.tokens.setdefault(req.req_id, []).append(token)
        p = req.params
        if len(req.tokens) >= p.max_new_tokens or (
                p.eos_token_id is not None and token == p.eos_token_id):
            self._finish(req)

    def _finish(self, req: Request):
        req.status = "done"
        if self._report is not None:
            self._report.finished.append(req.req_id)
        if self._acct is not None:
            # charge the page-occupancy tail while the request still
            # holds its pages, then attribute its totals to the ledger
            self._acct_tick(time.perf_counter())
            self._acct_request(req)
        if req.slot >= 0:
            del self._running[req.slot]
            self._tables[req.slot] = 0
            self._free.append(req.slot)
            req.slot = -1
            if req.epoch in self._epoch_vals and not any(
                    r.epoch == req.epoch for r in self._running.values()):
                # last in-flight request of a retired epoch: release its
                # pinned weight snapshot
                del self._epoch_vals[req.epoch]
        for page in req.page_ids:
            self.pool.decref(page)
        req.page_ids = []
        ttft = (None if req.first_token_time is None
                else req.first_token_time - req.submit_time)
        now = time.perf_counter()
        queue_s = (None if req.prefill_t0 is None
                   else req.prefill_t0 - req.submit_time)
        decode_s = 0.0 if req.decode_t0 is None else now - req.decode_t0
        if req.trace_id is not None and req.decode_t0 is not None:
            did = _obs.record_span(
                "srv_decode", trace_id=req.trace_id,
                parent_id=req.trace_parent, dur_s=decode_s,
                rid=req.req_id, steps=req.decode_steps_n,
                tokens=len(req.tokens), kernel=self._attn_kernel)
            if req.verify_steps_n:
                # the speculative share of the decode window, parented to
                # the srv_decode span it partitions
                _obs.record_span(
                    "srv_verify", trace_id=req.trace_id, parent_id=did,
                    dur_s=decode_s * req.verify_steps_n
                    / max(req.decode_steps_n, 1),
                    steps=req.verify_steps_n, accepted=req.spec_accepted_n)
        _obs.event("serving_request_done", req_id=req.req_id,
                   prompt_tokens=int(len(req.prompt)),
                   generated_tokens=len(req.tokens), ttft_seconds=ttft,
                   queue_s=queue_s, prefill_s=round(req.prefill_s, 6),
                   decode_s=round(decode_s, 6),
                   spec_accepted=req.spec_accepted_n,
                   spec_wasted=max(
                       self.config.speculate_k * req.verify_steps_n
                       - req.spec_accepted_n, 0),
                   tenant=req.tenant, slo_class=req.slo,
                   imported=req.imported, kv_page_us=req.acct_page_us,
                   resubmitted=req.resubmitted)

    def _acct_request(self, req: Request):
        """Fold one finished request into the per-tenant ledger. Token
        fields mirror the untagged counters exactly: an imported request's
        prompt + first token were metered on the prefill engine
        (prefill_export), so only its remaining generated tokens count
        here — summed across disaggregated engines every token lands in
        exactly one cell."""
        wasted = max(self.config.speculate_k * req.verify_steps_n
                     - req.spec_accepted_n, 0)
        queue_s = (0.0 if req.prefill_t0 is None
                   else max(req.prefill_t0 - req.submit_time, 0.0))
        self._acct.add(
            req.tenant, req.slo, requests=1,
            prefill_tokens=0 if req.imported else int(len(req.prompt)),
            decode_tokens=len(req.tokens) - (1 if req.imported else 0),
            spec_accepted_tokens=req.spec_accepted_n,
            spec_wasted_tokens=wasted, queue_seconds=queue_s)

    def _update_gauges(self):
        """The peaks that ``stats()`` reports, and, where telemetry is on,
        the four gauges (their arguments cost a scan of the running
        requests and of the pool's refcounts, so only then)."""
        in_use = self._num_pages - 1 - self.pool.available()
        self.peak_pages_in_use = max(self.peak_pages_in_use, in_use)
        self.peak_running = max(self.peak_running, len(self._running))
        if not _obs.enabled():
            return
        used = sum(len(r.prompt) + len(r.tokens)
                   for r in self._running.values())
        _obs.set_gauge("serving_batch_occupancy",
                       len(self._running) / float(self.config.num_slots))
        _obs.set_gauge("serving_kv_cache_utilization",
                       used / float((self._num_pages - 1)
                                    * self.config.page_size))
        _obs.set_gauge("serving_kv_pages_free", float(self.pool.available()))
        _obs.set_gauge("serving_kv_pages_shared",
                       float(self.pool.shared_pages()))

    def _mesh_ctx(self):
        """Activate the engine's mesh for a compiled-program call, so the
        sharding-constraint hints inside F.paged_attention and the pure
        bodies see it at trace time (thread-local; restored after). Also
        forces the mp_comm activation wire OFF for the traced body:
        model-internal mp collectives must stay exact for the greedy
        bit-equality contract — only the logit recombination quantizes,
        explicitly, via ``_wire_logits``."""
        import contextlib

        if self._mesh is None:
            return contextlib.nullcontext()
        from ..distributed import mp_comm as _mp_comm
        from ..distributed.mesh import global_mesh

        stack = contextlib.ExitStack()
        stack.enter_context(global_mesh(self._mesh))
        stack.enter_context(_mp_comm.activation_wire_disabled())
        return stack

    def program_text(self, name: str) -> str:
        """Optimised HLO of one program that has run ("decode",
        "verify_k4", "prefill_b16", ...): what the compiler kept of it — a
        kernel's custom call, the collectives of a sharded engine."""
        fn, args = self._programs[name]
        with self._mesh_ctx():
            return fn.lower(*args).compile().as_text()

    def _jitted(self, name: str):
        """The jitted program of that name, built at its first use."""
        fn = self._jit.get(name)
        if fn is None:
            kind, n = _program_kind(name)
            fn = self._jit[name] = (
                self._build_decode() if kind == "decode"
                else self._build_verify(n + 1) if kind == "verify"
                else self._build_block(n) if kind == "block"
                else self._build_commit(n) if kind == "commit"
                else self._build_prefill(n))
        return fn

    def _layout(self, name: str) -> tuple:
        """The fields of program ``name``'s one packed input, ``(shape,
        dtype)`` each in the order its body takes them: fixed by the
        program's kind and the engine's shapes (slots, the table's width,
        the bucket, ``k + 1``, the block length)."""
        lay = self._layouts.get(name)
        if lay is None:
            kind, n = _program_kind(name)
            s, mp = self.config.num_slots, self._mp
            i32, f32, u32, yes = (np.dtype(t) for t in (
                np.int32, np.float32, np.uint32, np.bool_))
            key = self._zero_key.shape
            if kind == "prefill":  # ids, cached_len, true_len, row, key
                lay = (((1, n), i32), ((), i32), ((), i32), ((mp,), i32),
                       (key, u32), ((), f32), ((), i32), ((), f32), ((), yes))
            elif kind == "commit":  # tokens, positions, tables
                lay = (((s, n), i32), ((s,), i32), ((s, mp), i32))
            else:  # tokens [, masked], positions, tables, keys, sampling
                lay = ((((s,), i32),) if kind == "decode"
                       else (((s, n + 1), i32),) if kind == "verify"
                       else (((s, n), i32), ((s, n), yes)))
                lay += (((s,), i32), ((s, mp), i32), ((s,) + key, u32),
                        ((s,), f32), ((s,), i32), ((s,), f32), ((s,), yes))
                if kind == "block":
                    lay += (((s,), i32),)  # how many to unmask
            self._layouts[name] = lay
        return lay

    def _pack(self, name: str, arrays) -> np.ndarray:
        """Program ``name``'s host inputs as its one packed int32 array."""
        return _pack_fields(self._layout(name), arrays)

    def _upload(self, packed: np.ndarray, sp=None):
        """ONE host-to-device transfer of a pass's packed inputs, counted
        in ``host_uploads`` and on its span ``sp`` (attrs ``arrays``,
        ``bytes``)."""
        self.host_uploads += 1
        if sp:
            sp.attrs.update(arrays=1, bytes=int(packed.nbytes))
        return jnp.asarray(packed)

    def _run(self, name: str, *args):
        """Run program ``name`` on ``(state values, pool, packed)``, keep
        the pool it returns and hand back ``(tokens, logits)``; a
        program's first run is timed and counted as its compile."""
        fn = self._jitted(name)
        first = name not in self._compiled
        t0 = time.perf_counter() if first else 0.0
        if first:
            # an uncommitted array follows the committed ones, as in the call
            self._programs[name] = (fn, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a), a.dtype, sharding=a.sharding
                    if getattr(a, "committed", False) else None), args))
        cached = self._aot.get(name)
        if cached is not None:
            fn = cached
        hit = None
        if first and cached is None:
            aot = _compile_cache.resolve()
            if aot is not None:
                try:
                    with self._mesh_ctx():
                        lowered = fn.lower(*args)
                    key = aot.key_for(
                        lowered, config=self._aot_key_parts(name),
                        mesh=self._mesh)
                    compiled, hit = aot.load_or_compile(
                        lowered, key, where="decode_engine")
                    self._aot[name] = compiled
                    fn = compiled
                    if hit:
                        self.aot_cache_hits += 1
                except Exception:  # noqa: BLE001 — never break serving
                    hit = None
        with self._mesh_ctx():
            out = fn(*args)
        if first:
            jax.block_until_ready(out[-2])
            dt = time.perf_counter() - t0
            self._compiled.add(name)
            self.compile_count += 1
            _obs.inc("serving_engine_compile_total")
            _obs.record_compile("decode_engine", dt, signature=name,
                                cache_hit=hit)
        self.kv, tokens, logits = out
        return tokens, logits

    def _aot_key_parts(self, name: str) -> dict:
        """Semantic fingerprint for the persistent AOT compile cache:
        everything about the engine geometry that shapes the program
        (the lowered-module hash covers the model body itself)."""
        cfg = self.config
        return {
            "program": name,
            "num_slots": cfg.num_slots,
            "max_length": cfg.max_length,
            "kv_dtype": cfg.kv_dtype,
            "page_size": cfg.page_size,
            "max_pages": self._mp,
            "buckets": list(self.buckets),
            "speculate_k": cfg.speculate_k,
            "donate": self._donate,
            "adapter": type(self.adapter).__name__,
            "logit_wire": self._logit_wire,
            "logit_verify": self._logit_verify,
            "attn_kernel": self._attn_kernel,
        }

    # -- compiled programs --------------------------------------------------
    #
    # All programs take the model state EXPLICITLY (param/buffer values are
    # swapped into the live tensors around the traced body and restored —
    # the jit.TracedLayer idiom), so parameters stay jit arguments rather
    # than baked-in constants, and the paged KV pool flows through as ONE
    # donated pytree input/output. A pass's host inputs (tokens, positions,
    # page tables, keys, sampling fields) arrive as ONE int32 array, moved
    # to the device in one transfer and cut back into its fields inside the
    # program (``_layout``, ``_pack_fields``, ``_unpack_fields``).

    def _wire_logits(self, logits):
        """Route mp-vocab-sharded logits [..., V] through the quantized
        recombination (docs/SERVING.md §5). Returns ``(logits_for_
        sampling, exact_argmax, replicated_out)``: with the f32 wire all
        three degrade to ``(logits, None, None)`` so callers trace
        exactly the historical program (mp_comm=off is byte-for-byte);
        quantized, sampling sees the dequantized wire payload while
        greedy rows take the exact verify winner."""
        if self._logit_wire == "f32":
            return logits, None, None
        from ..distributed import mp_comm as _mp_comm

        r = _mp_comm.quantized_logit_gather(logits, self._logit_wire,
                                            self._mesh)
        if r is None:
            return logits, None, None
        wl, exact = r
        rows = int(np.prod(logits.shape[:-1]))
        _, wire_b = _mp_comm.logit_wire_bytes(
            rows, int(logits.shape[-1]), self._mp_degree, self._logit_wire)
        _obs.set_gauge("serving_logit_wire_bytes", wire_b)
        if not self._logit_verify:
            exact = None
        return wl, exact, wl

    def _program(self, body, name: str):
        """Jit ``body(pool, *inputs) -> (pool, tokens, logits)`` as program
        ``name`` (the header above): the state swap, the returned pool's
        pin and the pool's donation, once for all of them, and the inputs
        cut out of the one packed array they arrive in (``_layout``)."""
        state, layout = self._state, self._layout(name)

        def pure(state_vals, pool, packed):
            originals = [t._value for t in state]
            try:
                for t_, v_ in zip(state, state_vals):
                    t_._value = v_
                with no_grad():
                    pool, tokens, logits = body(
                        pool, *_unpack_fields(layout, packed))
            finally:
                for t_, v_ in zip(state, originals):
                    t_._value = v_
            return pool.pin(), tokens, logits

        return jax.jit(pure, donate_argnums=(1,) if self._donate else ())

    def _forward(self, pool, ids, positions, write, attend_pages, read):
        """The one forward of the three programs (traced): ``embed ->
        layers -> head``. A layer is the MODEL's own block, handed
        ``attend(q, k, v)`` for the place where full attention stood: the
        new tokens' K/V go to their pages (``write(pool, l, k, v)``, a
        prompt block or a row a token), then attention over the slots'
        pages (``attend_pages(pool, l, q)``: the pool's block read in a
        prefill, its paged read in decode and verify). ``read(hidden,
        head)`` gives the logits of the rows the program wants. Each part
        under its ``named_scope``, which the compiled text keeps
        (``profiler.op_scopes``): ``embed``, ``kv_write``, ``attend``,
        ``loop_close`` and ``lm_head`` here, ``sample`` in the builders,
        ``qkv``, ``attn_out`` and ``mlp`` opened by the model.

        An adapter that states ``loops`` > 1 has its ``num_layers`` layers
        run that many times over the same weights, ``close_loop`` ending
        each run: ONE compiled loop over ``u`` with the pool in its carry,
        whose body is the model's layers at cache entries ``u * num_layers
        + l`` (a traced index: the pool's reads and writes take it as they
        take an int). Without ``loops`` the layers are traced once, at
        entries ``l``, and no loop construct reaches the program. Returns
        the pool and f32 logits."""
        ad = self.adapter

        def run_layers(u, carry):
            pool, x = carry[0], Tensor(carry[1])

            def attend(entry, q, k, v):
                nonlocal pool
                with _scope("kv_write"):
                    pool = write(pool, entry, _shard_kv_heads(raw(k)),
                                 _shard_kv_heads(raw(v)))
                with _scope("attend"):
                    return attend_pages(pool, entry, q)

            for l in range(ad.num_layers):
                x = ad.layer(l, x, positions, functools.partial(
                    attend, u * ad.num_layers + l))
            if self._close_loop is not None:
                with _scope("loop_close"):
                    x = self._close_loop(x)
            return pool, raw(x)

        with _scope("embed"):
            x = raw(ad.embed(Tensor(ids), positions))
        if self._loops == 1:
            pool, x = run_layers(0, (pool, x))
        else:
            pool, x = jax.lax.fori_loop(0, self._loops, run_layers, (pool, x))
        with _scope("lm_head"):
            logits = read(x, lambda h: raw(ad.head(Tensor(h))))
            return pool, logits.astype(jnp.float32)

    def _sample(self, logits, keys, temp, top_k, top_p, greedy):
        """One token a row of ``logits`` [..., V] on the typed ``keys``
        [...]; the sampling fields [S] hold for every row of their slot.
        Returns the tokens [...] and the logits a program hands back,
        both replicated."""
        s_logits, exact_arg, wired = self._wire_logits(logits)
        if wired is None:
            # mp-vocab-sharded logits are gathered once, here, as the
            # returned logits are anyway: the sampler's bisection steps
            # then reduce each row on one shard, with no collective a step
            s_logits = logits = _replicate_out(logits)
        n = int(np.prod(keys.shape))
        per = n // temp.shape[0]
        rep = (lambda a: a) if per == 1 else (
            lambda a: jnp.repeat(a, per, axis=0))
        tokens = _sample_tokens(
            s_logits.reshape(n, -1), keys.reshape(n), rep(temp), rep(top_k),
            rep(top_p), rep(greedy),
            exact_argmax=None if exact_arg is None else exact_arg.reshape(n)
        ).reshape(keys.shape)
        return _replicate_out(tokens), logits if wired is None else wired

    def _build_prefill(self, tb: int):
        def body(pool, ids, cached_len, true_len, row, key, temp, top_k,
                 top_p, greedy):
            positions = cached_len + jnp.arange(tb, dtype=jnp.int32)
            # right-pad positions >= true_len are inert under the position
            # mask; the real last-token logits sit at tail offset
            # true_len - 1 - cached_len
            pool, logits = self._forward(
                pool, ids, positions,
                lambda pool, l, k, v: pool.write_block(
                    l, k, v, row, cached_len, true_len),
                lambda pool, l, q: pool.attend_block(
                    q, l, row, cached_len, self._attn_kernel,
                    self._block or 1),
                lambda x, head: head(jax.lax.dynamic_slice_in_dim(
                    x, true_len - 1 - cached_len, 1, 1))[:, 0])
            # sample stream keyed by DESTINATION position: token landing at
            # position true_len uses fold_in(key, true_len), matching what
            # the decode step would use — scheduling-invariant
            with _scope("sample"):
                step_key = jax.random.fold_in(
                    jax.random.wrap_key_data(key, impl=_KEY_IMPL), true_len)
                nxt, logits = self._sample(
                    logits, step_key[None], temp[None], top_k[None],
                    top_p[None], greedy[None])
            return pool, nxt[0], logits[0]

        return self._program(body, f"prefill_b{tb}")

    def _build_decode(self):
        def body(pool, tokens, positions, tables, keys, *sampling):
            pos2 = positions[:, None]  # [S, 1]
            pool, logits = self._forward(
                pool, tokens[:, None], pos2,
                lambda pool, l, k, v: pool.write_tokens(
                    l, k, v, tables, pos2),
                lambda pool, l, q: pool.attend(
                    q, l, tables, positions, self._attn_kernel),
                lambda x, head: head(x)[:, 0])
            with _scope("sample"):
                step_keys = jax.vmap(jax.random.fold_in)(
                    jax.random.wrap_key_data(keys, impl=_KEY_IMPL),
                    positions + 1)
                return pool, *self._sample(logits, step_keys, *sampling)

        return self._program(body, "decode")

    def _build_verify(self, k1: int):
        """The speculative companion of the decode program: k1 = k + 1
        tokens per slot in one pass, per-position sampling on the SAME
        position-keyed streams."""
        def body(pool, tokens, positions, tables, keys, *sampling):
            pos2 = positions[:, None] + jnp.arange(
                k1, dtype=jnp.int32)[None, :]  # [S, k1]
            pool, logits = self._forward(
                pool, tokens, pos2,
                lambda pool, l, k, v: pool.write_tokens(
                    l, k, v, tables, pos2),
                lambda pool, l, q: pool.attend(
                    q, l, tables, positions, self._attn_kernel),
                lambda x, head: head(x))
            with _scope("sample"):
                step_keys = jax.vmap(jax.vmap(
                    jax.random.fold_in, in_axes=(None, 0)))(
                    jax.random.wrap_key_data(keys, impl=_KEY_IMPL), pos2 + 1)
                return pool, *self._sample(logits, step_keys, *sampling)

        return self._program(body, f"verify_k{k1 - 1}")

    def _block_forward(self, pool, ids, positions, tables, b, read):
        """The forward of the block and commit passes: every slot's ``b``
        positions from its ``positions`` entry written to its pages in
        place and attended under the block horizon. Returns the pool, what
        ``read`` takes of the last hidden state, the positions [S, b] and
        each routed layer's count of experts touched (``moe.count_experts``)."""
        from ..incubate import moe

        pos2 = positions[:, None] + jnp.arange(b, dtype=jnp.int32)[None]
        with moe.count_experts() as counts:
            pool, out = self._forward(
                pool, ids, pos2,
                lambda pool, l, k, v: pool.write_tokens(
                    l, k, v, tables, pos2),
                lambda pool, l, q: pool.attend(
                    q, l, tables, positions, self._attn_kernel, b),
                read)
        return pool, out, pos2, counts

    def _build_block(self, b: int):
        """The block pass of a block-diffusion model: every slot's block of
        ``b`` positions, masked ones as the mask token (``_block_forward``);
        a token drawn at every position on its position-keyed stream, and
        the model's rule unmasking ``count`` of each slot's masked ones.
        Returns ``(tokens, masked, experts touched)`` and the logits."""
        ad = self.adapter
        mask_id = int(ad.mask_token_id)
        rule, thr = ad.remasking, float(ad.confidence_threshold)

        def body(pool, tokens, masked, positions, tables, keys, temp, top_k,
                 top_p, greedy, count):
            pool, logits, pos2, counts = self._block_forward(
                pool, jnp.where(masked, mask_id, tokens), positions, tables,
                b, lambda x, head: head(x))  # [S, B, V]
            touched = sum(counts, jnp.int32(0))
            with _scope("sample"):
                # the token AT position p is drawn on fold_in(key, p)
                step_keys = jax.vmap(jax.vmap(
                    jax.random.fold_in, in_axes=(None, 0)))(
                    jax.random.wrap_key_data(keys, impl=_KEY_IMPL), pos2)
                drawn, logits = self._sample(logits, step_keys, temp, top_k,
                                             top_p, greedy)
                t = jnp.where(greedy, 1.0, temp)[:, None, None]
                conf = jnp.take_along_axis(
                    jax.nn.softmax(logits / t, axis=-1), drawn[..., None],
                    -1)[..., 0]
            with _scope("unmask"):
                tokens, masked = _unmask(drawn, conf, tokens, masked, count,
                                         rule, thr)
            return pool, (tokens, masked, touched), logits

        return self._program(body, f"block_b{b}")

    def _build_commit(self, b: int):
        """The commit pass of a block-diffusion model: each listed slot's
        final block through every layer (``_block_forward``), its keys and
        values written where the block passes wrote theirs. Nothing reads
        the last layer's output, so neither its attention nor its experts
        run. Returns the experts touched in the layers whose experts ran."""
        def body(pool, tokens, positions, tables):
            pool, _, _, counts = self._block_forward(
                pool, tokens, positions, tables, b,
                lambda x, head: jnp.zeros((1,), jnp.float32))
            touched = sum(counts[:-1], jnp.int32(0))
            return pool, touched, jnp.zeros((1,), jnp.float32)

        return self._program(body, f"commit_b{b}")
