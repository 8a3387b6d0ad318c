"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test here) cannot see what Mosaic
refuses: a slice off the tiling, more VMEM than a kernel may hold. The
TPU compiler is installed in the CPU sandbox and compiles for a chip that
is described, not attached, so these cases guard the flash, paged, prefill
and grouped-matmul kernels at the shapes `chip_smoke.py` and the roadmap's
cells run them at — at no chip time. Nothing executes: a compile that
passes says nothing about results or speed. The compiled text does say
what XLA:TPU made of a program, so the serving engine's own decode, verify
and prefill programs are compiled here too and held to their contract: the
KV pool keeps one layout and nothing copies, slices or re-lays it.

The topology is described inside a fixture, after a test of this file has
started, and compiled in the test's own process (libtpu allows one process
at a time); keep every such case in THIS file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as flash_mod
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from paddle_tpu.ops.pallas.paged_attention import paged_attention
from paddle_tpu.ops.pallas.prefill_attention import prefill_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one would warn)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Compile ``fn`` for the described chip; the kernel must be in it."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# -- flash attention (training path: ERNIE seq1024, GPT-1.3B seq2048) -------

FLASH_SHAPES = {
    "ernie_b32_t1024_h12_d64": (32, 1024, 12, 64),
    "gpt1p3b_b4_t2048_h16_d128": (4, 2048, 16, 128),
}


@pytest.fixture
def flash_on_tpu(monkeypatch):
    """The wrapper picks interpret mode from jax.default_backend(), which
    still says cpu here: steer it from the test, not with a new option."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", list(FLASH_SHAPES), ids=list(FLASH_SHAPES))
def test_flash_attention_compiles(one_chip, flash_on_tpu, shape, backward):
    causal = shape.startswith("gpt")

    def fwd(q, k, v):
        return flash_mod.flash_attention(q, k, v, causal=causal)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2)
        )(q, k, v)

    qkv = (FLASH_SHAPES[shape], jnp.bfloat16)
    _compile(fwd_bwd if backward else fwd, one_chip, qkv, qkv, qkv)


def test_flash_attention_padding_mask_compiles(one_chip, flash_on_tpu):
    """The (B,1,1,Tk) keep-mask of a padded ERNIE batch, forward+backward."""
    b, t, h, d = FLASH_SHAPES["ernie_b32_t1024_h12_d64"]

    def fwd_bwd(q, k, v, mask):
        return jax.grad(
            lambda *a: flash_mod.flash_attention(*a, mask=mask)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    qkv = ((b, t, h, d), jnp.bfloat16)
    _compile(fwd_bwd, one_chip, qkv, qkv, qkv, ((b, 1, 1, t), jnp.bool_))


# -- paged attention (serving path: GPT-1.3B, 8 slots, 2048 tokens, page 16;
# Ouro-2.6B, 8 slots, 512 tokens: the same heads and page, a table of 32) ----

def _paged_shapes(kv, slots, t, heads=16, kv_heads=16, max_pages=128):
    d, page = 128, 16
    n = 1 + 8 * max_pages
    pool = ((n, kv_heads, page, d),
            jnp.int8 if kv == "int8" else jnp.bfloat16)
    shapes = [((slots, t, heads, d), jnp.bfloat16), pool, pool,
              ((slots, max_pages), jnp.int32), ((slots,), jnp.int32)]
    if kv == "int8":
        scales = ((n, kv_heads, page), jnp.float32)
        shapes += [scales, scales]
    return shapes


def _paged(q, kp, vp, table, start, ks=None, vs=None):
    return paged_attention(q, kp, vp, table, start, k_scales=ks,
                           v_scales=vs, interpret=False)


#: (slots, T): the decode and verify passes over 8 slots, whose body walks
#: a slot's live pages in blocks of 8 (manual copies from the pool in HBM
#: into two VMEM buffers: PR 36), and one slot at what a prefill bucket's
#: row counts were, where a grid step's head block is what the VMEM budget
#: leaves (16, 4 and 2 heads of the 16). A lone kernel's compile is LENIENT
#: on VMEM: XLA keeps its small query and result in VMEM, where the
#: engine's program double-buffers them from HBM: the engine's own decode
#: and verify programs are compiled with the kernel inside further down
PAGED_CALLS = {"decode": (8, 1), "verify_k4": (8, 5),
               "prefill_128": (1, 128), "prefill_512": (1, 512),
               "prefill_1024": (1, 1024)}


@pytest.mark.parametrize("call", list(PAGED_CALLS))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_attention_compiles(one_chip, kv, call):
    _compile(_paged, one_chip, *_paged_shapes(kv, *PAGED_CALLS[call]))


@pytest.mark.parametrize("call", ["decode", "verify_k4"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_attention_compiles_over_the_looped_cells_table(
        one_chip, kv, call):
    """Ouro-2.6B's call, 192 a decode pass: 8 slots x 32 page slots."""
    compiled = _compile(_paged, one_chip, *_paged_shapes(
        kv, *PAGED_CALLS[call], max_pages=32))
    assert re.search(r"%paged_attention(\.\d+)? = f32\[8,16,8,128\]",
                     compiled.as_text())


@pytest.mark.parametrize("call", ["decode", "prefill_512"])
def test_paged_attention_gqa_compiles(one_chip, call):
    """Llama's layout: 32 query heads on 8 kv heads, four a kernel row
    group (at bucket 512 a head's row block is 2048 rows: one head a
    step)."""
    _compile(_paged, one_chip, *_paged_shapes(
        "bf16", *PAGED_CALLS[call], heads=32, kv_heads=8))


# -- prefill attention (the serving path's tail prefill, since PR 34) --------

def _prefill_shapes(kv, t, heads=16, kv_heads=16):
    d, keys = 128, 2048
    pool = ((kv_heads, keys, d), jnp.int8 if kv == "int8" else jnp.bfloat16)
    shapes = [((t, heads, d), jnp.bfloat16), pool, pool, ((), jnp.int32)]
    if kv == "int8":
        shapes += [((kv_heads, keys), jnp.float32)] * 2
    return shapes


def _prefill(q, k, v, cached_len, ks=None, vs=None):
    return prefill_attention(q, k, v, cached_len, k_scales=ks, v_scales=vs,
                             interpret=False)


@pytest.mark.parametrize("bucket", [128, 512, 1024])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_prefill_attention_compiles(one_chip, kv, bucket):
    """One slot's gathered keys (8 slots x 2048: 2048 keys) under each
    bucket's rows. Lenient on VMEM like every lone compile: the engine's
    own prefill programs are compiled further down."""
    _compile(_prefill, one_chip, *_prefill_shapes(kv, bucket))


def test_prefill_attention_gqa_compiles(one_chip):
    """Llama's layout at bucket 512: a kv head's four query heads ride in
    the row dimension, 2048 rows in four row blocks."""
    _compile(_prefill, one_chip, *_prefill_shapes(
        "bf16", 512, heads=32, kv_heads=8))


# -- grouped matmul (MoE expert FFN at OLMoE widths, ROADMAP R1) ------------

@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("n", [1024, 1408])
def test_grouped_matmul_compiles(one_chip, n, backward):
    m, k, groups = 8192, 2048, 64
    gmm = functools.partial(grouped_matmul, interpret=False)

    def fwd_bwd(lhs, rhs, sizes):
        return jax.grad(
            lambda a, b: gmm(a, b, sizes).astype(jnp.float32).sum(),
            argnums=(0, 1))(lhs, rhs)

    _compile(fwd_bwd if backward else gmm, one_chip,
             ((m, k), jnp.bfloat16), ((groups, k, n), jnp.bfloat16),
             ((groups,), jnp.int32))


# -- every kernel carries its name in the compiled program -------------------

KERNEL_NAMES = {
    "paged_attention": "paged",
    "prefill_attention": "prefill",
    "flash_attention_fwd": "flash",
    "flash_attention_bwd_fused": "flash",
    # a key length over one backward block keeps dQ in a kernel of its own
    "flash_attention_bwd_dq": "flash_gpt",
    "flash_attention_bwd_dkv": "flash_gpt",
    "grouped_matmul_fwd": "gmm",
    "grouped_matmul_drhs": "gmm",
}


@pytest.fixture(scope="module")
def named_programs(one_chip):
    """The compiled text of a forward+backward flash program at the ERNIE
    and at the GPT shape, one paged decode program, one bucket-1024 prefill
    call and one forward+backward grouped matmul, compiled when the first
    case asks."""
    texts = {}

    def flash(q, k, v, causal=False):
        return jax.grad(
            lambda *a: flash_mod.flash_attention(*a, causal=causal)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    def gmm(lhs, rhs, sizes):
        return jax.grad(
            lambda a, b: grouped_matmul(a, b, sizes, interpret=False)
            .astype(jnp.float32).sum(), argnums=(0, 1))(lhs, rhs)

    qkv = (FLASH_SHAPES["ernie_b32_t1024_h12_d64"], jnp.bfloat16)
    gpt = (FLASH_SHAPES["gpt1p3b_b4_t2048_h16_d128"], jnp.bfloat16)
    programs = {
        "flash": (flash, (qkv, qkv, qkv)),
        "flash_gpt": (functools.partial(flash, causal=True), (gpt, gpt, gpt)),
        "paged": (_paged, _paged_shapes("bf16", *PAGED_CALLS["decode"])),
        "prefill": (_prefill, _prefill_shapes("bf16", 1024)),
        "gmm": (gmm, (((8192, 2048), jnp.bfloat16),
                      ((64, 2048, 1024), jnp.bfloat16),
                      ((64,), jnp.int32))),
    }

    def text_of(which):
        if which not in texts:
            fn, shapes = programs[which]
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                texts[which] = _compile(fn, one_chip, *shapes).as_text()
        return texts[which]

    return text_of


@pytest.mark.parametrize("kernel", list(KERNEL_NAMES))
def test_kernel_is_named_in_the_compiled_program(named_programs, kernel):
    """XLA:TPU names a Mosaic custom call after ``pallas_call(name=...)``:
    what a device trace's event carries, so that kernels are told apart by
    name and not by the shape of their result. Under jax's transforms the
    name comes wrapped (``%jvp_flash_attention_fwd_.1``,
    ``%transpose_jvp_flash_attention_bwd_dq__.1``); alone it is
    ``%paged_attention.1``."""
    text = named_programs(KERNEL_NAMES[kernel])
    assert re.search(rf"%\w*{kernel}_*(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"', text), (
        re.findall(r"%([\w.]+) = [^\n]*tpu_custom_call", text))


def _decode_kernel_pattern(num_slots):
    """The pattern by which ``paged_attn_roofline`` and
    ``paged_attn_time_pct`` find the DECODE kernel's device events, read
    from the benchmark's own file, ``$num_slots`` filled in as its reader
    does."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           "paged_attn_roofline.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    assert "$num_slots" in pattern
    return re.compile(pattern.replace("$num_slots", str(num_slots)))


def test_the_decode_kernels_line_is_what_the_benchmark_looks_for(
        named_programs):
    """``paged_attn_roofline`` and ``paged_attn_time_pct`` find the decode
    kernel's device events by their HLO line: ONE custom call whose single
    result is float32 with the slot axis first. A tuple result, a bf16
    result or another leading axis silences both. The pattern is read from
    the benchmark's own file, ``$num_slots`` filled in as its reader does."""
    rx = _decode_kernel_pattern(8)
    lines = [ln for ln in named_programs("paged").splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(lines) == 1, lines
    assert rx.search(lines[0]), lines[0]
    assert re.search(r"%paged_attention(\.\d+)? = f32\[8,", lines[0])


def test_the_prefill_kernels_line_is_not_what_the_decode_roofline_counts(
        named_programs):
    """The prefill's call must stay out of the decode kernel's roofline
    share: its result leads with 1, never with the cell's 8 slots."""
    lines = [ln for ln in named_programs("prefill").splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(lines) == 1, lines
    assert re.search(r"%prefill_attention(\.\d+)? = f32\[1,16,1024,128\]",
                     lines[0]), lines[0]
    assert not _decode_kernel_pattern(8).search(lines[0]), lines[0]


# -- the engine's programs leave the KV pool where and as it is (PR 30) ------
#
# Until PR 30 a decode pass of the serving cell copied the whole pool four
# times and sliced and re-laid one layer of it before each of its 48 kernel
# calls: 79% of the device's busy time (PERF.md). The cause was visible in
# the compiled text alone, and so is its absence.

#: the serving cell's engine (GPT-3 1.3B: 16 heads of 128, 8 slots x 2048,
#: page 16). The layout choice does not depend on depth, so the bf16 pool
#: is two layers deep, and the int8 pool four: a K or V pool of less than
#: these 134 MB XLA takes into VMEM whole (a pool-sized ``slice-start``
#: before the first kernel call), which no pool of a served depth allows
ENGINE_PROGRAMS = ("decode", "verify_k4", "prefill_b512")
ENGINE_LAYERS = {"bf16": 2, "int8": 4}

#: what may have a result as large as one layer's pool: the program's own
#: arguments and results, and a write in place
_IN_PLACE = {"dynamic-update-slice", "scatter"}
_NO_TRAFFIC = {"parameter", "tuple", "get-tuple-element", "bitcast"}


@pytest.fixture(scope="module")
def engine_programs(one_chip):
    """``text_of(kv, program)``: the compiled text of one of the engine's
    own programs, built by the engine from the shapes of its own example
    arguments (a described chip holds no array), and the pool's shape. The
    engine asks ``jax.default_backend()`` for its kernel, its donation and
    the kernel's interpret mode: steered here."""
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    made = {}

    def engine(kv):
        if kv not in made:
            model = GPTForCausalLM(GPTConfig(
                vocab_size=1024, hidden_size=2048,
                num_hidden_layers=ENGINE_LAYERS[kv], num_attention_heads=16,
                intermediate_size=2048, max_position_embeddings=2048,
                hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
            )).astype("bfloat16")
            model.eval()
            made[kv] = DecodeEngine(model, EngineConfig(
                num_slots=8, max_length=2048, page_size=16, kv_dtype=kv,
                prompt_buckets=(128, 512, 1024), speculate_k=4))
        return made[kv]

    def shaped(a):
        return jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one_chip)

    def text_of(kv, program):
        if (kv, program) not in made:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                eng = engine(kv)
                assert eng.stats()["attn_kernel"] == "pallas" and eng._donate
                made[kv, program] = eng._jitted(program).lower(*jax.tree.map(
                    shaped, eng._example_args(program))).compile().as_text()
        return made[kv, program], made[kv].kv.shape

    return text_of


_COMPUTATION = re.compile(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(
    r"\s+(ROOT )?%[\w.\-]+ = (.+?) ([a-z][\w\-]*)\(")


def _elements(type_text):
    return max([int(np.prod([int(n) for n in dims.split(",") if n]))
                for dims in re.findall(r"\w+\[([\d,]*)\]", type_text)] or [0])


def _pool_sized_traffic(text, big):
    """Instructions that the device runs (those of a fused computation
    are its fusion's) whose result has ``big`` elements or more and is
    neither an argument, a tuple, a bitcast nor a write in place."""
    roots, large, body = {}, [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            body = head.group(1)
        ins = _INSTRUCTION.match(line)
        if not ins:
            continue
        root, result, op = ins.groups()
        if root:
            roots[body] = op
        if (not body.startswith("fused_computation")
                and _elements(result) >= big):
            large.append((op, line.strip()))
    found = []
    for op, line in large:
        if op == "fusion":  # a fusion is what its root is
            op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
        if op not in _NO_TRAFFIC | _IN_PLACE:
            found.append(line[:160])
    return found


#: which attention kernel each kind of program calls, and no other: decode
#: and verify the paged one, a prefill the blocked one over its slot's
#: gathered pages (PR 34)
_ENGINE_KERNEL = {"decode": "paged_attention", "verify": "paged_attention",
                  "prefill": "prefill_attention"}


def _kernels_in(text):
    return set(re.findall(
        r"%(\w+?)[.\d]* = f32\[[^\n]*tpu_custom_call", text))


@pytest.mark.parametrize("program", ENGINE_PROGRAMS)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_program_keeps_the_kv_pool_in_one_layout(
        engine_programs, kv, program):
    """(a) every array of the pool's shape in the compiled text, the
    program's two arguments and two results among them, has one and the
    same layout: the row-major one it arrives in. A second layout means a
    copy of the whole pool, 1.6 GB at the cell's 24 layers."""
    text, pool_shape = engine_programs(kv, program)
    dims = ",".join(str(n) for n in pool_shape)
    orders = set(re.findall(rf"\w+\[{dims}\]\{{([\d,]*)", text))
    assert orders == {"4,3,2,1,0"}, orders
    assert text.count(f"[{dims}]") >= 4  # two arguments, two results


@pytest.mark.parametrize("program", ENGINE_PROGRAMS)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_program_moves_nothing_as_large_as_a_layers_pool(
        engine_programs, kv, program):
    """(b) no copy, slice, transpose or fusion whose result is as large
    as ONE layer's pool, other than a write in place: the paged kernel
    reads its layer through its index maps, a prefill gathers its ONE
    slot's pages of a layer (an eighth of it) for the blocked kernel, the
    token write is an in-place ``dynamic-update-slice``, the prefill's
    page write a scatter."""
    text, (_, n, hkv, p, d) = engine_programs(kv, program)
    assert _kernels_in(text) == {_ENGINE_KERNEL[program.split("_")[0]]}
    assert _pool_sized_traffic(text, n * hkv * p * d) == []
    wrote = "scatter" if program.startswith("prefill") else (
        "dynamic-update-slice")
    assert re.search(rf" {wrote}\(", text)


@pytest.mark.parametrize("program", ["decode", "verify_k4"])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_decode_program_compiles_with_the_walking_kernel_inside(
        engine_programs, kv, program):
    """The cell's decode and verify programs compile for the described
    v5e with the kernel that walks the pages itself INSIDE them: the pools
    stay operands in HBM (two buffers of 8 pages each in VMEM, the scale
    rows beside them under int8), the query and result come from HBM and
    are double-buffered (what a lone compile cannot show of VMEM: PR 27),
    and every layer's call is the ONE line that the benchmark's readers
    look for: ``paged_attention``, a float32 result, the slot axis
    first."""
    text, pool_shape = engine_programs(kv, program)
    lines = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(lines) == ENGINE_LAYERS[kv]
    dims = ",".join(str(n) for n in pool_shape)
    for ln in lines:
        assert re.search(r"%paged_attention(\.\d+)? = f32\[8,16,8,128\]", ln)
        assert _decode_kernel_pattern(8).search(ln), ln
        # both pools reach the call whole, as its operands
        assert len(re.findall(rf"\w+\[{dims}\]", ln)) == 2, ln


@pytest.mark.parametrize("bucket", [128, 512, 1024])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_engine_prefill_program_compiles_with_the_blocked_kernel_inside(
        engine_programs, kv, bucket):
    """Each of the cell's three prefill programs compiles for the described
    v5e with ``prefill_attention`` INSIDE it, where its operands come from
    HBM and are double-buffered: what a lone compile of the kernel cannot
    show of VMEM (PR 27). The slot's gathered pages are the largest array
    the attention adds, and the pool keeps its layout beside the gather."""
    text, pool_shape = engine_programs(kv, f"prefill_b{bucket}")
    assert _kernels_in(text) == {"prefill_attention"}
    assert re.search(rf"%prefill_attention[.\d]* = f32\[1,16,{bucket},128\]",
                     text)
    dims = ",".join(str(n) for n in pool_shape)
    assert set(re.findall(rf"\w+\[{dims}\]\{{([\d,]*)", text)) == {
        "4,3,2,1,0"}


# -- a looped model's engine programs (PR 35): the pool in a loop's carry ----
#
# A model that runs its layers more than once (text/models/ouro.py) has the
# engine compile ONE loop over the runs with the pool in its carry: where
# PR 30 found that a ``fori_loop`` over token writes made XLA:TPU pick
# another layout for the pool. With the paged kernel in the body it does
# not, and the compiled text says so.

#: the looped cell's engine (Ouro-2.6B: 16 heads of 128, ffn 5632, 8 slots
#: x 512, page 16) at 2 layers x 4 loops: a K pool of 8 entries, 135 MB
LOOPED_PROGRAMS = ("decode", "prefill_b256")


@pytest.fixture(scope="module")
def looped_engine_programs(one_chip):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.text.models import OuroConfig, OuroForCausalLM

    made = {}

    def text_of(program):
        if program not in made:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                if "engine" not in made:
                    model = OuroForCausalLM(OuroConfig(
                        vocab_size=1024, num_hidden_layers=2,
                        total_ut_steps=4, max_position_embeddings=1024,
                    )).astype("bfloat16")
                    model.eval()
                    made["engine"] = DecodeEngine(model, EngineConfig(
                        num_slots=8, max_length=512, page_size=16,
                        kv_dtype="bf16", prompt_buckets=(64, 128, 256)))
                eng = made["engine"]
                assert eng.stats()["attn_kernel"] == "pallas" and eng._donate
                assert eng.stats()["cache_layers"] == 8
                made[program] = eng._jitted(program).lower(*jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        np.shape(a), a.dtype, sharding=one_chip),
                    eng._example_args(program))).compile().as_text()
        return made[program], made["engine"].kv.shape

    return text_of


@pytest.mark.parametrize("program", LOOPED_PROGRAMS)
def test_looped_engine_program_keeps_the_pool_in_one_layout_in_its_carry(
        looped_engine_programs, program):
    """The loops are one ``while`` with the pool in its carry, beside the
    sampler's two bisections (top-k's and top-p's), whose carry holds a
    uint32 threshold [rows, 1] and nothing of the pool's shape; every
    array of the pool's shape, the loop's carry among them, keeps the
    row-major layout it arrives in; the kernel is inside; and nothing as
    large as ONE entry's pool is copied, sliced or re-laid. (An entry's
    pool, 8.4 M elements, is smaller than an MLP matrix here, 11.5 M: the
    weights' prefetches into VMEM, rank 2, are not the pool's traffic.)"""
    text, pool_shape = looped_engine_programs(program)
    dims = ",".join(str(n) for n in pool_shape)
    whiles = [ln for ln in text.splitlines() if " while(" in ln]
    pooled = [ln for ln in whiles if f"[{dims}]" in ln]
    assert len(pooled) == 1 and len(whiles) == 3, whiles
    rows = 8 if program == "decode" else 1
    assert all(f"u32[{rows},1]" in ln for ln in whiles if ln not in pooled)
    assert set(re.findall(rf"\w+\[{dims}\]\{{([\d,]*)", text)) == {
        "4,3,2,1,0"}
    assert _kernels_in(text) == {_ENGINE_KERNEL[program.split("_")[0]]}
    big = int(np.prod(pool_shape[1:]))
    moved = [ln for ln in _pool_sized_traffic(text, big)
             if not ln.startswith("%while")  # the carry itself: in place
             and any(len(d.split(",")) > 2 and _elements(f"x[{d}]") >= big
                    for d in re.findall(r"\w+\[([\d,]*)\]", ln))]
    assert moved == []
    assert re.search(r" dynamic-update-slice\(" if program == "decode"
                     else r" scatter\(", text)


def test_the_looped_decode_kernels_line_is_what_the_benchmark_looks_for(
        looped_engine_programs):
    """Inside the loop's body the decode call is still ONE line a layer
    that ``paged_attn_roofline.json``'s pattern (and
    ``loop_paged_attn_roofline.json``'s, the same) finds: a float32 result
    with the slot axis first."""
    text, _ = looped_engine_programs("decode")
    lines = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(lines) == 2  # one a weight layer, whatever the loops
    for ln in lines:
        assert _decode_kernel_pattern(8).search(ln), ln
        assert re.search(r"%paged_attention(\.\d+)? = f32\[8,16,8,128\]", ln)


# -- a block-diffusion model's engine programs: block, commit, prefill -------
#
# SDAR-30B-A3B (text/models/sdar.py) at its widths: 32 query heads on 4 kv
# heads of 128 (8 a kernel row group, 4 rows a slot: 32 rows), 16 slots,
# page 16, and a routed-expert layer whose grouped products run inside the
# program. Two layers (the commit pass runs no experts in its last) and 16
# of the 128 experts held, an expert-parallel rank's share: the grouped
# kernel's blocks are an expert's whatever the count. The table is 3072
# positions a slot so that a K pool is the served cell's 100.7 MB (6 layers
# x 1024): a smaller pool XLA takes into VMEM whole (the note above
# ENGINE_LAYERS).

SDAR_PROGRAMS = ("block_b4", "commit_b4", "prefill_b512")


@pytest.fixture(scope="module")
def sdar_engine_programs(one_chip):
    from paddle_tpu.inference.engine import DecodeEngine, EngineConfig
    from paddle_tpu.text.models import SDARConfig, SDARForCausalLM

    made = {}

    def text_of(program):
        if program not in made:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                if "engine" not in made:
                    model = SDARForCausalLM(SDARConfig(
                        vocab_size=1024, num_hidden_layers=2,
                        max_position_embeddings=3072, expert_range=(0, 16),
                        expert_dtype="bfloat16", remasking="sequential",
                    )).astype("bfloat16")
                    model.eval()
                    made["engine"] = DecodeEngine(model, EngineConfig(
                        num_slots=16, max_length=3072, page_size=16,
                        kv_dtype="bf16", prompt_buckets=(128, 256, 512)))
                eng = made["engine"]
                assert eng.stats()["attn_kernel"] == "pallas" and eng._donate
                made[program] = eng._jitted(program).lower(*jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(
                        np.shape(a), a.dtype, sharding=one_chip),
                    eng._example_args(program))).compile().as_text()
        return made[program], made["engine"].kv.shape

    return text_of


def _metric_pattern(name, **values):
    import json
    import os
    import string

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics", name + ".json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    return re.compile(string.Template(pattern).substitute(values))


@pytest.mark.parametrize("program", SDAR_PROGRAMS)
def test_block_engine_program_keeps_the_pool_and_runs_its_kernels(
        sdar_engine_programs, program):
    """Every array of the pool's shape keeps the row-major layout, nothing
    as large as a layer's pool is copied or re-laid, and the passes call the
    paged kernel at 4 rows a slot and the grouped kernel, each on the ONE
    line the benchmark's block readers look for (a prefill's grouped calls
    have more rows and are left out of them)."""
    text, pool_shape = sdar_engine_programs(program)
    dims = ",".join(str(n) for n in pool_shape)
    assert set(re.findall(rf"\w+\[{dims}\]\{{([\d,]*)", text)) == {
        "4,3,2,1,0"}
    big = int(np.prod(pool_shape[1:]))
    moved = [ln for ln in _pool_sized_traffic(text, big)
             if any(len(d.split(",")) > 3 and _elements(f"x[{d}]") >= big
                    for d in re.findall(r"\w+\[([\d,]*)\]", ln))]
    assert moved == []
    lines = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    attn = [ln for ln in lines if "attention" in ln]
    gmm = [ln for ln in lines if "grouped_matmul_fwd" in ln]
    paged = _metric_pattern("block_attn_roofline", num_slots=16)
    routed = _metric_pattern("moe_gmm_hbm_roofline", rows=16 * 4 * 8)
    if program == "prefill_b512":
        assert all("prefill_attention" in ln for ln in attn) and len(attn) == 2
        assert len(gmm) == 4 and not any(routed.search(ln) for ln in gmm)
        return
    assert all(re.search(r"paged_attention(\.\d+)? = f32\[16,4,32,128\]", ln)
               and paged.search(ln) for ln in attn)
    assert all(routed.search(ln) for ln in gmm)
    # the commit pass runs neither the attention nor the experts of its
    # last layer: nothing reads that layer's output
    assert (len(attn), len(gmm)) == ((2, 4) if program == "block_b4"
                                     else (1, 2))
