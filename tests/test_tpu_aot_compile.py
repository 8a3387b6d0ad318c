"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test here) cannot see what Mosaic
refuses: a slice off the tiling, more VMEM than a kernel may hold. The
TPU compiler is installed in the CPU sandbox and compiles for a chip that
is described, not attached, so these cases guard the flash, paged and
grouped-matmul kernels at the shapes `chip_smoke.py` and the roadmap's
cells run them at — at no chip time. Nothing executes: a compile that
passes says nothing about results or speed.

The topology is described inside a fixture, after a test of this file has
started, and compiled in the test's own process (libtpu allows one process
at a time); keep every such case in THIS file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import flash_attention as flash_mod
from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
from paddle_tpu.ops.pallas.paged_attention import paged_attention


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


# -- paged attention (serving path: GPT-1.3B, 8 slots, 2048 tokens, page 16) -

def _paged_shapes(kv, slots, t, heads=16, kv_heads=16):
    d, page, max_pages = 128, 16, 128
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


#: (slots, T): the decode and verify passes over 8 slots, and the tail
#: prefill's one slot at each bucket, where a grid step's head block is
#: what the VMEM budget leaves (16, 4 and 2 heads of the 16). A lone
#: kernel's compile is LENIENT on VMEM: XLA keeps its small query and
#: result in VMEM, where the engine's program double-buffers them from HBM
#: (tests/test_pallas_attention.py pins the estimate on the chip's count)
PAGED_CALLS = {"decode": (8, 1), "verify_k4": (8, 5),
               "prefill_128": (1, 128), "prefill_512": (1, 512),
               "prefill_1024": (1, 1024)}


@pytest.mark.parametrize("call", list(PAGED_CALLS))
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_attention_compiles(one_chip, kv, call):
    _compile(_paged, one_chip, *_paged_shapes(kv, *PAGED_CALLS[call]))


@pytest.mark.parametrize("call", ["decode", "prefill_512"])
def test_paged_attention_gqa_compiles(one_chip, call):
    """Llama's layout: 32 query heads on 8 kv heads, four a kernel row
    group (at bucket 512 a head's row block is 2048 rows: one head a
    step)."""
    _compile(_paged, one_chip, *_paged_shapes(
        "bf16", *PAGED_CALLS[call], heads=32, kv_heads=8))


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
    "flash_attention_fwd": "flash",
    "flash_attention_bwd_dq": "flash",
    "flash_attention_bwd_dkv": "flash",
    "grouped_matmul_fwd": "gmm",
    "grouped_matmul_drhs": "gmm",
}


@pytest.fixture(scope="module")
def named_programs(one_chip):
    """The compiled text of one forward+backward flash program, one paged
    decode program and one forward+backward grouped matmul, compiled when
    the first case asks."""
    texts = {}

    def flash(q, k, v):
        return jax.grad(
            lambda *a: flash_mod.flash_attention(*a)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    def gmm(lhs, rhs, sizes):
        return jax.grad(
            lambda a, b: grouped_matmul(a, b, sizes, interpret=False)
            .astype(jnp.float32).sum(), argnums=(0, 1))(lhs, rhs)

    qkv = (FLASH_SHAPES["ernie_b32_t1024_h12_d64"], jnp.bfloat16)
    programs = {
        "flash": (flash, (qkv, qkv, qkv)),
        "paged": (_paged, _paged_shapes("bf16", *PAGED_CALLS["decode"])),
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


def test_the_decode_kernels_line_is_what_the_benchmark_looks_for(
        named_programs):
    """``paged_attn_roofline`` and ``paged_attn_time_pct`` find the decode
    kernel's device events by their HLO line: ONE custom call whose single
    result is float32 with the slot axis first. A tuple result, a bf16
    result or another leading axis silences both. The pattern is read from
    the benchmark's own file, ``$num_slots`` filled in as its reader does."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "metrics",
                           "paged_attn_roofline.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    assert "$num_slots" in pattern
    rx = re.compile(pattern.replace("$num_slots", "8"))
    lines = [ln for ln in named_programs("paged").splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert len(lines) == 1, lines
    assert rx.search(lines[0]), lines[0]
    assert re.search(r"%paged_attention(\.\d+)? = f32\[8,", lines[0])
