"""Persistent AOT compile cache (paddle_tpu/runtime/compile_cache.py,
docs/AUTOPLAN.md §4).

Tier-1 gates the FINGERPRINT contract — any config / topology / version
perturbation must change the key (a wrong hit would deserialize an
executable built for another world), identical re-lowers must hit, and a
corrupt entry must fall back to a fresh compile with a
``compile_cache_corrupt`` event, never a crash. The warm-process ≥5×
compile-time win runs subprocess-isolated in the slow tier: deserialized
CPU executables on this jaxlib can abort on re-execution (see
tests/conftest.py), so tier-1 never executes a deserialized program.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.observability as obs
from paddle_tpu.runtime import compile_cache


@pytest.fixture
def cache(tmp_path):
    return compile_cache.CompileCache(str(tmp_path / "aot"))


@pytest.fixture
def tdir(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tmp_path / "tel"))
    monkeypatch.delenv("PADDLE_TRAINER_ID", raising=False)
    obs.reset()
    yield tmp_path / "tel"
    obs.reset()


def _events(tdir, rank=0):
    p = tdir / f"events_rank{rank}.jsonl"
    if not p.exists():
        return []
    return [json.loads(l) for l in p.read_text().splitlines() if l.strip()]


def _lower(fn=None):
    f = fn or (lambda x: x + 1.0)
    return jax.jit(f).lower(jnp.zeros((4,), jnp.float32))


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------
def test_key_deterministic_across_relower(cache):
    k1 = cache.key_for(_lower(), config={"a": 1})
    k2 = cache.key_for(_lower(), config={"a": 1})
    assert k1 == k2


def test_module_text_differentiates_programs(cache):
    k1 = cache.key_for(_lower(lambda x: x + 1.0), config={"a": 1})
    k2 = cache.key_for(_lower(lambda x: x * 2.0), config={"a": 1})
    assert k1 != k2


def test_config_perturbation_misses(cache):
    low = _lower()
    base = cache.key_for(low, config={"bucket_mb": 32, "donate": True})
    assert cache.key_for(low, config={"bucket_mb": 64, "donate": True}) \
        != base
    assert cache.key_for(low, config={"bucket_mb": 32, "donate": False}) \
        != base
    # key order must NOT matter (canonical JSON)
    assert cache.key_for(low, config={"donate": True, "bucket_mb": 32}) \
        == base


def test_topology_perturbation_misses(cache):
    class FakeMesh:
        def __init__(self, shape):
            self.shape = shape

    low = _lower()
    k_none = cache.key_for(low, config={})
    k_m1 = cache.key_for(low, config={}, mesh=FakeMesh({"dp": 2, "mp": 4}))
    k_m2 = cache.key_for(low, config={}, mesh=FakeMesh({"dp": 4, "mp": 2}))
    assert len({k_none, k_m1, k_m2}) == 3


def test_version_perturbation_misses(cache, monkeypatch):
    low = _lower()
    base = cache.key_for(low, config={})
    monkeypatch.setattr(jax, "__version__", "0.0.0-perturbed")
    assert cache.key_for(low, config={}) != base


def test_format_bump_misses(cache, monkeypatch):
    low = _lower()
    base = cache.key_for(low, config={})
    monkeypatch.setattr(compile_cache, "_FORMAT", compile_cache._FORMAT + 1)
    assert cache.key_for(low, config={}) != base


def test_schedule_and_extra_parts_fingerprinted(cache):
    low = _lower()
    keys = {
        cache.key_for(low, config={}, schedule="1f1b"),
        cache.key_for(low, config={}, schedule="zero_bubble"),
        cache.key_for(low, config={}, schedule="1f1b", extra={"v": 2}),
    }
    assert len(keys) == 3


# ---------------------------------------------------------------------------
# hit / miss / corruption
# ---------------------------------------------------------------------------
def test_identical_relower_hits(cache):
    low1 = _lower()
    key = cache.key_for(low1, config={"p": 1})
    compiled, hit = cache.load_or_compile(low1, key, where="t")
    assert not hit and compiled is not None
    assert os.path.exists(cache.path_for(key))
    # a second process would re-lower the same program: same key, a hit
    low2 = _lower()
    assert cache.key_for(low2, config={"p": 1}) == key
    compiled2, hit2 = cache.load_or_compile(low2, key, where="t")
    assert hit2 and compiled2 is not None


def test_corrupt_entry_falls_back_to_fresh_compile(cache, tdir):
    low = _lower()
    key = cache.key_for(low, config={})
    with open(cache.path_for(key), "wb") as f:
        f.write(b"\x00not a pickle of an executable\xff")
    compiled, hit = cache.load_or_compile(low, key, where="t")
    assert not hit and compiled is not None          # fresh compile
    ev = [e for e in _events(tdir) if e["kind"] == "compile_cache_corrupt"]
    assert len(ev) == 1 and ev[0]["where"] == "t"
    snap = obs.snapshot()["metrics"]
    assert snap["compile_cache_corrupt_total"]["values"] == {"where=t": 1}
    # the poisoned entry was evicted, then re-stored by the fresh compile
    with open(cache.path_for(key), "rb") as f:
        assert f.read(4) != b"\x00not"


def test_wrong_key_header_treated_as_corrupt(cache):
    low = _lower()
    k1 = cache.key_for(low, config={"a": 1})
    k2 = cache.key_for(low, config={"a": 2})
    compiled, _ = cache.load_or_compile(low, k1, where="t")
    # copy k1's blob onto k2's path: header key mismatch must not load
    with open(cache.path_for(k1), "rb") as f:
        blob = f.read()
    with open(cache.path_for(k2), "wb") as f:
        f.write(blob)
    assert cache.load(k2, where="t") is None
    assert not os.path.exists(cache.path_for(k2))    # evicted


def test_store_failure_is_nonfatal(cache):
    assert cache.store("k", object(), where="t") is False


# ---------------------------------------------------------------------------
# resolution / gating
# ---------------------------------------------------------------------------
def test_resolve_disabled_by_default(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.resolve() is None


def test_resolve_env_and_explicit(tmp_path, monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    c = compile_cache.resolve()
    assert c is not None and c.directory == str(tmp_path / "env")
    c2 = compile_cache.resolve(str(tmp_path / "explicit"))
    assert c2.directory == str(tmp_path / "explicit")


# ---------------------------------------------------------------------------
# slow tier: warm process ≥5× compile win, bit-identical steps
# ---------------------------------------------------------------------------
_CHILD = """
import json, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.optimizer import AdamW
from paddle_tpu.runtime import compile_cache
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

paddle.seed(0)
model = GPTForCausalLM(GPTConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, max_position_embeddings=64,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
step = TrainStep(model, lambda m, i, l: m(i, labels=l), opt)
ids = np.random.default_rng(0).integers(0, 256, (4, 32), dtype=np.int64)
# time the COMPILE phase alone (tracing/lowering is paid either way)
lowered = step._lower_for(ids, ids)
aot = compile_cache.resolve()
t0 = time.perf_counter()
if aot is None:
    compiled, hit = lowered.compile(), False
else:
    key = aot.key_for(lowered, config=step._aot_key_parts(),
                      mesh=step._aot_mesh())
    compiled, hit = aot.load_or_compile(lowered, key, where="bench")
compile_s = time.perf_counter() - t0
losses = [float(step(ids, ids)) for _ in range(3)]
print(json.dumps({"compile_s": compile_s, "hit": hit, "losses": losses}))
"""


def _run_child(env_extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env.update(env_extra)
    p = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert p.returncode == 0 and lines, (
        f"child rc={p.returncode}: {p.stderr[-500:]}")
    return json.loads(lines[-1])


@pytest.mark.slow
def test_warm_process_compile_speedup_and_bit_identity(tmp_path):
    cache_dir = str(tmp_path / "aot")
    off = _run_child({})
    cold = _run_child({compile_cache.ENV_VAR: cache_dir})
    warm = _run_child({compile_cache.ENV_VAR: cache_dir})
    assert not off["hit"] and not cold["hit"] and warm["hit"]
    # bit-identical training across cache-off / cold / warm
    assert off["losses"] == cold["losses"] == warm["losses"]
    # the relaunched process must get (most of) the compile back
    assert warm["compile_s"] * 5 <= cold["compile_s"], (
        f"warm {warm['compile_s']:.2f}s vs cold {cold['compile_s']:.2f}s")
