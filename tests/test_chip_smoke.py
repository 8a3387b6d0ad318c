"""chip_smoke.py's own code paths, tiny, on the CPU.

The script proves the hot paths on a TPU; these tests keep its control
flow, checks and exit codes honest between chip runs. What only a chip can
show (the platform, a Mosaic kernel in the compiled text, donated pools) is
replaced from THIS side; the program has no option that relaxes it.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

import chip_smoke as cs
from paddle_tpu.runtime import jax_cache
from paddle_tpu.text.models import GPTConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_gpt(**kw):
    return GPTConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0, **kw)


TRAIN = cs.TrainSizes(
    config=dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=2, intermediate_size=64,
                max_position_embeddings=64),
    runs=((8, 16, True),), steps=4, lr=1e-3)
SERVE = cs.ServeSizes(
    config=tiny_gpt, num_slots=2, max_length=128, page_size=8,
    requests=((5, 4), (9, 6), (40, 4), (20, 5), (40, 4), (12, 6)),
    shared_prefix=(4, 2, 32), alone_greedy=4, alone_sampled=5, oracle=0,
    spec_max_length=128, spec_k=2)


@pytest.fixture
def on_chip(monkeypatch):
    """Stand in for what only the chip can show."""
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.distributed.fleet import topology

    monkeypatch.setattr(cs, "_platform", lambda: "tpu")
    monkeypatch.setattr(cs, "_kernel_in", lambda text: True)
    monkeypatch.setattr(cs, "_donated", lambda eng: True)
    # engines resolve attn_kernel "auto" to the kernel, in interpret mode
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    # the suite stays off the persistent cache (conftest.py says why)
    monkeypatch.setattr(jax_cache, "configure", lambda: "<not configured>")
    monkeypatch.setattr(cs, "build_native", lambda: True)
    # chip_smoke.py starts in a fresh process: shield it from a hybrid
    # group / global mesh that a fleet test left behind in this worker
    prev = (topology.get_hybrid_communicate_group(), _mesh.get_global_mesh())
    topology.set_hybrid_communicate_group(None)
    _mesh.set_global_mesh(None)
    yield
    topology.set_hybrid_communicate_group(prev[0])
    _mesh.set_global_mesh(prev[1])


def test_train_phase_tiny(on_chip):
    report = cs.Report()
    report.run("train", cs.train_phase, TRAIN, 0)
    assert report.failed == []


def test_serve_phase_tiny(on_chip, capsys):
    report = cs.Report()
    report.run("serve", cs.serve_phase, SERVE, 0)
    assert report.failed == []
    out = capsys.readouterr().out
    # every promised check was made, none silently skipped
    for needle in ("== the same request alone", "== einsum oracle",
                   "compile count did not grow", "prefix sharing ran",
                   "int8+spec verify program ran",
                   "paged kernel in the compiled verify_k2 program"):
        assert needle in out, needle


def test_main_last_line_and_exit_code(on_chip, monkeypatch, capsys):
    ran = []
    monkeypatch.setattr(cs, "serve_phase",  # has its own test above
                        lambda report, sizes, seed: ran.append(sizes))
    rc = cs.main([], train=TRAIN, serve=SERVE)
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and ran == [SERVE]
    last = json.loads(lines[-1])
    d = jax.devices()[0]
    assert last == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}
    early = "\n".join(lines[:6])
    for needle in ("device: platform=", "versions: jax=", "default PRNG impl",
                   "native runtime available", "compile cache:"):
        assert needle in early, needle


@pytest.mark.parametrize("how", ["failed_check", "exception"])
def test_failing_phase_yields_nonzero_exit(on_chip, monkeypatch, capsys, how):
    if how == "failed_check":
        # the real predicate: no Mosaic kernel in a CPU program's text
        monkeypatch.setattr(cs, "_kernel_in",
                            lambda text: cs.KERNEL_MARKER in text)
    else:
        def boom(*a, **kw):
            raise RuntimeError("injected kernel failure")

        monkeypatch.setattr("bench._ernie_step", boom)
    ran = []
    monkeypatch.setattr(cs, "serve_phase",
                        lambda report, sizes, seed: ran.append("serve"))
    rc = cs.main([], train=TRAIN, serve=SERVE)
    captured = capsys.readouterr()
    assert rc == 1
    assert '"ok": true' not in captured.out
    assert "[FAIL]" in captured.out and "failed" in captured.err
    assert ran == ["serve"]  # later phases still report


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = cs.main([])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "no TPU" in captured.err


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placed_from_outside(monkeypatch, tmp_path, from_env):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    seen = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (seen.append(k), real_update(k, v))[1])
    try:
        if from_env:
            monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
            assert jax_cache.configure() == str(tmp_path)
            # JAX reads the variable itself; no directory is set in code
            assert "jax_compilation_cache_dir" not in seen
        else:
            monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
            want = os.path.join(REPO, ".jax_cache")
            assert jax_cache.configure() == want
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        for k, v in before.items():
            real_update(k, v)
        cc.reset_cache()


def test_unsharded_arrays_fail_the_spread_check(capsys):
    """What a virtual CPU mesh can hide: everything on the first device."""
    import jax.numpy as jnp

    devices = jax.devices()[:2]
    report = cs.Report()
    piled = jax.device_put(jnp.ones((8, 8)), devices[0])
    cs._check_spread(report, "piled", [piled], devices)
    assert report.failed == ["piled sharded, nothing piled on one device"]


def test_chips4_path_on_four_virtual_devices():
    """`--chips 4` end to end in a process with exactly four CPU devices:
    dp2 x mp2 against one device, the mp2 engine against the one-device
    engine, the per-device byte checks, and count 4 in the last line."""
    code = textwrap.dedent("""
        import sys
        import chip_smoke as cs
        from paddle_tpu.runtime import jax_cache
        from test_chip_smoke import tiny_gpt

        cs._platform = lambda: "tpu"
        cs.build_native = lambda: True
        jax_cache.configure = lambda: "<not configured>"
        multi = cs.MultiSizes(
            config=tiny_gpt, batch=4, seq=16, steps=3, lr=1e-3, num_slots=2,
            max_length=64, page_size=8, requests=((5, 4), (12, 6)))
        ran = []
        cs.train_phase = cs.serve_phase = lambda *a: ran.append(a)
        rc = cs.main(["--chips", "4"], multi=multi)
        assert not ran, "--chips 4 ran a one-chip phase"
        sys.exit(rc)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "tests")]))
    env["XLA_FLAGS"] = " ".join(
        [f for f in env.get("XLA_FLAGS", "").split()
         if "xla_force_host_platform_device_count" not in f]
        + ["--xla_force_host_platform_device_count=4"])
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    assert json.loads(lines[-1])["device"]["count"] == 4
    assert "mesh dp=2 mp=2" in p.stdout
    for needle in ("multi parameters sharded, nothing piled on one device",
                   "multi optimizer state sharded",
                   "multi KV pool sharded",
                   "multi dp2 x mp2 losses == one device",
                   "multi mp2 engine request 0 == one-device engine"):
        assert f"[ok] {needle}" in p.stdout, needle
    assert "[FAIL]" not in p.stdout
