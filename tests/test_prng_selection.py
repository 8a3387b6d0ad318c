"""framework.rng PRNG auto-selection matrix (subprocess-isolated: the
decision runs at import time from env vars only — see rng.py docstring)."""
import os
import subprocess
import sys

import pytest

# NOT in the fast tier: six subprocess jax imports cost ~18s on this box;
# the selection contract still runs in the full suite.

_CODE = """
import os, jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu
print("IMPL=" + jax.config.jax_default_prng_impl)
"""


def _impl_for(env_overrides):
    env = dict(os.environ)
    for var in ("JAX_PLATFORMS", "JAX_DEFAULT_PRNG_IMPL",
                "PADDLE_TPU_PRNG_IMPL", "TPU_SKIP_MDS_QUERY", "TPU_NAME",
                "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
        env.pop(var, None)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env.update(env_overrides)
    p = subprocess.run([sys.executable, "-c", _CODE], env=env,
                       capture_output=True, text=True, timeout=180)
    for line in p.stdout.splitlines():
        if line.startswith("IMPL="):
            return line[5:]
    raise AssertionError(f"no IMPL line (rc={p.returncode}): {p.stderr[-300:]}")


def test_cpu_pinned_keeps_threefry():
    assert _impl_for({"JAX_PLATFORMS": "cpu"}) == "threefry2x32"


def test_tpu_primary_selects_rbg():
    # cpu as FALLBACK (second entry) must not disable the TPU default
    assert _impl_for({"JAX_PLATFORMS": "tpu,cpu"}) == "rbg"


def test_app_env_config_defers():
    assert _impl_for({"JAX_PLATFORMS": "tpu",
                      "JAX_DEFAULT_PRNG_IMPL": "threefry2x32"}) == "threefry2x32"


def test_explicit_opt_out_wins():
    assert _impl_for({"JAX_PLATFORMS": "tpu",
                      "PADDLE_TPU_PRNG_IMPL": "threefry"}) == "threefry2x32"


def test_explicit_override_selects():
    assert _impl_for({"JAX_PLATFORMS": "cpu",
                      "PADDLE_TPU_PRNG_IMPL": "unsafe_rbg"}) == "unsafe_rbg"
