"""C ABI serving entry (csrc/paddle_tpu_serve.cc): one inference through
the native path — load a jit.save'd StableHLO artifact and run a batch
from C, no Python written by the caller.

Reference capability: ``paddle_inference_api.h`` C++ AnalysisPredictor
(VERDICT r3 #9 / missing #6). Not in the fast tier: the test builds the
shared library and the embedded interpreter imports jax (~1 min cold).
"""
import os
import shutil
import subprocess
import sysconfig

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.static import InputSpec

_CSRC = os.path.join(os.path.dirname(__file__), "..", "csrc")
_REPO = os.path.abspath(os.path.join(_CSRC, ".."))


@pytest.mark.skipif(shutil.which("make") is None, reason="no make")
def test_one_inference_through_c_path(tmp_path):
    r = subprocess.run(["make", "-C", _CSRC, "serve_test"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr

    paddle.seed(5)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
    prefix = str(tmp_path / "toy")
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([2, 4], "float32", "x")])

    # the exact input serve_test generates: ramp 0.01*i over [2, 4]
    x = (0.01 * np.arange(8, dtype=np.float32)).reshape(2, 4)
    from paddle_tpu import inference

    pred = inference.create_predictor(inference.Config(prefix))
    expected = pred.run([x])[0]

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the embedded interpreter starts from the BASE prefix's sys.path:
    # point it at the repo and this interpreter's site-packages
    site = sysconfig.get_paths()["purelib"]
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO, site, env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    r = subprocess.run(
        [os.path.join(_CSRC, "build", "serve_test"), prefix, "2", "4"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    line = [l for l in r.stdout.splitlines() if l.startswith("OK ")][0]
    # OK n=6 rank=2 shape=[2,3] sum=<float>
    parts = dict(p.split("=", 1) for p in line[3:].split() if "=" in p)
    assert int(parts["n"]) == expected.size
    assert parts["shape"] == "[" + ",".join(str(d) for d in expected.shape) + "]"
    np.testing.assert_allclose(float(parts["sum"]), float(expected.sum()),
                               rtol=1e-4, atol=1e-5)
