"""On-chip microbench for the ragged grouped matmul (dropless-MoE hot op).

Compares the Pallas kernel (`paddle_tpu.ops.pallas.grouped_matmul`) against
the two honest XLA alternatives a dropless MoE would otherwise use:
  - `lax.ragged_dot` (XLA's own ragged contraction, where available);
  - the dense one-hot dispatch einsum (computes G x the useful FLOPs).

Covers the reference capability of fused expert GEMMs
(upstream: paddle/incubate MoE expert parallel compute path, SURVEY §2.2
Incubate row) with silicon numbers. Writes GMM_TPU.json at repo root.

Needs a TPU; exits 1 without one.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul  # noqa: E402
from paddle_tpu.runtime import jax_cache  # noqa: E402


def _timed(fn, *args, warmup=3, iters=20):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1000.0  # ms


def _git_head():
    try:
        import subprocess
        return subprocess.check_output(
            ["git", "-C", REPO, "rev-parse", "HEAD"], text=True).strip()
    except Exception:
        return None


def bench_config(m, k, n, g, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    # Imbalanced but full occupancy: draw group sizes from a Dirichlet so
    # the schedule exercises ragged (non-uniform) group boundaries.
    props = rng.dirichlet(np.ones(g) * 2.0)
    sizes = np.floor(props * m).astype(np.int64)
    sizes[-1] += m - sizes.sum()
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)) / np.sqrt(k), dtype)
    gs = jnp.asarray(sizes, jnp.int32)

    flops = 2.0 * m * k * n  # useful FLOPs (every row hits one expert)

    pallas_fn = jax.jit(lambda l, r, s: grouped_matmul(l, r, s))
    pallas_ms = _timed(pallas_fn, lhs, rhs, gs)

    # fwd+bwd through the kernel's custom VJP
    loss = jax.jit(jax.grad(
        lambda l, r: (grouped_matmul(l, r, gs).astype(jnp.float32) ** 2
                      ).mean(), argnums=(0, 1)))
    pallas_fb_ms = _timed(loss, lhs, rhs)

    entry = {
        "m": m, "k": k, "n": n, "g": g, "dtype": "bf16",
        "group_sizes": sizes.tolist(),
        "pallas_fwd_ms": round(pallas_ms, 3),
        "pallas_fwd_tflops": round(flops / pallas_ms / 1e9, 2),
        "pallas_fwdbwd_ms": round(pallas_fb_ms, 3),
    }

    # XLA ragged_dot where this jax exposes it.
    if hasattr(jax.lax, "ragged_dot"):
        rd = jax.jit(lambda l, r, s: jax.lax.ragged_dot(l, r, s))
        try:
            rd_ms = _timed(rd, lhs, rhs, gs)
            entry["ragged_dot_ms"] = round(rd_ms, 3)
            entry["speedup_vs_ragged_dot"] = round(rd_ms / pallas_ms, 3)
            ref = rd(lhs, rhs, gs)
            got = pallas_fn(lhs, rhs, gs)
            entry["max_abs_diff_vs_ragged_dot"] = float(
                jnp.max(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
        except Exception as e:  # pragma: no cover - backend-dependent
            entry["ragged_dot_error"] = repr(e)[:200]

    # Dense one-hot dispatch: the no-kernel fallback shape of the same op.
    def dense(l, r, s):
        bounds = jnp.cumsum(s)
        starts = bounds - s
        rows = jnp.arange(l.shape[0])[:, None]
        onehot = ((rows >= starts[None, :]) & (rows < bounds[None, :]))
        return jnp.einsum("mg,mk,gkn->mn", onehot.astype(l.dtype), l, r)

    dense_fn = jax.jit(dense)
    dense_ms = _timed(dense_fn, lhs, rhs, gs)
    entry["dense_onehot_ms"] = round(dense_ms, 3)
    entry["speedup_vs_dense"] = round(dense_ms / pallas_ms, 3)
    return entry


def main():
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "not on tpu", "backend":
                          jax.default_backend()}))
        return 1
    jax_cache.configure()
    configs = [
        # (tokens, d_model, d_ff, experts) — MoE MLP up-projection shapes
        (8192, 1024, 4096, 8),
        (16384, 2048, 5504, 16),
        (8192, 4096, 14336, 8),
    ]
    out = {"captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "git_sha": _git_head(),
           "platform": str(jax.devices()[0]).split(":")[0],
           "configs": []}
    for m, k, n, g in configs:
        entry = bench_config(m, k, n, g)
        print(json.dumps(entry))
        out["configs"].append(entry)
    with open(os.path.join(REPO, "GMM_TPU.json"), "w") as f:
        json.dump(out, f, indent=1)
    print("wrote GMM_TPU.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())
