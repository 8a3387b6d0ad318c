"""On-chip conv layout diagnosis for the ResNet-50 MFU question.

Times fwd+bwd for every distinct conv shape in ResNet-50 under
  (a) NCHW logical layout (the framework's current paddle-convention path,
      XLA layout assignment picks the physical layout), and
  (b) explicit NHWC end-to-end,
plus the stem (7x7/2 on 3 channels) against its space-to-depth rewrite
(4x4/1 on 12 channels at half resolution — the classic TPU stem fix).

Output: one JSON line per shape with ms + ratio, then a summary estimate
of the total step-time delta the better layout would buy. Informs whether
vision models should grow a data_format="NHWC" fast path (upstream paddle
exposes data_format on vision ops; SURVEY §2.2 Vision row).
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# (label, N set later) distinct ResNet-50 conv shapes:
# (in_ch, out_ch, kernel, stride, spatial_in, count_in_model)
SHAPES = [
    ("stem7x7", 3, 64, 7, 2, 224, 1),
    ("l1_1x1a", 64, 64, 1, 1, 56, 1),
    ("l1_3x3", 64, 64, 3, 1, 56, 3),
    ("l1_1x1b", 64, 256, 1, 1, 56, 3),
    ("l1_proj", 64, 256, 1, 1, 56, 1),
    ("l1_1x1c", 256, 64, 1, 1, 56, 2),
    ("l2_red", 256, 128, 1, 1, 56, 1),
    ("l2_3x3s2", 128, 128, 3, 2, 56, 1),
    ("l2_3x3", 128, 128, 3, 1, 28, 3),
    ("l2_1x1b", 128, 512, 1, 1, 28, 4),
    ("l2_proj", 256, 512, 1, 2, 56, 1),
    ("l2_1x1c", 512, 128, 1, 1, 28, 3),
    ("l3_red", 512, 256, 1, 1, 28, 1),
    ("l3_3x3s2", 256, 256, 3, 2, 28, 1),
    ("l3_3x3", 256, 256, 3, 1, 14, 5),
    ("l3_1x1b", 256, 1024, 1, 1, 14, 6),
    ("l3_proj", 512, 1024, 1, 2, 28, 1),
    ("l3_1x1c", 1024, 256, 1, 1, 14, 5),
    ("l4_red", 1024, 512, 1, 1, 14, 1),
    ("l4_3x3s2", 512, 512, 3, 2, 14, 1),
    ("l4_3x3", 512, 512, 3, 1, 7, 2),
    ("l4_1x1b", 512, 2048, 1, 1, 7, 3),
    ("l4_proj", 1024, 2048, 1, 2, 14, 1),
    ("l4_1x1c", 2048, 512, 1, 1, 7, 2),
]


def _timed(fn, args, warmup=2, iters=10):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1000.0


def conv_ms(batch, cin, cout, k, s, hw, layout):
    # odd k: symmetric SAME pad; even k (space-to-depth stem): asymmetric
    # (lo, hi) = ((k-1)//2, k//2) so a 4x4/1 conv keeps the 112 spatial dim
    pad_lo, pad_hi = (k - 1) // 2, k // 2
    if layout == "NCHW":
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (batch, cin, hw, hw)), jnp.bfloat16)
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            (cout, cin, k, k)) * 0.05, jnp.bfloat16)
        dn = ("NCHW", "OIHW", "NCHW")
    else:
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (batch, hw, hw, cin)), jnp.bfloat16)
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            (k, k, cin, cout)) * 0.05, jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")

    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (s, s), [(pad_lo, pad_hi), (pad_lo, pad_hi)],
            dimension_numbers=dn)

    g = jax.jit(jax.grad(lambda x, w: f(x, w).astype(jnp.float32).mean(),
                         argnums=(0, 1)))
    return _timed(g, (x, w))


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "not on tpu"}))
        return 1
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.runtime import jax_cache

    jax_cache.configure()
    tot = {"NCHW": 0.0, "NHWC": 0.0}
    for label, cin, cout, k, s, hw, count in SHAPES:
        row = {"shape": label, "count": count}
        for layout in ("NCHW", "NHWC"):
            ms = conv_ms(batch, cin, cout, k, s, hw, layout)
            row[layout + "_ms"] = round(ms, 3)
            tot[layout] += ms * count
        row["nhwc_speedup"] = round(row["NCHW_ms"] / row["NHWC_ms"], 3)
        print(json.dumps(row))
    # space-to-depth stem: 4x4/1 on 112x112x12 (equivalent receptive field
    # after the MLPerf weight rearrangement; ~30% more MACs, far better
    # MXU occupancy on the 12-channel input)
    s2d = conv_ms(batch, 12, 64, 4, 1, 112, "NHWC")
    print(json.dumps({"shape": "stem_space_to_depth_nhwc",
                      "ms": round(s2d, 3)}))
    print(json.dumps({
        "batch": batch,
        "sum_conv_fwdbwd_ms": {k: round(v, 2) for k, v in tot.items()},
        "note": "sums weight conv counts; excludes BN/ReLU/pool/fc",
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
