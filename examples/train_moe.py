"""Mixture-of-Experts training with expert parallelism on a device mesh.

A GShard-style MoELayer (stacked expert weights [E, ...], top-2 gating,
load-balancing aux loss) trains inside a tiny transformer-ish net. The
expert dim shards over the mesh's data axis — expert dispatch/combine
compile to XLA all-to-alls over ICI instead of the reference's
global_scatter/global_gather custom ops.

Run:  JAX_PLATFORMS=cpu python examples/train_moe.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS") == "cpu":
    import _cpu_mesh_flags

    _cpu_mesh_flags.apply()
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.incubate import MoELayer


class MoENet(paddle.nn.Layer):
    def __init__(self, d_model=32, d_hidden=64, experts=8, classes=4):
        super().__init__()
        self.embed = paddle.nn.Linear(16, d_model)
        self.moe = MoELayer(d_model=d_model, d_hidden=d_hidden,
                            num_experts=experts, top_k=2)
        self.head = paddle.nn.Linear(d_model, classes)

    def forward(self, x):
        h = paddle.nn.functional.relu(self.embed(x))
        h = self.moe(h)  # dispatch -> expert FFNs -> combine (+aux loss)
        return self.head(h.mean(axis=1))


def main():
    import jax

    ndev = len(jax.devices())
    s = fleet.DistributedStrategy()
    # experts ride the sharding axis; dp provides data parallelism
    s.hybrid_configs.update(dp_degree=2, mp_degree=1, pp_degree=1)
    s.hybrid_configs["sharding_degree"] = max(ndev // 2, 1)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(3)

    net = MoENet()
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=net.parameters())
    fleet.distributed_model(net)
    opt = fleet.distributed_optimizer(opt)

    def loss_fn(m, x, y):
        ce = paddle.nn.functional.cross_entropy(m(x), y)
        # the gate's load-balancing loss keeps experts evenly used
        return ce + m.moe.last_aux_loss

    step = fleet.DistTrainStep(net, loss_fn, opt)

    rng = np.random.default_rng(0)
    for it in range(30):
        x = rng.standard_normal((16, 8, 16)).astype("float32")
        y = (x.mean((1, 2)) > 0).astype("int32") * 2 + (
            x.std((1, 2)) > 1).astype("int32")
        loss = float(step(paddle.to_tensor(x), paddle.to_tensor(y)))
        # NOTE: net.moe.last_aux_loss holds a TRACED value after the
        # compiled step ran — it is consumed inside loss_fn; reading it
        # here would be a host sync on a tracer
        if it % 5 == 0:
            print(f"step {it:3d} loss {loss:.4f} (ce + moe aux)")
    print("final loss", loss)
    assert np.isfinite(loss)


if __name__ == "__main__":
    main()
