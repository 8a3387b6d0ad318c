"""Long-context training: ring attention over the `sep` (context-
parallel) mesh axis.

The sequence dimension shards across devices; attention runs as a ring —
each device holds one sequence shard of Q and rotates K/V shards around
the `sep` axis with `ppermute`, accumulating the softmax online. The
full [seq, seq] score matrix and the full-sequence activations NEVER
materialize on one chip, which is how context lengths exceed single-chip
HBM (the reference's sequence-parallel / DistAttention capability,
re-expressed as XLA collectives; paddle_tpu/nn/functional/ring_attention.py).

Run:  JAX_PLATFORMS=cpu python examples/train_long_context.py
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
from paddle_tpu import nn
from paddle_tpu.distributed import fleet
from paddle_tpu.framework.op import defop
from paddle_tpu.nn.functional.ring_attention import (
    context_parallel_attention,
)

VOCAB, HID, HEADS, SEQ = 128, 64, 4, 1024


@defop(name="ring_attn_example")
def ring_attn(q, k, v):
    # defop unwraps Tensors to raw arrays for the jax-level kernel and
    # hooks the result back into the autograd tape
    return context_parallel_attention(q, k, v, causal=True)


class LongContextLM(nn.Layer):
    """One attention block + LM head; attention is the ring kernel."""

    def __init__(self):
        super().__init__()
        self.emb = nn.Embedding(VOCAB, HID)
        self.qkv = nn.Linear(HID, 3 * HID)
        self.proj = nn.Linear(HID, HID)
        self.norm = nn.LayerNorm(HID)
        self.head = nn.Linear(HID, VOCAB)

    def forward(self, ids, labels=None):
        h = self.emb(ids)
        q, k, v = paddle.split(self.qkv(h), 3, axis=-1)
        r = lambda t: t.reshape(
            (t.shape[0], t.shape[1], HEADS, HID // HEADS))
        # ring attention: K/V shards rotate around the sep axis
        a = ring_attn(r(q), r(k), r(v))
        h = self.norm(h + self.proj(
            a.reshape((h.shape[0], h.shape[1], HID))))
        logits = self.head(h)
        loss = paddle.nn.functional.cross_entropy(
            logits.reshape((-1, VOCAB)), labels.reshape((-1,)))
        return loss


def main():
    import jax

    ndev = len(jax.devices())
    sep = 4 if ndev >= 8 else max(ndev // 2, 1)
    s = fleet.DistributedStrategy()
    # context parallelism on `sep`; the rest of the devices do dp
    s.hybrid_configs.update(dp_degree=ndev // sep, mp_degree=1,
                            pp_degree=1, sep_degree=sep)
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(11)

    model = LongContextLM()
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(
        model, lambda m, ids, lbl: m(ids, labels=lbl), opt)

    print(f"mesh: dp={ndev // sep} x sep={sep}, seq={SEQ} "
          f"(each device holds a {SEQ // sep}-token shard)")
    rng = np.random.default_rng(0)
    # next-token objective: inputs see tokens[:-1], labels are the
    # SHIFTED tokens[1:] (unshifted labels would train an identity copy)
    tokens = rng.integers(0, VOCAB, (2, SEQ + 1)).astype(np.int32)
    ids = paddle.to_tensor(tokens[:, :-1])
    labels = paddle.to_tensor(tokens[:, 1:])
    for it in range(8):
        loss = float(step(ids, labels))
        if it % 2 == 0:
            print(f"step {it} loss {loss:.4f}")
    print("final loss", loss)
    assert np.isfinite(loss)


if __name__ == "__main__":
    main()
