"""High-level (hapi) training: paddle.Model.fit on a vision model.

The reference workflow (paddle.Model over paddle.vision) unchanged:
prepare(optimizer, loss, metrics) -> fit(dataset) -> evaluate/predict.
Under the hood every batch runs as ONE compiled XLA program
(fleet.DistTrainStep) and parameters live on the device mesh.

Run:  JAX_PLATFORMS=cpu python examples/train_vision_hapi.py
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


def main():
    paddle.seed(7)
    # LeNet-sized conv net on synthetic 32x32 "images" (pretrained-weight
    # downloads are environment-blocked; the workflow is identical for
    # paddle.vision.models.resnet18(num_classes=10))
    net = paddle.nn.Sequential(
        paddle.nn.Conv2D(3, 8, 3, stride=2, padding=1), paddle.nn.ReLU(),
        paddle.nn.Conv2D(8, 16, 3, stride=2, padding=1), paddle.nn.ReLU(),
        paddle.nn.AdaptiveAvgPool2D(1), paddle.nn.Flatten(),
        paddle.nn.Linear(16, 10))

    model = paddle.Model(net)
    model.prepare(
        optimizer=paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=net.parameters()),
        loss=paddle.nn.CrossEntropyLoss(),
        metrics=paddle.metric.Accuracy())

    rng = np.random.default_rng(0)
    n = 256
    xs = rng.standard_normal((n, 3, 32, 32)).astype("float32")
    # learnable rule: class = argmax of per-channel-ish slice means
    ys = xs.reshape(n, 3, -1).mean(-1).argmax(-1).astype("int64")[:, None] % 10
    data = [(xs[i], ys[i]) for i in range(n)]

    print("== fit ==")
    model.fit(data, batch_size=32, epochs=3, verbose=1, log_freq=4)
    print("== evaluate ==")
    res = model.evaluate(data, batch_size=32, verbose=0)
    print("eval:", res)
    print("== predict one batch ==")
    out = model.predict_batch([paddle.to_tensor(xs[:4])])
    print("logits shape:", tuple(np.asarray(out[0]).shape))
    print("summary:")
    model.summary((1, 3, 32, 32))


if __name__ == "__main__":
    main()
