"""Static-graph training: Program capture + Executor, paddle 1.x style.

Ops run inside ``static.program_guard`` are RECORDED into a Program
instead of executing per-op; ``append_backward`` records the gradient
ops; the Executor compiles the whole program (forward + backward) as ONE
jit-replayed XLA program and caches the executable across run() calls —
the TPU reshaping of the reference's ProgramDesc + InterpreterCore
(SURVEY.md §3.4).

Run:  JAX_PLATFORMS=cpu python examples/train_static_program.py
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
import paddle_tpu.static as static


def main():
    paddle.seed(0)
    main_prog = static.Program()
    with static.program_guard(main_prog):
        x = static.data("x", [None, 8], "float32")
        y = static.data("y", [None, 1], "float32")
        h = static.nn.fc(x, 32, activation="relu", name="fc1")
        pred = static.nn.fc(h, 1, name="fc2")
        loss = paddle.mean((pred - y) ** 2)
        grads = static.append_backward(loss)  # [(param, grad_var), ...]

    exe = static.Executor()
    rng = np.random.default_rng(0)
    true_w = rng.standard_normal((8, 1)).astype("float32")
    lr = 0.05
    print(f"program captured: {main_prog.num_ops()} ops, "
          f"{len(grads)} trainable params")
    for step in range(60):
        xb = rng.standard_normal((64, 8)).astype("float32")
        yb = xb @ true_w + 0.01 * rng.standard_normal((64, 1)).astype("f")
        fetches = [loss] + [g for _, g in grads]
        vals = exe.run(main_prog, feed={"x": xb, "y": yb},
                       fetch_list=fetches)
        step_loss, grad_vals = vals[0], vals[1:]
        # classic static-mode SGD: apply fetched grads to the parameters
        for (p, _), g in zip(grads, grad_vals):
            p.set_value(p.numpy() - lr * g)
        if step % 10 == 0:
            print(f"step {step:3d} loss {float(step_loss):.5f}")
    assert float(step_loss) < 0.1, "static training did not converge"
    print("converged; final loss", float(step_loss))


if __name__ == "__main__":
    main()
