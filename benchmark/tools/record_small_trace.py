#!/usr/bin/env python3
"""Record the small trace that benchmark/tests pins trace_reduce.py on: three
steps of a toy program (a matrix product inside a loop, so that a while op
encloses its body's ops) under the harness's own spans, with a sleep between
steps so that there are idle gaps to attribute. Run once on the chip:

    python3 benchmark/tools/record_small_trace.py chiprun_out/small_trace
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

import harness  # noqa: E402


def main(out):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def toy(x):
        return jax.lax.fori_loop(0, 4, lambda i, a: jnp.tanh(a @ a), x)

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    toy(x).block_until_ready()
    spans = harness.Spans()
    tracer = harness.Tracer(True, out)
    tracer.start()
    for _ in range(3):
        with spans.span("make_batch"):
            time.sleep(0.01)
        with spans.span("train.step"):
            toy(x).block_until_ready()
    tracer.stop()
    print("trace in", out, [s.seconds for s in spans.rows])


if __name__ == "__main__":
    main(sys.argv[1])
