"""Record ``cpu_step.xplane.pb`` and ``cpu_step.hlo.txt``, the small CPU
trace the reduction's tests read:

    JAX_PLATFORMS=cpu python3 bench/testdata/make_cpu_trace.py

Three executions of a jitted step with a matmul under the
``exchange/bucket0/quantize_collective`` scope and one outside it."""

import glob
import os
import shutil

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


@jax.jit
def step(x, w):
    with jax.named_scope("exchange/bucket0/quantize_collective"):
        y = jnp.tanh(x @ w)
    return (y @ w.T).sum()


def main():
    x = jnp.ones((512, 512)) * 0.01
    step(x, x).block_until_ready()
    out = os.path.join(HERE, "_trace")
    jax.profiler.start_trace(out)
    for _ in range(3):
        r = step(x, x)
    r.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(HERE, "cpu_step.xplane.pb"))
    shutil.rmtree(out)
    with open(os.path.join(HERE, "cpu_step.hlo.txt"), "w") as f:
        f.write(step.lower(x, x).compile().as_text())


if __name__ == "__main__":
    main()
