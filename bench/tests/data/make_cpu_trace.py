"""Records `cpu_trace/`, the small CPU trace bench/tests/test_trace.py reads.

    JAX_PLATFORMS=cpu python3 bench/tests/data/make_cpu_trace.py

Three polls: each a `topup` annotation that sleeps 2 ms on the host, then
a `poll` annotation around one jitted call and a 1 ms host sleep.
"""
import glob
import os
import shutil
import time

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    out = os.path.join(HERE, "cpu_trace")
    shutil.rmtree(out, ignore_errors=True)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((192, 192), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("topup"):
            time.sleep(0.002)
        with jax.profiler.TraceAnnotation("poll"):
            f(x).block_until_ready()
            time.sleep(0.001)
    jax.profiler.stop_trace()
    for p in glob.glob(os.path.join(out, "plugins", "profile", "*", "*")):
        if not p.endswith(".xplane.pb"):
            os.remove(p)


if __name__ == "__main__":
    main()
