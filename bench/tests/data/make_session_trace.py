"""Records `session_trace/`, the CPU trace bench/tests/test_scopes.py reads.

    JAX_PLATFORMS=cpu python3 bench/tests/data/make_session_trace.py

A tiny `ClientSession` (W = 16, B = 2) over `MockProvider`, profiling on,
polled three times under the profiler, each poll inside a `poll`
annotation as the benchmark's live entry writes it.
"""
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "..", "..", "..", "src")]

import jax  # noqa: E402

from repro.client import ClientSession, MockProvider, Request, SessionConfig  # noqa: E402
from repro.core.policy import strategy  # noqa: E402
from repro.sim.provider import default_physics  # noqa: E402


def main():
    out = os.path.join(HERE, "session_trace")
    shutil.rmtree(out, ignore_errors=True)
    phys = default_physics()
    sess = ClientSession(MockProvider(phys, dt_ms=25.0),
                         strategy("final_adrr_olc"),
                         SessionConfig(window=16, max_grants=2),
                         clock="virtual", phys=phys)
    for i in range(12):
        sess.submit(Request(rid=0, prompt=None, max_new=40.0, p50=40.0,
                            bucket=i % 4, arrival_s=0.01 * i))
    sess.enable_profiling()
    for _ in range(3):
        sess.poll()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("poll"):
            sess.poll()
    jax.profiler.stop_trace()
    for p in glob.glob(os.path.join(out, "plugins", "profile", "*", "*")):
        if not p.endswith(".xplane.pb"):
            os.remove(p)


if __name__ == "__main__":
    main()
