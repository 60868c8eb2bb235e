"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the machine it is started on, which
must hold the TPU chips the cell asks for: there is no CPU fallback.
The last line of standard output is the result object (see
bench/harness.py).
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise write its logs to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    t0 = harness.process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    harness.run(a.workload, a.seed, a.seconds, bool(a.trace), t0=t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
