"""Host cost of a jitted call's host-resident arguments: the five per-poll
inputs of the live session's fused tick as separate NumPy arguments,
against the same bytes packed into one flat f32 buffer (DESIGN.md §8,
"One transfer each way").

Each NumPy array or scalar handed to a jitted call is a device put of
its own.  Both cases call a jitted function that reads one element of
each input and returns a scalar, so the device work is nil and the time
is the call's argument handling, its launch and, for the round trip,
the wait for the result.  Shapes are the live cell's, W = 256, B = 4:
the (2B,) verdicts, `comp` (2, W), `staged` (7, W), `n_stage` and `now`.

    python benchmarks/host_puts.py            # on the chip
    python benchmarks/host_puts.py --smoke    # few calls, any backend

Prints one JSON object with the device and, per case, us per call: the
call alone (`dispatch_us`, what `session.dispatch` spans) and the call
with the blocking pull of its result (`round_trip_us`).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

REPS = 2000
W, B = 256, 4


def five_args(w: int, b: int) -> tuple:
    verdicts = np.zeros(2 * b, np.float32)
    verdicts[:b] = 1.0
    comp = np.full((2, w), np.inf, np.float32)
    staged = np.ones((7, w), np.float32)
    return verdicts, comp, staged, np.int32(3), np.float32(25.0)


def packed_arg(w: int, b: int) -> tuple:
    return (np.concatenate([np.ravel(a) for a in five_args(w, b)],
                           dtype=np.float32),)


@jax.jit
def read_five(verdicts, comp, staged, n_stage, now):
    return verdicts[0] + comp[1, 0] + staged[0, 0] + n_stage + now


@jax.jit
def read_packed(xfer):
    return xfer[0] + xfer[-1]


def _time(fn, args: tuple, reps: int, wait: bool) -> float:
    """us per call, best of three loops of `reps` calls after a warm
    call; `wait` pulls each result to the host before the next call."""
    np.asarray(fn(*args))
    best = float("inf")
    for _ in range(3):
        outs = []
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
            if wait:
                np.asarray(out)
            else:
                outs.append(out)
        dt = time.perf_counter() - t0
        jax.block_until_ready(outs)
        best = min(best, dt)
    return best / reps * 1e6


def measure(w: int = W, b: int = B, reps: int = REPS) -> dict:
    five, packed = five_args(w, b), packed_arg(w, b)
    row = {"w": w, "b": b,
           "bytes": sum(np.asarray(a).nbytes for a in five),
           "packed_bytes": packed[0].nbytes}
    for name, fn, args in (("five", read_five, five),
                           ("packed", read_packed, packed)):
        row[f"{name}_dispatch_us"] = _time(fn, args, reps, wait=False)
        row[f"{name}_round_trip_us"] = _time(fn, args, reps, wait=True)
    return {k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in row.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="few calls (any backend)")
    args = ap.parse_args()
    reps = 20 if args.smoke else REPS
    d = jax.devices()[0]
    print(json.dumps({"device": {"platform": d.platform,
                                 "kind": d.device_kind},
                      "reps": reps, "row": measure(reps=reps)}))


if __name__ == "__main__":
    main()
