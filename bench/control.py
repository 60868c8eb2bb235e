"""Readings that set the limits of `correct`, and the control that has to
fail them.

The control is the plain reference itself, computed in bfloat16 (the
precision below the configuration's float32) and put in the program's
place.  In the live cell it decides every poll alone, and the float32
reference follows and checks its rows exactly as it follows the
program's; in the simulator cell its per-seed outcomes are compared
with the float32 reference's as the program's are.

    python3 bench/control.py --workload frontend.high_congestion --seeds 1 2 3 \
        --polls 5000 [--program-seeds 11 12 ...] [--seconds 3]

`--program-seeds` also runs the program (which needs the chip) for a
short window at the cell's own load, in this one process, and prints its
readings; `--seeds` gives the control's.  Results go to standard output,
one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise write its logs to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402
from bench.entries.session import poll_time_ms  # noqa: E402
from bench.generator import RequestStream, arrival_ms  # noqa: E402


def session_control(config: dict, traffic: dict, seed: int, n_polls: int,
                    dtype=None) -> dict:
    """bfloat16 reference in the program's place, checked by the float32
    reference over `n_polls` polls of the cell's stream."""
    import ml_dtypes

    from bench.entries.session import readings_dict, replay
    from bench.reference.session_ref import SessionReference

    ctl = SessionReference(config, dtype=dtype or ml_dtypes.bfloat16)
    ctl_stream = RequestStream(traffic, config["provider"], seed)

    def control_rows(i):
        """The control's own epoch i, fed the same arrivals."""
        feats = ctl_stream.take_until(
            poll_time_ms(i + 1, float(config["dt_ms"])))
        if len(feats["bucket"]):
            ctl.submit(feats, arrival_ms(feats["arrival_s"]))
        a, rids, sev, comp, aband, rej = ctl.poll()
        return (a, rids), sev, (comp, aband, rej)

    chk = replay(config, traffic, seed, n_polls, SessionReference(config),
                 control_rows)
    return readings_dict(chk.readings)


def scan_control(config: dict, traffic: dict, seed0: int) -> dict:
    """bfloat16 reference in the program's place for one call's seeds,
    compared with the float32 reference as the program is."""
    from bench.entries.scan import compare
    from bench.reference.scan_ref import run_reference

    seeds = list(range(seed0, seed0 + int(config["seeds_per_call"])))
    return compare(run_reference(config, traffic, seeds, "bfloat16"),
                   run_reference(config, traffic, seeds))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--polls", type=int, default=5000)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    a = ap.parse_args(argv)
    man = harness.manifest()
    wl = next(w for w in man["workloads"] if w["name"] == a.workload)
    config = harness.load_json(
        os.path.join(harness.BENCH, "configs", wl["config"] + ".json"))
    traffic = harness.load_json(
        os.path.join(harness.BENCH, "traffic", wl["traffic"] + ".json"))
    for seed in a.program_seeds:
        res = harness.run(a.workload, seed, a.seconds, False,
                          out=open(os.devnull, "w"))
        print(json.dumps({"side": "program", "seed": seed,
                          "check": res["check"]}), flush=True)
    for seed in a.seeds:
        if config["entry"] == "scan":
            from bench.entries.scan import seed0
            r = scan_control(config, traffic, seed0(seed, 0))
        else:
            r = session_control(config, traffic, seed, a.polls)
        print(json.dumps({"side": "control", "seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
