"""Shared benchmark harness utilities: cell execution, CSV emission,
and the persistent JAX compilation cache (`repro.compile_cache`) every
benchmark driver enables on import."""
from __future__ import annotations

import csv
import os
import time

from repro.compile_cache import enable_compilation_cache
from repro.core.policy import PolicyConfig
from repro.sim import SimConfig, WorkloadConfig, run_cell, summarize

TABLE_DIR = os.path.join(os.path.dirname(__file__), "..", "paper_results", "tables")


# every benchmark driver imports this module first, so enabling here
# covers the whole suite (harmless under pytest, which doesn't)
enable_compilation_cache()

SIM = SimConfig(n_ticks=14000)
N_REQ = 160
SEEDS = 5


def merge_rows(fresh: list[dict], old: list[dict], keys: tuple) -> list[dict]:
    """Merge bench artifact rows: fresh rows win; committed rows for
    cells not re-measured (e.g. the --scale-only N=1e6 cells in a
    regular run) are preserved so a default bench run cannot silently
    drop them.  Shared by every driver that writes keyed row lists into
    BENCH_scheduler.json."""
    measured = {tuple(r[k] for k in keys) for r in fresh}
    kept = [r for r in old if tuple(r.get(k) for k in keys) not in measured]
    return fresh + kept

METRIC_COLS = [
    "short_p95_ms", "short_p90_ms", "long_p90_ms", "global_p95_ms",
    "global_std_ms", "completion_rate", "satisfaction", "goodput_rps",
    "makespan_ms", "n_rejects", "n_defer_events", "n_abandoned",
]


def cell(policy: PolicyConfig, mix: str, congestion: str,
         information: str = "coarse", predictor_noise: float = 0.0,
         n_req: int = N_REQ, seeds: int = SEEDS):
    wl = WorkloadConfig(n_requests=n_req, mix=mix, congestion=congestion,
                        information=information,
                        predictor_noise=predictor_noise)
    m = run_cell(policy, wl, seeds=seeds, sim_cfg=SIM)
    return summarize(m)


def write_csv(name: str, rows: list[dict]) -> str:
    os.makedirs(TABLE_DIR, exist_ok=True)
    path = os.path.join(TABLE_DIR, f"{name}.csv")
    cols = list(rows[0].keys())
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        w.writerows(rows)
    return path


def row_from_summary(tag: dict, s: dict) -> dict:
    out = dict(tag)
    for k in METRIC_COLS:
        out[f"{k}_mean"] = round(s[k][0], 3)
        out[f"{k}_std"] = round(s[k][1], 3)
    return out


def fmt(s: dict, keys=("short_p95_ms", "global_p95_ms", "completion_rate",
                       "satisfaction", "goodput_rps")) -> str:
    return " ".join(
        f"{k.split('_ms')[0]}={s[k][0]:.0f}±{s[k][1]:.0f}"
        if "ms" in k else f"{k}={s[k][0]:.2f}±{s[k][1]:.2f}"
        for k in keys)


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.s = time.time() - self.t0
