"""The benchmark harness: finds a cell's files by name and runs it.

`BENCHMARK.json` names each cell's configuration and traffic mix.  The
harness reads `bench/configs/<config>.json` and
`bench/traffic/<traffic>.json`, loads the entry the configuration
names from `bench/entries/<entry>.py`, and, in a traced
run, each per-layer metric's reader from `bench/metrics/<metric>.py`.
Adding a cell, a mix or a metric therefore adds files and manifest
entries and edits none.

A run prints, in order: set-up and compile lines, the entry's extra
numbers, the per-layer profile, and then each number compared for
`correct` beside its limit, as the last lines of standard error and as
the `check` key of the result.  The last line of standard output is the
result object: `correct`, `attempted`, `failed`, `metrics`, `device`,
`breakdown` (traced runs only) and `check`.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".trace")

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_metrics(man: dict, workload: str, key: str) -> list[dict]:
    """The metrics of `man[key]` that cell `workload` reports."""
    e2e = {m["name"]: m for m in man["end_to_end"]}

    def reports(m):
        if "workloads" in m:
            return workload in m["workloads"]
        if key == "per_layer":
            return reports(e2e[m["moves"]])
        return True
    return [m for m in man[key] if reports(m)]


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile seconds, and counts
    backend compiles and persistent-cache hits, for the whole process."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == _COMPILE_EVENTS[-1]

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache key, so it never moves)."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no size cap, so no eviction scan: a capped cache (as a machine's
    # environment may set) refused every write once one entry lacked its
    # access-time file, and each run then compiled again
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def find_devices(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s), JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s); there is no CPU fallback")
    return devs[:chips]


class Cell:
    """Everything an entry needs: the cell's files, its seed and window,
    and the hooks that mark set-up, the window and the trace."""

    def __init__(self, workload: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, devices: list, t0: float):
        self.name = workload["name"]
        self.workload, self.config, self.traffic = workload, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.t0 = t0
        self.setup_s = None
        self.compile_s_setup = None
        self.clock = CompileClock()
        self.compiles_at_open = None
        self.hits_at_open = None
        self.compiles_in_window = None
        self.compile_s_window = None
        self.trace_dir = None
        self.annotations: set[str] = set()
        self.peak_bytes = 0

    # --- the window ----------------------------------------------------
    def window_open(self) -> None:
        self.setup_s = time.time() - self.t0
        self.compile_s_setup = self.clock.seconds
        self.compiles_at_open = self.clock.compiles
        self.hits_at_open = self.clock.cache_hits

    def window_close(self) -> None:
        self.compile_s_window = self.clock.seconds - self.compile_s_setup
        self.compiles_in_window = (self.clock.compiles - self.compiles_at_open
                                   + self.clock.cache_hits - self.hits_at_open)

    def memory_peak(self) -> None:
        """Read the fullest chip's peak before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        self.peak_bytes = int(max(peaks))

    # --- tracing -------------------------------------------------------
    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        self.annotations.add(name)
        return jax.profiler.TraceAnnotation(name)

    def trace_start(self) -> bool:
        if not self.trace:
            return False
        import shutil

        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        self.trace_dir = TRACE_DIR
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        return True

    def trace_stop(self) -> None:
        import jax

        jax.profiler.stop_trace()


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def judge(check: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number against its limit (a number passes when it
    is finite and not above its limit)."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = check.get(name)
        passed = _finite(value) and value <= limit
        ok &= passed
        out[name] = {"value": value if _finite(value) else str(value),
                     "limit": limit}
    return ok, out


def run(workload_name: str, seed: int, seconds: float, trace: bool, *,
        root: str = ROOT, require_tpu: bool = True,
        config_overrides: dict | None = None,
        traffic_overrides: dict | None = None, t0: float | None = None,
        out=sys.stdout, err=sys.stderr) -> dict:
    """Run one cell and print its lines; returns the result object."""
    t0 = process_start() if t0 is None else t0
    man = manifest(root)
    wl = next((w for w in man["workloads"] if w["name"] == workload_name),
              None)
    if wl is None:
        raise SystemExit(f"bench: no workload named {workload_name!r}")
    config = load_json(os.path.join(BENCH, "configs", wl["config"] + ".json"))
    traffic = load_json(os.path.join(BENCH, "traffic", wl["traffic"] + ".json"))
    config.update(config_overrides or {})
    traffic.update(traffic_overrides or {})
    if require_tpu:
        enable_compile_cache()
    devices = find_devices(int(wl["chips"]), require_tpu)
    entry = load_module("entries", config["entry"])
    cell = Cell(wl, config, traffic, seed, seconds, trace, devices, t0)
    res = entry.run(cell)

    say = lambda *a: print(*a, file=err, flush=True)  # noqa: E731
    say(f"[{cell.name}] setup_s={cell.setup_s:.3f} "
        f"compile_s_in_setup={cell.compile_s_setup:.3f} "
        f"compiles_in_window={cell.compiles_in_window} "
        f"compile_s_in_window={cell.compile_s_window:.3f}")
    for k, v in res.get("info", {}).items():
        say(f"[{cell.name}] {k}={v}")
    import jax

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": cell.peak_bytes}
    e2e = dict(res["metrics"], setup_s=cell.setup_s)
    breakdown = None
    if trace:
        from bench import trace as tr

        summary = tr.summarize(cell.trace_dir, len(devices), cell.annotations)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        ctx = dict(res, trace=summary, cell=cell)
        metrics = {}
        for m in cell_metrics(man, cell.name, "per_layer"):
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        prof = res.get("profile")
        if prof:
            say(f"[{cell.name}] profile over {prof['polls']} polls: " + " ".join(
                f"{k}_us={prof[k] / prof['polls'] * 1e6:.2f}"
                for k in ("stage", "dispatch", "pull", "grants")))
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(man, cell.name, "end_to_end")}
    chk = res["check"]
    correct, check = judge(chk, config["check"])
    if chk.get("_first_mismatch"):
        say(f"[{cell.name}] first mismatch: {chk['_first_mismatch']}")
    say(f"[{cell.name}] compared: " + " ".join(
        f"{k}={v}" for k, v in chk.items() if k.startswith("_")
        and k != "_first_mismatch"))
    for name, c in check.items():
        say(f"check {name} {c['value']} limit {c['limit']}")
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = check
    print(json.dumps(result), file=out, flush=True)
    return result
