"""Each cell end to end at a tiny size on the CPU, with and without the
trace; the chip check is bypassed only here."""
import io
import json
import os
import subprocess
import sys

import pytest

from bench import harness

from bench.tests.tiny import TINY


def run_tiny(cell, seed, trace=False, tmp_path=None, monkeypatch=None):
    if trace:
        monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    out, err = io.StringIO(), io.StringIO()
    res = harness.run(cell, seed, 1.0, trace, require_tpu=False, out=out,
                      err=err, **TINY[cell])
    return res, out.getvalue(), err.getvalue()


def test_the_measured_path_needs_a_tpu():
    """Without a TPU the command exits non-zero and prints no result."""
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload",
         "frontend.high_congestion", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""
    assert "no CPU fallback" in p.stderr


@pytest.mark.parametrize("cell", sorted(TINY))
def test_cell_runs_and_prints_its_last_line(cell):
    res, out, err = run_tiny(cell, 2**31 + 99)
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert last["correct"], err
    want = {m["name"] for m in harness.cell_metrics(
        harness.manifest(), cell, "end_to_end")}
    assert set(last["metrics"]) == want
    assert all(v["value"] > 0 for v in last["metrics"].values())
    assert last["device"]["platform"] == "cpu" and last["device"]["count"] == 1
    assert last["attempted"] > 0
    # the compared numbers are the last lines of standard error
    tail = err.strip().splitlines()[-len(last["check"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_cell_reports_its_layers(cell, tmp_path, monkeypatch):
    res, out, err = run_tiny(cell, 11, True, tmp_path, monkeypatch)
    assert res["correct"], err
    want = {m["name"] for m in harness.cell_metrics(
        harness.manifest(), cell, "per_layer")}
    assert set(res["metrics"]) == want
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert list(res)[-2:] == ["breakdown", "check"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
