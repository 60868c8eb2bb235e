"""The generator: a function of the seed, and found by name."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np

from bench import harness
from bench.generator import CHUNK, RequestStream, arrival_ms, arrival_rate_per_s

TRAFFIC = harness.load_json(os.path.join(harness.BENCH, "traffic",
                                         "high_congestion_stream.json"))
PROVIDER = harness.load_json(os.path.join(harness.BENCH, "configs",
                                          "frontend_w256_b4.json"))["provider"]
BIG = 2**31 + 12345


def stream(seed):
    return RequestStream(TRAFFIC, PROVIDER, seed)


def test_equal_seeds_give_equal_traffic_and_other_seeds_other_traffic():
    a, b, c = stream(BIG).take(5000), stream(BIG).take(5000), \
        stream(BIG + 1).take(5000)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["p50"], c["p50"])
    assert not np.array_equal(a["arrival_s"], c["arrival_s"])


def test_a_stream_does_not_depend_on_how_it_is_taken():
    whole = stream(7).take(3 * CHUNK)
    s = stream(7)
    parts = [s.take(n) for n in (1, 16, CHUNK - 1, 3)]
    parts.append(s.take_until(arrival_ms(whole["arrival_s"][2 * CHUNK])))
    parts.append(s.take(3 * CHUNK - sum(len(p["bucket"]) for p in parts)))
    for k in whole:
        assert np.array_equal(whole[k], np.concatenate([p[k] for p in parts]))


def test_take_until_returns_exactly_the_arrived_requests():
    s = stream(5)
    seen = 0
    for t in (0.0, 25.0, 5000.0, 5000.0, 9e5):
        got = s.take_until(np.float32(t))
        ms = arrival_ms(got["arrival_s"])
        assert (ms <= np.float32(t)).all()
        seen += len(ms)
    nxt = s.take(1)
    assert arrival_ms(nxt["arrival_s"])[0] > np.float32(9e5)
    assert seen > 0


def test_the_draw_follows_the_mix_and_the_rate():
    f = stream(3).take(100000)
    share = np.bincount(f["bucket"], minlength=4) / len(f["bucket"])
    assert np.allclose(share, TRAFFIC["mix"], atol=0.01)
    lohi = np.asarray(TRAFFIC["bucket_tokens"])
    assert (f["tokens"] >= lohi[f["bucket"], 0] * 0.999).all()
    assert (f["tokens"] <= lohi[f["bucket"], 1] * 1.001).all()
    assert len(np.unique(f["p50"])) > 90000  # varied priors, few ties
    rate = len(f["arrival_s"]) / f["arrival_s"][-1]
    assert abs(rate / arrival_rate_per_s(TRAFFIC, PROVIDER) - 1) < 0.01
    # the paper's rate: 1.2 x comfort 4 / (90 ms + 6.5 ms x 357 tokens)
    assert abs(arrival_rate_per_s(TRAFFIC, PROVIDER) - 1.99129) < 1e-4


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    """A mix added as one data file and one manifest entry runs, and no
    file that was there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    man = harness.manifest()
    before = _digest(root / "bench")
    tr = dict(TRAFFIC, name="stream_busy", congestion_mult=2.0, warm_polls=3)
    (root / "bench" / "traffic" / "stream_busy.json").write_text(
        json.dumps(tr))
    man["workloads"].append({
        "name": "frontend.busy", "config": "frontend_w256_b4",
        "traffic": "stream_busy", "chips": 1, "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "frontend.high_congestion" in m.get("workloads", []):
            m["workloads"].append("frontend.busy")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    src = os.path.join(harness.ROOT, "src")
    code = ("import sys; sys.path[:0] = [sys.argv[1], sys.argv[2]]\n"
            "from bench import harness\n"
            "r = harness.run('frontend.busy', 5, 0.5, False, "
            "root=sys.argv[1], require_tpu=False, "
            "config_overrides={'window': 32})\n"
            "assert r['correct'], r\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code, str(root), src],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1])["correct"]
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
