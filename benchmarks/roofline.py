import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Roofline analysis (assignment deliverable g).

Reads the dry-run artifacts (paper_results/dryrun/*.json), adds a
layer-probe correction for XLA's scan-once cost accounting (verified
empirically: cost_analysis counts a lax.scan body ONCE regardless of trip
count), computes the three roofline terms per (arch x shape) on the
single-pod mesh, and emits paper_results/roofline.{csv,md}.

Terms (TPU v5e constants from the assignment):
  compute_s    = MODEL-analytic FLOPs / (chips * 197e12)
  memory_s     = corrected per-device HLO bytes / 819e9
  collective_s = corrected per-device collective bytes / 50e9 (1 ICI link)

Corrections:
  corrected(X) = X(L=1) + (n_layers - 1) * (X(L=2) - X(L=1))
applied to HLO flops, bytes and collective bytes (layer-probe
extrapolation; inner attention scans are additionally handled on the
analytic side).  MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D
(prefill, decode) + exact attention/SSD terms.
"""
import argparse
import dataclasses
import json

from repro.config import SHAPES
from repro.configs import ARCHS
from repro.launch.mesh import HBM_BW, HBM_PER_CHIP, ICI_BW, PEAK_FLOPS_BF16

DRY_DIR = os.path.join(os.path.dirname(__file__), "..", "paper_results", "dryrun")
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "paper_results")


# ---------------------------------------------------------------------------
# Analytic FLOPs
# ---------------------------------------------------------------------------

def _attn_pairs(S: int, window: int) -> float:
    if window <= 0 or window >= S:
        return S * S / 2
    return window * S - window * window / 2


def analytic_flops(cfg, shape) -> float:
    """Global model FLOPs for one step (fwd [+bwd for train])."""
    B, S = shape.global_batch, shape.seq_len
    mult = 3.0 if shape.kind == "train" else 1.0
    n_act = cfg.active_param_count()
    if shape.kind == "decode":
        tokens = B
        base = 2.0 * n_act * tokens
        extra = 0.0
        if cfg.n_heads:
            skv = min(S, cfg.sliding_window) if cfg.sliding_window else S
            if cfg.arch_type == "hybrid":
                glob = len(cfg.global_layers)
                win_l = cfg.n_layers - glob
                eff = glob * S + win_l * min(S, cfg.sliding_window or S)
            else:
                eff = cfg.n_layers * skv
            extra += 4.0 * B * eff * cfg.n_heads * cfg.head_dim
        if cfg.ssm:
            extra += (6.0 * B * cfg.n_ssm_heads * cfg.ssm.head_dim
                      * cfg.ssm.d_state * cfg.n_layers)
        return base + extra
    tokens = B * S
    base = 2.0 * mult * n_act * tokens
    extra = 0.0
    if cfg.n_heads:
        if cfg.arch_type == "hybrid":
            glob = len(cfg.global_layers)
            pairs = (glob * _attn_pairs(S, 0)
                     + (cfg.n_layers - glob) * _attn_pairs(S, cfg.sliding_window))
        else:
            pairs = cfg.n_layers * _attn_pairs(S, cfg.sliding_window)
        extra += mult * 4.0 * B * pairs * cfg.n_heads * cfg.head_dim
    if cfg.ssm:
        s = cfg.ssm
        Q = s.chunk
        per_tok = (2 * Q * s.d_state + cfg.n_ssm_heads *
                   (2 * Q * s.head_dim + 2 * s.head_dim * s.d_state))
        extra += mult * B * S * per_tok * cfg.n_layers
    return base + extra


# ---------------------------------------------------------------------------
# Layer probes
# ---------------------------------------------------------------------------

def probe(arch: str, shape_name: str, n_layers: int) -> dict:
    """Lower+compile with a reduced layer count (same shapes otherwise)."""
    import jax
    from repro.launch.dryrun import collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_spec, config_for

    cfg = config_for(arch, shape_name)
    cfg = dataclasses.replace(
        cfg, n_layers=n_layers, scan_unroll=True,
        global_layers=tuple(g for g in cfg.global_layers if g < n_layers))
    mesh = make_production_mesh(multi_pod=False)
    spec = build_spec(arch, shape_name, mesh, cfg_override=cfg)
    with mesh:
        compiled = jax.jit(
            spec.fn, in_shardings=spec.in_shardings,
            donate_argnums=spec.donate).lower(*spec.args).compile()
    cost = compiled.cost_analysis() or {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_bytes(compiled.as_text())["total"],
    }


def probe_path(arch, shape_name):
    return os.path.join(DRY_DIR, f"probe__{arch}__{shape_name}.json")


def run_probes(archs=None, shapes=None):
    for arch in archs or ARCHS:
        for shape_name in shapes or list(SHAPES):
            path = probe_path(arch, shape_name)
            if os.path.exists(path):
                continue
            try:
                rec = {"L1": probe(arch, shape_name, 1),
                       "L2": probe(arch, shape_name, 2), "ok": True}
            except Exception as e:  # noqa: BLE001
                rec = {"ok": False, "error": f"{type(e).__name__}: {e}"}
            with open(path, "w") as f:
                json.dump(rec, f)
            print(f"[probe] {arch} {shape_name} "
                  f"{'ok' if rec['ok'] else rec['error']}", flush=True)


# ---------------------------------------------------------------------------
# Report
# ---------------------------------------------------------------------------

def corrected(full_rec, probe_rec, key_full, key_probe, L):
    if not probe_rec.get("ok"):
        return full_rec.get(key_full, 0.0)
    x1 = probe_rec["L1"][key_probe]
    x2 = probe_rec["L2"][key_probe]
    if x2 < x1:  # fusion noise can make the 2-layer probe cheaper
        # (seen on the prefix-stub archs); fall back to the uncorrected
        # full-model value rather than extrapolating a negative slope
        return full_rec.get(key_full, probe_rec["L2"][key_probe])
    return x1 + (L - 1) * (x2 - x1)


def build_report():
    from repro.launch.specs import config_for

    rows = []
    for arch in ARCHS:
        for shape_name in SHAPES:
            fn = os.path.join(DRY_DIR, f"{arch}__{shape_name}__pod.json")
            if not os.path.exists(fn):
                continue
            with open(fn) as f:
                rec = json.load(f)
            if not rec.get("ok"):
                rows.append({"arch": arch, "shape": shape_name,
                             "ok": False, "error": rec.get("error", "")})
                continue
            cfg = config_for(arch, shape_name)
            shape = SHAPES[shape_name]
            chips = rec["n_devices"]
            pp = {}
            ppath = probe_path(arch, shape_name)
            if os.path.exists(ppath):
                with open(ppath) as f:
                    pp = json.load(f)
            L = cfg.n_layers
            hlo_flops_c = corrected(rec, pp, "hlo_flops", "flops", L)
            hlo_bytes_c = corrected(rec, pp, "hlo_bytes", "bytes", L)
            coll_c = corrected(
                {"collectives": rec["collectives"],
                 "total": rec["collectives"]["total"]},
                pp, "total", "coll", L)
            model_flops = analytic_flops(cfg, shape)

            compute_s = model_flops / (chips * PEAK_FLOPS_BF16)
            memory_s = hlo_bytes_c / HBM_BW
            collective_s = coll_c / ICI_BW
            terms = {"compute": compute_s, "memory": memory_s,
                     "collective": collective_s}
            dominant = max(terms, key=terms.get)
            bound_s = terms[dominant]
            useful_ratio = model_flops / max(hlo_flops_c * chips, 1.0)
            hbm_frac = rec.get("bytes_per_device", 0) / HBM_PER_CHIP
            rows.append({
                "arch": arch, "shape": shape_name, "ok": True,
                "chips": chips,
                "model_flops": model_flops,
                "hlo_flops_per_dev_raw": rec["hlo_flops"],
                "hlo_flops_per_dev_corrected": hlo_flops_c,
                "hlo_bytes_per_dev_corrected": hlo_bytes_c,
                "collective_bytes_per_dev": coll_c,
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": collective_s,
                "dominant": dominant,
                "bound_s": bound_s,
                "roofline_frac_compute": compute_s / max(bound_s, 1e-30),
                "useful_flops_ratio": useful_ratio,
                "mem_per_device_gb": rec.get("bytes_per_device", 0) / 1e9,
                "fits_hbm": hbm_frac <= 1.0,
                "variant": rec.get("variant", ""),
            })
    return rows


SUGGEST = {
    "compute": "compute-bound: already near the right roofline; gains need "
               "fewer redundant FLOPs (remat policy) or lower precision.",
    "memory": "memory-bound: raise arithmetic intensity — fuse, batch more "
              "tokens per weight load, or quantize weights/KV to int8.",
    "collective": "collective-bound: reshard to cut cross-chip traffic "
                  "(more FSDP, less TP; overlap collectives with compute).",
}


def emit(rows):
    os.makedirs(OUT_DIR, exist_ok=True)
    import csv
    with open(os.path.join(OUT_DIR, "roofline.csv"), "w", newline="") as f:
        cols = list(rows[0].keys())
        for r in rows:
            cols += [c for c in r if c not in cols]
        w = csv.DictWriter(f, fieldnames=cols)
        w.writeheader()
        w.writerows(rows)
    lines = ["| arch | shape | compute_s | memory_s | collective_s | "
             "dominant | useful_ratio | mem/dev GB | fits |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if not r.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | FAILED: "
                         f"{r.get('error','')[:60]} | | | | | | |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
            f"**{r['dominant']}** | {r['useful_flops_ratio']:.2f} | "
            f"{r['mem_per_device_gb']:.2f} | "
            f"{'Y' if r['fits_hbm'] else 'N'} |")
    with open(os.path.join(OUT_DIR, "roofline.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    for r in rows:
        if r.get("ok"):
            print(f"{r['arch']:24s} {r['shape']:12s} dom={r['dominant']:10s} "
                  f"c={r['compute_s']:.2e} m={r['memory_s']:.2e} "
                  f"x={r['collective_s']:.2e} useful={r['useful_flops_ratio']:.2f}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    args = ap.parse_args()
    if args.probe:
        from repro.compile_cache import enable_compilation_cache
        enable_compilation_cache()
        run_probes([args.arch] if args.arch else None,
                   [args.shape] if args.shape else None)
    if args.report or not args.probe:
        emit(build_report())


if __name__ == "__main__":
    main()
