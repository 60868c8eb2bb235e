"""Plain reference of the simulated horizon (the scan engine's semantics).

A dense, straightforward `jax.numpy` restatement, importing nothing of
the program, of one seed of the paper's closed simulation
(arXiv:2604.06970 sec. 4): the paper's workload draw, then per tick of
`dt_ms`:

1. retirement: in-flight requests whose service has finished complete,
   unless their end-to-end latency passed `timeout_mult x deadline`
   (then abandoned); pending ones that waited that long are abandoned;
   the tail EMA follows (finish - arrival) / unloaded latency of the
   first `ema_sample_cap` completions, in request order;
2. one dispatch of up to `k_slots` grants: adaptive DRR over the
   classes, FIFO or scored ordering inside a class, and the overload
   ladder on the candidate;
3. the transition: an admit's finish time is now + unloaded latency x
   the load slowdown at the inflight count the grant saw x jitter; a
   defer backs off; a reject is terminal.

After the horizon every finished request lands and everything still
pending is abandoned.  The comparison reads each seed's counts of
completed, deadline-met, rejected and abandoned requests, its defer
events, its short and global 95th-percentile latencies, and the tail
EMA at the end of the horizon.

`dtype` is the precision of the decision arithmetic (scores, severity,
deficits, the EMA): float32 as the configuration states, or bfloat16
for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generator import arrival_rate_per_s
from bench.reference.session_ref import policy_table

PENDING, INFLIGHT, COMPLETED, REJECTED, ABANDONED = 0, 1, 2, 3, 4
IDLE, ADMIT, DEFER, REJECT = -1, 0, 1, 2
F32 = jnp.float32
# rounds each product once, as written: no multiply-add contraction
pin = jax.lax.optimization_barrier


def workload(key, config: dict, tr: dict):
    """The paper's draw (sim/workload.py `generate`, copied): Poisson
    arrivals, bucket by the mix, log-uniform tokens in the bucket, coarse
    priors p50 = tokens x U[1-e, 1+e], jitter U[0.95, 1.05]."""
    n = int(config["n_requests"])
    k_arr, k_bkt, k_tok, k_prior, _, k_jit = jax.random.split(key, 6)
    rate = arrival_rate_per_s(tr, config["provider"])
    arrival = jnp.cumsum(jax.random.exponential(k_arr, (n,)) * (1000.0 / rate))
    mix = jnp.asarray(tr["mix"], F32)
    bucket = jax.random.choice(k_bkt, 4, (n,), p=mix).astype(jnp.int32)
    lohi = jnp.asarray(tr["bucket_tokens"], F32)
    lo, hi = lohi[bucket, 0], lohi[bucket, 1]
    u = jax.random.uniform(k_tok, (n,))
    tokens = jnp.exp(jnp.log(lo) + u * (jnp.log(hi) - jnp.log(lo)))
    rel = tr["coarse_rel_err"]
    p50 = tokens * jax.random.uniform(k_prior, (n,), minval=1.0 - rel,
                                      maxval=1.0 + rel)
    jitter = jax.random.uniform(k_jit, (n,), minval=0.95, maxval=1.05)
    return arrival.astype(F32), bucket, tokens.astype(F32), \
        p50.astype(F32), jitter


def simulate(key, config: dict, tr: dict, dt=F32):
    """One seed's horizon; returns the per-seed readings."""
    p = {k: jnp.asarray(v, dt) for k, v in policy_table(config).items()}
    c = lambda x: jnp.asarray(x, dt)  # noqa: E731
    pv = config["provider"]
    base, mpt, comfort, slope, quad = (
        F32(pv[k]) for k in ("base_ms", "ms_per_token", "comfort_concurrency",
                             "slowdown_slope", "slowdown_quad"))
    n, b = int(config["n_requests"]), int(config["k_slots"])
    k = len(config["policy"]["drr_weights"])
    dt_ms = F32(config["dt_ms"])
    alpha, cap_n = F32(config["ema_alpha"]), int(config["ema_sample_cap"])
    arr, bucket, tokens, p50, jitter = workload(key, config, tr)
    budget = jnp.asarray(config["deadline_budget_ms"], F32)[bucket]
    cls = jnp.asarray(config["class_of_bucket"], jnp.int32)[bucket]
    thresh = jnp.asarray(policy_table(config)["timeout_mult"])[bucket] * budget
    scored = p["ord_scored"] > 0
    iota = jnp.arange(n)

    def retire(s, now):
        st, fin, ema = s["st"], s["fin"], s["ema"]
        landed = (st == INFLIGHT) & (fin <= now)
        timed_out = landed & ((fin - arr) > thresh)
        done = landed & ~timed_out
        st = jnp.where(done, COMPLETED, jnp.where(timed_out, ABANDONED, st))
        # first `cap_n` completions in request order
        rank = jnp.cumsum(done) - 1
        take = done & (rank < cap_n)
        ratio = (fin - arr) / jnp.maximum(base + mpt * tokens, F32(1.0))
        kk = done.sum()
        mean = jnp.where(take, ratio, 0.0).sum() / jnp.maximum(
            jnp.minimum(kk, cap_n), 1).astype(F32)
        delta = pin(alpha * (c(mean) - c(ema)))
        ema = jnp.where(kk > 0, c(ema) + delta, c(ema))
        stale = (st == PENDING) & (arr <= now) & ((now - arr) > thresh)
        st = jnp.where(stale, ABANDONED, st)
        return dict(s, st=st, ema=ema.astype(F32))

    def severity(n_infl, n_elig, ema):
        load = c(n_infl) / jnp.maximum(p["load_ref"], c(1.0))
        queue = c(n_elig) / jnp.maximum(p["queue_ref"], c(1.0))
        tail = (jnp.maximum(c(ema), c(1.0)) - c(1.0)) \
            / jnp.maximum(p["tail_ref"] - c(1.0), c(1e-3))
        t0, t1, t2 = pin((p["olc_w_load"] * jnp.minimum(load, c(2.0)),
                          p["olc_w_queue"] * jnp.minimum(queue, c(2.0)),
                          p["olc_w_tail"] * jnp.minimum(tail, c(2.0))))
        s = (t0 + t1) + t2
        return jnp.maximum(s, c(0.0))

    def dispatch(s, now):
        st, du, nd = s["st"], s["du"], s["nd"]
        elig = (st == PENDING) & (arr <= now) & (du <= now)
        sev = severity((st == INFLIGHT).sum(), elig.sum(), s["ema"])
        nw = c(now)
        wait = jnp.maximum(nw - c(arr), c(0.0))
        urg = jnp.clip(c(1.0) - ((c(arr) + c(budget)) - nw)
                       / jnp.maximum(c(budget), c(1.0)), c(0.0), c(2.0))
        cost = jnp.maximum(c(p50), c(1.0))
        t0, t1, t2 = pin((p["ord_w_wait"] * (wait / cost),
                          p["ord_w_size"] * (cost / p["ord_ref_tokens"]),
                          p["ord_w_urg"] * urg))
        score = (t0 - t1) + t2
        # ordering key per class: lower is better; ties go to the lower id
        key = jnp.where(scored[cls], -score.astype(F32), arr)
        w, protect = p["drr_weights"], p["class_protect"]

        def grant(g, cy):
            deficit, taken, infl_cls, infl_tot, acts, idxs, infl_at = cy
            avail = elig & ~taken
            heads, costs, backlog = [], [], []
            for kk in range(k):
                m = avail & (cls == kk)
                h = jnp.argmin(jnp.where(m, key, jnp.inf))
                ok = m.any()
                heads.append(h)
                costs.append(jnp.where(ok, c(p50[h]), c(jnp.inf)))
                backlog.append(ok)
            heads, head_cost = jnp.stack(heads), jnp.stack(costs)
            shrink = jnp.maximum(c(1.0) - p["cap_kappa"]
                                 * jnp.minimum(sev, c(1.2)), c(0.3))
            cap_eff = jnp.maximum(
                p["class_cap"] * jnp.where(protect > 0, c(1.0), shrink), c(1.0))
            has_work = jnp.stack(backlog) & (c(infl_cls) < cap_eff)
            w_eff = w * (c(1.0) + p["congestion_kappa"] * sev * protect)
            accrue = p["drr_quantum"] * w_eff * c(has_work)
            idle = (p["drr_quantum"] * w_eff * c(~has_work)).sum()
            wb = w_eff * c(has_work)
            denom = wb.sum()
            share = jnp.where(denom > 0, wb / denom, c(0.0))
            d = jnp.minimum((deficit + accrue) + idle * share, p["deficit_cap"])
            affordable = has_work & (d >= jnp.minimum(head_cost, p["deficit_cap"]))
            pref = jnp.where(affordable, d * w / w.sum(), c(-jnp.inf))
            cid = jnp.argmax(pref)
            ok = affordable.any() & (c(infl_tot) < p["max_inflight"])
            idx = heads[cid]
            over_rej = sev > p["reject_thr"][bucket[idx]]
            over_def = sev > p["defer_thr"][bucket[idx]]
            act = jnp.where(over_rej, REJECT, jnp.where(
                over_def & (nd[idx].astype(F32) < p["max_defers"].astype(F32)),
                DEFER, ADMIT))
            act = jnp.where(p["olc_enabled"] > 0, act, ADMIT)
            act = jnp.where(ok, act, IDLE)
            onehot = jnp.arange(k) == cid
            d = jnp.where(ok & onehot, d - head_cost, d)
            refund = (act == DEFER) | (act == REJECT)
            d = jnp.where(refund & onehot & jnp.isfinite(d + head_cost),
                          d + head_cost, d)
            d = jnp.where(has_work, d, c(0.0))
            live = act != IDLE
            adm = act == ADMIT
            return (d, taken | (live & (iota == idx)),
                    infl_cls + (onehot & adm).astype(jnp.int32),
                    infl_tot + adm.astype(jnp.int32),
                    acts.at[g].set(act), idxs.at[g].set(idx),
                    infl_at.at[g].set(infl_tot))

        infl_cls = jnp.stack([((st == INFLIGHT) & (cls == kk)).sum()
                              for kk in range(k)]).astype(jnp.int32)
        cy = (c(s["deficit"]), jnp.zeros(n, bool), infl_cls,
              (st == INFLIGHT).sum().astype(jnp.int32),
              jnp.full(b, IDLE, jnp.int32), jnp.zeros(b, jnp.int32),
              jnp.zeros(b, jnp.int32))
        deficit, _, _, _, acts, idxs, infl_at = jax.lax.fori_loop(
            0, b, grant, cy)
        # the transition
        excess = jnp.maximum(infl_at.astype(F32) - comfort, F32(0.0)) \
            / jnp.maximum(comfort, F32(1.0))
        service = (base + mpt * tokens[idxs]) \
            * (F32(1.0) + slope * excess + quad * excess ** 2) * jitter[idxs]
        backoff = (p["defer_backoff_ms"] * (c(0.5) + sev)
                   * (c(1.0) + c(0.5) * c(nd[idxs]))).astype(F32)
        drop = jnp.int32(n)
        adm_i = jnp.where(acts == ADMIT, idxs, drop)
        def_i = jnp.where(acts == DEFER, idxs, drop)
        rej_i = jnp.where(acts == REJECT, idxs, drop)
        st = st.at[adm_i].set(INFLIGHT, mode="drop")
        st = st.at[rej_i].set(REJECTED, mode="drop")
        fin = s["fin"].at[adm_i].set(now + service, mode="drop")
        du = du.at[def_i].set(now + backoff, mode="drop")
        nd = nd.at[def_i].add(1, mode="drop")
        return dict(s, st=st, fin=fin, du=du, nd=nd,
                    deficit=deficit.astype(F32))

    def tick(s, t):
        now = (t + 1).astype(F32) * dt_ms
        return dispatch(retire(s, now), now), None

    s0 = dict(st=jnp.zeros(n, jnp.int32), fin=jnp.full(n, jnp.inf, F32),
              du=jnp.zeros(n, F32), nd=jnp.zeros(n, jnp.int32),
              deficit=jnp.zeros(k, F32), ema=F32(1.0))
    s, _ = jax.lax.scan(tick, s0, jnp.arange(int(config["n_ticks"])))
    s = retire(s, F32(config["n_ticks"]) * dt_ms + F32(1e9))
    st, fin = s["st"], s["fin"]
    done = st == COMPLETED
    lat = fin - arr

    def p95(mask):
        m = mask.sum()
        srt = jnp.sort(jnp.where(mask, lat, jnp.inf))
        i = jnp.clip(jnp.ceil(0.95 * m).astype(jnp.int32) - 1, 0, n - 1)
        return jnp.where(m > 0, srt[i], jnp.nan)

    return {
        "completed": done.sum(), "rejected": (st == REJECTED).sum(),
        "abandoned": (st == ABANDONED).sum(),
        "met": (done & (fin <= arr + budget)).sum(),
        "defer_events": s["nd"].sum(),
        "short_p95_ms": p95(done & (bucket == 0)), "global_p95_ms": p95(done),
        "tail_ema": s["ema"],
    }


@functools.lru_cache(maxsize=4)
def _compiled(config_json: str, traffic_json: str, dtype_name: str):
    import json

    config, tr = json.loads(config_json), json.loads(traffic_json)
    dt = jnp.dtype(dtype_name)
    return jax.jit(jax.vmap(lambda key: simulate(key, config, tr, dt)))


def run_reference(config: dict, traffic: dict, seeds, dtype="float32") -> dict:
    """Readings for each seed in `seeds` (PRNGKey(seed), as the program
    keys its seeds), on JAX's default device: in a benchmark run the
    chip, whose arithmetic (division, exp and log in the draw) the
    program's horizon follows."""
    import json

    fn = _compiled(json.dumps(config, sort_keys=True),
                   json.dumps(traffic, sort_keys=True), dtype)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.int32))
    out = jax.device_get(fn(keys))
    return {k: np.asarray(v) for k, v in out.items()}
