"""Plain reference of the live front end's decision epoch.

A straightforward NumPy restatement of what one `ClientSession.poll`
must decide, written from the paper's three layers (arXiv:2604.06970
sec. 3) and the configuration file, and importing nothing of the
program:

* retirement: an admitted request lands once its provider finish time
  is due; a landed request past `timeout_mult x deadline` is abandoned,
  as is a pending one that waited that long; the tail signal is an EMA
  of (finish - arrival) / unloaded latency over the first
  `ema_sample_cap` completions of the epoch, in request-id order;
* the window: at most W live requests, admitted FIFO as slots free,
  kept in request-id order;
* allocation: adaptive deficit round robin over the classes, with
  work-conserving borrowing and severity-scaled weights;
* ordering: FIFO in unscored classes, the slowdown-aware score
  w1 wait/cost - w2 cost/ref + w3 urgency in scored ones;
* overload: severity from load, queue pressure and the tail EMA, and a
  per-bucket ladder of defer and reject thresholds.

`SessionReference.poll(rows)` advances one epoch.  Given the program's
grant rows it follows them, as a served model's reference is run over
the served tokens, and records by how far each row departs from what
the reference itself would decide (`Readings`).  Without rows it makes
its own decisions, computed in `dtype`: that is how the control runs
the reference in bfloat16 in the program's place.
"""
from __future__ import annotations

import dataclasses

import numpy as np

PENDING, INFLIGHT, DONE = 0, 1, 2
IDLE, ADMIT, DEFER, REJECT = -1, 0, 1, 2

F32 = np.float32


def policy_table(config: dict) -> dict:
    """The configuration's policy as float32 arrays ("inf" = never)."""
    out = {}
    for k, v in config["policy"].items():
        a = np.asarray([float(x) for x in v] if isinstance(v, list)
                       else float(v))
        out[k] = a.astype(np.int32 if k == "alloc_mode" else F32)
    return out


@dataclasses.dataclass
class Readings:
    """What the comparison found, accumulated over the compared polls."""

    polls: int = 0
    rows: int = 0                # grant rows compared (IDLE included)
    decisions: int = 0           # non-IDLE rows
    pick_gap: float = 0.0        # widest relative score gap of a scored pick
    alloc_gap: float = 0.0       # widest relative margin of a class override
    severity_gap: float = 0.0    # widest relative severity difference
    mismatches: int = 0          # rows or retirements that disagree outright
    first_mismatch: str = ""

    def miss(self, what: str) -> None:
        self.mismatches += 1
        if not self.first_mismatch:
            self.first_mismatch = what


class SessionReference:
    def __init__(self, config: dict, dtype=F32):
        self.w = int(config["window"])
        self.b = int(config["max_grants"])
        self.dt_ms = F32(config["dt_ms"])
        self.cls_of_bucket = np.asarray(config["class_of_bucket"], np.int32)
        self.budget = np.asarray(config["deadline_budget_ms"], F32)
        self.alpha = F32(config["ema_alpha"])
        self.sample_cap = int(config["ema_sample_cap"])
        self.p = policy_table(config)
        if int(self.p["alloc_mode"]) != 2:
            raise ValueError("the reference states adaptive DRR only")
        pv = config["provider"]
        self.base, self.mpt, self.comfort, self.slope, self.quad = (
            F32(pv[k]) for k in ("base_ms", "ms_per_token",
                                 "comfort_concurrency", "slowdown_slope",
                                 "slowdown_quad"))
        self.dt = dtype
        self.c = {k: np.asarray(v, dtype) for k, v in self.p.items()
                  if k != "alloc_mode"}
        self.k = len(self.p["drr_weights"])
        self.t = 0
        # per-request columns, indexed by session rid
        self.cols = {k: np.zeros(0, d) for k, d in (
            ("arrival", F32), ("bucket", np.int32), ("cls", np.int32),
            ("tokens", F32), ("p50", F32), ("thresh", F32), ("jitter", F32),
            ("status", np.int32), ("defer_until", F32),
            ("n_defers", np.int32), ("finish", F32))}
        self.n = 0
        self.queue_head = 0           # rids >= queue_head are not admitted
        self.win = np.zeros(0, np.int64)
        self.deficit = np.zeros(self.k, F32)
        self.ema = F32(1.0)
        self.readings = Readings()

    # --- traffic ------------------------------------------------------
    def submit(self, feats: dict, arrival_ms: np.ndarray) -> None:
        n = len(feats["bucket"])
        bucket = np.asarray(feats["bucket"], np.int32)
        new = {
            "arrival": np.asarray(arrival_ms, F32), "bucket": bucket,
            "cls": self.cls_of_bucket[bucket],
            "tokens": np.asarray(feats["tokens"], F32),
            "p50": np.asarray(feats["p50"], F32),
            "thresh": self.p["timeout_mult"][bucket] * self.budget[bucket],
            "jitter": np.asarray(feats["jitter"], F32),
            "status": np.full(n, PENDING, np.int32),
            "defer_until": np.zeros(n, F32),
            "n_defers": np.zeros(n, np.int32),
            "finish": np.full(n, np.inf, F32)}
        cap = len(self.cols["status"])
        if self.n + n > cap:
            grow = max(2 * cap, self.n + n)
            self.cols = {k: np.concatenate([v[:self.n], np.zeros(
                grow - self.n, v.dtype)]) for k, v in self.cols.items()}
        for k, v in new.items():
            self.cols[k][self.n:self.n + n] = v
        self.n += n

    # --- one epoch ----------------------------------------------------
    def _retire(self, now):
        c, win = self.cols, self.win
        st, arr = c["status"][win], c["arrival"][win]
        fin, th = c["finish"][win], c["thresh"][win]
        landed = (st == INFLIGHT) & (fin <= now)
        timed_out = landed & ((fin - arr) > th)
        done = landed & ~timed_out
        stale = (st == PENDING) & (arr <= now) & ((now - arr) > th)
        # tail EMA over the first `sample_cap` completions, in rid order
        d_idx = win[done][:self.sample_cap]
        if done.any():
            expected = self.base + self.mpt * c["tokens"][d_idx]
            ratio = (c["finish"][d_idx] - c["arrival"][d_idx]) \
                / np.maximum(expected, F32(1.0))
            mean = F32(ratio.sum(dtype=F32) / F32(len(d_idx)))
            self.ema = F32(self.ema + F32(self.alpha * F32(mean - self.ema)))
        dead = landed | stale
        c["status"][win[dead]] = DONE
        self.win = win[~dead]
        return set(win[done].tolist()), set(win[timed_out | stale].tolist())

    def _admit(self, now) -> None:
        free = self.w - len(self.win)
        arr = self.cols["arrival"]
        take = 0
        while take < free and self.queue_head + take < self.n \
                and arr[self.queue_head + take] <= now:
            take += 1
        if take:
            new = np.arange(self.queue_head, self.queue_head + take)
            self.win = np.concatenate([self.win, new])
            self.queue_head += take

    def _severity(self, now, n_infl, n_elig):
        dt, p = self.dt, self.p
        c = lambda x: np.asarray(x, dt)  # noqa: E731
        load = c(n_infl) / c(max(float(p["load_ref"]), 1.0))
        queue = c(n_elig) / c(max(float(p["queue_ref"]), 1.0))
        tail = (c(max(self.ema, F32(1.0))) - c(1.0)) \
            / c(max(float(p["tail_ref"]) - 1.0, 1e-3))
        terms = (c(p["olc_w_load"]) * min(load, c(2.0)),
                 c(p["olc_w_queue"]) * min(queue, c(2.0)),
                 c(p["olc_w_tail"]) * min(tail, c(2.0)))
        return max(c(c(terms[0] + terms[1]) + terms[2]), c(0.0))

    def _scores(self, now, rids):
        """The scored-class ordering key, in the reference's dtype."""
        c = lambda x: np.asarray(x, self.dt)  # noqa: E731
        p, cols = self.p, self.cols
        arr, budget = c(cols["arrival"][rids]), c(self.budget[cols["bucket"][rids]])
        nw = c(now)
        wait = np.maximum(nw - arr, c(0.0))
        time_left = (arr + budget) - nw
        urg = np.clip(c(1.0) - time_left / np.maximum(budget, c(1.0)),
                      c(0.0), c(2.0))
        cost = np.maximum(c(cols["p50"][rids]), c(1.0))
        return (c(p["ord_w_wait"]) * (wait / cost)
                - c(p["ord_w_size"]) * (cost / c(p["ord_ref_tokens"]))) \
            + c(p["ord_w_urg"]) * urg

    def _allocate(self, sev, backlog, head_cost, infl_cls, infl_tot, deficit):
        """One adaptive-DRR grant opportunity.  Returns (class, ok, d,
        pref, has_work): d is the deficit after accrual and before the
        charge, pref each class's normalized deficit (-inf when it
        cannot afford its head)."""
        c = lambda x: np.asarray(x, self.dt)  # noqa: E731
        p = self.c
        w, protect = p["drr_weights"], p["class_protect"]
        under_cap = c(infl_tot) < p["max_inflight"]
        shrink = max(c(1.0) - p["cap_kappa"] * min(sev, c(1.2)), c(0.3))
        cap_eff = np.maximum(
            p["class_cap"] * np.where(protect > 0, c(1.0), shrink), c(1.0))
        has_work = (backlog > 0) & (c(infl_cls) < cap_eff)
        w_eff = w * (c(1.0) + p["congestion_kappa"] * sev * protect)
        q = p["drr_quantum"]
        accrue = q * w_eff * has_work.astype(self.dt)
        idle = (q * w_eff * (~has_work).astype(self.dt)).sum(dtype=self.dt)
        wb = w_eff * has_work.astype(self.dt)
        denom = wb.sum(dtype=self.dt)
        share = wb / denom if denom > 0 else np.zeros_like(wb)
        cap = p["deficit_cap"]
        d = np.minimum((c(deficit) + accrue) + idle * share, cap)
        affordable = has_work & (d >= np.minimum(c(head_cost), cap))
        pref = np.where(affordable, d * w / w.sum(dtype=self.dt),
                        c(-np.inf))
        return int(np.argmax(pref)), bool(affordable.any() and under_cap), \
            d, pref, has_work

    def _ladder(self, sev, bucket, n_defers):
        p = self.p
        if float(p["olc_enabled"]) <= 0:
            return ADMIT
        c = lambda x: np.asarray(x, self.dt)  # noqa: E731
        if sev > c(p["reject_thr"][bucket]):
            return REJECT
        if sev > c(p["defer_thr"][bucket]) \
                and F32(n_defers) < p["max_defers"]:
            return DEFER
        return ADMIT

    def poll(self, rows=None, severity=None, retired=None):
        """One decision epoch.  `rows` = (actions, rids) of the program's
        (B,) grant rows to follow and check, or None to decide alone;
        `severity` and `retired` = (completed, abandoned, rejected) rid
        lists are the program's to check.  Returns the epoch's own
        (actions, rids, severity, completed, abandoned, rejected)."""
        self.t += 1
        now = F32(F32(self.t) * self.dt_ms)
        completed, abandoned = self._retire(now)
        self._admit(now)
        cols, win, rd = self.cols, self.win, self.readings
        st = cols["status"][win]
        elig = (st == PENDING) & (cols["arrival"][win] <= now) \
            & (cols["defer_until"][win] <= now)
        n_infl = int((st == INFLIGHT).sum())
        sev = self._severity(now, n_infl, int(elig.sum()))
        e_rids = win[elig]
        e_cls = cols["cls"][e_rids]
        scored = self.p["ord_scored"] > 0
        # per-class candidate keys: scored classes rank by descending
        # score, FIFO ones by ascending arrival; ties go to the lower rid
        cand, key = [], []
        for k in range(self.k):
            r = e_rids[e_cls == k]
            cand.append(r)
            key.append(-self._scores(now, r).astype(F32) if scored[k]
                       else cols["arrival"][r])
        taken = [np.zeros(len(cand[k]), bool) for k in range(self.k)]
        # best-first order per class; a stable sort keeps ties in rid order
        order = [np.argsort(key[k], kind="stable") for k in range(self.k)]
        n_vis = np.array([min(len(cand[k]), self.b) for k in range(self.k)])
        ptr = np.zeros(self.k, np.int64)
        infl_cls = np.array([int(((st == INFLIGHT)
                                  & (cols["cls"][win] == k)).sum())
                             for k in range(self.k)])
        infl_tot = n_infl
        deficit = self.deficit.astype(self.dt)
        out_a = np.full(self.b, IDLE, np.int32)
        out_r = np.full(self.b, -1, np.int64)
        infl_at = np.zeros(self.b, np.int64)
        for g in range(self.b):
            heads = []
            for k in range(self.k):
                if ptr[k] < n_vis[k]:
                    i = 0
                    while taken[k][order[k][i]]:
                        i += 1
                    heads.append(int(order[k][i]))
                else:
                    heads.append(-1)
            head_cost = np.array(
                [cols["p50"][cand[k][h]] if h >= 0 else np.inf
                 for k, h in enumerate(heads)], F32)
            backlog = n_vis - ptr
            cls_ref, ok_ref, d, pref, has_work = self._allocate(
                sev, backlog, head_cost, infl_cls, infl_tot, deficit)
            if rows is None:
                a = self._ladder(sev, cols["bucket"][cand[cls_ref][heads[cls_ref]]],
                                 cols["n_defers"][cand[cls_ref][heads[cls_ref]]]) \
                    if ok_ref else IDLE
                cls_g, pos = cls_ref, heads[cls_ref]
            else:
                a, rid = int(rows[0][g]), int(rows[1][g])
                rd.rows += 1
                if a == IDLE:
                    if ok_ref:
                        rd.miss(f"poll {self.t} row {g}: program idle, "
                                f"reference grants class {cls_ref}")
                    cls_g, pos = cls_ref, -1
                else:
                    cls_g = int(cols["cls"][rid]) if 0 <= rid < self.n else -1
                    hit = (np.nonzero(cand[cls_g] == rid)[0]
                           if cls_g >= 0 else np.zeros(0, np.int64))
                    if not len(hit) or taken[cls_g][hit[0]]:
                        rd.miss(f"poll {self.t} row {g}: rid {rid} is not "
                                f"an eligible candidate")
                        rd.pick_gap = np.inf
                        continue
                    pos = int(hit[0])
                    if not ok_ref:
                        rd.miss(f"poll {self.t} row {g}: program grants, "
                                f"reference has nothing sendable")
                    elif cls_g != cls_ref:
                        ref_p = float(pref[cls_ref])
                        gap = ((ref_p - float(pref[cls_g]))
                               / max(abs(ref_p), 1.0)
                               if np.isfinite(pref[cls_g]) else
                               (min(float(head_cost[cls_g]),
                                    float(self.p["deficit_cap"]))
                                - float(d[cls_g])) / max(abs(float(d[cls_g])), 1.0))
                        rd.alloc_gap = max(rd.alloc_gap, gap)
                    best = heads[cls_g]
                    if scored[cls_g]:
                        kb, kp = float(key[cls_g][best]), float(key[cls_g][pos])
                        rd.pick_gap = max(rd.pick_gap,
                                          (kp - kb) / max(abs(kb), 1.0))
                    elif pos != best:
                        rd.miss(f"poll {self.t} row {g}: FIFO pick rid {rid}, "
                                f"reference rid {int(cand[cls_g][best])}")
                    want = self._ladder(sev, cols["bucket"][rid],
                                        cols["n_defers"][rid])
                    if want != a:
                        rd.miss(f"poll {self.t} row {g}: action {a}, "
                                f"reference {want}")
            # the deficit carry moves on every row, granted or not
            if a != IDLE:
                cost = self.dt(cols["p50"][cand[cls_g][pos]])
                d = d.copy()
                d[cls_g] = d[cls_g] - cost
                if a in (DEFER, REJECT) and np.isfinite(d[cls_g] + cost):
                    d[cls_g] = d[cls_g] + cost
            deficit = np.where(has_work, d, self.dt(0.0))
            if a == IDLE:
                continue
            rid = int(cand[cls_g][pos])
            taken[cls_g][pos] = True
            ptr[cls_g] += 1
            out_a[g], out_r[g], infl_at[g] = a, rid, infl_tot
            if a == ADMIT:
                infl_cls[cls_g] += 1
                infl_tot += 1
        self.deficit = deficit.astype(F32)
        rejected = self._apply(now, sev, out_a, out_r, infl_at)
        if rows is not None:
            rd.polls += 1
            rd.decisions += int((out_a != IDLE).sum())
            if severity is not None:
                s = float(F32(sev))
                rd.severity_gap = max(rd.severity_gap,
                                      abs(float(severity) - s) / max(abs(s), 1e-3))
            if retired is not None:
                for name, mine, theirs in zip(
                        ("completed", "abandoned", "rejected"),
                        (completed, abandoned, rejected), retired):
                    if mine != set(int(x) for x in theirs):
                        rd.miss(f"poll {self.t}: {name} sets differ "
                                f"({len(mine ^ set(theirs))} rids)")
        return out_a, out_r, F32(sev), completed, abandoned, rejected

    def _apply(self, now, sev, acts, rids, infl_at) -> set:
        """The epoch's transition: admits go to the provider model,
        defers back off, rejects are terminal."""
        c, p = self.cols, self.p
        rejected = set()
        for a, rid, infl in zip(acts, rids, infl_at):
            if a == ADMIT:
                unloaded = self.base + self.mpt * c["tokens"][rid]
                excess = max(F32(infl) - self.comfort, F32(0.0)) \
                    / max(self.comfort, F32(1.0))
                mult = F32(1.0) + self.slope * excess \
                    + self.quad * F32(excess * excess)
                loaded = F32(unloaded * mult)
                # the provider's service time, single-rounded
                # service x jitter + now
                c["finish"][rid] = F32(float(loaded) * float(c["jitter"][rid])
                                       + float(now))
                c["status"][rid] = INFLIGHT
            elif a == DEFER:
                backoff = p["defer_backoff_ms"] * (F32(0.5) + F32(sev)) \
                    * (F32(1.0) + F32(0.5) * F32(c["n_defers"][rid]))
                c["defer_until"][rid] = F32(now + backoff)
                c["n_defers"][rid] += 1
            elif a == REJECT:
                c["status"][rid] = DONE
                rejected.add(int(rid))
        if rejected:
            self.win = self.win[~np.isin(self.win, list(rejected))]
        return rejected
