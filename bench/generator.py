"""The benchmark's one traffic generator for the live front end.

A traffic file (`bench/traffic/<name>.json`) holds only parameters:
the bucket mix, the token range of each bucket, the coarse predictor's
relative error, the provider-side jitter range and the offered load as
a multiple of the provider's comfortable capacity.  `RequestStream`
turns those parameters, the configuration's provider physics and a seed
into an open-loop stream of requests, drawn in fixed-size chunks so that
the n-th request of a stream is the same whatever sizes the caller
takes it in.

The draw is the paper's workload (sim/workload.py `generate`, copied
here so that the program cannot change the yardstick): Poisson arrivals
at `congestion_mult` x the provider's comfortable capacity, bucket by
the mix, output tokens log-uniform within the bucket, a coarse p50 prior
that is the true count times U[1 - e, 1 + e], p90 = 1.8 x p50, and a
provider jitter multiplier.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("bucket", "tokens", "p50", "p90", "jitter", "arrival_s")
CHUNK = 4096  # requests drawn at a time; fixes the stream whatever is taken


def arrival_rate_per_s(traffic: dict, provider: dict) -> float:
    """Offered rate: `congestion_mult` x the provider's comfortable
    capacity on the mix (comfort / mean unloaded service time)."""
    service_s = (provider["base_ms"] + provider["ms_per_token"]
                 * traffic["mean_tokens"]) / 1000.0
    return traffic["congestion_mult"] * (provider["comfort_concurrency"]
                                         / service_s)


def arrival_ms(arrival_s: np.ndarray) -> np.ndarray:
    """`ClientSession.submit`'s own float32 conversion of `arrival_s`."""
    return (np.asarray(arrival_s, np.float64) * 1000.0).astype(np.float32)


class RequestStream:
    """Deterministic, seed-keyed stream of request features and arrival
    times (seconds from the session's start).  Seeds of any size are
    accepted."""

    def __init__(self, traffic: dict, provider: dict, seed: int):
        self.mix = np.asarray(traffic["mix"], np.float64)
        self.lohi = np.asarray(traffic["bucket_tokens"], np.float64)
        self.rel = float(traffic["coarse_rel_err"])
        self.p90_mult = float(traffic["p90_over_p50"])
        self.jit_lo, self.jit_hi = (float(x) for x in traffic["jitter"])
        self.gap_ms = 1000.0 / arrival_rate_per_s(traffic, provider)
        self._rng = np.random.default_rng(int(seed))
        self._t_ms = 0.0
        self._buf = self._draw_chunk()
        self._pos = 0

    def _draw_chunk(self) -> dict:
        n, rng = CHUNK, self._rng
        arrival = self._t_ms + np.cumsum(rng.exponential(self.gap_ms, n))
        self._t_ms = float(arrival[-1])
        bucket = rng.choice(len(self.mix), size=n, p=self.mix / self.mix.sum())
        lo, hi = self.lohi[bucket, 0], self.lohi[bucket, 1]
        u = rng.random(n)
        tokens = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
        eps = rng.uniform(1.0 - self.rel, 1.0 + self.rel, n)
        p50 = tokens * eps
        jitter = rng.uniform(self.jit_lo, self.jit_hi, n)
        f32 = lambda a: a.astype(np.float32)  # noqa: E731
        return {"bucket": bucket.astype(np.int32), "tokens": f32(tokens),
                "p50": f32(p50), "p90": f32(p50 * self.p90_mult),
                "jitter": f32(jitter), "arrival_s": arrival / 1000.0}

    def _have(self, n: int) -> None:
        while len(self._buf["bucket"]) - self._pos < n:
            new = self._draw_chunk()
            self._buf = {k: np.concatenate([self._buf[k][self._pos:], new[k]])
                         for k in FIELDS}
            self._pos = 0

    def take(self, n: int) -> dict:
        """The next `n` requests, as arrays."""
        self._have(n)
        out = {k: self._buf[k][self._pos:self._pos + n] for k in FIELDS}
        self._pos += n
        return out

    def take_until(self, now_ms: np.float32) -> dict:
        """Every further request that has arrived by `now_ms`, in the
        session's float32 milliseconds."""
        n = 0
        while True:
            self._have(n + 1)
            if arrival_ms(self._buf["arrival_s"][self._pos + n]) > now_ms:
                return self.take(n)
            n += 1
