"""Device time of the window view's gathers: one element gather per field
against one gather of a packed table, row-major `(N, 8)` or field-major
`(8, N)` as `sim/engine.pack_batch` builds it (DESIGN.md §6).

Each case runs `REPS` iterations of a `fori_loop`, vmapped over the
seed axis the way `run_scenario_cell` vmaps the engine, and gathers
(S, N) request fields into the (S, W) window at fresh indices each
iteration.  The request-state cases also scatter one element into every
field per iteration, as the engine's apply step does, so stacking them
into rows cannot be hoisted out of the loop.  A loop that only draws
the indices (and, for the request state, scatters) is the baseline; each
case reports its time per iteration above that baseline.

    python benchmarks/window_gather.py            # on the chip
    python benchmarks/window_gather.py --smoke    # tiny, any backend

Prints one JSON object with the device and us/iteration per case.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax import lax

REPS = 2000
SIZES = ((5, 160, 256), (1, 1_000_000, 4096))  # (seeds, N, W)
N_BATCH = 8   # arrival, bucket, cls, true_tokens, p50, p90, budget, valid
N_REQ = 6     # status, submit, finish, defer_until, n_defers, n_throttles


def _fields(key, n: int, f: int) -> tuple:
    """f (n,) int32 fields; gathers move bits, so the dtype of a real
    field (f32, s32) costs the same once bitcast."""
    return tuple(jax.random.randint(jax.random.fold_in(key, i), (n,), 0,
                                    1 << 20, jnp.int32) for i in range(f))


def _next_idx(idx, i, n):
    return (idx * 5 + 3 + i) % n


def _loop(view, fields, n: int, w: int, mutate: bool, reps: int):
    """Per seed: `reps` iterations of `view(fields, idx)`, each (W,)
    field of the view summed into an accumulator of its own."""

    def body(i, c):
        idx, acc, fs = c
        idx = _next_idx(idx, i, n)
        if mutate:
            fs = tuple(f.at[i % n].set(f[i % n] + 1) for f in fs)
        v = view(fs, idx)
        return idx, tuple(a + x for a, x in zip(acc, v)), fs

    idx0 = jnp.arange(w, dtype=jnp.int32) % n
    acc0 = tuple(jnp.zeros((w,), jnp.int32) for _ in fields)
    _, acc, _ = lax.fori_loop(0, reps, body, (idx0, acc0, fields))
    return sum(a.sum() for a in acc)


def view_none(fs, idx):
    return tuple(idx + j for j in range(len(fs)))


def view_elements(fs, idx):
    return tuple(f[idx] for f in fs)


def _unpack(rows):
    return tuple(rows[:, j] for j in range(rows.shape[1]))


def view_rows_packed(table):
    """The batch case: the table is packed once, outside the loop."""
    return lambda fs, idx: _unpack(table[idx])


def view_cols_packed(table_t):
    """The batch case with the table field-major, (F, N): one gather of
    the column at each index, lane-dense along W."""
    return lambda fs, idx: tuple(table_t[:, idx])


def view_rows_stacked(fs, idx):
    """The request-state case: fields change every iteration, so the
    rows are stacked inside the loop and then gathered."""
    return _unpack(jnp.stack(fs, axis=-1)[idx])


def _time(fn, reps: int, *args) -> float:
    """us per iteration, best of three calls after a warm call."""
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e6


def measure(s: int, n: int, w: int, reps: int = REPS) -> dict:
    keys = jax.random.split(jax.random.PRNGKey(0), s)
    batch = jax.vmap(lambda k: _fields(k, n, N_BATCH))(keys)
    req = jax.vmap(lambda k: _fields(jax.random.fold_in(k, 99), n, N_REQ))(
        keys)
    table = jnp.stack(batch, axis=-1)

    def case(view, fields, mutate, packed=None):
        if packed is None:
            fn = jax.jit(jax.vmap(
                lambda fs: _loop(view, fs, n, w, mutate, reps)))
            return _time(fn, reps, fields)
        fn = jax.jit(jax.vmap(lambda fs, t: _loop(
            view(t), fs, n, w, mutate, reps)))
        return _time(fn, reps, fields, packed)

    base = case(view_none, batch, False)
    base_req = case(view_none, req, True)
    one = case(view_none, batch[:1], False)
    row = {
        "seeds": s, "n": n, "w": w,
        "baseline_us": base,
        "baseline_req_us": base_req,
        "element_gather_1_field_us":
            case(view_elements, batch[:1], False) - one,
        "element_gather_batch_8_us":
            case(view_elements, batch, False) - base,
        "row_gather_batch_8_us":
            case(view_rows_packed, batch, False, packed=table) - base,
        "column_gather_batch_8_us":
            case(view_cols_packed, batch, False,
                 packed=jnp.swapaxes(table, -1, -2)) - base,
        "element_gather_req_6_us":
            case(view_elements, req, True) - base_req,
        "stack_row_gather_req_6_us":
            case(view_rows_stacked, req, True) - base_req,
    }
    return {k: (round(v, 3) if isinstance(v, float) else v)
            for k, v in row.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes and few iterations (any backend)")
    args = ap.parse_args()
    reps, sizes = (20, ((2, 16, 32), (1, 1000, 64))) if args.smoke \
        else (REPS, SIZES)
    d = jax.devices()[0]
    rows = [measure(*sz, reps=reps) for sz in sizes]
    print(json.dumps({"device": {"platform": d.platform,
                                 "kind": d.device_kind},
                      "reps": reps, "rows": rows}))


if __name__ == "__main__":
    main()
