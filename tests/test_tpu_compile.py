"""Compile rehearsal for one TPU v5e chip, with no chip attached.

The TPU compiler is installed beside the CPU backend, so a program can
be lowered and compiled for a *described* v5e chip.  That catches what
interpret mode cannot — Mosaic refusing a scalar VMEM store, a block
whose minor dims do not tile, an unlowerable primitive — at no chip
time.  Nothing runs: these tests say nothing about results or speed
(`chip_smoke.py` checks those on the chip).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers each import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sched_score import sched_score as ss


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _queue(one_chip, n, route):
    args = [_sds(one_chip, (n,)) for _ in range(3)]
    args += [_sds(one_chip, (n,), jnp.bool_),
             _sds(one_chip, (5 if route else 4,))]
    if route:
        args.append(_sds(one_chip, (n,)))
    return args


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n,b", [(1024, 1), (1024, 16), (4096, 1),
                                 (4096, 16)])
def test_sched_score_topb_compiles(one_chip, n, b):
    def f(wait, cost, urg, mask, w):
        return ss.sched_score_topb(wait, cost, urg, mask, w, b=b,
                                   interpret=False)

    assert "tpu_custom_call" in _compiled_text(f, *_queue(one_chip, n, False))


def test_sched_score_topb_route_compiles(one_chip):
    def f(wait, cost, urg, mask, w, route):
        return ss.sched_score_topb(wait, cost, urg, mask, w, route, b=16,
                                   interpret=False)

    assert "tpu_custom_call" in _compiled_text(f, *_queue(one_chip, 4096, True))


def test_sched_score_argmax_compiles(one_chip):
    def f(wait, cost, urg, mask, w):
        return ss.sched_score_argmax(wait, cost, urg, mask, w, interpret=False)

    assert "tpu_custom_call" in _compiled_text(f, *_queue(one_chip, 4096, False))


def test_fused_session_tick_compiles(one_chip, monkeypatch):
    """The whole `ClientSession.poll` device step on the Pallas ordering
    backend at W=4096, B=16, lowered from shapes."""
    import repro.kernels.sched_score.ops as ops
    from repro.client import session
    from repro.core.policy import n_classes, strategy
    from repro.core.types import (
        empty_window_batch, empty_window_request_state, init_sim_state,
    )
    from repro.sim.provider import default_physics

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(session, "_TICK_CACHE", {})  # a fresh trace
    w, b = 4096, 16
    policy = strategy("final_adrr_olc")
    tick = session._tick_for(policy, default_physics(), b, "pallas")

    def shapes(tree):
        return jax.tree.map(
            lambda x: _sds(one_chip, jnp.shape(x), jnp.result_type(x)), tree)

    batch = shapes(empty_window_batch(w))
    state = shapes(init_sim_state(w, n_classes(policy))._replace(
        req=empty_window_request_state(w)))
    n_xfer = session._xfer_parts(w, b)[4].stop  # no watchdog: no charge
    compiled = tick.lower(batch, state, None,
                          _sds(one_chip, (n_xfer,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _flash(one_chip):
    from repro.kernels.flash_attention.flash_attention import flash_attention

    def f(q, k, v):
        return flash_attention(q, k, v, window=200, bq=128, bk=128,
                               interpret=False)
    return f, [_sds(one_chip, (2, 512, 8, 64), jnp.bfloat16),
               *[_sds(one_chip, (2, 512, 2, 64), jnp.bfloat16)] * 2]


def _decode(one_chip):
    from repro.kernels.decode_attention.decode_attention import (
        decode_attention,
    )

    def f(q, k, v, valid):
        return decode_attention(q, k, v, valid, bk=256, interpret=False)
    return f, [_sds(one_chip, (2, 16, 128), jnp.bfloat16),
               *[_sds(one_chip, (2, 1024, 2, 128), jnp.bfloat16)] * 2,
               _sds(one_chip, (1024,), jnp.bool_)]


def _ssd(one_chip):
    from repro.kernels.ssd_scan.ssd_scan import ssd_intra

    def f(xc, bc, cc, dtc, cum):
        return ssd_intra(xc, bc, cc, dtc, cum, interpret=False)
    b, nc, q, h, p, n = 1, 2, 128, 8, 64, 128
    return f, [_sds(one_chip, (b, nc, q, h, p)),
               *[_sds(one_chip, (b, nc, q, n))] * 2,
               *[_sds(one_chip, (b, nc, q, h))] * 2]


@pytest.mark.parametrize("build", [_flash, _decode, _ssd],
                         ids=["flash_attention", "decode_attention",
                              "ssd_intra"])
def test_model_stack_kernel_compiles(one_chip, build):
    """The model-stack kernels behind the real-engine provider: GQA,
    sliding window and bf16 where they apply."""
    fn, args = build(one_chip)
    assert "tpu_custom_call" in _compiled_text(fn, *args)
