"""The scope and span readers (bench/scopes.py), on synthetic intervals
and on a small trace of a profiled `ClientSession` recorded on the CPU
(data/session_trace, made by data/make_session_trace.py)."""
import ast
import glob
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
SESSION_TRACE = os.path.join(HERE, "data", "session_trace")
OLD_TRACE = os.path.join(HERE, "data", "cpu_trace")
PHASES = ("ingest", "classify", "staging", "dispatch", "mirrors", "pull",
          "grants")
SRC = os.path.realpath(os.path.join(HERE, "..", "..", "src", "repro"))
NEW = ("tick.device_us.live", "tick.fetch_us", "session.put_us",
       "device.busy_us.sim", "scan.retire_us", "scan.admit_us",
       "scan.order_us", "scan.grant_us", "scan.apply_us")


def scoped(ops=(), spans=None, puts=(), lo=0.0, hi=1e9, done=()):
    return scopes.Scoped(lo, hi, [scopes.Op(*o) for o in ops], spans or {},
                         list(puts), sorted(done))


def test_stage_is_the_innermost_tick_scope():
    assert scopes.stage_of("jit(f)/while/body/tick.retire/add") == \
        "tick.retire"
    assert scopes.stage_of(
        "jit(f)/tick.grant/while/body/tick.order/sort") == "tick.order"
    assert scopes.stage_of("tick.apply/tick.apply/scatter") == "tick.apply"
    assert scopes.stage_of("jit(f)/ticket.x/add") is None
    assert scopes.stage_of("") is None and scopes.stage_of(None) is None


def test_stage_self_time_leaves_nested_ops_out():
    ops = [(0, 10, "while", "tick.grant"), (1, 3, "cond", "tick.grant"),
           (4, 6, "fusion.1", "tick.order"), (5, 6, "copy", None),
           (12, 14, "fusion.2", None),            # outside any scope
           (15, 18, "sort", "tick.order")]
    # the while keeps 10 - 2 - 2, the nested fusion 2 - 1
    assert scopes.stage_self_ns(scoped(ops)) == {
        "tick.grant": 8.0, "tick.order": 4.0, None: 3.0}


def _pb(*fields) -> bytes:
    """A protobuf message from (field number, int | bytes | str | list of
    ints) pairs: varints, length-delimited values, packed varints."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
            continue
        if isinstance(v, str):
            v = v.encode()
        elif isinstance(v, list):
            v = b"".join(varint(x) for x in v)
        out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _instr(iid, name, opcode="add", op_name="", operands=(), called=()):
    fields = [(1, name), (2, opcode), (7, _pb((2, op_name))), (35, iid)]
    if operands:
        fields.append((36, list(operands)))
    if called:
        fields.append((38, list(called)))
    return _pb(*fields)


def test_module_stages_follow_scope_fusion_root_users_then_operands():
    fused = _pb((1, "fused"), (5, 1), (6, 11),
                (2, _instr(10, "p", "parameter")),
                (2, _instr(11, "scatter", "scatter",
                           "jit(f)/tick.admit/scatter", [10])))
    main = _pb(
        (1, "main"), (5, 2), (6, 26),
        (2, _instr(20, "a", "add", "jit(f)/tick.retire/add")),
        (2, _instr(21, "reshape.1", "reshape", "", [20])),   # users: fusion
        (2, _instr(22, "fusion.1", "fusion", "", [21], [1])),
        (2, _instr(23, "copy.1", "copy", "", [22])),         # only user: root
        (2, _instr(24, "b", "sort",
                   "jit(f)/tick.grant/while/body/tick.order/sort", [22])),
        (2, _instr(25, "const", "constant")),                # no scope around
        (2, _instr(26, "tuple", "tuple", "", [23, 24])))
    stages = scopes._module_stages(memoryview(_pb((3, fused), (3, main))))
    # own scope, or the fusion root's: not inferred
    assert stages["scatter"] == stages["fusion.1"] == ("tick.admit", False)
    assert stages["a"] == ("tick.retire", False)
    assert stages["b"] == ("tick.order", False)
    # by the first user, else the first operand: inferred
    assert stages["p"] == stages["reshape.1"] == ("tick.admit", True)
    assert stages["copy.1"] == stages["tuple"] == ("tick.admit", True)
    assert stages["const"] == (None, False)


def test_inferred_time_is_reported_apart():
    ops = [(0, 10, "fusion.1", "tick.retire"),
           (10, 14, "copy.1", "tick.retire", True),
           (14, 20, "fusion.2", "tick.admit"),
           (20, 21, "copy.2", "tick.admit", True),   # nested in fusion.3
           (20, 30, "fusion.3", "tick.grant", True)]
    sc = scoped(ops)
    assert scopes.stage_self_ns(sc) == {"tick.retire": 14.0,
                                        "tick.admit": 7.0,
                                        "tick.grant": 9.0}
    assert scopes.inferred_self_ns(sc) == {"tick.retire": 4.0,
                                           "tick.admit": 1.0,
                                           "tick.grant": 9.0}
    # copy.1 lies between retire and admit ops, copy.2 between admit and
    # nothing; fusion.3's neighbours with a scope of their own are admit
    # only, so its 9 ns disagree
    assert scopes.neighbour_agreement(sc) == pytest.approx(5 / 14)
    assert scopes.neighbour_agreement(scoped(ops[:1])) is None


def test_iterations_count_one_retire_op_not_a_nested_loop():
    ops = []
    for i in range(5):               # five scan iterations of 100 ns
        t0 = 1000 + 100 * i
        ops += [(t0, t0 + 5, "a", "tick.retire"),
                (t0 + 5, t0 + 10, "b", "tick.retire"),
                (t0 + 10, t0 + 15, "c", "tick.retire")]
        ops += [(t0 + 20 + 10 * g, t0 + 25 + 10 * g, "g", "tick.grant")
                for g in range(4)]   # the grant loop's op runs 4x
    # a retire op whose first run the window cuts off
    sc = scoped(ops, lo=1003, hi=2000)
    assert scopes.iterations(sc) == 5
    assert scopes.iterations(sc, "tick.grant") == 20
    assert scopes.iterations(scoped([(0, 1, "x", None)])) == 0
    per = scopes.scan_stage_us(sc, "tick.grant")
    assert per == pytest.approx(4 * 5 / 1e3)  # four 5 ns runs a tick
    assert scopes.scan_stage_us(scoped([(0, 1, "x", None)]),
                                "tick.grant") is None


def _live(lo=0.0):
    """Three polls of 100 ns: dispatch [d, d+20), pull [d+40, d+80);
    device ops inside each, one of the first poll's ops before `lo`; the
    runtime notes each tick done at d+65, and a stray program's done
    before the first pull."""
    spans = {"session.dispatch": [], "session.pull": []}
    ops, puts, done = [], [], [30]
    for k in range(3):
        d = 100 * k
        spans["session.dispatch"].append((d, d + 20))
        spans["session.pull"].append((d + 40, d + 80))
        puts += [(d + 2, d + 6), (d + 8, d + 10)]
        done.append(d + 65)
        ops += [(d + 25, d + 45, "f", "tick.retire"),
                (d + 30, d + 35, "g", "tick.order"),   # nested in f
                (d + 50, d + 60, "h", "tick.grant")]
    puts.append((90, 95))  # a put outside any dispatch span
    return scoped(ops, spans, puts, lo=lo, hi=300, done=done)


def test_live_poll_device_time_and_fetch():
    sc = _live()
    assert scopes.polls(sc) == [(0, 40, 80), (100, 140, 180),
                                (200, 240, 280)]
    assert scopes.tick_busy(sc) == [30, 30, 30]
    assert scopes.device_us_live(sc) == pytest.approx(30 / 1e3)
    # the pull ends 15 ns after the last done of its poll
    assert scopes.fetch_us(sc) == pytest.approx(15 / 1e3)
    assert scopes.put_us(sc) == pytest.approx(6 / 1e3)


def test_fetch_counts_only_the_pull_after_the_done():
    """A tick that is done before its pull starts leaves the whole pull
    as fetch; a poll with no done is left out."""
    sc = _live()
    early = sc._replace(done=[30, 35, 165])   # poll 0 done before its pull
    assert scopes.fetch_us(early) == pytest.approx((40 + 15) / 2 / 1e3)
    assert scopes.fetch_us(sc._replace(done=[])) is None


def test_a_poll_that_straddles_the_slice_edge_is_left_out():
    sc = _live(lo=10)
    assert scopes.polls(sc) == [(100, 140, 180), (200, 240, 280)]
    # its ops are not counted toward the next poll either
    assert scopes.tick_busy(sc) == [30, 30]
    assert scopes.fetch_us(sc) == pytest.approx(15 / 1e3)
    assert scopes.put_us(sc) == pytest.approx(6 / 1e3)


def test_a_dispatch_without_its_pull_is_no_poll():
    sc = _live()
    spans = dict(sc.spans, **{"session.pull": sc.spans["session.pull"][1:]})
    assert scopes.polls(sc._replace(spans=spans)) == [(100, 140, 180),
                                                      (200, 240, 280)]


def test_nothing_to_read_gives_none():
    empty = scoped()
    assert scopes.device_us_live(empty) is None
    assert scopes.fetch_us(empty) is None
    assert scopes.put_us(empty) is None


@pytest.fixture(scope="module")
def recorded():
    path = trace.find_xplane(SESSION_TRACE)
    return path, scopes.load(path, {"poll"})


def test_recorded_session_spans_nest_under_poll(recorded):
    path, _ = recorded
    _, annots, host = trace.load(path, 1, {"poll"})
    assert len(annots) == 3
    labels = {lab for *_, lab in trace.host_segments(host, {"poll"})}
    assert {f"poll>session.{p}" for p in PHASES} <= labels
    for s, t, n in host:
        if n.startswith("session."):
            assert any(a <= s and t <= b for a, b, _ in annots), n


def test_recorded_session_reads_its_layers(recorded):
    path, sc = recorded
    assert {k: len(v) for k, v in sc.spans.items()} == {
        f"session.{p}": 3 for p in PHASES}
    # the CPU trace's ops carry no path: their stages come from the
    # compiled modules the trace holds
    programs = scopes.op_stages(path)
    assert any(st == ("tick.order", False) for m in programs.values()
               for st in m.values())
    staged = {o.stage for o in sc.ops}
    assert {"tick.retire", "tick.admit", "tick.order", "tick.grant",
            "tick.apply"} <= staged
    assert len(scopes.polls(sc)) == 3
    assert scopes.device_us_live(sc) > 0
    assert scopes.put_us(sc) > 0
    # on the CPU an op's end stands for the runtime's done: the fetch is
    # the part of each pull after the poll's last op
    assert len(sc.done) == len(sc.ops)
    pulls = sc.spans["session.pull"]
    assert 0 < scopes.fetch_us(sc) <= max(t - s for s, t in pulls) / 1e3


@pytest.mark.parametrize("name", NEW)
def test_metric_reads_none_from_a_program_without_scopes(name):
    """A trace of code that has neither the scopes nor the spans (as the
    program before them): each new metric reports nothing, and raises
    nothing."""
    names = {"topup", "poll"}
    cell = SimpleNamespace(trace_dir=OLD_TRACE, annotations=names)
    ctx = {"cell": cell, "trace": trace.summarize(OLD_TRACE, 1, names)}
    assert harness.load_module("metrics", name).read(ctx) is None
    assert harness.load_module("metrics", name).read({}) is None


# --- the inference against the program's source ---------------------------
def _source_scopes() -> list:
    """(file, first line, last line, stage) of each scope in the program's
    source: a function under `@stages.scoped(stages.X)` or a block under
    `with jax.named_scope(stages.X)`."""
    from repro.core import stages

    out = []
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef):
                exprs = node.decorator_list
            elif isinstance(node, ast.With):
                exprs = [item.context_expr for item in node.items]
            else:
                continue
            for e in exprs:
                if (isinstance(e, ast.Call) and e.args
                        and ast.unparse(e.func) in ("stages.scoped",
                                                    "jax.named_scope")
                        and ast.unparse(e.args[0]).startswith("stages.")):
                    out.append((path, node.lineno, node.end_lineno,
                                getattr(stages, e.args[0].attr)))
    return out


def _frames(module) -> dict:
    """Stack frame id -> [(file, line)], innermost call first, from the
    module's stack frame index (HloModuleProto field 17: file names 1,
    file locations 3 (file id 1, line 3), frames 4 (location id 1, parent
    frame id 2); ids count from 1)."""
    index = next((v for k, v in scopes._fields(module) if k == 17), b"")
    files, locs, frames = [], [], []
    for k, v in scopes._fields(index):
        if k == 1:
            files.append(os.path.realpath(scopes._text(v)))
        elif k == 3:
            locs.append(dict(scopes._fields(v)))
        elif k == 4:
            frames.append(dict(scopes._fields(v)))
    out = {}
    for fid in range(1, len(frames) + 1):
        chain, f = [], fid
        while f:
            loc = locs[frames[f - 1][1] - 1]
            chain.append((files[loc.get(1, 1) - 1], loc.get(3, 0)))
            f = frames[f - 1].get(2, 0)
        out[fid] = chain
    return out


def _source_stage(chain, scoped_src):
    """The innermost scope around the first call of `chain` that lies in
    one, or None."""
    for path, line in chain:
        inside = [(hi - lo, st) for p, lo, hi, st in scoped_src
                  if p == path and lo <= line <= hi]
        if inside:
            return min(inside)[1]
    return None


def _inferred_against_source(module: bytes, scoped_src) -> list:
    """(inferred stage, stage of its source line) of each instruction
    whose stage was inferred and whose stack frame lies in a scope."""
    module = memoryview(module)
    stages, frames = scopes._module_stages(module), _frames(module)
    out = []
    for k, comp in scopes._fields(module):
        if k != 3:
            continue
        for j, instr in scopes._fields(comp):
            if j != 2:
                continue
            fields = dict(scopes._fields(instr))
            st, inferred = stages[scopes._text(fields[1])]
            fid = dict(scopes._fields(fields.get(7, b""))).get(15)
            truth = _source_stage(frames.get(fid, ()), scoped_src)
            if inferred and truth:
                out.append((st, truth))
    return out


def test_inferred_stages_follow_the_source_line():
    """The compiler leaves its own instructions without a scope; those
    that keep a stack frame name the source line they came from.  In the
    live tick at the recorded trace's size (W = 16, B = 2) and in a short
    windowed scan, compiled now, the stage inferred for such an
    instruction is that of the scope around its source line in all but a
    few cases (a rewritten cumsum whose only users lie in the next
    stage)."""
    import jax
    import jax.numpy as jnp

    from repro.client import ClientSession, MockProvider, Request, \
        SessionConfig
    from repro.core.policy import strategy
    from repro.sim import SimConfig, default_physics, runner
    from repro.sim import scenarios as scn

    phys = default_physics()
    sess = ClientSession(MockProvider(phys, dt_ms=25.0),
                         strategy("final_adrr_olc"),
                         SessionConfig(window=16, max_grants=2),
                         clock="virtual", phys=phys)
    for i in range(12):
        sess.submit(Request(rid=0, prompt=None, max_new=40.0, p50=40.0,
                            bucket=i % 4, arrival_s=0.01 * i))
    for _ in range(3):
        sess.poll()
    live = sess._tick.lower(
        sess._win_batch, sess._dev_state, sess._pending, sess._comp,
        sess._staged_px, np.int32(0), np.float32(25.0)).compile()
    scan = runner._run_scenario_seeds.lower(
        strategy("final_adrr_olc"), phys,
        jax.vmap(jax.random.PRNGKey)(jnp.arange(0, 2)),
        scn.get_scenario("high_congestion"),
        SimConfig(n_ticks=40, window=32, k_slots=4), 40, "paper2",
        "coarse", 1.0).compile()
    src = _source_scopes()
    pairs = []
    for compiled in (live, scan):
        pairs += _inferred_against_source(
            compiled.runtime_executable().hlo_modules()[0]
            .as_serialized_hlo_module_proto(), src)
    assert len(pairs) >= 50
    assert sum(a == b for a, b in pairs) >= 0.95 * len(pairs)
