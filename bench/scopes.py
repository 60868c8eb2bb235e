"""Device scopes and host spans of a traced run, for the per-layer metrics
of the decision tick's stages and of the live poll's phases.

The program names its work on the profiler's clock in two ways:

* Device scopes: each stage of the decision tick traces its ops under a
  `jax.named_scope` `tick.<stage>` (`repro/core/stages.py`), which the
  compiled program keeps as each instruction's `op_name`.  The op events
  carry no such path (a TPU's `XLA Ops` events hold only their device
  offset and duration), so it is read from the compiled modules the
  profiler stores in the trace's `/host:metadata` plane: the `op_name`
  of the instruction the event names, in the program it ran in (on a
  TPU the `XLA Modules` event around it, `<name>(<program id>)`; on the
  CPU its `program_id` stat).  An op goes to the innermost `tick.*`
  scope of its path.  A fusion carries its root instruction's
  `op_name`, so a fusion's time goes to the stage of its root.  An
  instruction the compiler made without an `op_name` is given a stage
  by its neighbours in the module and is marked inferred.
* Host spans: `ClientSession.poll` opens a profiler annotation
  `session.<phase>` for each phase of a profiled poll; the runtime's
  `DevicePut` events (host-to-device argument transfers) sit inside
  `session.dispatch`, and its `tpu::System::Execute=>Done` event (the
  host learning that a program finished) inside `session.pull`.  On the
  CPU backend, whose ops are host events, an op's end is itself on the
  host's clock and stands for that notice.

The window is bench/trace.py's: from the first to the last host
annotation the harness wrote.  `of(ctx)` parses the run's `.xplane.pb`
once and keeps the result for every metric of the run.  The readers
below take plain interval lists, so the tests drive them directly; each
returns None where the trace holds nothing to read (a program without
the scopes or spans).

    python3 -m bench.scopes <trace dir> [<annotation> ...]

prints the scan stages' self time per iteration of a traced run, split
into the time of ops scoped by their own `op_name` and inferred time.
"""
from __future__ import annotations

import re
import statistics
import sys
from collections import Counter
from typing import NamedTuple, Optional

from bench import trace

_STAGE = re.compile(r"(?:^|/)(tick\.[A-Za-z_]+)(?=/|$)")
_PROGRAM = re.compile(r"\((\d+)\)$")
SESSION = "session."
DEVICE_PUT = "DevicePut"
EXECUTE_DONE = "tpu::System::Execute=>Done"


class Op(NamedTuple):
    start: float
    end: float
    name: str
    stage: Optional[str]
    inferred: bool = False   # stage taken from neighbours, not own op_name


class Scoped(NamedTuple):
    lo: float                 # the window, ns
    hi: float
    ops: list                 # [Op] of chip 0
    spans: dict               # "session.<phase>" -> [(start, end)]
    puts: list                # [(start, end)] of DevicePut events
    done: list                # host-clock ends of device work (see load)


def stage_of(path: str) -> Optional[str]:
    """The innermost `tick.<stage>` component of an op-name path."""
    found = _STAGE.findall(path or "")
    return found[-1] if found else None


def _fields(buf):
    """(field number, value) of a protobuf message, in wire order: an int
    for a varint, a memoryview for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return out, i


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _packed(buf) -> list[int]:
    out, i = [], 0
    while i < len(buf):
        v, i = _varint(buf, i)
        out.append(v)
    return out


def op_stages(path: str) -> dict:
    """program id -> {instruction name -> (stage, inferred)}, from the
    `Hlo Proto` stats of the trace's `/host:metadata` plane.  Wire
    fields: XSpace planes 1; XPlane name 2, event_metadata 4 (map: key 1,
    value 2), stat_metadata 5; XEventMetadata id 1, stats 5; XStat
    metadata_id 1, bytes_value 6; HloProto hlo_module 1."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[int, dict] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if _text(next((v for k, v in fields if k == 2), b"")) != \
                "/host:metadata":
            continue
        stat_name = {}
        for k, v in fields:
            if k == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_name[meta.get(1, 0)] = _text(meta.get(2, b""))
        for k, v in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            pid = next((x for j, x in meta if j == 1), None)
            for j, stat in meta:
                stat = dict(_fields(stat)) if j == 5 else {}
                if stat_name.get(stat.get(1)) != "Hlo Proto" or 6 not in stat:
                    continue
                try:
                    out[pid] = _module_stages(
                        dict(_fields(stat[6])).get(1, b""))
                except (ValueError, IndexError):
                    pass  # a module we cannot decode: its ops stay unscoped
    return out


def _module_stages(module) -> dict:
    """Instruction name -> (stage, inferred).  An instruction's stage is
    the innermost `tick.*` scope of its own `op_name`; for a fusion
    without one, its fused computation root's.  The compiler's own
    instructions (it gives them an empty `op_name`: the pieces of a
    rewritten scatter or cumsum, copies, reshapes) take the stage of
    their first user that has one, else of their first operand that has
    one, and are marked inferred.  Wire fields: HloModuleProto
    computations 3; HloComputationProto instructions 2, id 5, root_id 6;
    HloInstructionProto name 1, opcode 2, metadata 7, id 35, operand_ids
    36, called_computation_ids 38; OpMetadata op_name 2."""
    stage: dict = {}    # instruction id -> stage
    root: dict = {}     # computation id -> root instruction id
    comps = []          # [(instruction id, name, operand ids)] each
    users: dict = {}    # instruction id -> user ids
    # callees come before callers, operands before users
    for k, comp in _fields(module):
        if k != 3:
            continue
        cid = rid = None
        instrs = []
        for j, instr in _fields(comp):
            if j == 5:
                cid = instr
            elif j == 6:
                rid = instr
            if j != 2:
                continue
            name, opcode, iid, st, operands, called = None, "", None, None, \
                [], []
            for i, v in _fields(instr):
                if i == 1:
                    name = _text(v)
                elif i == 2:
                    opcode = _text(v)
                elif i == 7:
                    st = stage_of(_text(dict(_fields(v)).get(2, b"")))
                elif i == 35:
                    iid = v
                elif i == 36:
                    operands = _packed(v)
                elif i == 38:
                    called = _packed(v)
            if st is None and opcode == "fusion":
                st = next((stage[root[c]] for c in called
                           if stage.get(root.get(c))), None)
            stage[iid] = st
            instrs.append((iid, name, operands))
            for o in operands:
                users.setdefault(o, []).append(iid)
        root[cid] = rid
        comps.append(instrs)
    own = {iid for iid, st in stage.items() if st}
    for instrs in comps:
        for iid, _, _ in reversed(instrs):
            if stage[iid] is None:
                stage[iid] = next((stage[u] for u in users.get(iid, ())
                                   if stage[u]), None)
        for iid, _, operands in instrs:
            if stage[iid] is None:
                stage[iid] = next((stage[o] for o in operands
                                   if stage.get(o)), None)
    return {name: (stage[iid], bool(stage[iid]) and iid not in own)
            for instrs in comps for iid, name, _ in instrs}


def _tpu_ops(lines: dict, programs: dict) -> list:
    """[Op] of chip 0's `XLA Ops` events; each op's program is that of
    the `XLA Modules` event it starts in."""
    modules = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, int(m.group(1)))
        for e in (lines["XLA Modules"].events if "XLA Modules" in lines
                  else ()) if (m := _PROGRAM.search(e.name)))
    ops, k = [], 0
    evs = lines["XLA Ops"].events if "XLA Ops" in lines else ()
    for s, t, n in sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in evs):
        while k < len(modules) and modules[k][1] <= s:
            k += 1
        stages = programs.get(modules[k][2], {}) \
            if k < len(modules) and modules[k][0] <= s else {}
        ops.append(Op(s, t, n, *stages.get(n.split(" ", 1)[0].lstrip("%"),
                                           (None, False))))
    return ops


def load(path: str, names) -> Scoped:
    """Parse one `.xplane.pb`; `names` are the harness's annotations.
    The annotations and the spans come from `trace.load`; the ops'
    programs and the host-clock instants at which device work was done
    from a walk of the device and host planes: the ends of the runtime's
    `Execute=>Done` events (on its own threads) on a TPU, each op's end
    on the CPU."""
    from jax.profiler import ProfileData

    _, annots, host = trace.load(path, 1, names)
    spans: dict[str, list] = {}
    puts = []
    for s, t, n in host:
        if n.startswith(SESSION):
            spans.setdefault(n, []).append((s, t))
        elif n == DEVICE_PUT:
            puts.append((s, t))
    programs = op_stages(path)
    planes = list(ProfileData.from_file(path).planes)
    has_tpu = any(p.name.startswith("/device:TPU:") for p in planes)
    ops, done = [], []
    for plane in planes:
        if plane.name == "/device:TPU:0":
            ops = _tpu_ops({ln.name: ln for ln in plane.lines}, programs)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == EXECUTE_DONE:
                        done.append(e.start_ns + e.duration_ns)
                    elif not has_tpu:  # the CPU backend's ops are host events
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            done.append(e.start_ns + e.duration_ns)
                            ops.append(Op(
                                e.start_ns, e.start_ns + e.duration_ns,
                                stats["hlo_op"], *programs.get(
                                    stats.get("program_id"), {}).get(
                                        stats["hlo_op"], (None, False))))
    ends = [(s, t) for s, t, _ in annots] or [(o.start, o.end) for o in ops]
    lo = min((s for s, _ in ends), default=0.0)
    hi = max((t for _, t in ends), default=0.0)
    return Scoped(lo, hi, ops, spans, puts, sorted(done))


def of(ctx) -> Optional[Scoped]:
    """The run's scopes and spans: parsed by the first metric that asks,
    and kept in the run's metric context for the others."""
    if "scoped" not in ctx:
        cell = ctx.get("cell")
        ctx["scoped"] = load(trace.find_xplane(cell.trace_dir),
                             cell.annotations) \
            if getattr(cell, "trace_dir", None) else None
    return ctx["scoped"]


# --- the live poll --------------------------------------------------------
def polls(sc: Scoped) -> list[tuple[float, float, float]]:
    """(start of `session.dispatch`, start and end of `session.pull`) of
    each poll that lies wholly inside the window: each dispatch is paired
    with the first pull that starts after it and before the next
    dispatch."""
    disp = sorted(sc.spans.get(SESSION + "dispatch", []))
    pull = sorted(sc.spans.get(SESSION + "pull", []))
    out, j = [], 0
    for i, (d0, _) in enumerate(disp):
        nxt = disp[i + 1][0] if i + 1 < len(disp) else float("inf")
        while j < len(pull) and pull[j][0] < d0:
            j += 1
        if j < len(pull) and pull[j][0] < nxt:
            p0, p1 = pull[j]
            if sc.lo <= d0 and p1 <= sc.hi:
                out.append((d0, p0, p1))
            j += 1
    return out


def tick_busy(sc: Scoped) -> list[float]:
    """Per poll: busy ns of the device ops that start between its
    dispatch start and pull end; polls with no such op are left out."""
    ops = sorted((o.start, o.end) for o in sc.ops)
    out, k = [], 0
    for d0, _, p1 in polls(sc):
        while k < len(ops) and ops[k][0] < d0:
            k += 1
        mine = []
        while k < len(ops) and ops[k][0] < p1:
            mine.append(ops[k])
            k += 1
        if mine:
            out.append(sum(b - a for a, b in
                           trace.union(mine, d0, float("inf"))))
    return out


def device_us_live(sc: Scoped) -> Optional[float]:
    per = tick_busy(sc)
    return sum(per) / len(per) / 1e3 if per else None


def fetch_us(sc: Scoped) -> Optional[float]:
    """Host time per poll in `session.pull` after the poll's device work
    was done (the last of `sc.done` between its dispatch start and its
    pull end): the summary's copy back and the return to Python.  Both
    ends are on the host's clock.  Polls with no such instant are left
    out."""
    done = sc.done
    out, k = [], 0
    for d0, p0, p1 in polls(sc):
        while k < len(done) and done[k] < d0:
            k += 1
        last = None
        while k < len(done) and done[k] <= p1:
            last = done[k]
            k += 1
        if last is not None:
            out.append(p1 - max(last, p0))
    return sum(out) / len(out) / 1e3 if out else None


def put_us(sc: Scoped) -> Optional[float]:
    """Host time in `DevicePut` events inside the window's
    `session.dispatch` spans, per such span (one a poll)."""
    disp = [(s, t) for s, t in sc.spans.get(SESSION + "dispatch", [])
            if sc.lo <= s and t <= sc.hi]
    if not disp:
        return None
    total = sum(b - a for s, t in disp for a, b in trace.union(sc.puts, s, t))
    return total / len(disp) / 1e3


# --- the scan engine ------------------------------------------------------
def _in_window(sc: Scoped) -> list:
    return [o for o in sc.ops if sc.lo <= o.start < sc.hi]


def iterations(sc: Scoped, stage: str = "tick.retire") -> int:
    """Scan iterations in the window: how often one op of `stage` ran
    there (the median count over that stage's op names, so that an op
    in a nested loop, or one cut by the window's edge, does not set it)."""
    counts = Counter(o.name for o in _in_window(sc) if o.stage == stage)
    return statistics.median_low(counts.values()) if counts else 0


def stage_self_ns(sc: Scoped) -> dict:
    """Self time (ns) of the window's device ops, summed by stage (None
    for unscoped ops); an op's self time excludes the ops nested in it,
    so a loop's own time stays with the loop's stage."""
    return trace.self_times([(o.start, o.end, o.stage)
                             for o in _in_window(sc)])


def inferred_self_ns(sc: Scoped) -> dict:
    """The part of `stage_self_ns` carried by ops whose stage was
    inferred, by stage."""
    acc = trace.self_times([(o.start, o.end, (o.stage, o.inferred))
                            for o in _in_window(sc)])
    return {st: v for (st, inferred), v in acc.items() if inferred}


def neighbour_agreement(sc: Scoped) -> Optional[float]:
    """Share of the inferred ops' self time whose stage is that of the
    nearest op before or after them, in the order the device ran them,
    with a stage of its own: a check of the inference by the schedule,
    which it does not read."""
    ops = sorted(_in_window(sc), key=lambda o: (o.start, -o.end))
    self_ns = trace.self_times([(o.start, o.end, i)
                                for i, o in enumerate(ops)])
    own = [i for i, o in enumerate(ops) if o.stage and not o.inferred]
    agree = total = 0.0
    j = 0
    for i, o in enumerate(ops):
        if not o.inferred:
            continue
        while j < len(own) and own[j] < i:
            j += 1
        near = {ops[own[x]].stage for x in (j - 1, j) if 0 <= x < len(own)}
        total += self_ns[i]
        agree += self_ns[i] * (o.stage in near)
    return agree / total if total else None


def scan_stage_us(sc: Scoped, stage: str) -> Optional[float]:
    n = iterations(sc)
    if not n:
        return None
    return stage_self_ns(sc).get(stage, 0.0) / n / 1e3


def main(argv: list[str]) -> None:
    names = set(argv[1:]) or {"sim_call", "poll", "arrive"}
    sc = load(trace.find_xplane(argv[0]), names)
    n = iterations(sc)
    if not n:
        raise SystemExit("no scan iteration in the trace's window")
    total, inferred = stage_self_ns(sc), inferred_self_ns(sc)
    print(f"{n} iterations; us per iteration: stage, self, of it inferred")
    for st in sorted(total, key=lambda x: -total[x]):
        print(f"{st} {total[st] / n / 1e3:.2f} "
              f"{inferred.get(st, 0.0) / n / 1e3:.2f}")
    print(f"inferred time in its schedule neighbours' stage: "
          f"{neighbour_agreement(sc)}")


if __name__ == "__main__":
    main(sys.argv[1:])
