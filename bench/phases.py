"""Per-phase reduction of a profiler trace: the program's own scopes and
spans beside the harness's.

The program names each phase of its round with ``jax.named_scope``
(``sssp.local``, ``sssp.prune``, ``sssp.send``, ``sssp.exchange``,
``sssp.deliver``, ``sssp.merge``, ``sssp.toka``, ``sssp.fused``, and
``sssp.init``, ``sssp.finalize``, ``sssp.certificate`` outside the
round), so each device operation carries its phase in its ``op_name``.
Under ``vmap`` the scope lands inside the name
(``jit(f)/vmap(sssp.local)/while/body/...``), so an operation's phase is
the innermost ``sssp.<name>`` token of its ``op_name``, not a prefix. A
control-flow container (``%while``, ``%conditional``) spans its body's
operations but counts only toward its own scope, and one outside every
scope (the shard_map solve's outer ``%while``) toward none.

The engine wraps its host steps in ``TraceAnnotation`` spans
(``sssp.solve``, ``sssp.init``, ``sssp.round`` or ``sssp.compile``,
``sssp.sync``, ``sssp.finalize``, ``sssp.copy_out``, ``sssp.stats``,
``sssp.certificate``, ``sssp.drain``), on the one clock of the harness's
``bench.*`` spans and the device's operations.

``bench/trace.py`` keeps only the harness's spans and the operations'
names; this module reads the same trace file again with the engine's
spans and each operation's phase (from the programs' compiled HLO, which
the profiler stores in the file), and finds the file of a run by its
``bench.window``. From the same HLO it marks the operations of the body of
each loop a phase opens (``loop_trips``: the local fixpoint's sweeps), and
from the spans it times each served query's wait (``queue_waits``).

    python3 bench/phases.py <profile dir>   # phase split, gaps, op stats
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import itertools
import os
import re
import sys
from typing import NamedTuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as trace_mod  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")   # run.py's trace dirs
SPAN_PREFIXES = ("bench.", "sssp.")
PHASES = ("sssp.local", "sssp.prune", "sssp.send", "sssp.exchange",
          "sssp.deliver", "sssp.merge", "sssp.toka", "sssp.fused",
          "sssp.init", "sssp.finalize", "sssp.certificate")
# the phase names alone: a source path such as ``core/sssp.py`` is no scope
SCOPE = re.compile(r"\bsssp\.(?:%s)\b" % "|".join(
    p.split(".", 1)[1] for p in PHASES))


def phase_of(op_name: str) -> str | None:
    """The innermost ``sssp.<name>`` scope of an ``op_name``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


# --------------------------------------------------- op_name lookup ----
#
# ``jax.profiler.ProfileData`` gives a device operation's event its name
# (the HLO instruction's text, ``%fusion.12 = f32[...] fusion(...)``) and
# its own stats, not its op_name. The op_names are in the optimized HLO of
# each program, which the profiler stores in the ``/host:metadata`` plane
# (one event metadata per program, named ``<module>(<program id>)``, with
# an ``Hlo Proto`` stat). This module reads them from the file's protobuf
# wire format (tsl/profiler/protobuf/xplane.proto, xla/service/hlo.proto),
# skipping the event lines. An instruction's phase is its own op_name's
# innermost scope; failing that (XLA leaves some fusions and loops without
# an op_name), the one phase of the instructions it calls, else of its
# nearest neighbours by data flow, else that of the instruction that calls
# its computation. The program of an operation is that of the
# ``XLA Modules`` event around it.

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """(field number, value) of each field of one protobuf message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _ints(buf, value) -> list:
    """A repeated integer field's value: packed (a span) or one varint."""
    if not isinstance(value, tuple):
        return [value]
    out, i = [], value[0]
    while i < value[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def phase_loop(op_name: str) -> str | None:
    """The phase whose scope a ``while`` with this op_name opens directly
    in: the innermost ``sssp.<name>`` token, with the op_name ending in the
    one ``while`` that follows it (``jit(sssp_round)/sssp.local/
    vmap(vmap())/while``). A loop nested in that loop's body, or one that
    XLA made, opens in none."""
    found = list(SCOPE.finditer(op_name))
    if not found or not re.search(r"\bwhile\)*$", op_name):
        return None
    rest = op_name[found[-1].end():]
    return found[-1].group() if len(re.findall(r"\bwhile\b", rest)) == 1 \
        else None


class OpInfo(NamedTuple):
    """What the compiled HLO says of one instruction."""

    phase: str | None = None
    inferred: bool = False    # the phase was not read from op_names
    loop: tuple | None = None
    # (phase, while): the loop opened directly in a phase's scope
    # (:func:`phase_loop`) whose body holds the instruction, which so runs
    # once a trip of that loop


NO_INFO = OpInfo()


def _hlo_phases(buf, span) -> dict:
    """{instruction name: :class:`OpInfo`} of one HloProto, where
    ``inferred`` is False for a phase read from op_names (the
    instruction's own, or those of the instructions it calls) and True
    for one taken from data-flow neighbours or a caller: hlo_module = 1;
    HloModuleProto.computations = 3; HloComputationProto.instructions = 2,
    id = 5; HloInstructionProto.name = 1, opcode = 2, metadata = 7
    (OpMetadata.op_name = 2), id = 35, operand_ids = 36,
    called_computation_ids = 38 (a ``while``'s body first, then its
    condition)."""
    module = next(v for n, v in _fields(buf, *span) if n == 1)
    name, own, calls, operands, home, loops = {}, {}, {}, {}, {}, {}
    for n, comp in _fields(buf, *module):
        if n != 3:
            continue
        ids = []
        for k, v in _fields(buf, *comp):
            if k != 2:
                continue
            i, op_name, called, ops, text, opcode = None, "", [], [], "", ""
            for f, x in _fields(buf, *v):
                if f == 1:
                    text = _text(buf, x)
                elif f == 2:
                    opcode = _text(buf, x)
                elif f == 7:
                    op_name = next((_text(buf, y) for g, y in
                                    _fields(buf, *x) if g == 2), "")
                elif f == 35:
                    i = x
                elif f == 36:
                    ops.extend(_ints(buf, x))
                elif f == 38:
                    called.extend(_ints(buf, x))
            name[i], own[i], calls[i], operands[i] = (
                text, phase_of(op_name), called, ops)
            opens = phase_loop(op_name) if opcode == "while" else None
            if opens and called:
                loops[called[0]] = (opens, text)
            ids.append(i)
        cid = next((v for k, v in _fields(buf, *comp) if k == 5), None)
        home.update((i, cid) for i in ids)
    members, users, caller = {}, {}, {}
    for i, cid in home.items():
        members.setdefault(cid, []).append(i)
        for o in operands[i]:
            users.setdefault(o, []).append(i)
        for c in calls[i]:
            caller[c] = i
    below = {}

    def down(i) -> frozenset:
        """The phases of ``i``: its own, or those of what it calls."""
        if i not in below:
            below[i] = (frozenset([own[i]]) if own[i] else
                        frozenset().union(*(down(j) for c in calls[i]
                                            for j in members.get(c, ()))))
        return below[i]

    def nearest(i) -> frozenset:
        """The phases of the nearest instructions, by data flow, that have
        one phase (XLA's own loops and slices carry no op_name; they take
        the phase of the values they consume and produce)."""
        seen, ring = {i}, [i]
        while ring:
            nxt = []
            for k in ring:
                for j in (*operands[k], *users.get(k, ())):
                    if j not in seen:
                        seen.add(j)
                        nxt.append(j)
            ring = nxt
            found = {next(iter(down(j))) for j in ring if len(down(j)) == 1}
            if found:
                return frozenset(found)
        return frozenset()

    def resolve(i):
        if len(down(i)) == 1:
            return next(iter(down(i))), False
        while i is not None:
            for phases in (down(i), nearest(i)):
                if len(phases) == 1:
                    return next(iter(phases)), True
            i = caller.get(home[i])
        return None, False

    return {name[i]: OpInfo(*resolve(i), loops.get(home[i])) for i in own}


MODULE_ID = re.compile(r"\((\d+)\)$")


def op_phases(path: str) -> dict:
    """{program id: {instruction name: :class:`OpInfo`}} of the programs
    whose HLO the xplane file holds: XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4 (map entry: value = 2); XEventMetadata.name = 2,
    stats = 5; XStat.bytes_value = 6."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        fields = list(_fields(buf, *plane))
        if next((_text(buf, v) for n, v in fields if n == 2), "") != \
                "/host:metadata":
            continue
        for n, entry in fields:
            if n != 4:
                continue
            meta = next(v for k, v in _fields(buf, *entry) if k == 2)
            name, protos = "", []
            for k, v in _fields(buf, *meta):
                if k == 2:
                    name = _text(buf, v)
                elif k == 5:
                    protos.extend(x for f, x in _fields(buf, *v) if f == 6)
            program = MODULE_ID.search(name)
            if program and protos:
                out[int(program.group(1))] = _hlo_phases(buf, protos[0])
    return out


def instruction(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _programs(line) -> tuple[list, list]:
    """Start times and program ids of an ``XLA Modules`` line's events."""
    mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events)
    starts = [s for s, _, _ in mods]
    ids = []
    for s, e, name in mods:
        m = MODULE_ID.search(name)
        ids.append((e, int(m.group(1)) if m else None))
    return starts, ids


def _program_at(starts, ids, t) -> int | None:
    i = bisect.bisect_right(starts, t) - 1
    return ids[i][1] if i >= 0 and t <= ids[i][0] else None


@dataclasses.dataclass
class PhaseTrace(trace_mod.Trace):
    """A :class:`bench.trace.Trace` whose spans include the program's
    ``sssp.*`` ones, with each device operation's phase and whether that
    phase was inferred rather than read from op_names."""

    phases: dict = dataclasses.field(default_factory=dict)
    # device index -> [phase or None], aligned with ``ops``
    inferred: dict = dataclasses.field(default_factory=dict)
    # device index -> [bool], aligned with ``ops``; absent: none inferred
    loops: dict = dataclasses.field(default_factory=dict)
    # device index -> [(program id, phase, while) or None], aligned with
    # ``ops``: the phase loop whose body holds the operation


def load(profile_dir: str) -> PhaseTrace | None:
    """The trace under ``profile_dir``; None without a ``bench.window``."""
    path = trace_mod.find_xplane(profile_dir)
    return None if path is None else _load_file(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _load_file(path: str, mtime: float) -> PhaseTrace | None:
    from jax.profiler import ProfileData
    programs = op_phases(path)
    ops, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        dev = trace_mod.DEVICE_PLANE.match(plane.name)
        lines = list(plane.lines)
        if dev is not None:
            d = int(dev.group(1))
            starts, ids = [], []
            for line in lines:
                if line.name == "XLA Modules":
                    starts, ids = _programs(line)
            for line in lines:
                if line.name != trace_mod.DEVICE_LINE:
                    continue
                for e in line.events:
                    prog = _program_at(starts, ids, e.start_ns)
                    info = programs.get(prog, {}).get(instruction(e.name),
                                                      NO_INFO)
                    if info.loop:
                        info = info._replace(loop=(prog, *info.loop))
                    ops.setdefault(d, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name, info))
            continue
        for line in lines:
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIXES))
    windows = [(s, e) for name, s, e in spans if name == "bench.window"]
    if not windows:
        return None
    phases, inferred, loops = {}, {}, {}
    for d, evs in ops.items():
        evs.sort(key=lambda x: x[:3])
        phases[d] = [info.phase for *_, info in evs]
        inferred[d] = [info.inferred for *_, info in evs]
        loops[d] = [info.loop for *_, info in evs]
        ops[d] = [(s, e, name) for s, e, name, _ in evs]
    return PhaseTrace(ops=ops, spans=spans, window=windows[0], phases=phases,
                      inferred=inferred, loops=loops)


def for_run(run) -> PhaseTrace | None:
    """The trace of a ``--trace 1`` run of ``bench/run.py``: the newest
    file under its trace directory, if its ``bench.window`` is the run's."""
    if getattr(run, "trace", None) is None:
        return None
    found = load(TRACE_ROOT)
    return found if found is not None and found.window == run.trace.window \
        else None


def phase_busy_s(trace: PhaseTrace | None, names,
                 inferred: bool | None = None) -> float | None:
    """Seconds of the window in which an operation whose phase is in
    ``names`` ran, averaged over the devices that ran any; None where no
    operation of the trace has one of those phases (a program without
    the scopes). ``inferred`` True or False counts only the operations
    whose phase was (or was not) inferred. JAX's compilation cache
    ignores op_names, so a program that differs only by its scopes can
    load an executable compiled from another version: one such program
    does not make the others scoped."""
    names = set(names)
    if trace is None or not any(p in names for ps in trace.phases.values()
                                for p in ps):
        return None
    lo, hi = trace.window
    per = []
    for d, evs in trace.ops.items():
        guessed = trace.inferred.get(d) or [False] * len(evs)
        mine = [op for op, p, g in zip(evs, trace.phases[d], guessed)
                if p in names and inferred in (None, g)]
        per.append(trace_mod.covered(trace_mod.merge(mine), lo, hi))
    return sum(per) / len(per) * 1e-9


def unscoped_s(trace: PhaseTrace | None) -> float | None:
    """Seconds of the window in which operations ran but none with a
    phase, averaged over the devices."""
    busy = None if trace is None else trace_mod.busy_s(trace)
    phased = phase_busy_s(trace, PHASES)
    return None if busy is None or phased is None else busy - phased


def loop_trips(trace: PhaseTrace | None, phase: str) -> int | None:
    """Trips run in the window by the loops opened directly in ``phase``'s
    scope, on the device that ran most; None where the trace holds no such
    loop. Every operation of a loop's body runs once a trip, so a loop's
    trips are the events of the body operation seen most often (an
    instruction that runs no kernel leaves no event), summed over the
    loop's runs: under ``vmap`` one trip is a sweep of every lane at once,
    as many as the longest lane needs."""
    if trace is None:
        return None
    lo, hi = trace.window
    per = []
    for d, keys in trace.loops.items():
        seen = {}
        for (s, _, text), key in zip(trace.ops[d], keys):
            if key is not None and key[1] == phase and lo <= s <= hi:
                op = (key, instruction(text))
                seen[op] = seen.get(op, 0) + 1
        trips = {}
        for (key, _), n in seen.items():
            trips[key] = max(trips.get(key, 0), n)
        per.append(sum(trips.values()))
    return max(per, default=0) or None


def queue_waits(trace: PhaseTrace | None, window) -> list | None:
    """Seconds each query of an open loop's window waited from its due time
    to the start of the ``sssp.solve`` span of the batch that answered it:
    its generator lateness (due time to ``submit``, the loop busy in a
    drain) plus the time from its ``bench.submit`` span to that solve.
    ``drain`` packs the pending queries into batches in the order they were
    submitted and solves the batches in turn, so the i-th submit of the
    window is answered by the batch that holds the i-th query. None where
    the spans do not match the window's batches."""
    if trace is None:
        return None
    lo, hi = trace.window

    def starts(span):
        return sorted(s for name, s, _ in trace.spans
                      if name == span and lo <= s <= hi)
    submits, solves = starts("bench.submit"), starts("sssp.solve")
    real = [b.real for b in window.batches]
    if (not real or len(solves) != len(real)
            or not len(submits) == sum(real) == len(window.lateness_s)):
        return None
    begun = [s for s, k in zip(solves, real) for _ in range(k)]
    if any(b < q for b, q in zip(begun, submits)):
        return None
    return [(b - q) * 1e-9 + late
            for b, q, late in zip(begun, submits, window.lateness_s)]


def ms_per_batch(run, names) -> float | None:
    """Device milliseconds per window batch under the phases ``names``."""
    busy = phase_busy_s(for_run(run), names)
    batches = len(run.window.batches)
    return 1e3 * busy / batches if busy is not None and batches else None


def breakdown(trace: PhaseTrace | None) -> dict | None:
    """``bench.trace.breakdown`` with each device operation's phase after
    its name, and each idle gap named by the innermost span open over it,
    the engine's or the harness's."""
    base = trace_mod.breakdown(trace)
    if base is None:
        return None
    lo, hi = trace.window
    dev = min(trace.ops)
    per_op = {}
    for (s, e, text), phase in zip(trace.ops[dev], trace.phases[dev]):
        d = min(e, hi) - max(s, lo)
        if d > 0:
            name = f"{text.split(' = ', 1)[0]} {phase or '-'}"
            per_op[name] = per_op.get(name, 0.0) + d * 1e-9
    base["device_ops"] = [[n, v] for n, v in sorted(
        per_op.items(), key=lambda kv: -kv[1])[:trace_mod.TOP]]
    return base


def describe(profile_dir: str) -> None:
    """Print the stat names of a few device operations (no op_name among
    them, hence the HLO), each phase's busy seconds and largest operation,
    on a line of its own each phase's seconds under operations whose
    phase comes from op_names and the rest, which only operations with an
    inferred phase cover, the time under no phase, the labelled idle gaps, and span
    and event counts."""
    from jax.profiler import ProfileData
    path = trace_mod.find_xplane(profile_dir)
    print("xplane", path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        if trace_mod.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_mod.DEVICE_LINE:
                    evs = list(itertools.islice(line.events, 3))
                    print("op_stats", sorted({k for e in evs
                                              for k, _ in e.stats}))
    t = load(profile_dir)
    if t is None:
        print("no bench.window in the trace")
        return
    busy = trace_mod.busy_s(t)
    print(f"window_s {t.window_s} busy_s {busy} unscoped_s {unscoped_s(t)}")
    lo, hi = t.window
    dev = min(t.ops)
    for name in PHASES:
        s = phase_busy_s(t, (name,))
        top = {}
        for (a, b, text), p in zip(t.ops[dev], t.phases[dev]):
            if p == name and min(b, hi) > max(a, lo):
                key = text.split(" = ", 1)[0]
                top[key] = top.get(key, 0.0) + (min(b, hi) - max(a, lo)) * 1e-9
        big = max(top.items(), key=lambda kv: kv[1]) if top else None
        print(f"phase {name} busy_s {s} largest {big}")
        if s is not None:
            named = phase_busy_s(t, (name,), inferred=False)
            print(f"phase_source {name} op_name_s {named} "
                  f"inferred_s {s - named}")
    parts = breakdown(t)
    for name, secs in parts["device_ops"]:
        print("op_s", name, secs)
    for name, secs in parts["idle_gaps"]:
        print("gap_s", name, secs)
    counts = {}
    for name, s, e in t.spans:
        if e > lo and s < hi:
            counts[name] = counts.get(name, 0) + 1
    in_window = sum(1 for s, e, _ in t.ops[dev] if e > lo and s < hi)
    print("loop_trips", {name: loop_trips(t, name) for name in PHASES})
    print("spans_in_window", sorted(counts.items()))
    print("device_events_in_window", in_window)


if __name__ == "__main__":
    describe(sys.argv[1])
