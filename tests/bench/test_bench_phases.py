"""The per-phase trace reduction (``bench/phases.py``) on a hand-built
trace whose operations carry scopes and whose spans nest ``sssp.sync``
inside ``bench.solve``, and on a traced tiny run of the harness."""
from __future__ import annotations

import dataclasses

import pytest
from bench_tiny import run_tiny, tiny_cell

from bench import loads, phases, run
from bench import trace as trace_mod

# ns on the trace's clock; the window is [0, 1000]
OPS = [  # (start, end, name, phase, inferred)
    (-50, 50, "%fusion.12 = f32[8] fusion(...)", "sssp.send", False),
    (100, 400, "%while.5 = (s32[]) while(...)", "sssp.local", False),
    (120, 200, "%fusion.6 = f32[8] fusion(...)", "sssp.local", True),
    (250, 300, "%fusion.7 = pred[8] fusion(...)", "sssp.prune", False),
    (450, 550, "%fusion.8 = f32[8] fusion(...)", "sssp.send", False),
    (520, 550, "%while.13 = (s32[]) while(...)", "sssp.send", True),
    (560, 600, "%copy.9 = f32[8] copy(...)", "sssp.exchange", False),
    (600, 650, "%fusion.10 = f32[8] fusion(...)", "sssp.merge", False),
    (700, 720, "%add.11 = s32[] add(...)", None, False),
]
HARNESS = [("bench.window", 0, 1000), ("bench.solve", 0, 800),
           ("bench.check", 800, 1000)]
ENGINE = [("sssp.solve", 0, 790), ("sssp.round", 90, 110),
          ("sssp.sync", 660, 690)]


def synthetic(engine_spans=True, phased=True) -> phases.PhaseTrace:
    return phases.PhaseTrace(
        ops={0: [op[:3] for op in OPS]},
        spans=HARNESS + (ENGINE if engine_spans else []),
        window=(0, 1000),
        phases={0: [op[3] if phased else None for op in OPS]},
        inferred={0: [op[4] for op in OPS]})


def test_phase_busy_counts_each_op_toward_its_innermost_scope():
    t = synthetic()
    # the while spans its body, but counts only toward its own scope
    assert phases.phase_busy_s(t, ["sssp.local"]) == pytest.approx(300e-9)
    assert phases.phase_busy_s(t, ["sssp.prune"]) == pytest.approx(50e-9)
    # clipped to the window
    assert phases.phase_busy_s(t, ["sssp.send"]) == pytest.approx(150e-9)
    assert phases.phase_busy_s(
        t, ["sssp.exchange", "sssp.merge"]) == pytest.approx(90e-9)
    assert trace_mod.busy_s(t) == pytest.approx(560e-9)
    assert phases.unscoped_s(t) == pytest.approx(20e-9)


def test_phase_busy_splits_op_name_from_inferred_phases():
    """Operations whose phase was inferred (XLA's own loops and slices)
    are counted apart from those whose op_names carry it; a trace that
    records no inference reads as all op_name."""
    t = synthetic()
    assert phases.phase_busy_s(t, ["sssp.local"], inferred=False) == \
        pytest.approx(300e-9)
    assert phases.phase_busy_s(t, ["sssp.local"], inferred=True) == \
        pytest.approx(80e-9)
    assert phases.phase_busy_s(t, ["sssp.send"], inferred=True) == \
        pytest.approx(30e-9)
    assert phases.phase_busy_s(t, ["sssp.merge"], inferred=True) == 0.0
    t.inferred = {}
    assert phases.phase_busy_s(t, ["sssp.send"], inferred=False) == \
        phases.phase_busy_s(t, ["sssp.send"])
    assert phases.phase_busy_s(t, ["sssp.send"], inferred=True) == 0.0


def test_without_scopes_the_phases_read_nothing():
    t = synthetic(phased=False)
    assert phases.phase_busy_s(t, ["sssp.local"]) is None
    assert phases.unscoped_s(t) is None


def test_phase_metrics_per_batch(monkeypatch):
    t = synthetic()
    monkeypatch.setattr(phases, "for_run", lambda r: t)
    window = loads.Window(batches=[object(), object()])
    r = run.Run(setup_s=1.0, window=window, trace=t)
    assert run.load_metric("local_ms.batch")(r) == pytest.approx(1.5e-4)
    assert run.load_metric("send_ms.batch")(r) == pytest.approx(0.75e-4)
    assert run.load_metric("exchange_merge_ms.batch")(r) == \
        pytest.approx(0.45e-4)
    monkeypatch.setattr(phases, "for_run", lambda r: synthetic(phased=False))
    assert run.load_metric("local_ms.batch")(r) is None
    # a program without the round's scopes whose certificate was loaded,
    # scopes and all, from another version's compiled executable
    cert_only = synthetic(phased=False)
    cert_only.phases[0][-1] = "sssp.certificate"
    monkeypatch.setattr(phases, "for_run", lambda r: cert_only)
    for name in ("local_ms.batch", "send_ms.batch",
                 "exchange_merge_ms.batch"):
        assert run.load_metric(name)(r) is None


def test_breakdown_labels_gaps_by_engine_spans_and_ops_by_phase():
    parts = phases.breakdown(synthetic())
    # longest first; the 50 ns gaps from the latest back
    assert [name for name, _ in parts["idle_gaps"]] == [
        "bench.check", "sssp.sync", "sssp.solve", "sssp.solve", "sssp.solve"]
    assert [round(s * 1e9) for _, s in parts["idle_gaps"]] == [
        280, 50, 50, 50, 10]
    ops = dict(parts["device_ops"])
    assert ops["%while.5 sssp.local"] == pytest.approx(300e-9)
    assert ops["%fusion.12 sssp.send"] == pytest.approx(50e-9)
    assert ops["%add.11 -"] == pytest.approx(20e-9)
    # the harness's own breakdown: same gaps, named by its spans alone
    base = trace_mod.breakdown(synthetic(engine_spans=False))
    assert [name for name, _ in base["idle_gaps"]] == [
        "bench.check", "bench.solve", "bench.solve", "bench.solve",
        "bench.solve"]
    assert [s for _, s in base["idle_gaps"]] == [
        s for _, s in parts["idle_gaps"]]


def test_engine_spans_leave_the_existing_metrics_unchanged():
    """``idle_share``, ``host_gap`` and ``busy_s`` read the same from a
    trace with the engine's spans as from one without."""
    with_spans, without = synthetic(), synthetic(engine_spans=False)
    for fn in (trace_mod.busy_s, trace_mod.idle_share):
        assert fn(with_spans) == fn(without)
    for span in ("bench.solve", "bench.drain", "bench.check"):
        assert trace_mod.host_gap(with_spans, span) == \
            trace_mod.host_gap(without, span)
    window = loads.Window(batches=[object()])
    for name in ("device_idle.batch", "host_gap.batch", "host_gap.serve"):
        read = run.load_metric(name)
        assert read(run.Run(1.0, window, with_spans)) == \
            read(run.Run(1.0, window, without))


def test_traced_tiny_run_holds_the_engine_spans(tmp_path, monkeypatch):
    cell = tiny_cell("paper-graph1.batch")
    trace_dir = str(tmp_path / "trace")
    res = run_tiny(cell, trace_dir, trace=True)
    assert res["correct"] is True
    t = phases.load(trace_dir)
    names = {name for name, _, _ in t.spans}
    assert {"sssp.solve", "sssp.round", "sssp.sync", "sssp.copy_out",
            "sssp.certificate", "bench.solve", "bench.window"} <= names
    # the engine's spans nest inside the harness's
    solves = [(s, e) for n, s, e in t.spans if n == "bench.solve"]
    for n, s, e in t.spans:
        if n == "sssp.sync" and t.window[0] <= s <= t.window[1]:
            assert any(a <= s and e <= b for a, b in solves)
    # a run finds its own trace by its window, and no other
    base = trace_mod.load(trace_dir)
    monkeypatch.setattr(phases, "TRACE_ROOT", trace_dir)
    window = loads.Window()
    assert phases.for_run(run.Run(1.0, window, base)).window == base.window
    base.window = (base.window[0] + 1, base.window[1])
    assert phases.for_run(run.Run(1.0, window, base)) is None


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, payload) -> bytes:
    """A protobuf field: a varint for an int, length-delimited bytes."""
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _xplane_of(compiled, path, program: int = 42) -> str:
    """A trace file that holds only ``compiled``'s optimized HLO, as the
    profiler stores it: an ``Hlo Proto`` stat in ``/host:metadata``."""
    module = compiled.runtime_executable().hlo_modules()[0]
    stat = _field(1, 7) + _field(6, _field(
        1, module.as_serialized_hlo_module_proto()))
    meta = (_field(1, 3) + _field(2, f"jit_f({program})".encode())
            + _field(5, stat))
    plane = (_field(1, 5) + _field(2, b"/host:metadata")
             + _field(4, _field(1, 3) + _field(2, meta)))
    path.write_bytes(_field(1, plane))
    return str(path)


def test_op_phases_read_the_compiled_hlo_in_the_trace_file(tmp_path):
    """The phases of a program's instructions, from its optimized HLO as
    the profiler stores it (an ``Hlo Proto`` stat in ``/host:metadata``):
    an op_name's innermost scope, and for an instruction without one (a
    parameter or slice inside a fusion, a loop XLA made) the phase of what
    it calls or of its neighbours."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("sssp.local"):
            y = jax.lax.while_loop(lambda c: c[0] < 3,
                                   lambda c: (c[0] + 1, jnp.sin(c[1]) * 2),
                                   (0, x))[1]
        with jax.named_scope("sssp.send"):
            return jnp.cumsum(y) + 1

    compiled = jax.jit(f).lower(jnp.ones(64)).compile()
    got = phases.op_phases(_xplane_of(compiled, tmp_path / "t.xplane.pb"))
    assert list(got) == [42]
    table = got[42]
    text = compiled.as_text()
    whiles = [n for n in table if n.startswith("while")]
    assert whiles and all(table[n][0] == "sssp.local" for n in whiles)
    root = text.split("ENTRY", 1)[1].split("ROOT %", 1)[1]
    assert table[root.split(" ", 1)[0]][0] == "sssp.send"
    # the root fusion's own instructions, parameters and slices included,
    # take the phase of the ones that carry an op_name
    fused = root.split("calls=%", 1)[1].split(",", 1)[0].split()[0]
    body = text.split(f"%{fused} ", 1)[1].split("\n}", 1)[0]
    names = [line.split(" = ", 1)[0].split("%")[-1]
             for line in body.splitlines()[1:] if " = " in line]
    assert names and {table[n][0] for n in names} == {"sssp.send"}
    # an instruction whose own op_name carries a scope reads it, not
    # inferred; one without (a parameter) takes its phase by inference
    for line in body.splitlines()[1:]:
        if " = " in line:
            n = line.split(" = ", 1)[0].split("%")[-1]
            assert table[n][1] == ("sssp.send" not in line), line
    # XLA's own ops between two phases (the cumsum's reduce-window, whose
    # op_name lost the scope) may stay unscoped, never take a third phase
    assert {v.phase for v in table.values()} <= {"sssp.local", "sssp.send",
                                                  None}


@pytest.mark.parametrize("op_name,opens", [
    ("jit(sssp_round)/sssp.local/vmap(vmap())/while", "sssp.local"),
    ("jit(f)/vmap(sssp.local)/while", "sssp.local"),
    ("jit(f)/shard_map/while/body/sssp.local/vmap(while)", "sssp.local"),
    ("jit(f)/sssp.local/while/body/while", None),     # nested in the loop
    ("jit(f)/sssp.local/while/body/mul", None),       # in the body
    ("jit(f)/shard_map/while", None),                 # in no phase
    ("", None),                                       # XLA's own loop
])
def test_phase_loop_is_the_while_opened_directly_in_a_scope(op_name, opens):
    assert phases.phase_loop(op_name) == opens


def _body(text: str, loop: str) -> list:
    """Instruction names of the body computation of ``%<loop>``."""
    line = next(x for x in text.splitlines() if f"%{loop} = " in x)
    body = line.split("body=%", 1)[1].split(",", 1)[0].split()[0]
    comp = text.split(f"%{body} ", 1)[1].split("\n}", 1)[0]
    return [x.split(" = ", 1)[0].split("%")[-1].strip()
            for x in comp.splitlines()[1:] if " = " in x]


def test_op_phases_mark_the_body_of_the_phase_loop(tmp_path):
    """Each instruction of the body of the ``while`` opened in
    ``sssp.local`` runs once a trip and is marked with that loop; those
    of its condition, of a loop nested in its body and outside it are
    not."""
    import jax
    import jax.numpy as jnp

    def inner(c):
        return jax.lax.while_loop(lambda d: d[1][0] < 50.0,
                                  lambda d: (d[0] + 1, d[1] * 3.0 + 1.0),
                                  (0, c[1]))[1]

    def f(x):
        with jax.named_scope("sssp.local"):
            y = jax.lax.while_loop(
                lambda c: c[1][0] < 1e6,
                lambda c: (c[0] + 1, jnp.sin(inner(c)) + c[1] * 2.0),
                (0, x))[1]
        with jax.named_scope("sssp.send"):
            return jnp.cumsum(y) + 1

    compiled = jax.jit(f).lower(jnp.ones(64)).compile()
    table = phases.op_phases(_xplane_of(compiled, tmp_path / "t.xplane.pb"))[42]
    text = compiled.as_text()
    loops = {v.loop for v in table.values()} - {None}
    assert len(loops) == 1
    ((phase, outer),) = loops
    assert phase == "sssp.local" and outer.startswith("while")
    marked = sorted(n for n, v in table.items() if v.loop)
    assert marked == sorted(_body(text, outer))
    # the loop nested in the body marks none of its own body's
    whiles = [x.split(" = ", 1)[0].split("%")[-1] for x in text.splitlines()
              if " while(" in x]
    nested = [w for w in whiles if w != outer]
    assert nested
    for w in nested:
        assert not any(table[n].loop for n in _body(text, w))


def test_loop_trips_count_the_body_op_seen_most():
    W = (0, 1000)
    local_a, local_b = (1, "sssp.local", "while.5"), (2, "sssp.local",
                                                      "while.9")
    send = (1, "sssp.send", "while.11")
    evs = ([(t, t + 5, "%fusion.6 = f32[8] fusion()", local_a)
            for t in (-5, 10, 20, 30, 40)]          # the first before the window
           + [(t + 1, t + 3, "%fusion.7 = f32[8] fusion()", local_a)
              for t in (10, 20, 30, 40)]
           + [(t, t + 5, "%fusion.3 = f32[8] fusion()", local_b)
              for t in (100, 200)]
           + [(t, t + 5, "%fusion.12 = f32[8] fusion()", send)
              for t in range(300, 370, 10)]
           + [(500, 600, "%while.5 = (s32[]) while()", None)])
    evs.sort()

    def trace(per_device):
        return phases.PhaseTrace(
            ops={d: [e[:3] for e in es] for d, es in per_device.items()},
            spans=[], window=W,
            phases={d: [None] * len(es) for d, es in per_device.items()},
            loops={d: [e[3] for e in es] for d, es in per_device.items()})

    t = trace({0: evs, 1: evs[:3]})
    # 4 trips of while.5 in the window and 2 of while.9; the device that
    # ran most; the send loop is another phase's
    assert phases.loop_trips(t, "sssp.local") == 6
    assert phases.loop_trips(t, "sssp.send") == 7
    assert phases.loop_trips(t, "sssp.merge") is None
    assert phases.loop_trips(None, "sssp.local") is None
    assert phases.loop_trips(synthetic(), "sssp.local") is None


def test_sweeps_per_round_over_the_windows_rounds(monkeypatch):
    t = synthetic()
    monkeypatch.setattr(phases, "loop_trips",
                        lambda tr, phase: 12 if phase == "sssp.local" else None)
    monkeypatch.setattr(phases, "for_run", lambda r: t)
    batches = [loads.Batch(real=16, lanes=16, rounds=r, relaxations=1,
                           reached_edges=1) for r in (2, 1)]
    read = run.load_metric("sweeps_per_round.batch")
    assert read(run.Run(1.0, loads.Window(batches=batches), t)) == 4.0
    assert read(run.Run(1.0, loads.Window(), t)) is None
    monkeypatch.setattr(phases, "loop_trips", lambda tr, phase: None)
    assert read(run.Run(1.0, loads.Window(batches=batches), t)) is None


def test_queue_waits_from_due_time_to_the_batchs_solve(monkeypatch):
    spans = [("bench.window", 0, 1000), ("bench.submit", 100, 101),
             ("bench.submit", 150, 151), ("bench.submit", 300, 301),
             ("bench.drain", 190, 700), ("sssp.solve", 200, 290),
             ("sssp.solve", 400, 690)]
    t = phases.PhaseTrace(ops={}, spans=spans, window=(0, 1000))
    batches = [loads.Batch(real=k, lanes=k, rounds=1, relaxations=1,
                           reached_edges=0) for k in (2, 1)]
    # due times 20 ns, 0 and 50 ns before each submit
    w = loads.Window(batches=batches, lateness_s=[20e-9, 0.0, 50e-9])
    waits = phases.queue_waits(t, w)
    assert waits == pytest.approx([120e-9, 50e-9, 150e-9])
    monkeypatch.setattr(phases, "for_run", lambda r: t)
    read = run.load_metric("queue_wait_p85_ms.serve")
    assert read(run.Run(1.0, w, t)) == pytest.approx(150e-6)
    # spans that do not match the window's batches read nothing
    for other in ([batches[0]], [batches[0], batches[0]]):
        assert phases.queue_waits(t, dataclasses.replace(
            w, batches=other)) is None
    assert phases.queue_waits(t, dataclasses.replace(
        w, lateness_s=[0.0, 0.0])) is None
    assert phases.queue_waits(None, w) is None
    # a batch that began before one of its queries was submitted
    t.spans[-1] = ("sssp.solve", 250, 690)
    assert phases.queue_waits(t, w) is None
