"""The program's own tracing: every phase of the round names its device
operations with a ``jax.named_scope`` (``sssp.local``, ``sssp.send``, ...),
the engine wraps its host steps in profiler spans (``sssp.solve``,
``sssp.round``, ``sssp.sync``, ...) on the profiler's clock."""
import glob
import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import FaultPlan, SsspConfig, SsspEngine
from repro.core import sssp as sssp_mod
from repro.graph import random_graph

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROUND_SCOPES = {"sssp.local", "sssp.prune", "sssp.send", "sssp.exchange",
                "sssp.merge", "sssp.toka"}


def innermost_scopes(hlo_text: str) -> set:
    """The innermost ``sssp.<phase>`` of each op_name in compiled HLO."""
    out = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        found = re.findall(r"sssp\.[a-z_]+", op_name)
        if found:
            out.add(found[-1])
    return out


@pytest.fixture(scope="module")
def graph():
    return random_graph(n=160, m=700, seed=41)


def _engine_and_carry(graph, cfg, sources=(3, 50, 99)):
    eng = SsspEngine.build(graph, cfg, n_parts=4)
    src = np.asarray(sources + (0,) * (4 - len(sources)), np.int32)
    valid = np.arange(4) < len(sources)
    carry = sssp_mod._init_carry(eng.shards, src, cfg, rank=None,
                                 vmapped=True, q_valid=valid)
    return eng, carry


@pytest.mark.parametrize("name,cfg,extra", [
    ("staged", SsspConfig(), set()),
    ("faulted", SsspConfig(faults=FaultPlan(drop=0.2, seed=3),
                           toka="toka3"), {"sssp.deliver"}),
    ("fused", SsspConfig(round="fused"), {"sssp.fused"}),
])
def test_round_ops_carry_phase_scopes(graph, name, cfg, extra):
    """The compiled sim round: each phase's operations keep its scope as
    their innermost ``sssp.*`` name through XLA's optimizations."""
    eng, carry = _engine_and_carry(graph, cfg)
    text = eng.round_fn.lower(eng.shards, carry).compile().as_text()
    want = (ROUND_SCOPES - ({"sssp.local"} if name == "fused" else set())
            | extra)
    assert want <= innermost_scopes(text), innermost_scopes(text)
    if eng._finalize_fn is not None:
        fin = eng._finalize_fn.lower(eng.shards, carry).compile().as_text()
        assert "sssp.finalize" in innermost_scopes(fin)


def test_init_and_certificate_carry_scopes(graph):
    cfg = SsspConfig()
    eng, carry = _engine_and_carry(graph, cfg)
    init = jax.jit(lambda s, v: sssp_mod._init_carry(
        eng.shards, s, cfg, rank=None, vmapped=True, q_valid=v))
    text = init.lower(np.zeros(4, np.int32),
                      np.ones(4, bool)).compile().as_text()
    assert "sssp.init" in innermost_scopes(text)
    cert = eng._cert_fn.lower(eng.shards, carry.dist).compile().as_text()
    assert innermost_scopes(cert) == {"sssp.certificate"}


_SHMAP_SCOPES_PROG = textwrap.dedent("""
    import os, re
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from repro import compat
    from repro.core import SsspConfig, SsspEngine, build_shards
    from repro.graph import random_graph

    def scopes(text):
        out = set()
        for op_name in re.findall(r'op_name="([^"]*)"', text):
            found = re.findall(r"sssp\\.[a-z_]+", op_name)
            if found:
                out.add(found[-1])
        return out

    g = random_graph(n=160, m=700, seed=41)
    sh = build_shards(g, 4)
    mesh = compat.make_mesh((2, 2), ("x", "y"))
    eng = SsspEngine.build(sh, SsspConfig(), backend="shmap", mesh=mesh,
                           axis_names=("x", "y"))
    src = np.asarray([3, 50, 99, 0], np.int32)
    valid = np.asarray([True, True, True, False])
    solver = eng.shmap_solver.lower(eng.shards, src, valid).compile()
    want = {"sssp.init", "sssp.local", "sssp.prune", "sssp.send",
            "sssp.exchange", "sssp.merge", "sssp.toka"}
    assert want <= scopes(solver.as_text()), scopes(solver.as_text())
    res = eng.solve([3, 50, 99])
    assert res.status == "converged"
    cert = eng._cert_shmap.lower(eng.shards, np.zeros(
        (4, 4, eng.shards.block), np.float32)).compile()
    assert "sssp.certificate" in scopes(cert.as_text())
    sim = SsspEngine.build(sh, SsspConfig()).solve([3, 50, 99])
    assert np.array_equal(sim.dist, res.dist)
    print("SHMAP SCOPES OK")
""")


def test_shmap_solver_ops_carry_phase_scopes():
    """The shard_map whole solve reuses the staged round, so its ops carry
    the same scopes; its certificate carries its own (subprocess: the
    device count must be set before jax initializes)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SHMAP_SCOPES_PROG], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHMAP SCOPES OK" in out.stdout


def test_engine_spans_land_in_the_trace(graph, tmp_path):
    from jax.profiler import ProfileData
    eng = SsspEngine.build(graph, SsspConfig(), n_parts=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.solve([3, 50])          # first solve of the bucket: compiles
        eng.solve([7, 9])
        eng.submit(11)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert {"sssp.solve", "sssp.init", "sssp.compile", "sssp.round",
            "sssp.sync", "sssp.copy_out", "sssp.stats", "sssp.certificate",
            "sssp.drain"} <= names, sorted(n for n in names
                                            if n.startswith("sssp."))
