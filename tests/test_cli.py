"""launch/sssp_run CLI: argument parsing + end-to-end tiny-graph runs.

The runner had no direct tests; these pin down the flag surface (including
the new --landmarks/--warm-start/--result-cache) and the validated
end-to-end path on graphs small enough for seconds-scale runs.
"""
import sys

import pytest

from repro.launch import sssp_run


def _run(capsys, monkeypatch, *argv):
    monkeypatch.setattr(sys, "argv", ["sssp_run", *argv])
    sssp_run.main()
    return capsys.readouterr().out


TINY = ("--graph", "random", "--scale", "7", "--edge-factor", "4",
        "--parts", "4", "--no-prune")


# ----------------------------------------------------------- parsing ----

def test_bad_flag_values_rejected(monkeypatch, capsys):
    for argv in (["--graph", "mystery"],
                 ["--exchange", "carrier-pigeon"],
                 ["--solver", "dijkstra"],
                 ["--warm-start", "oracle"],
                 ["--backend", "mpi"]):
        monkeypatch.setattr(sys, "argv", ["sssp_run", *argv])
        with pytest.raises(SystemExit):
            sssp_run.main()


def test_warm_start_requires_landmarks(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv",
                        ["sssp_run", *TINY, "--warm-start", "landmark"])
    with pytest.raises(SystemExit):
        sssp_run.main()
    assert "--landmarks" in capsys.readouterr().err


def test_shmap_needs_a_device_per_part(monkeypatch, capsys):
    # the CPU test process has one device; --parts 4 asks for four
    monkeypatch.setattr(sys, "argv", ["sssp_run", *TINY, "--backend", "shmap"])
    with pytest.raises(SystemExit, match="needs 4 devices, but only 1"):
        sssp_run.main()


def test_out_of_range_source_rejected(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv",
                        ["sssp_run", *TINY, "--sources", "999999"])
    with pytest.raises(ValueError, match="out of range"):
        sssp_run.main()


# ------------------------------------------------------- end to end ----

def test_single_source_run_validates(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--source", "3", "--validate")
    assert "validation vs Dijkstra (1 query): OK" in out
    assert "reachable:" in out


def test_batched_run_with_explicit_sources(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--sources", "0,5,9",
               "--exchange", "pmin", "--toka", "toka1", "--solver", "delta",
               "--validate")
    assert "sources=[0, 5, 9]" in out
    assert "query[2] source=9:" in out
    assert "validation vs Dijkstra (3 queries): OK" in out


def test_sampled_batch_run(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--num-sources", "4", "--batch")
    assert "bucket K=4" in out
    assert "query[3]" in out


def test_warm_start_run_with_landmarks_and_cache(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--sources", "0,5",
               "--warm-start", "landmark", "--landmarks", "3",
               "--result-cache", "8", "--validate")
    assert "landmarks: 3 pivots solved" in out
    assert "warm_start=landmark" in out
    assert "[warm-started]" in out
    assert "cache_hits=2/2" in out and "rounds=0" in out
    assert "validation vs Dijkstra (2 queries): OK" in out


def test_result_cache_without_warm_start(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--sources", "1,8",
               "--result-cache", "4")
    assert "cache_hits=2/2" in out


# -------------------------------------------------------------- async ----

def test_async_lag_flag_validation(monkeypatch, capsys):
    for argv in ([*TINY, "--async-lag", "0", "--exchange", "async"],
                 [*TINY, "--async-lag", "2"],  # sync exchange ignores lag
                 [*TINY, "--async-lag", "2", "--exchange", "async_ppermute"]):
        monkeypatch.setattr(sys, "argv", ["sssp_run", *argv])
        with pytest.raises(SystemExit):
            sssp_run.main()
        assert "--async-lag" in capsys.readouterr().err


def test_async_run_reports_overlap_and_validates(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--sources", "0,5,9",
               "--exchange", "async", "--validate")
    assert "async: overlap=" in out
    assert "stale_merges=" in out and "bytes_moved=" in out
    assert "validation vs Dijkstra (3 queries): OK" in out


def test_async_ppermute_lagged_run_validates(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--source", "3",
               "--exchange", "async_ppermute", "--round", "fused",
               "--validate")
    assert "async: overlap=" in out
    assert "validation vs Dijkstra (1 query): OK" in out


# ------------------------------------------------------------- faults ----

def test_faulted_run_heals_and_validates(capsys, monkeypatch):
    out = _run(capsys, monkeypatch, *TINY, "--sources", "0,5",
               "--fault-drop", "0.2", "--resend-period", "4",
               "--toka", "toka3", "--validate")
    assert "status: converged (converged 2/2 queries)" in out
    assert "resends=" in out
    assert "validation vs Dijkstra (2 queries): OK" in out


def test_validate_fails_loudly_on_degraded(capsys, monkeypatch):
    # heavy drops, no resend: --validate must exit 1 BEFORE the Dijkstra
    # check, naming the unconverged sources
    monkeypatch.setattr(sys, "argv",
                        ["sssp_run", *TINY, "--sources", "0,5",
                         "--fault-drop", "0.6", "--fault-seed", "2",
                         "--validate"])
    with pytest.raises(SystemExit, match="1"):
        sssp_run.main()
    out = capsys.readouterr().out
    assert "validation FAILED: status=degraded" in out
