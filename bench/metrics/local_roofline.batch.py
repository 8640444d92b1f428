"""The local fixpoint's share of the chip's HBM roofline: the least bytes
the window's relaxations move (``bench.stats.local_floor_bytes``) over the
device seconds under ``sssp.local`` (as ``local_ms.batch`` reads them) at
the chip's HBM bandwidth, on each device that ran."""
from bench import phases
from bench.stats import local_floor_bytes, peaks


def read(run):
    t = phases.for_run(run)
    secs = phases.phase_busy_s(t, ("sssp.local",))
    moved = sum(local_floor_bytes(b.relaxations, b.lanes)
                for b in run.window.batches)
    if not secs or not moved or run.device_kind is None:
        return None
    rate = peaks(run.device_kind)["hbm_bytes_per_s"] * len(t.ops)
    return 100.0 * moved / (secs * rate)
