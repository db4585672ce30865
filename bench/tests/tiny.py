"""The benchmark's cells at a size a CPU test run holds: the same
files, with the model cut by its module's ``tiny`` and the sequence cut
(tests only; the benchmark's cells keep the published widths)."""
from __future__ import annotations

from bench import harness

TINY_SEQ = 32


def cell(name: str, root: str = harness.ROOT) -> harness.Cell:
    c = harness.load_cell(name, root=root)
    c.cfg = c.model.tiny(c.cfg)
    c.traffic = dict(c.traffic, seq=TINY_SEQ)
    return c


def run(c: harness.Cell, seed: int, plant=None):
    """A run's output check with the chip's look skipped: set-up through
    the timed round, then the reference; returns (gaps, correct)."""
    from bench import check
    pool = harness.make_pool(c, harness.keys(seed)["data"])
    prog = harness.Program(c, seed, plant=plant)
    prog.setup(pool)
    got = prog.readings
    prog.free()
    found = check.gaps(got, harness.reference_readings(c, seed, pool))
    return found, check.verdict(found, c.limits)
