"""The benchmark's cells at a size a CPU test run holds: the same
files, with the widths, the vocabulary and the sequence cut (tests
only; the benchmark's cells keep the published widths)."""
from __future__ import annotations

from bench import harness

TINY_MODEL = dict(hidden_size=128, num_attention_heads=4,
                  num_key_value_heads=4, intermediate_size=256,
                  dim_model_base=128)
TINY_SEQ = 32


def cell(name: str) -> harness.Cell:
    c = harness.load_cell(name)
    c.cfg = dict(c.cfg, **TINY_MODEL,
                 vocab_size=min(c.cfg["vocab_size"], 500) // (
                     2 if "v8" in name else 1))
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
