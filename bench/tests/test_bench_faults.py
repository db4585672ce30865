"""Faults planted under the timed path make ``correct`` false at a size
the CPU holds, with each cell's own limits: a round that returns its
state unchanged; half of the round's batch left out, the mean taken over
the rest; one leaf's update applied twice where the round produces it.

One client's half-batch fault (half of each sequence) moves the compared
numbers at these widths by less than the limits; it is read on the chip
at the cell's own size instead (PERF.md)."""
import pytest

from bench import faults
from bench.tests import tiny

SEED = 2 ** 31 + 11
C1 = "minicpm-2b.l2.sync-c1-s2048"
C2 = "minicpm-2b.l2.v8.sync-c2-s512"
CASES = [(C2, "unchanged"), (C2, "half_batch"), (C2, "altered"),
         (C1, "unchanged"), (C1, "altered")]


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault):
    found, ok = tiny.run(tiny.cell(name), SEED, plant=faults.PLANTS[fault])
    assert not ok, (fault, found)
