"""Faults planted under the timed path make ``correct`` false at a size
the CPU holds, with each cell's own limits, in every cell of
BENCHMARK.json: a round that returns its state unchanged; half of the
round's batch left out, the mean taken over the rest; one leaf's update
applied twice where the round produces it.

One client's half-batch fault (half of each sequence) moves the compared
numbers at these widths by less than the limits; it is read on the chip
at the cell's own size instead (PERF.md), so a one-client cell is
checked here for the other two."""
import pytest

from bench import faults, harness
from bench.tests import tiny

SEED = 2 ** 31 + 11


def _cases():
    for name in harness.cell_names():
        clients = harness.load_cell(name).traffic["clients"]
        for fault in faults.PLANTS:
            if fault == "half_batch" and clients < 2:
                continue
            yield name, fault


CASES = list(_cases())


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(name, fault):
    found, ok = tiny.run(tiny.cell(name), SEED, plant=faults.PLANTS[fault])
    assert not ok, (fault, found)
