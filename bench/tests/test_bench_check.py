"""The output check at a size the CPU holds: a sound run passes the
cells' limits; the lower-precision control fails them.  Every cell of
BENCHMARK.json is checked, cut by its model module's ``tiny``."""

import pytest

from bench import check, harness
from bench.tests import tiny

CELLS = harness.cell_names()
SEED = 2 ** 31 + 7


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    found, ok = tiny.run(tiny.cell(name), SEED)
    assert ok, found


@pytest.mark.parametrize("name", CELLS)
def test_lower_precision_control_is_not_correct(name):
    c = tiny.cell(name)
    pool = harness.make_pool(c, harness.keys(SEED)["data"])
    ref = harness.reference_readings(c, SEED, pool)
    ctl = harness.reference_readings(c, SEED, pool, "float8_e4m3fn")
    found = check.gaps(ctl, ref)
    assert not check.verdict(found, c.limits), found
