"""Work counts against hand values, the peaks table, the trace
reduction against a CPU trace recorded once and committed, and the CPU
cut of every cell.  Each model's own counts are beside its module
(``bench/models/test_<arch>.py``)."""
import os

import pytest

from bench import harness, reference, tracing, work
from bench.tests import tiny

FIXTURE = os.path.join(harness.BENCH, "fixtures", "cpu_trace.xplane.pb")
CELLS = harness.cell_names()


def test_round_flops_count_refreshes():
    t = {"clients": 1, "batch": 1, "seq": 2048, "local_iters": 5,
         "tau": 5}
    per_token = 1.5e9
    assert work.round_flops(per_token, t) == 6 * 2048 * per_token
    t["tau"] = 10        # a refresh every other round
    assert work.round_flops(per_token, t, rounds=2) == 11 * 2048 * per_token
    t["clients"] = 2     # and every client's tokens
    assert work.round_flops(per_token, t, rounds=2) == 22 * 2048 * per_token


# one batched Sophia update over 2 clients of 1,538 x 1,024 coordinates,
# as a compiled module prints it: theta f32, m e4m3, h e5m2 out; theta,
# m, h, grad f32, curvature estimate f32 and two f32 flags in
SOPHIA_LINE = (
    "  %pallas_sophia_update_batched.10 = (f32[2,1538,1024]{2,1,0:T(8,128)"
    "S(1)}, f8e4m3fn[2,1538,1024]{2,1,0:T(8,128)(4,1)S(1)}, f8e5m2[2,1538,"
    "1024]{2,1,0:T(8,128)(4,1)S(1)}) custom-call(%copy.805, %copy.806, "
    "%copy.807, %reshape.2383, %get-tuple-element.2792, /*index=5*/%max"
    "imum_bitcast_fusion.2), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={f32[2,1538,1024]{2,1,0}, f8e4m3fn[2,1538"
    ",1024]{2,1,0}, f8e5m2[2,1538,1024]{2,1,0}, f32[2,1538,1024]{2,1,0}, "
    "f32[2,1538,1024]{2,1,0}, f32[1,2]{1,0}}, frontend_attributes={kernel"
    "_metadata={}}, metadata={op_name=\"jit(round)/while/body\"}")


def test_sophia_update_bytes():
    (call,) = work.kernel_calls(SOPHIA_LINE)
    coords = 2 * 1538 * 1024
    assert call["name"] == "pallas_sophia_update_batched.10"
    assert call["family"] == "sophia_update"
    # in: 4 + 1 + 1 + 4 + 4 bytes; out: 4 + 1 + 1; flags 2 x 4
    assert call["bytes"] == coords * 20 + 8


def test_peaks_by_device_kind():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_trace_reduction_of_the_fixture():
    from jax.profiler import ProfileData
    red = tracing.reduce(ProfileData.from_file(FIXTURE))
    assert red.window == (129664.0, 22070603.0)
    assert red.busy_ns == 16768027.0
    assert red.op_count == {"dot_general.2": 3, "dot_general.3": 3,
                            "wrapped_tanh": 3}
    assert sum(red.op_ns.values()) == 16768027.0   # ops never overlap
    gaps = dict(red.gaps)
    assert sum(gaps.values()) == red.window_ns - red.busy_ns
    assert max(gaps, key=gaps.get) == "bench.batch"


def test_self_times_subtract_nested_ops():
    """A loop around two ops, as a TPU trace nests a while loop's body:
    the loop keeps only its own time, and the window clips."""
    ops = [("while.1", 0, 100), ("fusion.2", 10, 40), ("copy.3", 50, 90),
           ("fusion.2", 120, 130)]
    got = tracing.self_times(ops, 0, 125)
    assert got == {"while.1": [30], "fusion.2": [30, 5], "copy.3": [40]}
    assert tracing.op_name("%copy.3 = f32[8]{0} copy(f32[8]{0} %p)") == (
        "copy.3")


def test_union_merges_and_clips():
    assert tracing.union([(0, 4), (2, 6), (8, 9), (10, 20)], 1, 15) == [
        (1, 6), (8, 9), (10, 15)]


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cells_keep_the_wire_geometry(name):
    """A tiny cell is its cell's files with the model cut by its module
    and the sequence cut: the engine, the traffic mix and the limits
    stay, so the wire keeps its block of columns."""
    full, c = harness.load_cell(name), tiny.cell(name)
    assert c.cfg["engine"] == full.cfg["engine"]
    assert c.traffic == dict(full.traffic, seq=tiny.TINY_SEQ)
    assert c.limits == full.limits
    small = reference.wire_of(c.model, c.cfg)
    assert small.cols == full.cfg["engine"]["quant_block"]
    assert 0 < small.total < reference.wire_of(full.model, full.cfg).total
