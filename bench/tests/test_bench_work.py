"""Work counts against hand values, the peaks table, and the trace
reduction against a CPU trace recorded once and committed."""
import os

import pytest

from bench import harness, reference, tracing, work
from bench.tests import tiny

FIXTURE = os.path.join(harness.BENCH, "fixtures", "cpu_trace.xplane.pb")


def _cfg(name):
    return harness.load_cell(name).cfg


def test_minicpm_l2_parameter_count():
    cfg = _cfg("minicpm-2b.l2.sync-c1-s2048")
    wire = reference.Wire.of(reference.Model.from_config(cfg), 1024)
    # 122,880 padded rows x 2304 + 2 x (4 x 2304^2 + 3 x 2304 x 5760
    # + 2 x 2304) + 2304
    assert wire.total == 405_220_608
    assert (wire.rows, wire.cols) == (395_724, 1024)
    # the unpadded head: 122,753 x 2304, plus the layers' matrices
    assert work.matmul_params(cfg) == 122_753 * 2304 + 2 * (
        4 * 2304 ** 2 + 3 * 2304 * 5760)


def test_vocab_slice_parameter_count():
    cfg = _cfg("minicpm-2b.l2.v8.sync-c2-s512")
    wire = reference.Wire.of(reference.Model.from_config(cfg), 1024)
    assert wire.total == 15_360 * 2304 + 2 * (
        4 * 2304 ** 2 + 3 * 2304 * 5760 + 2 * 2304) + 2304


def test_round_flops_count_refreshes():
    cfg = _cfg("minicpm-2b.l2.sync-c1-s2048")
    t = {"clients": 1, "batch": 1, "seq": 2048, "local_iters": 5,
         "tau": 5}
    per_token = 6 * work.matmul_params(cfg) + 6 * 2 * 2048 * 2304
    assert work.round_flops(cfg, t) == 6 * 2048 * per_token
    t["tau"] = 10        # a refresh every other round
    assert work.round_flops(cfg, t, rounds=2) == 11 * 2048 * per_token


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


def test_tiny_cells_keep_the_wire_geometry():
    for name in ("minicpm-2b.l2.sync-c1-s2048",
                 "minicpm-2b.l2.v8.sync-c2-s512"):
        c = tiny.cell(name)
        assert c.cfg["hidden_size"] == 128 and c.traffic["seq"] == 32
