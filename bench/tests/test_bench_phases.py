"""Device time per phase of the round: the mapping from a compiled
module's op_name metadata to phases, the seven readings summing to the
busy time, the scopes in the tiny cells' compiled rounds, and the
compiled round unchanged by them."""
import contextlib
import os

import jax
import pytest

from bench import engine, harness, tracing
from bench.tests import tiny

FIXTURE = os.path.join(harness.BENCH, "fixtures", "cpu_trace.xplane.pb")
CELLS = harness.cell_names()
READERS = ("local_grad_ms", "gnb_ms", "sophia_step_ms", "wire_ms",
           "combine_ms", "client_rows_ms", "round_other_ms")

# a scheduled module as a TPU compile prints it: a fusion whose own
# metadata is its root's, a Pallas kernel in its own scope inside a
# phase, a loop, an op inside a transform, an op with two fed. scopes,
# ops outside every phase, a loop and a branch that run inside a phase,
# and what the compiler makes without an op_name: a fusion whose root
# has none, copies that feed one phase, two phases or only the
# module's result, and a copy of a kernel's result
HLO = """HloModule jit_round, is_scheduled=true

%fused_computation.7 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %add.3 = f32[8]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(round)/fed.combine/add"}
}

%fused_computation.8 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %multiply.1 = f32[8]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(round)/while/body/fed.gnb/cond/mul"}
  ROOT %dynamic-update-slice.7 = f32[8]{0} dynamic-update-slice(%multiply.1, %param_0.2)
}

%scatter_body (p.2: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.2 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.9 = f32[8]{0} get-tuple-element(%p.2), index=1
  ROOT %dynamic-update-slice.9 = f32[8]{0} dynamic-update-slice(%get-tuple-element.9)
}

%branch_zeros () -> (f32[8]) {
  %broadcast.75 = f32[8]{0} broadcast(%constant.245), dimensions={}, metadata={op_name="jit(round)/while/body/closed_call"}
  ROOT %tuple.415 = (f32[8]{0}) tuple(%broadcast.75)
}

ENTRY %main.5 (p.1: f32[8]) -> (f32[8], f32[8]) {
  %p.1 = f32[8]{0} parameter(0)
  %fusion.9 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(round)/fed.combine/add" stack_frame_id=4}
  %copy.794 = f32[8]{0:T(8,128)} copy(%p.1)
  %copy.795 = f32[8]{0:T(8,128)} copy(%fusion.9)
  %pallas_sophia_update_batched.10 = f32[8]{0} custom-call(%copy.794, %copy.795), custom_call_target="tpu_custom_call", metadata={op_name="jit(round)/while/body/closed_call/fed.sophia/jit(sophia_update_batched)/pallas:sophia_update_batched/pallas_call"}
  %gather.2 = f32[8]{0} gather(%copy.794), metadata={op_name="jit(round)/while/body/fed.grad/vmap(transpose(jvp(jit(take_along_axis))))/gather"}
  %reshape.18457 = f32[8]{0} reshape(%gather.2), metadata={op_name="jit(round)/while/body/vmap(fed.grad)/reshape"}
  %bitcast_dynamic-update-slice_fusion.16 = f32[8]{0} fusion(%reshape.18457), kind=kLoop, calls=%fused_computation.8
  %copy.4 = f32[8]{0} copy(%p.1), metadata={op_name="jit(round)/fed.wire/fed.rows/copy"}
  %while.1 = f32[8]{0} while(%p.1), condition=%c, body=%b, metadata={op_name="jit(round)/while"}
  %copy.398 = f32[8]{0} copy(%while.1)
  %dynamic-update-slice.3685 = f32[8]{0} dynamic-update-slice(%copy.398), metadata={op_name="jit(round)/fed.rows/scatter" stack_frame_id=9}
  %copy.749 = f32[8]{0} copy(%while.1)
  %while.3 = (s32[], f32[8]{0}) while(%p.1), condition=%c, body=%scatter_body, metadata={op_name="jit(round)/fed.rows/scatter"}
  %conditional.2 = (f32[8]{0}) conditional(%p.1), branch_computations={%branch_zeros}, metadata={op_name="jit(round)/while/body/closed_call/fed.gnb/cond"}
  %get-tuple-element.2692 = f32[8]{0} get-tuple-element(%pallas_sophia_update_batched.10), index=0
  %copy.4870 = f32[8]{0} copy(%get-tuple-element.2692)
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%dynamic-update-slice.3685, %copy.749, %while.3, %conditional.2, %copy.4870)
}
"""


def test_phase_of_takes_the_innermost_fed_scope():
    assert tracing.phase_of("jit(round)/fed.wire/fed.rows/copy") == "rows"
    assert tracing.phase_of("jit(round)/vmap(fed.grad)/dot") == "grad"
    assert tracing.phase_of(
        "jit(round)/fed.sophia/pallas:sophia_update_batched/x") == "sophia"
    assert tracing.phase_of("jit(round)/while/body/closed_call") is None
    # a name that only begins like a phase is none
    assert tracing.phase_of("jit(round)/fed.gradual/add") is None


def test_hlo_phases_of_a_handwritten_module():
    got = tracing.hlo_phases(HLO)
    assert got["fusion.9"] == got["add.3"] == "combine"
    assert got["pallas_sophia_update_batched.10"] == "sophia"
    assert got["gather.2"] == got["reshape.18457"] == "grad"
    assert got["copy.4"] == "rows"
    assert got["dynamic-update-slice.3685"] == "rows"
    # an op_name outside every phase stays outside
    assert got["while.1"] is None and got["p.1"] is None
    # what the compiler made: a fusion takes the phase it fuses, a
    # copy that of its users where they agree
    assert got["bitcast_dynamic-update-slice_fusion.16"] == "gnb"
    assert got["copy.795"] == "sophia" and got["copy.398"] == "rows"
    assert got["copy.794"] is None          # feeds grad and sophia
    assert got["copy.749"] is None          # feeds the result alone
    assert got["copy.4870"] == "sophia"     # relays the kernel's result
    # what runs inside a phase's loop or branch is that phase's
    assert got["dynamic-update-slice.9"] == "rows"
    assert got["broadcast.75"] == got["tuple.415"] == "gnb"


def _reduction(op_ns, busy_ns, devices=1):
    return tracing.Reduction(window=(0.0, 2 * busy_ns), busy_ns=busy_ns,
                             op_ns=op_ns, op_count={}, gaps=[],
                             devices=devices)


def test_phase_ns_closes_the_sum_with_the_rest():
    red = _reduction({"fusion.9": 10.0, "pallas_sophia_update_batched.10":
                      40.0, "gather.2": 5.0, "reshape.18457": 15.0,
                      "while.1": 3.0, "copy.398": 7.0, "copy.794": 4.0,
                      "bitcast_dynamic-update-slice_fusion.16": 6.0,
                      "dynamic-update-slice.3685": 10.0}, 100.0)
    got = tracing.phase_ns(red, tracing.hlo_phases(HLO))
    assert got == {"grad": 20.0, "gnb": 6.0, "sophia": 40.0, "wire": 0.0,
                   "combine": 10.0, "rows": 17.0, "other": 7.0}
    # a module without a single fed. scope reads nothing
    assert tracing.phase_ns(red, {"fusion.9": None}) is None


class _Compiled:
    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text

    def memory_analysis(self):
        return None


def _fixture_context(hlo_text):
    """The recorded trace's context, as `harness.trace_context` makes it
    for a run whose compiled round is a module of ``hlo_text``."""
    cell = harness.load_cell(CELLS[0])
    prog = harness.Program.__new__(harness.Program)
    prog.cell, prog.compiled = cell, _Compiled(hlo_text)
    return harness.trace_context(cell, prog, 0, os.path.dirname(FIXTURE),
                                 peaks={})


def test_seven_readings_sum_to_busy_time_per_round():
    """On the recorded CPU trace (three rounds of two products and a
    tanh), with one product in `fed.grad` and the tanh in `fed.wire`."""
    hlo = ('  %dot_general.2 = f32[8]{0} dot(), metadata={op_name='
           '"jit(f)/fed.grad/dot_general"}\n'
           '  %wrapped_tanh = f32[8]{0} fusion(), metadata={op_name='
           '"jit(f)/fed.wire/tanh"}\n'
           '  %dot_general.3 = f32[8]{0} dot(), metadata={op_name='
           '"jit(f)/dot_general"}\n')
    ctx = _fixture_context(hlo)
    assert ctx.hlo_text == hlo
    got = {m: harness.read_metric(m, ctx) for m in READERS}
    red = ctx.reduction
    per_round = red.busy_ns / harness.TRACE_ROUNDS * 1e-6
    assert sum(got.values()) == pytest.approx(per_round, rel=1e-12)
    assert got["local_grad_ms"] == pytest.approx(
        red.op_ns["dot_general.2"] / 3 * 1e-6)
    assert got["round_other_ms"] == pytest.approx(
        red.op_ns["dot_general.3"] / 3 * 1e-6)
    assert got["gnb_ms"] == 0.0
    # a program without the scopes names no phase: every reader is
    # silent, as where the run kept no compiled module
    ctx = _fixture_context(hlo.replace("fed.", "f."))
    assert all(harness.read_metric(m, ctx) is None for m in READERS)
    ctx = _fixture_context(hlo)
    ctx.hlo_text = None
    assert all(harness.read_metric(m, ctx) is None for m in READERS)


def test_any_named_scope_reads_its_device_time():
    """A scope outside the round's phases (as an expert layer's would
    be) reads its own self time per round, the rest the busy time less
    it; a scope no instruction names reads nothing."""
    hlo = ('  %dot_general.2 = f32[8]{0} dot(), metadata={op_name='
           '"jit(f)/fed.grad/moe.experts/dot_general"}\n')
    ctx = _fixture_context(hlo)
    red = ctx.reduction
    experts = {"experts": "moe.experts"}
    assert ctx.phase_ms("experts", experts) == pytest.approx(
        red.op_ns["dot_general.2"] / 3 * 1e-6)
    assert ctx.phase_ms("experts", experts) + ctx.phase_ms(
        tracing.REST, experts) == pytest.approx(
            red.busy_ns / harness.TRACE_ROUNDS * 1e-6, rel=1e-12)
    # the round's phases are read apart, on the same context
    assert ctx.phase_ms("grad") == ctx.phase_ms("experts", experts)
    assert ctx.phase_ms("router", {"router": "moe.router"}) is None


def _compiled_text(name):
    c = tiny.cell(name)
    k = harness.keys(5)
    pool = harness.make_pool(c, k["data"])
    sysm = engine.build(c, k["init"])
    return sysm.round_fn.lower(engine.avals(sysm.state), pool[0],
                               jax.random.PRNGKey(0)).compile().as_text()


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_round_carries_every_phase(name, monkeypatch):
    """The round names all six phases; patched to a null scope it
    compiles to the same program, metadata aside."""
    from repro.core import fed
    text = _compiled_text(name)
    assert set(tracing.hlo_phases(text).values()) == set(
        tracing.PHASES) | {None}
    monkeypatch.setattr(fed, "phase",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(name)
    assert not any(tracing.hlo_phases(bare).values())
    assert tracing.without_metadata(text) == tracing.without_metadata(bare)
