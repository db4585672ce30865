"""repro.obs tests: frozen schema golden, record validation, exact
int64 byte counters, Sophia health probes (value correctness, bitwise
probes-on/off state equality, layout-op neutrality), the packed device
metrics buffer, sinks/manifest, the Eq. 13-14 energy wiring over exact
wire bytes, and SchedTrace <-> JSONL round-trip determinism.

The schema golden freezes the FULL canonical registry dump (metric
names, dtypes, units, record field sets) against
``tests/golden/obs_schema.json`` — any schema edit is a deliberate,
reviewed event.  Regenerate:

    PYTHONPATH=src python tests/test_obs.py --regen
"""
import dataclasses
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import (CommConfig, FedConfig, ObsConfig,
                               SchedConfig)
from repro.core.fed import FedEngine
from repro.data import synthetic as syn
from repro.metrics import energy
from repro.models.small import MLPTask
from repro.obs import schema as obs_schema
from repro.sched import SchedTrace, VirtualScheduler

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "obs_schema.json")


# ------------------------------------------------------- schema golden
def test_schema_matches_golden():
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert obs.describe() == golden, (
        "obs schema diverged from the committed golden — if the change "
        "is deliberate, regenerate with "
        "`python tests/test_obs.py --regen` (and bump SCHEMA_VERSION "
        "on any removal/retype)")


def test_fingerprint_is_stable_and_canonical():
    assert obs.fingerprint() == obs.fingerprint()
    # canonical dump is valid JSON of describe()
    assert json.loads(obs_schema.canonical_json()) == obs.describe()


def test_every_record_field_is_a_registered_metric():
    for name, rt in obs_schema.RECORDS.items():
        for f in rt.required + rt.optional:
            assert f in obs_schema.METRICS, (name, f)


# --------------------------------------------------- record validation
def _round_rec(**over):
    rec = {"record": "round", "round": 0, "loss": 1.5, "lr": 0.01,
           "participants": 4, "uplink_bytes": 100, "downlink_bytes": 100,
           "hessian_uplink_bytes": 0, "hessian_downlink_bytes": 0,
           "total_bytes": 200, "cum_total_bytes": 200,
           "energy_J": 0.1, "carbon_kg": 1e-8}
    rec.update(over)
    return rec


def test_validate_accepts_valid_round():
    assert obs.validate_record(_round_rec()) == _round_rec()


def test_validate_rejects_unknown_type_missing_and_extra_fields():
    with pytest.raises(obs.ObsSchemaError, match="unknown record type"):
        obs.validate_record({"record": "bogus"})
    with pytest.raises(obs.ObsSchemaError, match="missing required"):
        rec = _round_rec()
        del rec["total_bytes"]
        obs.validate_record(rec)
    with pytest.raises(obs.ObsSchemaError, match="not in the schema"):
        obs.validate_record(_round_rec(surprise=1))


def test_byte_counters_reject_floats_and_bools():
    """The whole point of the schema: byte counts never pass through
    floats (satellite: the float32 in-jit mirrors lose exactness above
    2^24)."""
    with pytest.raises(obs.ObsSchemaError, match="exact int64"):
        obs.validate_record(_round_rec(uplink_bytes=100.0))
    with pytest.raises(obs.ObsSchemaError, match="exact int64"):
        obs.validate_record(_round_rec(participants=True))
    with pytest.raises(obs.ObsSchemaError, match="int64 range"):
        obs.validate_record(_round_rec(total_bytes=2 ** 63))


def test_int64_exactness_beyond_float32_and_float64():
    """2^53+1 is not representable in float64 (nor 2^24+1 in float32);
    the schema carries it exactly through a JSON round-trip."""
    big = 2 ** 53 + 1
    assert float(big) != big                  # would be lost as a float
    rec = _round_rec(total_bytes=big, cum_total_bytes=big)
    back = json.loads(json.dumps(obs.validate_record(rec)))
    assert back["total_bytes"] == big


# -------------------------------------------------------- energy model
def test_channel_rate_hand_computed():
    """Default ChannelModel: R = B log2(1 + P/(d B N0)) with B=1MHz,
    P=0.1W, d=1e12 -> SNR=1 -> R = 2 Mb/s exactly (Eq. 13)."""
    chan = energy.ChannelModel()
    assert chan.rate() == pytest.approx(2e6, rel=1e-12)


def test_tx_energy_joules_hand_computed():
    """Eq. 14 over exact bytes: 250 kB = 2 Mb at 2 Mb/s = 1 s at
    0.1 W = 0.1 J."""
    chan = energy.ChannelModel()
    assert energy.tx_energy_joules(250_000, chan) == pytest.approx(0.1)
    # consistency with the per-round raw-fp32 helper: n params = 4n bytes
    n = 12_345
    assert energy.tx_energy_joules(4 * n, chan) == pytest.approx(
        chan.tx_energy_per_round(n))
    assert energy.tx_energy_joules(0) == 0.0


# ------------------------------------------------------- Sophia probes
@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    x, y = syn.make_image_data(key, 512, "mnist", noise=1.0)
    part = syn.dirichlet_partition(jax.random.PRNGKey(1), y, 4, alpha=0.5)
    tr, _ = syn.train_test_split(part)
    task = MLPTask(hidden=16)

    def batch_fn(v):
        return syn.client_batches(jax.random.fold_in(key, 100 + v),
                                  x, y, tr, 32)

    return task, batch_fn


def _fed(**kw):
    base = dict(num_clients=4, local_iters=2, optimizer="fed_sophia",
                lr=0.01, tau=2)
    base.update(kw)
    return FedConfig(**base)


RUN_RNG = jax.random.PRNGKey(7)


def _run_rounds(task, batch_fn, fed, rounds=3):
    eng = FedEngine(task, fed)
    state = eng.pack_state(eng.init(jax.random.PRNGKey(2)))
    rf = eng.round_fn(donate=False)
    metrics = None
    for r in range(rounds):
        state, metrics = rf(state, batch_fn(r),
                            jax.random.fold_in(RUN_RNG, r))
    return state, metrics


def test_probes_on_state_bitwise_equals_probes_off(setup):
    """The acceptance bar: enabling probes changes ONLY the metrics
    dict — every state leaf is bitwise identical."""
    task, batch_fn = setup
    s_off, m_off = _run_rounds(task, batch_fn, _fed())
    s_on, m_on = _run_rounds(task, batch_fn,
                             _fed(obs=ObsConfig(probes=True)))
    l_off, l_on = jax.tree.leaves(s_off), jax.tree.leaves(s_on)
    assert len(l_off) == len(l_on)
    for a, b in zip(l_off, l_on):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m_off["loss"]) == float(m_on["loss"])
    for k in obs.PROBE_METRICS:
        assert k in m_on and k not in m_off


def test_probe_values(setup):
    task, batch_fn = setup
    fed = _fed(obs=ObsConfig(probes=True))
    _, m = _run_rounds(task, batch_fn, fed, rounds=3)
    clip = float(m["clip_fraction"])
    assert 0.0 <= clip <= 1.0
    assert float(m["m_norm"]) > 0 and float(m["h_norm"]) > 0
    # hessian_every_unit="step" (default), J=2, tau=2: after round
    # r=2 the last local step index is (r+1)*J-1 = 5 ->
    # staleness 5 % 2 = 1, refreshes 5 // 2 + 1 = 3
    assert float(m["h_staleness"]) == 1.0
    assert float(m["gnb_refreshes"]) == 3.0


def test_probe_staleness_round_unit(setup):
    task, batch_fn = setup
    fed = _fed(obs=ObsConfig(probes=True), hessian_every_unit="round",
               tau=3)
    _, m = _run_rounds(task, batch_fn, fed, rounds=4)
    # round unit: last refresh opportunity index is r=3 -> 3 % 3 = 0,
    # 3 // 3 + 1 = 2
    assert float(m["h_staleness"]) == 0.0
    assert float(m["gnb_refreshes"]) == 2.0


def test_sophia_health_hand_built():
    """Value correctness on a hand-built optimizer state: h=1
    everywhere, m ramp -> clip fraction is the exact count of
    |m| >= rho coordinates."""
    from repro.core.sophia import SophiaState
    from repro.obs.probes import sophia_health
    C, R, Ccols = 2, 2, 4
    total = R * Ccols
    m = jnp.stack([jnp.full((R, Ccols), 0.5),
                   jnp.zeros((R, Ccols))])          # half the coords clip
    h = jnp.ones((C, R, Ccols))
    fed = _fed(rho=0.04)
    out = sophia_health(SophiaState(m=m, h=h), 0, fed, total)
    assert float(out["clip_fraction"]) == pytest.approx(0.5)
    # RMS over clients: sqrt(sum(m^2)/C), sqrt(sum(h^2)/C)
    assert float(out["m_norm"]) == pytest.approx(
        math.sqrt(0.25 * total / C))
    assert float(out["h_norm"]) == pytest.approx(
        math.sqrt(C * total / C))


def test_probes_require_stateful_sophia(setup):
    task, _ = setup
    with pytest.raises(ValueError, match="probes"):
        FedEngine(task, _fed(optimizer="fedavg",
                             obs=ObsConfig(probes=True)))


def test_probes_add_no_layout_ops(setup):
    """Probe math is elementwise/reduction only — the layout-op gate
    (benchmarks/run.py LAYOUT_PRIMS) must see the identical count."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    try:
        from benchmarks.run import _count_layout_ops
    finally:
        sys.path.pop(0)
    task, batch_fn = setup
    counts = {}
    for name, fed in (("off", _fed()),
                      ("on", _fed(obs=ObsConfig(probes=True)))):
        eng = FedEngine(task, fed)
        state = eng.pack_state(eng.init(jax.random.PRNGKey(2)))
        jaxpr = jax.make_jaxpr(eng.round)(state, batch_fn(0), RUN_RNG)
        counts[name] = _count_layout_ops(jaxpr.jaxpr)
    assert counts["on"] == counts["off"]


# ------------------------------------------------------- device buffer
def test_metrics_accumulator_batches_rows():
    acc = obs.MetricsAccumulator(4)
    for i in range(3):
        acc.add({"a": jnp.asarray(float(i)), "b": jnp.asarray(10.0 + i)})
    assert len(acc) == 3
    rows = acc.flush()
    assert rows == [{"a": float(i), "b": 10.0 + i} for i in range(3)]
    assert len(acc) == 0                      # reset after flush
    acc.add({"a": jnp.asarray(5.0), "b": jnp.asarray(6.0)})
    assert acc.flush() == [{"a": 5.0, "b": 6.0}]


def test_metrics_accumulator_guards():
    acc = obs.MetricsAccumulator(1)
    acc.add({"a": jnp.asarray(1.0)})
    with pytest.raises(ValueError, match="full"):
        acc.add({"a": jnp.asarray(2.0)})
    acc.flush()
    with pytest.raises(ValueError, match="names"):
        acc.add({"z": jnp.asarray(1.0)})


# ---------------------------------------------------- sinks / recorder
def test_run_recorder_jsonl_and_manifest(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = obs.RunRecorder(path, meta={"arch": "mlp"})
    rec.emit(_round_rec())
    rec.emit(_round_rec(round=1, cum_total_bytes=400))
    rec.close()
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["record"] == "manifest"
    assert lines[0]["schema_sha256"] == obs.fingerprint()
    assert lines[0]["meta"] == {"arch": "mlp"}
    assert [l["record"] for l in lines[1:]] == ["round", "round"]
    man = json.load(open(rec.manifest_path))
    assert man["records"] == {"manifest": 1, "round": 2}
    assert man["schema_version"] == obs.SCHEMA_VERSION
    # the ring mirrors the stream for in-process consumers
    assert [r["record"] for r in rec.ring.records()][-1] == "round"


def test_run_recorder_validates_on_emit(tmp_path):
    rec = obs.RunRecorder(str(tmp_path / "run.jsonl"))
    with pytest.raises(obs.ObsSchemaError):
        rec.emit({"record": "round"})


# --------------------------------------------- sched trace round-trip
def _run_sched(task, batch_fn, fed, events, seed=2):
    eng = FedEngine(task, fed)
    sched = VirtualScheduler(eng, batch_fn)
    state = eng.init(jax.random.PRNGKey(seed))
    return sched.run(state, events, RUN_RNG)


@pytest.mark.parametrize("disc", ["semisync", "async"])
def test_sched_trace_jsonl_roundtrip_deterministic(setup, disc):
    """Two identical scheduler runs serialize to byte-identical JSONL;
    from_records(to_records(t)) re-serializes exactly."""
    task, batch_fn = setup
    fed = _fed(obs=ObsConfig(probes=True),
               sched=SchedConfig(discipline=disc))
    chan = energy.ChannelModel()

    def lines(trace):
        return [json.dumps(r, sort_keys=True)
                for r in trace.to_records(channel=chan)]

    _, t1 = _run_sched(task, batch_fn, fed, 3)
    _, t2 = _run_sched(task, batch_fn, fed, 3)
    assert lines(t1) == lines(t2)
    for rec in t1.to_records(channel=chan):
        obs.validate_record(rec)
    back = SchedTrace.from_records(t1.to_records(channel=chan))
    assert lines(back) == lines(t1)
    assert back.discipline == disc
    assert back.staleness_hist() == t1.staleness_hist()


def test_sched_event_stream_counters_sum_to_cum_bytes(setup):
    """The new per-stream int64 counters decompose the pre-existing
    cum_bytes exactly, event by event."""
    task, batch_fn = setup
    fed = _fed(sched=SchedConfig(discipline="async"))
    _, trace = _run_sched(task, batch_fn, fed, 4)
    for ev in trace.events:
        assert (ev.cum_uplink_bytes + ev.cum_downlink_bytes
                + ev.cum_hessian_uplink_bytes
                + ev.cum_hessian_downlink_bytes) == ev.cum_bytes


def test_from_records_requires_summary():
    with pytest.raises(ValueError, match="sched_summary"):
        SchedTrace.from_records([])


# ------------------------------------------------------------- spans
def test_span_log_records():
    log = obs.SpanLog()
    with log.span("pack"):
        pass
    with log.span("dispatch", virtual_s=12.5):
        pass
    recs = log.records()
    assert [r["name"] for r in recs] == ["pack", "dispatch"]
    assert recs[1]["virtual_s"] == 12.5
    for r in recs:
        obs.validate_record(r)
        assert r["wall_s"] >= 0.0


# ------------------------------------------------- device phases
def _phases_in(hlo_text):
    """The ``fed.*`` phases named in a compiled module's op_name
    metadata; no op_name names two different phases."""
    found = set()
    for op in re.findall(r'op_name="([^"]*)"', hlo_text):
        names = set(re.findall(r"fed\.(\w+)", op))
        assert len(names) <= 1, op
        found |= names
    return found


ALL_PHASES = set(obs.PHASES)
WIRES = CommConfig(compressor="int8", downlink_compressor="int8",
                   hessian_compressor="int8", participation=0.5)


@pytest.mark.parametrize("path,strategy,unit,comm,phases", [
    ("comm", "parallel", "step", WIRES, ALL_PHASES),
    ("comm", "parallel", "round", WIRES, ALL_PHASES),
    ("comm", "sequential", "step", WIRES, ALL_PHASES),
    ("comm", "sequential", "round", WIRES, ALL_PHASES),
    # fp32 state: the direct path's store of the client rows is no op
    ("direct", "parallel", "step", CommConfig(),
     ALL_PHASES - {"wire", "rows"}),
])
def test_round_carries_its_phase_scopes(setup, path, strategy, unit, comm,
                                        phases):
    """Every phase a round's path runs is a named device scope of its
    compiled program (the chunked dispatch's among them, the GNB
    estimate hoisted per round or per step), and no phase nests in
    another."""
    task, batch_fn = setup
    fed = _fed(strategy=strategy, comm=comm, hessian_every_unit=unit,
               sched=SchedConfig(dispatch_chunk=2))
    eng = FedEngine(task, fed)
    assert eng.uses_direct_path() == (path == "direct")
    state = eng.pack_state(eng.init(jax.random.PRNGKey(2)))
    text = eng.round_fn(donate=False).lower(
        state, batch_fn(0), RUN_RNG).compile().as_text()
    assert _phases_in(text) == phases


def test_scheduler_jits_carry_phase_scopes(setup):
    """The scheduler's dispatch runs the local step, the wires and the
    row gather; its apply runs the combine and the row scatter."""
    task, batch_fn = setup
    # the curvature stream is round-synchronous: off under semisync
    fed = _fed(comm=dataclasses.replace(WIRES, hessian_compressor="off"),
               sched=SchedConfig(discipline="semisync"))
    eng = FedEngine(task, fed)
    sched = VirtualScheduler(eng, batch_fn)
    state = eng.init(jax.random.PRNGKey(2))
    idx = jnp.arange(2, dtype=jnp.int32)
    version = jnp.asarray(0, jnp.int32)
    args = (state, batch_fn(0), idx, RUN_RNG, version)
    text = sched._dispatch_fn.lower(*args).compile().as_text()
    assert _phases_in(text) == ALL_PHASES - {"combine"}
    (wires, stats, ef, opt, _, dnm, dnef, _, _) = sched._dispatch_fn(
        *args)
    text = sched._apply_fn.lower(
        state, wires, stats, jnp.ones((2,), jnp.float32), idx, ef, opt,
        dnm, dnef).compile().as_text()
    assert _phases_in(text) == {"combine", "rows"}


def test_phase_rejects_unknown_name():
    with pytest.raises(ValueError, match="unknown phase"):
        obs.phase("forward")


# ------------------------------------------------- trace contexts
def _traced_fed(disc, trace=True):
    return _fed(obs=ObsConfig(probes=True, trace=trace),
                sched=SchedConfig(discipline=disc))


@pytest.mark.parametrize("disc", ["sync", "semisync", "async"])
def test_trace_ids_roundtrip_byte_identical(setup, disc):
    """trace_id survives to_records/from_records byte-identically, ids
    are contiguous 1-based in dispatch order, and every event's folded
    trace_ids point at a real dispatch."""
    task, batch_fn = setup
    _, trace = _run_sched(task, batch_fn, _traced_fed(disc), 3)
    assert trace.dispatches, "tracing on but no dispatch contexts"
    tids = [d.trace_id for d in trace.dispatches]
    assert tids == list(range(1, len(tids) + 1))
    recs = trace.to_records()
    for r in recs:
        obs.validate_record(r)
    lines = [json.dumps(r, sort_keys=True) for r in recs]
    back = SchedTrace.from_records(recs)
    assert [json.dumps(r, sort_keys=True)
            for r in back.to_records()] == lines
    by_id = {d.trace_id for d in trace.dispatches}
    for ev in trace.events:
        assert ev.trace_ids and set(ev.trace_ids) <= by_id


def test_tracing_off_keeps_v1_serialization(setup):
    """With tracing off the record stream is byte-compatible with v1
    consumers: no sched_dispatch records, no trace_ids field."""
    task, batch_fn = setup
    _, trace = _run_sched(task, batch_fn,
                          _traced_fed("semisync", trace=False), 3)
    assert not trace.dispatches
    for r in trace.to_records():
        assert r["record"] != "sched_dispatch"
        assert "trace_ids" not in r


@pytest.mark.parametrize("disc", ["semisync", "async"])
def test_tracing_on_state_bitwise_identical(setup, disc):
    """The acceptance bar: trace contexts are pure host bookkeeping —
    the scheduler's state trajectory and event stream are bitwise
    unchanged, tracing on vs off."""
    task, batch_fn = setup
    s_off, t_off = _run_sched(task, batch_fn,
                              _traced_fed(disc, trace=False), 3)
    s_on, t_on = _run_sched(task, batch_fn,
                            _traced_fed(disc, trace=True), 3)
    for a, b in zip(jax.tree.leaves(s_off), jax.tree.leaves(s_on)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    off_lines = [json.dumps(r, sort_keys=True)
                 for r in t_off.to_records()]
    on_recs = [r for r in t_on.to_records()
               if r["record"] != "sched_dispatch"]
    for r in on_recs:
        r.pop("trace_ids", None)
    assert [json.dumps(r, sort_keys=True) for r in on_recs] == off_lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true",
                    help="rewrite the committed schema golden")
    if ap.parse_args().regen:
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump(obs.describe(), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {GOLDEN}")
