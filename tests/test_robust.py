"""Adversarial-fleet tests (docs/robustness.md).

The load-bearing suite is the DEGENERACY harness: robust aggregators
with zero adversaries and a zero trim/clip must be **bitwise**
identical to the weighted-mean path — across {sync, semisync, async}
disciplines and {direct, uplink-int8, bidirectional} comm regimes —
because `repro.robust.aggregators.resolve` maps degenerate
parameterizations to ``"mean"`` at trace time and the caller keeps its
existing traced graph.  Alongside: kernel-vs-reference conformance at
fp32/bf16/fp8, attack-transform geometry, deterministic fault masks,
and a small end-to-end recovery check (robust aggregation beats plain
mean under sign-flip byzantine clients).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (AGGREGATORS, CommConfig, FedConfig,
                                RobustConfig, SchedConfig)
from repro.core.fed import FedEngine
from repro.data import synthetic as syn
from repro.kernels.ref import robust_agg_ref
from repro.kernels.robust_agg import robust_agg_flat
from repro.models.small import MLPTask
from repro.robust import (aggregators as ragg, attacks as ratt)
from repro.sched import SchedTrace, VirtualScheduler

RUN_RNG = jax.random.PRNGKey(7)

#: every degenerate parameterization resolves to "mean" — same traced
#: graph as the default, hence bitwise (docs/robustness.md)
DEGENERATE = [
    pytest.param(RobustConfig(aggregator="trimmed_mean",
                              trim_fraction=0.0), id="trim0"),
    pytest.param(RobustConfig(aggregator="norm_clip", clip_norm=0.0),
                 id="clip0"),
    pytest.param(RobustConfig(attack="sign_flip", attack_fraction=0.0),
                 id="attack-frac0"),
]

COMM_REGIMES = [
    pytest.param(CommConfig(), id="direct"),
    pytest.param(CommConfig(compressor="int8"), id="uplink-int8"),
    pytest.param(CommConfig(compressor="int8",
                            downlink_compressor="int8"), id="bidir"),
]


# ------------------------------------------------------ engine fixtures
@pytest.fixture(scope="module")
def setup():
    key = jax.random.PRNGKey(0)
    x, y = syn.make_image_data(key, 1024, "mnist", noise=1.0)
    part = syn.dirichlet_partition(jax.random.PRNGKey(1), y, 4, alpha=0.5)
    tr, _ = syn.train_test_split(part)
    task = MLPTask(hidden=32)

    def batch_fn(v):
        return syn.client_batches(jax.random.fold_in(key, 100 + v),
                                  x, y, tr, 32)

    return task, batch_fn


def _fed(**kw):
    base = dict(num_clients=4, local_iters=2, optimizer="fed_sophia",
                lr=0.01, tau=2)
    base.update(kw)
    return FedConfig(**base)


def _run_engine(task, fed, batch_fn, rounds=2):
    eng = FedEngine(task, fed)
    state = eng.init(jax.random.PRNGKey(2))
    rf = eng.round_fn(donate=False)
    for v in range(rounds):
        state, m = rf(state, batch_fn(v), jax.random.fold_in(RUN_RNG, v))
    return state, m


def _run_sched(task, fed, batch_fn, events):
    eng = FedEngine(task, fed)
    sched = VirtualScheduler(eng, batch_fn)
    state = eng.init(jax.random.PRNGKey(2))
    return sched.run(state, events, RUN_RNG)


def _assert_states_equal(s0, s1):
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- degeneracy: engine
@pytest.mark.parametrize("comm", COMM_REGIMES)
@pytest.mark.parametrize("robust", DEGENERATE)
def test_engine_degenerate_robust_is_bitwise_mean(setup, comm, robust):
    """A degenerate RobustConfig keeps the engine round BITWISE equal
    to the default weighted-mean path, per comm regime."""
    task, batch_fn = setup
    fed = _fed(comm=comm)
    s0, _ = _run_engine(task, fed, batch_fn)
    s1, _ = _run_engine(task, dataclasses.replace(fed, robust=robust),
                        batch_fn)
    _assert_states_equal(s0, s1)


@pytest.mark.parametrize("robust", DEGENERATE)
def test_engine_sequential_degenerate_bitwise(setup, robust):
    """The sequential (scan) strategy keeps the same contract."""
    task, batch_fn = setup
    fed = _fed(strategy="sequential", comm=CommConfig(compressor="int8"))
    s0, _ = _run_engine(task, fed, batch_fn)
    s1, _ = _run_engine(task, dataclasses.replace(fed, robust=robust),
                        batch_fn)
    _assert_states_equal(s0, s1)


# ---------------------------------------------- degeneracy: scheduler
@pytest.mark.parametrize("sched", [
    pytest.param(SchedConfig(), id="sync"),
    pytest.param(SchedConfig(discipline="semisync", buffer_size=2,
                             latency_profile="lognormal", seed=5),
                 id="semisync"),
    pytest.param(SchedConfig(discipline="async",
                             latency_profile="lognormal", seed=5),
                 id="async"),
])
@pytest.mark.parametrize("robust", DEGENERATE)
def test_sched_degenerate_robust_is_bitwise_mean(setup, sched, robust):
    """Every scheduler discipline keeps the degeneracy contract: state
    leaf-for-leaf bitwise equal, and the event log reports the
    resolved default aggregator/attack."""
    task, batch_fn = setup
    fed = _fed(comm=CommConfig(compressor="int8"), sched=sched)
    s0, t0 = _run_sched(task, fed, batch_fn, 3)
    s1, t1 = _run_sched(task, dataclasses.replace(fed, robust=robust),
                        batch_fn, 3)
    _assert_states_equal(s0, s1)
    assert [e.time for e in t0.events] == [e.time for e in t1.events]
    assert all(e.aggregator == "mean" and e.attack == "none"
               and e.byzantine == () and e.dropped == ()
               for e in t1.events)


# ----------------------------------------- kernel-vs-ref conformance
@pytest.mark.parametrize("dtype", ["float32", "bfloat16",
                                   "float8_e4m3fn"])
@pytest.mark.parametrize("trim", [0, 1, 3])
@pytest.mark.parametrize("normalize", [True, False])
def test_robust_agg_kernel_matches_ref(dtype, trim, normalize):
    """Pallas kernel == jnp oracle to one fp32 rounding per add, per
    storage dtype, trim count and normalization mode.

    Both run the same op sequence on fp32 values (bf16/fp8 loads upcast
    exactly), so trimming picks the same survivors; but a compiler may
    contract ``w * x`` into the running sum as one FMA, which rounds
    once where the other side rounds twice.  Each of the K adds may
    then differ by one fp32 ulp of its partial sum, so the difference
    is bounded by ``K * eps32 * sum_k |w_k s_k x_k|`` per coordinate
    (over the surviving weight when normalized).  Which cases round
    alike depends on XLA:CPU's fusion, so no bitwise pin holds; a
    wrong survivor moves the result by a whole term, far outside."""
    K, R, C = 9, 20, 96
    key = jax.random.PRNGKey(3)
    wires = (10.0 * jax.random.normal(key, (K, R, C))).astype(
        jnp.dtype(dtype))
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (K,),
                                 minval=0.5, maxval=2.0)
    scales = jax.random.uniform(jax.random.fold_in(key, 2), (K,),
                                minval=0.1, maxval=1.0)
    out = robust_agg_flat(wires, weights, scales, trim=trim,
                          normalize=normalize, interpret=True)
    ref = robust_agg_ref(wires, weights, scales, trim=trim,
                         normalize=normalize)
    assert out.dtype == jnp.float32 and ref.dtype == jnp.float32
    w, s = np.asarray(weights), np.asarray(scales)
    terms = np.abs(np.asarray(wires, np.float32) * (w * s)[:, None, None])
    bound = K * np.finfo(np.float32).eps * terms.sum(axis=0)
    if normalize:
        # the surviving weight is at least K - 2*trim smallest weights
        bound = bound / ((K - 2 * trim) * w.min())
    diff = np.abs(np.asarray(out) - np.asarray(ref))
    assert (diff <= bound).all(), float((diff - bound).max())


def test_coordinate_median_is_numpy_median():
    """Maximal trim with uniform weights is the per-coordinate median
    (odd K: exact; the kernel's surviving-mean of one value)."""
    K, R, C = 7, 6, 10
    wires = jax.random.normal(jax.random.PRNGKey(0), (K, R, C))
    ones = jnp.ones((K,), jnp.float32)
    rb = RobustConfig(aggregator="coordinate_median")
    out = ragg.aggregate_stack(rb, wires, ones)
    np.testing.assert_allclose(np.asarray(out),
                               np.median(np.asarray(wires), axis=0),
                               rtol=1e-6, atol=1e-6)


def test_trimmed_mean_bounded_by_survivors():
    """The trimmed mean lies within the per-coordinate min/max of the
    surviving (sorted-interior) values."""
    K, R, C = 10, 5, 8
    trim = 3
    wires = 5.0 * jax.random.normal(jax.random.PRNGKey(1), (K, R, C))
    ones = jnp.ones((K,), jnp.float32)
    out = np.asarray(robust_agg_ref(wires, ones, ones, trim=trim,
                                    normalize=True))
    srt = np.sort(np.asarray(wires), axis=0)[trim:K - trim]
    assert (out >= srt.min(axis=0) - 1e-5).all()
    assert (out <= srt.max(axis=0) + 1e-5).all()


def test_norm_clip_scales_and_resolve():
    """clip_scales: exactly 1.0 inside the ball, clip/||x|| outside;
    resolve degenerates norm_clip only when the clip is off."""
    wires = jnp.stack([jnp.ones((2, 4)), 10.0 * jnp.ones((2, 4))])
    s = np.asarray(ragg.clip_scales(wires, jnp.float32(5.0)))
    nrm1 = float(np.sqrt(8.0)) * 10.0
    assert s[0] == 1.0
    np.testing.assert_allclose(s[1], 5.0 / nrm1, rtol=1e-6)
    assert ragg.resolve(RobustConfig(aggregator="norm_clip",
                                     clip_norm=0.0), 4) == "mean"
    assert ragg.resolve(RobustConfig(aggregator="norm_clip",
                                     clip_norm=1.0), 4) == "norm_clip"
    with pytest.raises(ValueError):
        ragg.resolve(RobustConfig(aggregator="bogus"), 4)


def test_kernel_rejects_full_trim():
    wires = jnp.zeros((4, 2, 2))
    ones = jnp.ones((4,), jnp.float32)
    with pytest.raises(ValueError):
        robust_agg_flat(wires, ones, ones, trim=2, normalize=True,
                        interpret=True)


# --------------------------------------------------- attacks & masks
def test_byzantine_mask_deterministic_and_sized():
    rb = RobustConfig(attack="sign_flip", attack_fraction=0.25, seed=9)
    m1 = ratt.byzantine_mask(rb, 8)
    m2 = ratt.byzantine_mask(rb, 8)
    np.testing.assert_array_equal(m1, m2)
    assert int(m1.sum()) == 2
    m3 = ratt.byzantine_mask(dataclasses.replace(rb, seed=10), 8)
    assert m1.shape == m3.shape
    assert not ratt.byzantine_mask(RobustConfig(), 8).any()
    with pytest.raises(ValueError):
        ratt.byzantine_mask(dataclasses.replace(rb, attack="bogus"), 8)


@pytest.mark.parametrize("attack", ["sign_flip", "scale", "random_wire"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_attacks_preserve_wire_geometry(attack, dtype):
    """Attack transforms keep the packed stack's shape and dtype, touch
    ONLY the masked rows, and sign_flip is exact negation."""
    rb = RobustConfig(attack=attack, attack_fraction=0.5,
                      attack_scale=3.0)
    wires = jax.random.normal(jax.random.PRNGKey(2), (6, 4, 8)).astype(
        jnp.dtype(dtype))
    mask = jnp.asarray([True, False, True, False, False, True])
    out = ratt.attack_wires(rb, wires, mask, jax.random.PRNGKey(5))
    assert out.shape == wires.shape and out.dtype == wires.dtype
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(out)[~m],
                                  np.asarray(wires)[~m])
    if attack == "sign_flip":
        np.testing.assert_array_equal(np.asarray(out)[m],
                                      -np.asarray(wires)[m])
    elif attack == "scale":
        np.testing.assert_allclose(
            np.asarray(out)[m].astype(np.float32),
            3.0 * np.asarray(wires)[m].astype(np.float32),
            rtol=1e-2)
    else:
        assert not np.array_equal(np.asarray(out)[m],
                                  np.asarray(wires)[m])


def test_corrupt_labels_only_masked_clients():
    rb = RobustConfig(label_noise_fraction=0.5, label_noise_rate=1.0,
                      seed=3)
    labels = np.zeros((4, 32), np.int64)
    mask = np.array([True, False, True, False])
    out = ratt.corrupt_labels(rb, labels, mask, 10)
    assert out.shape == labels.shape
    np.testing.assert_array_equal(out[~mask], 0)
    # rate 1.0 resamples every masked label uniformly over 10 classes —
    # all-zeros surviving on 64 draws has probability 1e-64
    assert (out[mask] != 0).any()


# ---------------------------------------------- sched event round-trip
def test_sched_event_records_roundtrip_with_robust_fields(setup):
    """to_records/from_records is exact for events carrying the new
    aggregator/attack/byzantine/dropped context."""
    task, batch_fn = setup
    fed = _fed(comm=CommConfig(compressor="int8"),
               sched=SchedConfig(discipline="semisync", buffer_size=4,
                                 latency_profile="lognormal", seed=5),
               robust=RobustConfig(aggregator="trimmed_mean",
                                   trim_fraction=0.3, attack="sign_flip",
                                   attack_fraction=0.5, dropout_prob=0.4,
                                   rejoin_delay_s=3.0))
    _, trace = _run_sched(task, fed, batch_fn, 4)
    assert any(e.byzantine for e in trace.events)
    assert any(e.aggregator != "mean" for e in trace.events)
    back = SchedTrace.from_records(trace.to_records())
    for a, b in zip(trace.events, back.events):
        assert a.aggregator == b.aggregator
        assert a.attack == b.attack
        assert a.byzantine == b.byzantine
        assert a.dropped == b.dropped


# ------------------------------------------------- end-to-end recovery
def test_robust_aggregation_recovers_under_sign_flip(setup):
    """25% sign-flip byzantine clients: plain mean ends with a worse
    training loss than trimmed mean and coordinate median (the CI-sized
    version of the `--only robust` benchmark headline)."""
    task, batch_fn = setup
    base = _fed(lr=0.05)
    atk = dict(attack="sign_flip", attack_fraction=0.25)

    def final_loss(robust):
        fed = dataclasses.replace(base, robust=robust)
        _, m = _run_engine(task, fed, batch_fn, rounds=4)
        return float(m["loss"])

    mean = final_loss(RobustConfig(**atk))
    trimmed = final_loss(RobustConfig(aggregator="trimmed_mean",
                                      trim_fraction=0.3, **atk))
    median = final_loss(RobustConfig(aggregator="coordinate_median",
                                     **atk))
    clean = final_loss(RobustConfig())
    assert trimmed < mean and median < mean
    # robust aggregation lands closer to the clean run than mean does
    assert abs(trimmed - clean) < abs(mean - clean)
    assert abs(median - clean) < abs(mean - clean)


def test_aggregator_registry_is_complete():
    """Every registered aggregator resolves on a non-degenerate config
    (the registry and the dispatch can't drift apart)."""
    cfgs = {
        "mean": RobustConfig(),
        "trimmed_mean": RobustConfig(aggregator="trimmed_mean",
                                     trim_fraction=0.3),
        "coordinate_median": RobustConfig(
            aggregator="coordinate_median"),
        "norm_clip": RobustConfig(aggregator="norm_clip", clip_norm=1.0),
    }
    assert set(cfgs) == set(AGGREGATORS)
    for name, rb in cfgs.items():
        assert ragg.resolve(rb, 8) == name
