"""Auto-scheduler conformance + explorer properties.

The conformance stake of the autotune layer: the explorer only ever selects
among already-conformant points, so ANY auto-picked schedule must bit-match
the golden model and the engine must serve a target-carrying stream
bit-identically to direct ``predict`` under the selected schedule.

Property tests (hypothesis, or the deterministic stub the conftest
installs): every point the explorer enumerates survives the
``schedule_key``/``from_key`` round-trip, and no frontier point is dominated
by any legal point in the enumerated space.
"""

import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.autotune import (DesignTarget, InfeasibleTargetError, SpaceSpec,
                            divisors, enumerate_space, explore, is_feasible,
                            pareto, select, violation)
from repro.config import FixedPointConfig
from repro.core.hls import price_point
from repro.core.hls.resources import gate_count
from repro.kernels.schedule import KernelSchedule, schedule_key
from repro.models import build_model
from repro.registry import get_config
from repro.serving import LMServingEngine, RNNServingEngine
from repro.testing import (assert_schedule_conformance,
                           assert_serving_conformance, tiny_config)

CFG = get_config("top-tagging-lstm")
GRU_CFG = get_config("top-tagging-gru")

#: a CPU-friendly slice of the space, shared by most tests
SMALL_SPEC = SpaceSpec(reuse_factors=(1, 2, 4), iis=(0, 1),
                       backends=("pallas_interpret",))
XLA_SPEC = SpaceSpec(reuse_factors=(1, 2, 4), iis=(0, 1),
                     backends=("xla",))

FPS = (None, FixedPointConfig(16, 6))


def _params_for(cfg):
    return build_model(cfg).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def lstm_engine():
    return RNNServingEngine(CFG, _params_for(CFG), max_batch=8)


@pytest.fixture(scope="module")
def gru_engine():
    return RNNServingEngine(GRU_CFG, _params_for(GRU_CFG), max_batch=8)


# ---------------------------------------------------------------------------
# Space enumeration
# ---------------------------------------------------------------------------


def test_space_is_legal_deduped_deterministic():
    space = enumerate_space(CFG, SMALL_SPEC)
    assert space                                   # non-empty
    gd = gate_count(CFG.rnn.cell) * CFG.rnn.hidden
    keys = [s.key() for s in space]
    assert len(keys) == len(set(keys))             # deduplicated
    assert keys == sorted(keys)                    # deterministic order
    for s in space:
        assert gd % s.reuse_factor == 0            # executes exactly as named
        assert s.effective_reuse(gd) == s.reuse_factor
        if s.hoist_reuse > 1:
            assert s.hoist_input
        if s.ii:
            assert s.mode == "pipeline"
    assert enumerate_space(CFG, SMALL_SPEC) == space


def test_space_full_reuse_axis_is_divisors():
    space = enumerate_space(CFG, SpaceSpec(modes=("static",),
                                           hoist=(False,)))
    gd = gate_count(CFG.rnn.cell) * CFG.rnn.hidden
    assert {s.reuse_factor for s in space} == set(divisors(gd))


def test_space_prunes_misaligned_tpu_points():
    """pallas_tpu points whose partial column tile is off the 128-lane
    boundary are pruned (they would raise at dispatch), never clamped; the
    whole-width tile (R=1) is legal at any width."""
    spec = SpaceSpec(reuse_factors=None, modes=("static",), hoist=(False,),
                     block_batches=(8,), backends=("pallas_tpu",))
    gd = gate_count(CFG.rnn.cell) * CFG.rnn.hidden   # 80: no 128-wide tile
    assert gd == 80
    assert [s.reuse_factor for s in enumerate_space(CFG, spec)] == [1]
    partial = SpaceSpec(reuse_factors=(2,), modes=("static",),
                        hoist=(False,), block_batches=(8,),
                        backends=("pallas_tpu",))
    assert enumerate_space(CFG, partial) == ()
    big = get_config("quickdraw-lstm")               # h=128 -> gd=512
    aligned = enumerate_space(big, spec)
    assert aligned
    g2 = gate_count(big.rnn.cell) * big.rnn.hidden
    for s in aligned:
        assert (g2 // s.reuse_factor) % 128 == 0


# ---------------------------------------------------------------------------
# Property: schedule_key / from_key round-trip over the enumerated space
# ---------------------------------------------------------------------------

_PROP_SPACE = enumerate_space(
    CFG, SpaceSpec(reuse_factors=None, hoist_reuses=(1, 2, 4),
                   iis=(0, 1, 2, 4), block_batches=(1, 8, 128),
                   backends=("auto", "xla", "pallas_interpret")))
_PROP_FPS = (None, FixedPointConfig(16, 6),
             FixedPointConfig(8, 3, rounding="trn", saturation="wrap"),
             FixedPointConfig(24, 12, signed=False))


@settings(max_examples=60)
@given(i=st.integers(0, len(_PROP_SPACE) - 1),
       j=st.integers(0, len(_PROP_FPS) - 1))
def test_schedule_key_roundtrip_over_enumerated_space(i, j):
    """Every token an explorer-enumerated point emits must survive the
    inverse, with and without the fp tail."""
    s, fp = _PROP_SPACE[i], _PROP_FPS[j]
    assert KernelSchedule.from_key(s.key()) == s
    assert KernelSchedule.from_key(schedule_key(s, fp)) == s


def test_schedule_key_roundtrip_exhaustive_small_space():
    """The stub-friendly exhaustive sweep of the same invariant."""
    for s in enumerate_space(CFG, SMALL_SPEC):
        for fp in _PROP_FPS:
            assert KernelSchedule.from_key(schedule_key(s, fp)) == s


# ---------------------------------------------------------------------------
# Pareto frontier
# ---------------------------------------------------------------------------


def test_frontier_nondominated_by_any_legal_point():
    """Acceptance criterion: no returned point is dominated in
    (latency_cycles, dsp, bram) by ANY legal point in the enumerated
    space."""
    ex = explore(CFG, spec=SMALL_SPEC)
    assert ex.frontier
    for f in ex.frontier:
        for p in ex.points:
            assert not p.dominates(f), (p.key, f.key)
    # and every non-frontier point IS dominated by some frontier point
    front_keys = {f.key for f in ex.frontier}
    for p in ex.points:
        if p.key not in front_keys:
            assert any(f.dominates(p) for f in ex.frontier), p.key


def test_frontier_latency_monotone_in_reuse_static():
    """Along the static-mode R axis the frontier's own pricing must be the
    paper's curve: latency strictly rises, DSP strictly falls."""
    pts = [price_point(CFG, KernelSchedule(reuse_factor=r, mode="static",
                                           block_batch=8,
                                           backend="pallas_interpret"))
           for r in (1, 2, 4, 8)]
    lats = [p.latency_cycles for p in pts]
    dsps = [p.dsp for p in pts]
    assert lats == sorted(lats) and len(set(lats)) == len(lats)
    assert dsps == sorted(dsps, reverse=True) and len(set(dsps)) == len(dsps)


def test_pareto_of_frontier_is_frontier():
    ex = explore(CFG, spec=SMALL_SPEC)
    assert pareto(ex.frontier) == ex.frontier


# ---------------------------------------------------------------------------
# Target feasibility + selection
# ---------------------------------------------------------------------------


def test_select_respects_budgets():
    r1 = select(CFG, DesignTarget(objective="latency"), SMALL_SPEC)
    assert r1.schedule.reuse_factor == 1           # unconstrained: fastest
    cap = r1.dsp - 1                               # force R > 1
    saver = select(CFG, DesignTarget(max_dsp=cap), SMALL_SPEC)
    assert saver.dsp <= cap and saver.latency_cycles >= r1.latency_cycles
    thr = select(CFG, DesignTarget(min_throughput_eps=1e7,
                                   objective="throughput"), SMALL_SPEC)
    assert thr.ii_cycles <= 2                      # pipeline/nonstatic pick
    assert thr.schedule.mode in ("pipeline", "nonstatic")


def test_select_feasible_points_all_meet_target():
    target = DesignTarget(max_latency_us=1.0, max_dsp=5000)
    ex = explore(CFG, target, SMALL_SPEC)
    assert ex.feasible
    for p in ex.feasible:
        assert is_feasible(p, target)
        assert p.latency_us(target.clock_mhz) <= 1.0 and p.dsp <= 5000
    assert ex.best is ex.feasible[0]


def test_infeasible_target_names_nearest_point():
    target = DesignTarget(max_latency_us=1e-4)     # nothing is this fast
    with pytest.raises(InfeasibleTargetError) as ei:
        select(CFG, target, SMALL_SPEC)
    err = ei.value
    assert err.nearest is not None
    assert err.nearest.key in str(err)             # nearest point is NAMED
    assert "nearest-to-feasible" in str(err)
    assert violation(err.nearest, target) > 0
    # nearest really is nearest: no legal point violates less
    for p in explore(CFG, target, SMALL_SPEC).points:
        assert violation(p, target) >= violation(err.nearest, target)


def test_replicas_axis_scales_throughput_feasibility():
    """K data-parallel replicas make a K x throughput floor feasible: the
    constraint is read against aggregate events/s, everything else
    (latency, resources) stays per-replica."""
    best_eps = max(p.throughput_eps(200.0)
                   for p in explore(CFG, DesignTarget(), SMALL_SPEC).points)
    floor = best_eps * 2.5
    single = DesignTarget(min_throughput_eps=floor, objective="throughput")
    with pytest.raises(InfeasibleTargetError):
        select(CFG, single, SMALL_SPEC)
    tripled = dataclasses.replace(single, replicas=3)
    pt = select(CFG, tripled, SMALL_SPEC)
    assert pt.throughput_eps(200.0) * 3 >= floor
    assert is_feasible(pt, tripled) and not is_feasible(pt, single)
    assert "over 3 replicas" in tripled.describe()


def test_infeasible_throughput_suggests_smallest_replica_count():
    best_eps = max(p.throughput_eps(200.0)
                   for p in explore(CFG, DesignTarget(), SMALL_SPEC).points)
    target = DesignTarget(min_throughput_eps=best_eps * 2.5,
                          objective="throughput")
    with pytest.raises(InfeasibleTargetError) as ei:
        select(CFG, target, SMALL_SPEC)
    err = ei.value
    assert err.suggested_replicas == 3                 # ceil(2.5)
    assert err.suggested_point is not None
    assert f"replicas={err.suggested_replicas}" in str(err)
    assert err.suggested_point.key in str(err)
    # the suggestion is REAL: a target with that many replicas selects
    fixed = dataclasses.replace(target, replicas=err.suggested_replicas)
    assert select(CFG, fixed, SMALL_SPEC) is not None
    # and it is the SMALLEST such count
    with pytest.raises(InfeasibleTargetError):
        select(CFG, dataclasses.replace(
            target, replicas=err.suggested_replicas - 1), SMALL_SPEC)


def test_no_replica_suggestion_for_latency_or_resource_busts():
    """Replication cannot fix a per-replica latency or resource bust —
    the error must NOT suggest scaling out."""
    with pytest.raises(InfeasibleTargetError) as ei:
        select(CFG, DesignTarget(max_latency_us=1e-4), SMALL_SPEC)
    assert ei.value.suggested_replicas is None
    assert "replicas=" not in str(ei.value)
    # throughput floor AND an impossible latency budget: still no
    # suggestion (no point clears the non-throughput constraints)
    with pytest.raises(InfeasibleTargetError) as ei:
        select(CFG, DesignTarget(max_latency_us=1e-4,
                                 min_throughput_eps=1e12), SMALL_SPEC)
    assert ei.value.suggested_replicas is None


def test_replicas_axis_validation():
    with pytest.raises(ValueError, match="replicas"):
        DesignTarget(replicas=0)
    with pytest.raises(ValueError, match="replicas"):
        DesignTarget(replicas=1.5)
    assert DesignTarget(replicas=2).replicas == 2


def test_select_measured_refinement_returns_topk_member():
    target = DesignTarget(objective="latency")
    ex = explore(CFG, target, XLA_SPEC)
    top_keys = {p.key for p in ex.feasible[:3]}
    pt = select(CFG, target, XLA_SPEC, measure_top_k=3)
    assert pt.key in top_keys


def test_select_measured_refinement_never_degrades_resources_objective():
    """Wall clock carries no resource information: under
    objective="resources" the analytic (DSP-optimal) pick must stand."""
    target = DesignTarget(objective="resources")
    analytic = select(CFG, target, XLA_SPEC)
    assert select(CFG, target, XLA_SPEC, measure_top_k=3).key == analytic.key


def test_select_empty_space_raises_clear_error():
    """An all-pruned space (e.g. pallas_tpu alignment of R=2 tiles on
    gate_dim 80) must raise an explanatory ValueError, not min()-on-empty."""
    spec = SpaceSpec(reuse_factors=(2,), modes=("static",), hoist=(False,),
                     backends=("pallas_tpu",))
    assert enumerate_space(CFG, spec) == ()
    with pytest.raises(ValueError, match="space is empty"):
        select(CFG, DesignTarget(), spec)


# ---------------------------------------------------------------------------
# Conformance stake: the explorer only selects among conformant points
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cell", ("lstm", "gru"))
def test_explored_frontier_points_are_conformant(cell):
    cfg = CFG if cell == "lstm" else GRU_CFG
    ex = explore(cfg, spec=SpaceSpec(reuse_factors=(1, 4),
                                     backends=("pallas_interpret",)))
    for p in ex.frontier:
        err = assert_schedule_conformance(cell, p.schedule, B=3,
                                          T=cfg.rnn.seq_len,
                                          F=cfg.rnn.input_size,
                                          H=cfg.rnn.hidden)
        assert np.isfinite(err)


# ---------------------------------------------------------------------------
# Engine auto-scheduling (the serving side of the tentpole)
# ---------------------------------------------------------------------------

#: targets that force distinct (mode x R) picks — the conformance cells
TARGETS = (
    DesignTarget(objective="latency"),                       # static R=1
    DesignTarget(max_dsp=600),                               # static, R up
    DesignTarget(min_throughput_eps=1e7, objective="throughput"),  # pipeline
)


@pytest.mark.parametrize("cell", ("lstm", "gru"))
@pytest.mark.parametrize("fp", FPS, ids=("float", "ap16_6"))
@pytest.mark.parametrize("ti", range(len(TARGETS)))
def test_auto_schedule_bitmatches_direct_predict(cell, fp, ti, rng,
                                                 lstm_engine, gru_engine):
    """Acceptance criterion: auto_schedule(target) serves bit-identically to
    predict() under the selected schedule, per (cell x mode x R x fp)."""
    cfg = CFG if cell == "lstm" else GRU_CFG
    base = lstm_engine if cell == "lstm" else gru_engine
    target = TARGETS[ti]
    if fp is not None:
        import dataclasses
        target = dataclasses.replace(target, fp=fp)
    eng = RNNServingEngine(cfg, base.params, max_batch=8)
    pt = eng.auto_schedule(target, spec=SMALL_SPEC, warmup=False)
    x = rng.randn(5, cfg.rnn.seq_len, cfg.rnn.input_size).astype(np.float32)
    auto = eng.predict(x)                          # engine-default schedule
    direct = eng.predict(x, schedule=pt.schedule, fp=pt.fp)
    np.testing.assert_array_equal(auto, direct)
    # the auto-picked schedule is itself golden-model conformant
    assert_serving_conformance(eng, x, schedule=pt.schedule, fp=pt.fp)
    # and the picked point meets its own target
    assert is_feasible(pt, target)


def test_target_carrying_stream_cobatches_on_selected_key(gru_engine, rng):
    """submit(target=...) resolves the explorer ONCE, lands every request on
    the selected schedule's queue, and bit-matches direct predict."""
    cfg = GRU_CFG
    eng = RNNServingEngine(cfg, gru_engine.params, max_batch=4)
    target = DesignTarget(max_dsp=600)
    x = rng.randn(6, 20, 6).astype(np.float32)
    reqs = [eng.submit(x[i], target=target) for i in range(6)]
    eng.flush(force=True)
    pt = eng.schedule_for_target(target)
    assert len({r.key for r in reqs}) == 1         # one auto-picked queue
    assert reqs[0].key == pt.key
    assert eng.trace_count(pt.key) == 1            # whole stream: one trace
    direct = eng.predict(x, schedule=pt.schedule, fp=pt.fp)
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(np.asarray(r.result), direct[i])


def test_schedule_for_target_memoizes_per_spec(gru_engine):
    """The same target under a DIFFERENT space spec must re-resolve, never
    be served from the other spec's cache."""
    eng = RNNServingEngine(GRU_CFG, gru_engine.params, max_batch=4)
    target = DesignTarget(objective="latency")
    default_pt = eng.schedule_for_target(target)         # engine xla spec
    assert default_pt.schedule.backend == "xla"
    interp_pt = eng.schedule_for_target(target, spec=SMALL_SPEC)
    assert interp_pt.schedule.backend == "pallas_interpret"
    # both resolutions stay cached independently
    assert eng.schedule_for_target(target) is default_pt
    assert eng.schedule_for_target(target, spec=SMALL_SPEC) is interp_pt


def test_engine_infeasible_target_raises_with_nearest(gru_engine):
    eng = RNNServingEngine(GRU_CFG, gru_engine.params, max_batch=4)
    with pytest.raises(InfeasibleTargetError, match="nearest-to-feasible"):
        eng.auto_schedule(DesignTarget(max_latency_us=1e-4), spec=SMALL_SPEC)


def test_default_queue_reports_resolved_schedule(gru_engine, rng):
    """Satellite fix: requests on the bare DEFAULT_SCHEDULE_KEY queue are
    served under — and reported as — the engine's resolved schedule, not an
    estimate-less row."""
    eng = RNNServingEngine(GRU_CFG, gru_engine.params, max_batch=4)
    x = rng.randn(3, 20, 6).astype(np.float32)
    for i in range(3):
        eng.batcher.submit(x[i])                   # no schedule, no key
    done = eng.flush(force=True)
    assert len(done) == 3 and all(r.result is not None for r in done)
    direct = eng.predict(x)                        # the resolved schedule
    for i, r in enumerate(done):
        np.testing.assert_array_equal(np.asarray(r.result), direct[i])
    row = eng.serve_report()["default"]
    assert row["schedule"] == eng.resolved_schedule
    assert row["analytical"] is not None           # priced, not estimate-less
    assert row["resolved_key"] == schedule_key(*eng.resolve())
    assert row["measured"]["served"] == 3


# ---------------------------------------------------------------------------
# LM engine on the schedule-key abstraction
# ---------------------------------------------------------------------------


def test_lm_engine_keyed_decoders_isolate_and_report():
    cfg = tiny_config(get_config("stablelm-3b"))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    eng = LMServingEngine(cfg, params, max_batch=2, max_seq=32)
    a = eng.add_request([3, 4, 5], max_new=2)
    b = eng.add_request([6], max_new=3)
    assert eng.add_request([7]) is None            # default pool full
    sched = KernelSchedule(reuse_factor=2, mode="nonstatic")
    c = eng.add_request([7, 8], max_new=2, schedule=sched)
    assert c is not None                           # own pool, own cache
    done = eng.run_to_completion()
    assert set(done) == {a, b, c}
    report = eng.serve_report()
    assert set(report) == {"default", schedule_key(sched)}
    assert report["default"]["measured"]["served"] == 2
    assert report[schedule_key(sched)]["measured"]["served"] == 1
    assert report[schedule_key(sched)]["schedule"] == sched
    # exactly one decode trace per schedule key (keyed jit-cache criterion)
    assert eng.trace_count("default") == 1
    assert eng.trace_count(schedule_key(sched)) == 1
    # greedy decode identical to a fresh single-key engine (keying the
    # batcher must not change the math)
    ref = LMServingEngine(cfg, params, max_batch=2, max_seq=32)
    ra = ref.add_request([3, 4, 5], max_new=2)
    assert ref.run_to_completion()[ra] == done[a]
