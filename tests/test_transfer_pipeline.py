"""Event-based transfer pipeline: scalar-reduction and strict-improvement
properties, pipeline-vs-closed-form feasibility, the PlanDrain async-apply
state machine, degenerate split/merge round-trips, drain byte accounting,
and engine/simulator bubble-accounting agreement."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.configs import ARCHS
from repro.core import (
    PlanDrain, RemapPlan, TransferEngine, identity_plan, make_fetch,
    make_plan, simulate_decode_step, split_blocks,
    sync_step_time,
)
from repro.core import layer_selection as ls
from repro.core import transfer_pipeline as tpl
from repro.serving.hw import GH200
from repro.serving.perf_model import PerfModel


_uniform = tpl.uniform_plan      # the shared plan constructor under test


# --------------------------------------------------- reduction to the scalar
@settings(max_examples=30, deadline=None)
@given(batch=st.integers(1, 64), ctx=st.integers(1, 4096))
def test_pipeline_reduces_to_scalar_when_m0(batch, ctx):
    """Acceptance property: with m=0 the event pipeline IS the scalar
    model — PerfModel.decode_step_time(plan=identity) must equal the
    plain scalar path exactly."""
    pm = PerfModel(ARCHS["granite-3-8b"], GH200)
    plan = identity_plan(pm.repeats)
    scalar = pm.decode_step_time(batch, float(ctx))
    via_plan = pm.decode_step_time(batch, float(ctx), plan=plan)
    assert math.isclose(scalar, via_plan, rel_tol=1e-9)
    timing = pm.decode_step_timing(batch, float(ctx), plan)
    assert timing.bubble_time == 0.0 and not timing.misses
    assert math.isclose(timing.total, scalar, rel_tol=1e-9)


@settings(max_examples=60, deadline=None)
@given(n=st.integers(4, 24), alpha=st.integers(1, 22),
       ratio=st.floats(0.01, 0.99))
def test_pipeline_strictly_beats_sync_when_fetch_hides(n, alpha, ratio):
    """Acceptance property: with m>0, β>=2 and per-layer fetch < per-layer
    compute, the pipeline reports strictly less stall than the
    synchronous (no-overlap) model — warm AND cold."""
    m = alpha + 2
    if m > n:
        return
    plan = _uniform(n, alpha, m)
    t_c, t_f = 1.0, ratio
    sync_stall = sync_step_time(plan, t_c, t_f) - n * t_c   # == m * t_f
    for cold in (False, True):
        timing = simulate_decode_step(plan, t_c, t_f, cold=cold)
        assert timing.bubble_time < sync_stall
        assert timing.total < sync_step_time(plan, t_c, t_f)


@settings(max_examples=40, deadline=None)
@given(n=st.integers(4, 20), alpha=st.integers(1, 18),
       ratio=st.floats(0.05, 5.0))
def test_cold_start_never_faster_than_steady_state(n, alpha, ratio):
    m = alpha + 2
    if m > n:
        return
    plan = _uniform(n, alpha, m)
    warm = simulate_decode_step(plan, 1.0, ratio)
    cold = simulate_decode_step(plan, 1.0, ratio, cold=True)
    assert cold.bubble_time >= warm.bubble_time - 1e-9


@settings(max_examples=40, deadline=None)
@given(n=st.integers(5, 20), alpha=st.integers(1, 18),
       ratio=st.floats(0.1, 2.0))
def test_uniform_selection_no_worse_than_contiguous(n, alpha, ratio):
    """Paper §5.4 through the event model: the uniform-interval layout
    never bubbles more than the contiguous strawman at equal m."""
    m = alpha + 2
    if m >= n:
        return
    uni = _uniform(n, alpha, m)
    contig = RemapPlan(n, alpha, m, tuple(range(m)), tuple(range(m, n)))
    bu = simulate_decode_step(uni, 1.0, ratio).bubble_time
    bc = simulate_decode_step(contig, 1.0, ratio).bubble_time
    assert bu <= bc + 1e-9


def test_pipeline_feasibility_tracks_closed_form():
    """Deep in feasible / infeasible territory the event model agrees with
    eqs. 4/5; the paper's n=40 example threshold survives the refactor."""
    for n in (8, 16, 40):
        for alpha in (1, 2, n // 4):
            assert tpl.choose_m_pipeline(n, alpha, 1.0, 0.01) \
                == ls.choose_m(n, alpha, 1.0, 0.01)
            assert tpl.choose_m_pipeline(n, alpha, 1.0, 100.0) == 0
    assert tpl.max_alpha_pipeline(40, 1.0, 1.0) == ls.max_alpha(40, 1.0, 1.0)
    with pytest.raises(ValueError):
        tpl.make_plan_pipeline(8, 6, 0.01, 1.0)


def test_link_bound_pipeline_matches_serial_chain():
    """When the link is the bottleneck the round degenerates to the fetch
    chain: total ~= m * t_fetch (the old scalar's t_stream term)."""
    plan = _uniform(8, 2, 4)
    timing = simulate_decode_step(plan, 0.01, 1.0)
    assert timing.total == pytest.approx(4 * 1.0, rel=0.05)


# -------------------------------------------------------------- prefill fix
def test_prefill_time_honours_resident_fraction():
    """Satellite: a remapped model's prefill reads only resident params
    from HBM; in the HBM-bound regime the charge must drop with α."""
    pm = PerfModel(ARCHS["granite-3-8b"], GH200)
    full = pm.prefill_time(1)                      # HBM-bound at 1 token
    half = pm.prefill_time(1, resident_fraction=0.5)
    assert half < full
    # the streamed cycling layers ride the host link: a slow enough link
    # dominates via max()
    streamed = pm.prefill_time(1, resident_fraction=0.5,
                               streamed_bytes=int(GH200.host_link_bw))
    assert streamed == pytest.approx(1.0)


# ----------------------------------------------------- PlanDrain state machine
def test_plan_drain_interim_consistency_and_accounting():
    old = _uniform(8, 1, 3)     # cycle {0, 2, 5}
    new = _uniform(8, 2, 4)     # cycle {0, 2, 4, 6}
    d = PlanDrain(old, new, 100)
    assert d.to_load == [5] and d.transition_bytes == 100
    interim = d.current_plan
    # pending layer stays cycling; drops are immediate
    assert 5 in interim.cycle_layers
    assert set(interim.cycle_layers) == {0, 2, 4, 5, 6}
    assert set(interim.cycle_layers) | set(interim.resident_layers) \
        == set(range(8))
    assert not set(interim.cycle_layers) & set(interim.resident_layers)
    used, completed = d.advance(60)
    assert (used, completed) == (60, []) and d.remaining_bytes == 40
    used, completed = d.advance(60)                # only 40 still owed
    assert (used, completed) == (40, [5]) and d.done
    assert d.current_plan == new


def test_plan_drain_degenerate_transitions():
    n = 6
    ident = identity_plan(n)
    remap = _uniform(n, 1, 3)
    # identity -> remap: drops only, nothing to load
    assert PlanDrain(ident, remap, 100).done
    # remap -> identity: every cycling layer must come home
    d = PlanDrain(remap, ident, 100)
    assert d.transition_bytes == 300
    used, completed = d.advance(float("inf"))
    assert used == 300 and completed == list(remap.cycle_layers) and d.done


# ------------------------------------------- split/merge degenerate round-trips
def _blocks(n, key=0):
    k = jax.random.PRNGKey(key)
    return ({"w": jax.random.normal(k, (n, 3, 3)),
             "b": jax.random.normal(k, (n, 3))},)


@pytest.mark.parametrize("n,plan_fn", [
    (6, lambda n: identity_plan(n)),                            # all-resident
    (6, lambda n: RemapPlan(n, n - 2, n, tuple(range(n)), ())), # all-cycle
    (1, lambda n: identity_plan(n)),                            # single, res
    (1, lambda n: RemapPlan(1, 0, 1, (0,), ())),                # single, cyc
    (5, lambda n: _uniform(n, 1, 3)),                           # mixed odd n
])
def test_split_merge_roundtrip_degenerate(n, plan_fn):
    blocks = _blocks(n)
    plan = plan_fn(n)
    res, cyc, maps = split_blocks(blocks, plan)
    assert {a.sharding.memory_kind for a in jax.tree.leaves(cyc)} \
        <= {"pinned_host"}
    fetch = make_fetch(res, cyc, maps)
    for r in range(n):
        got = fetch(jnp.asarray(r))
        for key in ("w", "b"):
            assert got[0][key].sharding.memory_kind == "device"
            assert float(
                jnp.abs(got[0][key] - blocks[0][key][r]).max()) == 0.0


# --------------------------------------------- TransferEngine async apply
def test_transfer_engine_submit_advance_drain_accounting():
    n, lb = 8, 64
    eng = TransferEngine()
    blocks = _blocks(n)
    eng.register("m", blocks, lb)
    # remap from identity: drops only — completes at submit
    remap = _uniform(n, 2, 4)
    eng.submit_plan("m", remap)
    assert not eng.pending and eng.plans["m"] == remap
    assert eng.stats.remap_drops_bytes == 2 * lb
    assert eng.stats.drain_bytes == 0
    # revert to identity: every cycling layer drains back
    eng.submit_plan("m", identity_plan(n))
    assert eng.pending_bytes("m") == 4 * lb
    assert eng.stats.revert_bytes == 2 * lb     # donation-level debt (Δα)
    # mid-drain: interim plan keeps pending layers cycling and fetch_for
    # still reaches every layer with the right values
    interim = eng.plans["m"]
    assert set(interim.cycle_layers) == set(remap.cycle_layers)
    fetch = eng.fetch_for("m")
    for r in range(n):
        got = fetch(jnp.asarray(r))
        assert float(jnp.abs(got[0]["w"] - blocks[0]["w"][r]).max()) == 0.0
    # drain one unit per call, bytes accounted exactly
    moved = 0
    while eng.pending:
        moved += eng.advance("m", lb)
        fetch = eng.fetch_for("m")
        for r in range(n):
            got = fetch(jnp.asarray(r))
            assert float(
                jnp.abs(got[0]["w"] - blocks[0]["w"][r]).max()) == 0.0
    assert moved == 4 * lb and eng.stats.drain_bytes == 4 * lb
    assert eng.plans["m"] == identity_plan(n)
    assert eng.advance("m", lb) == 0            # nothing pending


def test_transfer_engine_resubmit_mid_drain():
    n, lb = 8, 100
    eng = TransferEngine()
    eng.register("m", _blocks(n), lb)
    eng.apply_plan("m", _uniform(n, 3, 5))      # sync path still works
    assert not eng.pending
    eng.submit_plan("m", identity_plan(n))      # 5 layers owed
    eng.advance("m", 2 * lb)                    # 2 home, 3 pending
    eng.submit_plan("m", _uniform(n, 1, 3))     # retarget mid-drain
    # loads still owed = interim cycling layers that are resident in the
    # new target; everything stays a valid partition throughout
    p = eng.plans["m"]
    assert set(p.cycle_layers) | set(p.resident_layers) == set(range(n))
    eng.advance("m", float("inf"))
    assert eng.plans["m"] == _uniform(n, 1, 3) and not eng.pending


# --------------------------------------- engine/simulator bubble agreement
def test_engine_and_simulator_agree_on_bubble_accounting():
    """Both runtimes resolve the same plan through the same event model
    with identically derived inputs: the engine's note_decode_step and
    the simulator's decode_step_timing must charge the same bubble."""
    pm = PerfModel(ARCHS["granite-3-8b"], GH200)
    n = pm.repeats
    plan = _uniform(n, 4, 6)
    batch, ctx = 16, 1024.0
    # simulator side
    sim_timing = pm.decode_step_timing(batch, ctx, plan)
    # engine side: the shared input derivation ServingEngine._decode
    # feeds TransferEngine.note_decode_step
    t_c_layer, t_f_layer = pm.pipeline_inputs(batch, ctx, plan)
    eng = TransferEngine()
    eng.register("m", _blocks(4), pm.unit_bytes)
    eng.plans["m"] = plan                       # inject: timing-only check
    eng._cold.pop("m", None)                    # warm, like the sim's steady
    eng_timing = eng.note_decode_step("m", t_c_layer, t_f_layer)
    assert eng_timing.bubble_time == pytest.approx(sim_timing.bubble_time)
    assert eng_timing.total == pytest.approx(sim_timing.total)
    assert eng.stats.bubble_time_s == pytest.approx(sim_timing.bubble_time)
    assert eng.stats.decode_time_s == pytest.approx(sim_timing.total)


def test_incremental_apply_first_step_cheaper_than_sync():
    """Acceptance: the first decode step after a tier switch no longer
    pays the full plan transfer — a reversion keeps the old (warm,
    feasible) schedule while layers come home, undercutting the
    synchronous cold step + transition stall."""
    pm = PerfModel(ARCHS["granite-3-8b"], GH200)
    n = pm.repeats
    for alpha in (4, 8):
        old = tpl.make_plan_pipeline(n, alpha, 1.0, 1e-9)
        new = tpl.make_plan_pipeline(n, alpha - 1, 1.0, 1e-9)
        drain = PlanDrain(old, new, pm.unit_bytes)
        assert drain.transition_bytes > 0
        assert drain.current_plan == old       # reversion: no early drops
        sync_first = pm.decode_step_timing(64, 1024.0, new, cold=True).total \
            + drain.transition_bytes / GH200.host_link_bw
        incr_first = pm.decode_step_timing(64, 1024.0, old).total
        assert incr_first < sync_first


def test_simulator_bubble_metrics_and_drains():
    """End-to-end: a pressured single-tenant run produces remap decisions,
    the metrics carry the pipeline's bubble accounting, and incremental vs
    sync apply preserve the workload's completion."""
    from benchmarks.common import frac, run_sim, trace_for
    from repro.serving.simulator import SimTenantConfig

    def tenants():
        return {"granite-3-8b": SimTenantConfig(
            ARCHS["granite-3-8b"], 64, frac("granite-3-8b", 0.75))}

    tn = tenants()
    trace = trace_for(tn, "sharegpt", 20.0, duration=6.0)
    n_req = len(trace)
    met_i, sim_i = run_sim(tenants(), list(trace), "mirage",
                           scheduler="temporal", hw=GH200)
    assert sim_i.controller.decisions_log      # pressure reached
    assert met_i.bubble_time == sim_i.bubble_time_s
    assert 0.0 <= met_i.bubble_fraction <= 1.0
    assert sim_i.decode_time_s > 0.0
    trace2 = trace_for(tenants(), "sharegpt", 20.0, duration=6.0)
    met_s, sim_s = run_sim(tenants(), trace2, "mirage",
                           scheduler="temporal", hw=GH200,
                           incremental_apply=False)
    assert len(sim_i.finished) == len(sim_s.finished) == n_req
    assert not sim_s._drains                   # sync never leaves residue
