"""Expert-granular remapping: the routing-driven residency test suite.

Four pillars:

1. **Residency fuzz** — under arbitrary routing skew, pressure/calm
   sequences and mid-drain retargets, every flattened expert unit is in
   exactly one of {resident, remapped, in_flight}, pinned hot experts are
   never victimized, and the pages reclaimed from donated experts match
   the allocator's elastic-page accounting after every decision
   (``execute_remap_decision`` against a real ``PagedKVAllocator``, the
   ``test_controller_fuzz`` pattern at expert grain).
2. **Differential decode** — the data-plane split/merge along the expert
   axis is bit-exact (tokens identical with remapping on/off when routed
   experts are resident; a victimized routed expert provably perturbs the
   output under ``absent='zero'``), and engine vs simulator charge the
   same bubbles for the same routed-slot fetch schedule.
3. **Config accessors** — ``bytes_for_layer`` / ``expert_bytes`` /
   ``active_params_per_token`` agree with ``param_count`` /
   ``active_param_count`` across the registry, including period>1 MoE
   interleaves (jamba).
4. **Transfer-pipeline edge cases** — single-expert plans, all-cold cold
   starts, and rotation-driven mid-drain retargets, across host-link
   tiers.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.configs.base import ModelConfig, MoEConfig
from repro.configs.registry import ARCHS
from repro.core import (
    ControllerConfig, ExpertPlan, ExpertRemapState, ExpertRoutingStats,
    MemoryInfo, MetadataStore, ModelInfo, PagedKVAllocator,
    RemappingController, TransferEngine, expert_plan_from_units,
    identity_expert_plan, merge_experts, min_circular_gap, residency_states,
    split_experts, step_fetch_plan,
)
from repro.core.expert_remap import EXPERT_PARAM_KEYS, expert_unit, unit_expert
from repro.core.transfer_pipeline import simulate_decode_step
from repro.models.blocks import MoE
from repro.models.common import tree_init
from repro.models.lm import LM
from repro.serving.engine import execute_remap_decision
from repro.serving.hw import GH200, HOST_LINKS
from repro.serving.perf_model import PerfModel
from repro.serving.simulator import Simulator, SimTenantConfig
from repro.serving.slo import SLOSpec
from repro.serving.traces import ExpertSkewSpec, ZipfRouting, expert_skew_trace


def _expert_tree(L, E, width=2):
    return {k: np.arange(L * E * width, dtype=np.float32).reshape(L, E, width)
            + i * 1000.0
            for i, k in enumerate(EXPERT_PARAM_KEYS)}


# ===========================================================================
# 1. residency fuzz
# ===========================================================================

def _assert_partition(te, name, L, E):
    res = te.expert_residency(name)
    sets = [res["resident"], res["remapped"], res["in_flight"]]
    assert set().union(*sets) == set(range(L * E))
    assert sum(len(s) for s in sets) == L * E  # pairwise disjoint
    return res


def _assert_pool(alloc, elastic, store, pages_per_unit):
    per = {m: 0 for m in elastic}
    for seg in alloc.segments:
        if seg.source in per:
            per[seg.source] += seg.num_pages
    assert per == elastic, (per, elastic)
    assert alloc.check_invariants() is None
    assert all(seg.end <= alloc.page_id_bound for seg in alloc.segments)
    expect = sum(m.remapped_alpha * pages_per_unit
                 for m in store.models.values())
    assert store.memory.elastic_kv_pages == expect


@settings(max_examples=25, deadline=None)
@given(
    L=st.integers(1, 4),
    E=st.sampled_from([4, 8]),
    k=st.integers(1, 2),
    steps=st.lists(
        st.tuples(st.booleans(),            # kv pressure?
                  st.floats(0.001, 5.0),    # step compute scale
                  st.floats(0.0, 1.0)),     # drain budget fraction
        min_size=1, max_size=40),
    policy=st.sampled_from(["mru", "lru"]),
    cap=st.floats(0.2, 1.0),
    pipeline_cap=st.booleans(),
    stride=st.integers(1, 3),
    seed=st.integers(0, 99),
)
def test_expert_residency_fuzz(L, E, k, steps, policy, cap, pipeline_cap,
                               stride, seed):
    _run_residency_fuzz(L, E, k, steps, policy, cap, pipeline_cap,
                        stride, seed)


def test_expert_residency_fuzz_deterministic():
    """Fixed-seed slice of the fuzz space, so the residency invariants run
    in tier-1 even where hypothesis is unavailable (the hypcompat shim
    skips ``@given`` tests there)."""
    rng = np.random.default_rng(0)
    for case, (policy, pcap) in enumerate(
            [("mru", True), ("lru", False), ("mru", False), ("lru", True)]):
        steps = [(bool(rng.integers(0, 2)), float(rng.uniform(0.001, 5.0)),
                  float(rng.random())) for _ in range(30)]
        _run_residency_fuzz(
            L=int(rng.integers(1, 5)), E=int(rng.choice([4, 8])),
            k=int(rng.integers(1, 3)), steps=steps, policy=policy,
            cap=float(rng.uniform(0.2, 1.0)), pipeline_cap=pcap,
            stride=int(rng.integers(1, 4)), seed=case)


def _run_residency_fuzz(L, E, k, steps, policy, cap, pipeline_cap,
                        stride, seed):
    name = "moe"
    expert_bytes, page_bytes = 2048, 1024
    pages_per_unit = expert_bytes // page_bytes
    store = MetadataStore(MemoryInfo(
        hbm_bytes=1 << 30, page_bytes=page_bytes, base_kv_pages=32))
    store.register(ModelInfo(name=name, num_layers=L * E,
                             layer_bytes=expert_bytes,
                             max_remap_fraction=cap))
    es = ExpertRemapState(L, E, k, expert_bytes,
                          units_per_decision=stride)
    ctrl = RemappingController(
        store,
        ControllerConfig(victim_policy=policy, pipeline_cap=pipeline_cap,
                         revert_patience=2, reversion_hysteresis=0.05),
        {name: 0.5}, expert_state={name: es})
    te = TransferEngine()
    te.register_experts(name, _expert_tree(L, E), expert_bytes, L, E)
    alloc = PagedKVAllocator(32, page_size=1)
    elastic = {name: 0}
    rng = np.random.default_rng(seed)
    live_rids: list = []

    for pressure, tc, budget_frac in steps:
        # routing signal: random skew, occasionally rotated
        es.observe(rng.random((L, E)) * 10.0)
        es.note_step_compute(tc)
        store.mark_active([name])
        # request churn pins donated segments sometimes (the undo path)
        if rng.integers(0, 3) < 2 and alloc.free_pages > 0:
            rid = f"r{rng.integers(1 << 30)}"
            if alloc.allocate(rid, int(rng.integers(1, 5))) is not None:
                live_rids.append(rid)
        elif live_rids:
            alloc.free(live_rids.pop(int(rng.integers(len(live_rids)))))
        store.note_kv_usage(store.memory.total_pages if pressure else 0)

        decisions = ctrl.step(kv_pressure=pressure, t_compute={name: tc})
        for d in decisions:
            m = store.models[name]
            ep = d.expert_plan
            assert ep is not None
            assert ep.num_moe_layers == L and ep.num_experts == E
            # pinned hot experts are never victimized
            for l in range(L):
                assert set(ep.pinned[l]) <= set(ep.resident[l])
                assert not set(ep.pinned[l]) & set(ep.remapped[l])
            # per-layer residency floor holds
            for l in range(L):
                assert len(ep.resident[l]) >= min(
                    max(es.pin_k, es.min_resident), E)
            # flattened plan mirrors the residency plan exactly
            assert ep.alpha == d.new_alpha == m.remapped_alpha
            assert d.plan.alpha == ep.alpha and d.plan.m == ep.alpha
            got = sorted(d.plan.cycle_layers + d.plan.resident_layers)
            assert got == list(range(L * E))
            for u in d.plan.cycle_layers:
                l, e = unit_expert(u, E)
                assert e not in ep.resident[l]
            if d.reverted:
                assert not pressure
            # reclaimed bytes must land in the allocator's elastic pages
            outcome = execute_remap_decision(alloc, store, elastic, d)
            if outcome == "undone":
                assert d.reverted
                assert store.models[name].remapped_alpha == d.new_alpha + 1
            else:
                te.submit_expert_plan(name, ep)
                assert te.expert_plans[name].alpha == \
                    store.models[name].remapped_alpha
            _assert_pool(alloc, elastic, store, pages_per_unit)
            _assert_partition(te, name, L, E)

        # drain a random slice of any pending restores
        pend = te.expert_pending.get(name)
        if pend is not None:
            te.advance_experts(
                name, int(budget_frac * pend.remaining_bytes))
        res = _assert_partition(te, name, L, E)
        # once no drain is pending, the live plan IS the target: pinned
        # experts sit in the resident set, never remapped. (Mid-drain the
        # fixed interim plan may still stream an already-restored pinned
        # expert — it hops to resident in one step when the drain lands.)
        if name not in te.expert_pending:
            live = te.expert_plans[name]
            for l, pins in enumerate(live.pinned):
                for e in pins:
                    assert expert_unit(l, e, E) in res["resident"]

    # drain everything: partition collapses to the final target
    te.advance_experts(name, float("inf"))
    res = _assert_partition(te, name, L, E)
    assert not res["in_flight"]
    assert res["remapped"] == set(te.expert_plans[name]
                                  .to_remap_plan().cycle_layers)


# ===========================================================================
# 2a. differential decode (bit-identity through the expert data plane)
# ===========================================================================

def _moe_cfg(L=4, E=8, k=2):
    return ModelConfig(
        "tmoe", "moe", L, 64, 4, 4, 0, 128,
        moe=MoEConfig(num_experts=E, top_k=k, d_expert=64,
                      capacity_factor=8.0, min_capacity=64),
        dtype="float32")


def _greedy_decode(lm, params, prompt, steps, max_context=32):
    logits, state = lm.prefill(params, prompt, max_context)
    toks = []
    for _ in range(steps):
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(np.asarray(nxt))
        logits, state = lm.decode_step(params, state, nxt, max_context)
    return np.stack(toks), np.asarray(logits)


def _with_ffn(params, ffn):
    blk = dict(params["blocks"][0])
    blk["ffn"] = jax.tree.map(jnp.asarray, ffn)
    return {**params, "blocks": (blk,)}


def test_decode_bit_identical_split_merge_roundtrip():
    """Remapping on vs off: splitting the expert stacks into resident +
    cold trees and merging them back (``absent='host'`` — cold experts
    stream from the host copy) must reproduce the dense decode
    bit-for-bit, token by token."""
    cfg = _moe_cfg()
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 128)
    toks_dense, logits_dense = _greedy_decode(lm, params, prompt, 6)

    ffn = jax.tree.map(np.asarray, params["blocks"][0]["ffn"])
    resident = [0, 1, 2, 4]                      # donate experts 3,5,6,7
    res_tree, cold_tree, maps = split_experts(ffn, resident, expert_axis=1)
    # the cold tree holds exactly the donated experts' weights
    assert list(maps["cold_ids"]) == [3, 5, 6, 7]
    merged = merge_experts(res_tree, cold_tree, maps, expert_axis=1)
    for key in EXPERT_PARAM_KEYS:
        assert np.array_equal(merged[key], ffn[key])

    toks_remap, logits_remap = _greedy_decode(
        lm, _with_ffn(params, merged), prompt, 6)
    assert np.array_equal(toks_dense, toks_remap)
    assert np.array_equal(logits_dense, logits_remap)


def test_decode_perturbed_when_routed_expert_victimized():
    """Negative control: zero every expert of MoE layer 0 (``absent='zero'``
    engine semantics). Some routed expert is then cold for every token, so
    the decode output MUST differ from dense — proving the bit-identity
    test above has teeth."""
    cfg = _moe_cfg()
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 128)
    _, logits_dense = _greedy_decode(lm, params, prompt, 4)

    te = TransferEngine()
    ffn = jax.tree.map(np.asarray, params["blocks"][0]["ffn"])
    te.register_experts("m", ffn, cfg.expert_bytes(4), cfg.num_layers,
                        cfg.moe.num_experts)
    # victimize ALL of layer 0's experts; other layers stay dense
    units = [expert_unit(0, e, cfg.moe.num_experts)
             for e in range(cfg.moe.num_experts)]
    te.submit_expert_plan("m", expert_plan_from_units(
        cfg.num_layers, cfg.moe.num_experts, units))
    zeroed = te.expert_params_for("m", absent="zero")
    for key in EXPERT_PARAM_KEYS:
        assert not np.any(zeroed[key][0])          # layer 0 gone
        assert np.array_equal(zeroed[key][1:], ffn[key][1:])
    _, logits_zero = _greedy_decode(lm, _with_ffn(params, zeroed), prompt, 4)
    assert not np.array_equal(logits_dense, logits_zero)

    # 'host' semantics under the same heavy plan stay bit-exact
    hosted = te.expert_params_for("m", absent="host")
    _, logits_host = _greedy_decode(lm, _with_ffn(params, hosted), prompt, 4)
    assert np.array_equal(logits_dense, logits_host)


def test_moe_return_stats_counts():
    """``MoE(..., return_stats=True)`` routing counts equal the brute-force
    top-k histogram — the raw signal ``ExpertRoutingStats`` smooths."""
    cfg = _moe_cfg(L=1, E=8, k=2)
    moe = MoE()
    p = tree_init(moe.specs(cfg), jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 10, 64)) * 0.5
    out, aux, counts = moe(p, x, cfg, return_stats=True)
    out2, aux2 = moe(p, x, cfg)
    assert np.array_equal(np.asarray(out), np.asarray(out2))
    xf = np.asarray(x).reshape(-1, 64)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(xf @ np.asarray(p["router"])), -1))
    order = np.argsort(-probs, axis=-1)[:, :cfg.moe.top_k]
    ref = np.bincount(order.reshape(-1), minlength=cfg.moe.num_experts)
    assert np.array_equal(np.asarray(counts).astype(int), ref)
    assert int(np.asarray(counts).sum()) == xf.shape[0] * cfg.moe.top_k


def test_routing_stats_ema_and_pins():
    stats = ExpertRoutingStats(2, 4, decay=0.5)
    # cold start: uniform loads, every expert equally hot
    assert np.allclose(stats.loads(), 0.25)
    for _ in range(8):
        stats.observe(np.array([[8.0, 1.0, 1.0, 0.0],
                                [0.0, 1.0, 1.0, 8.0]]))
    assert stats.hot_sets(1) == ((0,), (3,))
    # the hot set follows a rotation once the EMA forgets
    for _ in range(16):
        stats.observe(np.array([[0.0, 8.0, 1.0, 1.0],
                                [1.0, 1.0, 8.0, 0.0]]))
    assert stats.hot_sets(1) == ((1,), (2,))


def test_feasible_alpha_matches_bruteforce():
    """The prefix-sum feasibility bound equals the definitional one:
    largest α whose expected cold-fetch time (over the α coldest eligible
    experts) hides under ``hide_fraction`` of step compute."""
    rng = np.random.default_rng(7)
    es = ExpertRemapState(3, 8, 2, 4096, batch_hint=4)
    es.observe(rng.random((3, 8)) * 5.0)
    es.note_step_compute(0.01)
    t_fetch = 0.002
    budget = es.hide_fraction * 0.01

    def brute(alpha):
        plan = es.plan_for_alpha(alpha)
        return float(es.expected_cold_fetches(plan).sum() * t_fetch)

    want = max((a for a in range(es.max_alpha() + 1)
                if brute(a) <= budget), default=0)
    assert es.feasible_alpha(t_fetch) == want
    # free when the link is infinitely fast; clamped by pins otherwise
    assert es.feasible_alpha(0.0) == es.max_alpha()
    # monotone in compute headroom
    es.note_step_compute(1.0)
    assert es.feasible_alpha(t_fetch) >= want


def test_expert_plan_flatten_roundtrip():
    ep = expert_plan_from_units(2, 4, [1, 3, 6], pinned=[(0,), (0,)])
    flat = ep.to_remap_plan()
    assert flat.n == 8 and flat.alpha == flat.m == 3
    assert flat.cycle_layers == (1, 3, 6)
    back = expert_plan_from_units(2, 4, flat.cycle_layers,
                                  pinned=ep.pinned)
    assert back == ep
    assert ep.freed_bytes(100) == 300
    with pytest.raises(ValueError):
        ExpertPlan(1, 4, ((0, 1),), ((2,),))      # pinned must be resident


# ===========================================================================
# 3. config accessors
# ===========================================================================

MOE_NAMES = ["moonshot-v1-16b-a3b", "jamba-v0.1-52b", "kimi-k2-1t-a32b"]


@pytest.mark.parametrize("name", MOE_NAMES + ["llama3-8b"])
def test_bytes_for_layer_sums_to_param_count(name):
    cfg = ARCHS[name]
    b = cfg.dtype_bytes
    embed = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    total = sum(cfg.bytes_for_layer(i) for i in range(cfg.num_layers))
    assert total + embed * b == cfg.param_count() * b
    assert cfg.active_params_per_token() == cfg.active_param_count()


@pytest.mark.parametrize("name", MOE_NAMES)
def test_expert_bytes_and_moe_layer_count(name):
    cfg = ARCHS[name]
    b = cfg.dtype_bytes
    assert cfg.expert_bytes(b) == 3 * cfg.d_model * cfg.moe.d_expert * b
    n_moe = sum(1 for k in cfg.layer_kinds() if "moe" in k)
    assert cfg.num_moe_layers() == n_moe > 0
    # an MoE layer out-weighs a dense layer by its expert stack; each
    # expert's share is exactly expert_bytes
    for i, kind in enumerate(cfg.layer_kinds()):
        if "moe" in kind:
            assert cfg.bytes_for_layer(i) > \
                cfg.moe.num_experts * cfg.expert_bytes(b)
            break


def test_jamba_period_interleave():
    cfg = ARCHS["jamba-v0.1-52b"]
    assert cfg.moe.period > 1
    assert 0 < cfg.num_moe_layers() < cfg.num_layers
    assert cfg.num_moe_layers() == cfg.num_layers // cfg.moe.period
    kinds = cfg.layer_kinds()
    moe_layers = [i for i, k in enumerate(kinds) if "moe" in k]
    dense_layers = [i for i, k in enumerate(kinds) if "moe" not in k]
    b = cfg.dtype_bytes
    # only the MoE positions carry the expert stack
    assert min(cfg.bytes_for_layer(i) for i in moe_layers) > \
        cfg.moe.num_experts * cfg.expert_bytes(b)


def test_dense_model_has_no_expert_unit():
    cfg = ARCHS["llama3-8b"]
    assert cfg.expert_bytes() == 0
    assert cfg.num_moe_layers() == 0


# ===========================================================================
# 4. transfer-pipeline expert edge cases (per host-link tier)
# ===========================================================================

@pytest.mark.parametrize("link", sorted(HOST_LINKS))
def test_single_expert_fetch(link):
    """One cold expert in one layer: m=1, no double-buffer partner, the
    whole fetch must still complete within the step."""
    eb = 16 << 20
    t_f = eb / HOST_LINKS[link]
    plan = step_fetch_plan(8, 2, [1] + [0] * 7)
    assert plan.n == 16 and plan.m == 1 and plan.alpha == 0
    timing = simulate_decode_step(plan, t_f / 4, t_f, cold=True)
    assert timing.total >= 16 * (t_f / 4)
    assert timing.total < 16 * (t_f / 4) + 2 * t_f + 1e-12
    warm = simulate_decode_step(plan, t_f / 4, t_f, cold=False)
    assert warm.total <= timing.total


@pytest.mark.parametrize("link", sorted(HOST_LINKS))
def test_all_cold_cold_start(link):
    """Cold start with every routed slot cold (first step after a tier
    switch on a fully-donated model): the pipeline degenerates toward
    serial fetches; slot spacing still bounds the damage."""
    L, K = 6, 2
    eb = 16 << 20
    t_f = eb / HOST_LINKS[link]
    plan = step_fetch_plan(L, K, [K] * L)
    assert plan.m == L * K and plan.alpha == plan.m - 2
    for l in range(L):
        in_layer = [u - l * K for u in plan.cycle_layers
                    if l * K <= u < (l + 1) * K]
        assert in_layer == list(range(K))
    cold = simulate_decode_step(plan, t_f / 8, t_f, cold=True)
    warm = simulate_decode_step(plan, t_f / 8, t_f, cold=False)
    assert len(cold.misses) >= 1
    assert cold.total >= warm.total
    assert cold.total <= plan.n * (t_f / 8) + plan.m * t_f + 1e-9


def test_step_fetch_plan_spacing_and_clamp():
    rng = np.random.default_rng(11)
    for _ in range(50):
        L = int(rng.integers(1, 9))
        K = int(rng.integers(1, 5))
        counts = rng.integers(0, K + 3, size=L)     # over-asking clamps to K
        plan = step_fetch_plan(L, K, counts)
        assert plan.n == L * K
        assert plan.m == int(np.minimum(counts, K).sum())
        for l in range(L):
            slots = [u - l * K for u in plan.cycle_layers
                     if l * K <= u < (l + 1) * K]
            c = min(int(counts[l]), K)
            assert len(slots) == c
            if c >= 2:
                assert min_circular_gap(tuple(slots), K) >= K // c - 1


@pytest.mark.parametrize("link", sorted(HOST_LINKS))
def test_rotation_mid_drain_retarget(link):
    """Hot-set rotation arrives while a reversion is still draining: the
    engine retargets from the interim plan, pending loads re-queue only if
    the new target still wants them resident, and the residency partition
    stays exact at every point."""
    L, E = 2, 8
    eb = 1 << 20
    te = TransferEngine()
    te.register_experts("m", _expert_tree(L, E), eb, L, E)
    donate_a = [expert_unit(l, e, E) for l in range(L) for e in (4, 5, 6, 7)]
    te.submit_expert_plan("m", expert_plan_from_units(L, E, donate_a))
    assert "m" not in te.expert_pending        # donations are free drops
    res = _assert_partition(te, "m", L, E)
    assert res["remapped"] == set(donate_a)

    # revert half of them; drain only one expert's bytes...
    donate_half = [u for u in donate_a if unit_expert(u, E)[1] in (6, 7)]
    te.submit_expert_plan("m", expert_plan_from_units(L, E, donate_half))
    pend = te.expert_pending["m"]
    assert set(pend.to_load) == {u for u in donate_a if u not in donate_half}
    te.advance_experts("m", eb)
    res = _assert_partition(te, "m", L, E)
    assert len(res["in_flight"]) == len(donate_a) - len(donate_half) - 1

    # ...then the rotation flips the hot set: victims become (0,1,2,3)
    donate_b = [expert_unit(l, e, E) for l in range(L) for e in (0, 1, 2, 3)]
    te.submit_expert_plan("m", expert_plan_from_units(L, E, donate_b))
    res = _assert_partition(te, "m", L, E)
    drain = te.expert_pending.get("m")
    if drain is not None:
        assert set(drain.to_load) <= set(
            drain.target.resident_layers)
        te.advance_experts("m", float("inf"))
    res = _assert_partition(te, "m", L, E)
    assert res["remapped"] == set(donate_b)
    assert not res["in_flight"]


# ===========================================================================
# full-sim smoke on the expert-load-skew trace
# ===========================================================================

def test_expert_skew_sim_smoke():
    name = "moonshot-v1-16b-a3b"
    cfg = ARCHS[name]
    pm = PerfModel(cfg, GH200)
    reqs, routing = expert_skew_trace([ExpertSkewSpec(
        name, "sharegpt", 16.0, cfg.moe.num_experts, cfg.moe.top_k,
        duration=2.0, zipf_s=1.5, rotation_period=1.0)], seed=2)
    assert name in routing and len(reqs) > 0
    mem_frac = (pm.param_bytes + (1 << 28)) / GH200.hbm_bytes
    sim = Simulator(
        {name: SimTenantConfig(cfg, 64, mem_frac,
                               slo=SLOSpec(tbt_target=0.2, tier="latency"))},
        mode="mirage", pipeline_cap=False, max_remap_fraction=0.3,
        expert_granular=True, expert_routing=routing)
    m = sim.run(reqs)
    assert len(sim.finished) > 0
    assert math.isfinite(m.p99_tbt) and m.p99_tbt >= 0
    assert sim.bubble_time_s >= 0 and sim.decode_time_s > 0
    # expert-granular registration: the store's unit IS one expert
    info = sim.store.models[name]
    assert info.num_layers == cfg.num_moe_layers() * cfg.moe.num_experts
    assert info.layer_bytes == pm.expert_bytes
    # final residency partition over the live plan stays exact
    states = residency_states(sim._live_plan[name],
                              sim._drains.get(name))
    assert len(states) == info.num_layers
    assert set(states.values()) <= {"resident", "remapped", "in_flight"}


def test_zipf_routing_determinism_and_rotation():
    zr = ZipfRouting(8, 2, zipf_s=1.0, rotation_period=10.0)
    p0, p0b = zr.probs_at(0.0), zr.probs_at(9.9)
    assert np.array_equal(p0, p0b)           # static within a period
    p1 = zr.probs_at(10.1)
    assert not np.array_equal(p0, p1)        # rolled after rotation
    assert np.isclose(p0.sum(), 1.0) and np.isclose(p1.sum(), 1.0)
    assert np.isclose(zr.counts_at(0.0, 5).sum(), 5 * zr.top_k)
    rp = zr.routed_probability(0.0, 4)
    assert np.all((0 <= rp) & (rp <= 1))
    # identical arrivals across granularity modes: same seed, same trace
    spec = ExpertSkewSpec("m", "sharegpt", 4.0, 8, 2, duration=2.0)
    r1, _ = expert_skew_trace([spec], seed=5)
    r2, _ = expert_skew_trace([spec], seed=5)
    assert [(r.rid, r.arrival, r.prompt_len) for r in r1] == \
        [(r.rid, r.arrival, r.prompt_len) for r in r2]
