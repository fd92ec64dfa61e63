"""MIRAGE core: layer selection optimality (property), feasibility equations,
controller Algorithm-1 behavior, victim policies, transfer-engine split."""
from itertools import combinations

import jax
import jax.numpy as jnp
import pytest
from hypcompat import given, settings, st

from repro.core import (
    ControllerConfig, MemoryInfo, MetadataStore, ModelInfo,
    RemappingController, beta1_feasible, beta2_feasible, choose_m,
    make_plan, max_alpha, min_circular_gap, split_blocks,
    make_fetch, uniform_interval_layers, victim_order,
)


# ------------------------------------------------------------ layer selection
@settings(max_examples=40, deadline=None)
@given(n=st.integers(3, 12), m=st.integers(1, 12))
def test_uniform_interval_is_optimal(n, m):
    """Paper theorem: uniform interval maximizes the min circular gap.
    Verified against brute force for every (n, m)."""
    if m > n:
        return
    sel = uniform_interval_layers(n, m)
    assert len(sel) == m and len(set(sel)) == m
    best = max(min_circular_gap(c, n) for c in combinations(range(n), m))
    assert min_circular_gap(sel, n) == best


@settings(max_examples=40, deadline=None)
@given(n=st.integers(4, 64), alpha=st.integers(1, 63),
       t_c=st.floats(0.1, 10.0), t_t=st.floats(0.1, 10.0))
def test_choose_m_consistent_with_feasibility(n, alpha, t_c, t_t):
    if alpha >= n:
        return
    m = choose_m(n, alpha, t_c, t_t)
    if m == alpha + 1:
        assert beta1_feasible(n, alpha, t_c, t_t)
    elif m == alpha + 2:
        assert beta2_feasible(n, alpha, t_c, t_t)
        assert not beta1_feasible(n, alpha, t_c, t_t)
    else:
        assert m == 0
        assert not beta2_feasible(n, alpha, t_c, t_t)


def test_paper_example_alpha_threshold():
    """Paper §5.4: n=40, with T_T == T_c the dynamic scheme must switch to
    m=α+2 before α+1 becomes infeasible; eq.4 fails when α+1 > n-α-1."""
    n, t = 40, 1.0
    for alpha in range(1, 19):
        assert choose_m(n, alpha, t, t) == alpha + 1
    assert choose_m(n, 20, t, t) == 22      # eq4: 21 > 19 fails -> double
    assert max_alpha(n, t, t) == 38         # eq5: 40 <= 40 at alpha=38


def test_plan_slots_and_freed_bytes():
    plan = make_plan(8, alpha=2, t_c=1.0, t_t=1.0)
    assert plan.m == 3 and plan.beta == 1
    assert len(plan.cycle_layers) == 3
    assert len(plan.resident_layers) == 5
    assert plan.freed_layer_bytes(100) == 200


# ----------------------------------------------------------------- controller
def _store(names, layers=8, layer_bytes=4096, page_bytes=1024, base=64):
    store = MetadataStore(MemoryInfo(
        hbm_bytes=1 << 30, page_bytes=page_bytes, base_kv_pages=base))
    for i, n in enumerate(names):
        store.register(ModelInfo(name=n, num_layers=layers,
                                 layer_bytes=layer_bytes, priority=i))
    return store


def test_controller_remaps_inactive_first():
    store = _store(["A", "B", "C"])
    ctrl = RemappingController(store, ControllerConfig(),
                               {n: 0.1 for n in "ABC"})
    store.mark_active(["A"])
    t_c = {n: 1.0 for n in "ABC"}
    d = ctrl.step(kv_pressure=True, t_compute=t_c)
    assert d and d[0].model in ("B", "C")
    assert store.models[d[0].model].remapped_alpha == 1


def test_controller_respects_fraction_cap():
    store = _store(["A", "B"])
    for m in store.models.values():
        m.max_remap_fraction = 0.25        # cap = 2 of 8 units
    ctrl = RemappingController(store, ControllerConfig(),
                               {"A": 0.1, "B": 0.1})
    store.mark_active(["A"])
    t_c = {"A": 1.0, "B": 1.0}
    for _ in range(10):
        ctrl.step(kv_pressure=True, t_compute=t_c)
    assert store.models["B"].remapped_alpha <= 2
    # active model A capped by pipeline feasibility, not starved entirely
    assert store.models["A"].remapped_alpha <= 2


def test_dynamic_reversion_after_calm():
    store = _store(["A", "B"])
    cfg = ControllerConfig(revert_patience=2, reversion_hysteresis=0.1)
    ctrl = RemappingController(store, cfg, {"A": 0.1, "B": 0.1})
    store.mark_active(["A"])
    t_c = {"A": 1.0, "B": 1.0}
    ctrl.step(kv_pressure=True, t_compute=t_c)
    assert store.total_remapped_bytes() > 0
    store.note_kv_usage(0)                  # pool now free
    outs = []
    for _ in range(4):
        outs += ctrl.step(kv_pressure=False, t_compute=t_c)
    assert any(d.reverted for d in outs)
    assert store.total_remapped_bytes() == 0


def test_mru_vs_lru_order():
    store = _store(["A", "B", "C"], layers=8)
    for m in store.models.values():
        m.priority = 0                      # no scheduler priority
    store.mark_active(["A"]); store.mark_active(["B"]); store.mark_active(["C"])
    store.mark_active([])                   # all inactive now
    mru = [m.name for m in victim_order(store, "mru")]
    lru = [m.name for m in victim_order(store, "lru")]
    assert mru[0] == "C" and lru[0] == "A"
    assert mru[:3] == list(reversed(lru[:3]))


# ------------------------------------------------------------ transfer engine
def test_split_merge_roundtrip_and_fetch():
    key = jax.random.PRNGKey(0)
    blocks = ({"w": jax.random.normal(key, (8, 4, 4)),
               "b": jax.random.normal(key, (8, 4))},)
    plan = make_plan(8, alpha=3, t_c=1.0, t_t=0.5)
    res, cyc, maps = split_blocks(blocks, plan)
    assert res[0]["w"].shape[0] == len(plan.resident_layers)
    assert len(cyc) == len(plan.cycle_layers)
    assert {a.sharding.memory_kind for a in jax.tree.leaves(res)} == {"device"}
    assert {a.sharding.memory_kind for a in jax.tree.leaves(cyc)} \
        == {"pinned_host"}
    fetch = make_fetch(res, cyc, maps)
    for r in range(8):
        got = fetch(jnp.asarray(r))
        assert float(jnp.abs(got[0]["w"] - blocks[0]["w"][r]).max()) == 0.0
