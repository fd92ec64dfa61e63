"""Remapping is a real memory layout: after a remap the cycled layers live
only in pinned host memory, the device parameter bytes drop by the layers
that left, and a donated layer buys the pages its bytes pay for."""
import gc

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, RuntimeConfig, scaled_config
from repro.models import build_model
from repro.serving import ServingEngine, TenantConfig
from repro.serving.traces import tiny_trace


def _engine(names, base_pages, reversion=True, seed=1234):
    """Engine whose tenants' params are referenced by nothing else."""
    tenants = {}
    for i, n in enumerate(names):
        cfg = scaled_config(ARCHS[n], num_layers=4)
        tenants[n] = TenantConfig(
            cfg, build_model(cfg).init(jax.random.PRNGKey(seed + i)),
            max_batch=4, max_context=32, paged=True)
    return ServingEngine(
        tenants, mode="mirage", base_kv_pages=base_pages, page_size=4,
        quantum_steps=4,
        runtime=RuntimeConfig(dynamic_reversion=reversion))


def _param_bytes(eng, name, kind):
    t = eng.tenants[name]
    resident, cycle, _ = eng.xfer.split[name]
    return sum(a.nbytes for a in jax.tree.leaves((t.params, resident, cycle))
               if a.sharding.memory_kind == kind)


def _run(eng):
    eng.submit(tiny_trace(list(eng.tenants), n_per_model=4, prompt_len=10,
                          max_new=8, vocab=256))
    eng.run(max_steps=800)
    eng.allocator.check_invariants()
    return {r.rid: list(r.generated) for r in eng.finished}


def test_remap_moves_cycled_layers_off_device():
    names = ["h2o-danube-3-4b", "granite-3-8b"]
    eng = _engine(names, base_pages=8, reversion=False)
    dev0 = {n: _param_bytes(eng, n, "device") for n in names}
    assert all(_param_bytes(eng, n, "pinned_host") == 0 for n in names)
    _run(eng)
    remapped = [n for n in names if eng.store.models[n].remapped_alpha]
    assert remapped, eng.events

    cycled = []
    for n in remapped:
        plan, lb = eng.xfer.plans[n], eng.store.models[n].layer_bytes
        alpha = eng.store.models[n].remapped_alpha
        # device parameter bytes dropped by every layer that left
        drop = dev0[n] - _param_bytes(eng, n, "device")
        assert drop == plan.m * lb >= alpha * lb
        # packed one buffer per layer, each leaf padded to whole tiles
        assert _param_bytes(eng, n, "pinned_host") >= plan.m * lb
        assert {a.sharding.memory_kind for a in jax.tree.leaves(
            eng.xfer.split[n][1])} == {"pinned_host"}
        # random weights identify their layer (norm scales start at ones
        # in every layer and identify nothing)
        cycled += [(a[c], a.shape[1:])
                   for a in jax.tree.leaves(eng.xfer.host_copy[n])
                   for c in plan.cycle_layers if np.ptp(a[c]) > 0]

    # no device-kind array anywhere holds a cycled layer
    assert cycled
    gc.collect()
    for arr in jax.live_arrays():
        if arr.sharding.memory_kind != "device":
            continue
        for layer, shape in cycled:
            if arr.shape[-len(shape):] != shape or arr.ndim > len(shape) + 1:
                continue
            rows = np.asarray(arr).reshape((-1,) + shape)
            assert not any(np.array_equal(r, layer) for r in rows)


def test_remap_outputs_match_unremapped_run():
    names = ["h2o-danube-3-4b", "granite-3-8b"]
    ref = _run(_engine(names, base_pages=64))
    eng = _engine(names, base_pages=8)
    out = _run(eng)
    assert any(k == "remap" for _, k, _d in eng.events)
    assert out == ref


def test_page_bytes_is_pool_bytes_per_page_id():
    eng = _engine(["h2o-danube-3-4b", "granite-3-8b"], base_pages=16)
    per_id = 0
    for t in eng.tenants.values():
        pk, pv = t.state["pool_k"], t.state["pool_v"]
        per_id += (pk.nbytes + pv.nbytes) // pk.shape[1]
    assert eng.store.memory.page_bytes == per_id


@pytest.mark.parametrize("alpha", [1, 2])
def test_donated_layers_grow_pool_by_their_bytes(alpha):
    eng = _engine(["h2o-danube-3-4b", "granite-3-8b"], base_pages=16)
    page_bytes = eng.store.memory.page_bytes
    for _ in range(alpha):
        eng.store.mark_active([])
        eng._memory_control(True)
    models = eng.store.models.values()
    assert sum(m.remapped_alpha for m in models) == alpha
    grown = eng.allocator.total_pages - 16
    assert grown == sum(m.remapped_alpha * (m.layer_bytes // page_bytes)
                        for m in models) > 0
    for t in eng.tenants.values():
        assert t.state["pool_k"].shape[1] == eng.allocator.page_id_bound + 1
