"""Compile-only checks for one TPU v5e at h2o-danube-3-4b's published
widths: the paged Pallas kernels and the engine's remapped decode and
chunked-prefill steps, compiled for a described (not attached) v5e chip.
Nothing executes, so these say nothing about results or times; they catch
what the chip's compiler would refuse (tiling, VMEM, memory spaces).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import PackedLayers, make_fetch, packed_spec
from repro.kernels.paged_attention import kernel as paged_kernel
from repro.kernels.paged_attention import ops as paged_ops
from repro.models import build_model
from repro.serving.engine import make_decode

DANUBE = get_arch("h2o-danube-3-4b")
BATCH, PAGE, CONTEXT, CHUNK, POOL_PAGES = 8, 16, 2048, 256, 512
HQ, HKV, HD = DANUBE.num_heads, DANUBE.num_kv_heads, DANUBE.resolved_head_dim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip can be written to the persistent cache
    # but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def on_chip(topo):
    dev = topo.devices[0]

    def sds(shape, dtype, memory_kind="device"):
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=SingleDeviceSharding(dev, memory_kind=memory_kind))
    return sds


@pytest.fixture
def kernels_on(monkeypatch):
    """The model code picks the Pallas kernels by ``default_backend()``,
    which is the CPU here: route the ops to the TPU kernels instead."""
    monkeypatch.setattr(
        paged_ops, "paged_decode_attention",
        lambda q, kp, vp, pt, cl, window=0:
        paged_kernel.paged_decode_attention(q, kp, vp, pt, cl, window=window))
    monkeypatch.setattr(
        paged_ops, "paged_prefill_attention",
        lambda q, kp, vp, pt, qs, cl, window=0:
        paged_kernel.paged_prefill_attention(q, kp, vp, pt, qs, cl,
                                             window=window))


def _pools(sds, layers=None):
    lead = () if layers is None else (layers,)
    shape = lead + (POOL_PAGES + 1, PAGE, HKV, HD)
    return sds(shape, jnp.bfloat16), sds(shape, jnp.bfloat16)


def _nbytes(tree):
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree.leaves(tree))


def test_paged_decode_kernel_compiles(on_chip):
    kp, vp = _pools(on_chip)
    compiled = paged_kernel.paged_decode_attention.lower(
        on_chip((BATCH, HQ, HD), jnp.bfloat16), kp, vp,
        on_chip((BATCH, CONTEXT // PAGE), jnp.int32),
        on_chip((BATCH,), jnp.int32),
        window=DANUBE.sliding_window).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_prefill_kernel_compiles(on_chip):
    kp, vp = _pools(on_chip)
    compiled = paged_kernel.paged_prefill_attention.lower(
        on_chip((1, CHUNK, HQ, HD), jnp.bfloat16), kp, vp,
        on_chip((1, CONTEXT // PAGE), jnp.int32),
        on_chip((1,), jnp.int32), on_chip((1,), jnp.int32),
        window=DANUBE.sliding_window).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _remapped_tenant(sds, layers=5, cycling=2):
    """Danube-width model cut to ``layers``, ``cycling`` of them held in
    pinned host memory: (model, params without blocks, split). By default
    the resident stack's 3 layers share no other dimension of the step."""
    cfg = dataclasses.replace(DANUBE, num_layers=layers)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = {k: sds(v.shape, v.dtype) for k, v in shapes.items()
              if k != "blocks"}

    resident = jax.tree.map(
        lambda a: sds((layers - cycling,) + a.shape[1:], a.dtype),
        shapes["blocks"])
    spec, words = packed_spec(shapes["blocks"])
    cycle = PackedLayers(
        sds((cycling, words // 128, 128), jnp.uint32, "pinned_host"), spec)
    split = (resident, cycle,
             {"is_resident": sds((layers,), jnp.bool_),
              "idx_in_stack": sds((layers,), jnp.int32)})
    return model, params, split


def _paged_state(sds, model):
    kp, vp = _pools(sds, layers=model.repeats)
    return {"pool_k": kp, "pool_v": vp,
            "page_table": sds((BATCH, CONTEXT // PAGE), jnp.int32),
            "ctx": sds((BATCH,), jnp.int32)}


def _stack_copies(hlo, resident):
    """``copy``/``copy-start`` ops in ``hlo`` whose result has the shape of
    a leaf of the ``resident`` stack: a re-layout of the whole stack."""
    stack = {tuple(a.shape) for a in jax.tree.leaves(resident)}
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) copy(?:-start)?\(", line)
        if m and any(tuple(int(d) for d in dims.split(",")) in stack
                     for dims in re.findall(r"\[([\d,]+)\]", m.group(1))):
            found.append(line.strip()[:120])
    return found


def test_remapped_decode_step_compiles(on_chip, kernels_on):
    model, params, split = _remapped_tenant(on_chip)
    state = _paged_state(on_chip, model)
    step = jax.jit(make_decode(model, paged=True, max_context=CONTEXT))
    lowered = step.lower(params, split, state, on_chip((BATCH,), jnp.int32))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # each branch of the fetch's cond runs the layer on the weights where
    # they lie: no copy of the resident stack into another layout
    assert _stack_copies(hlo, split[0]) == []
    # the names the benchmark's trace readers select the programs by
    assert "jit_step" in lowered.as_text().split("\n", 1)[0]
    assert "paged_decode" in hlo
    # the cycle layers' host->HBM copies carry the transfer engine's scope
    assert any("remap_fetch" in line for line in hlo.splitlines()
               if "metadata={" in line)
    # the cycle stack is read from host memory, not copied into HBM whole
    assert compiled.memory_analysis().host_argument_size_in_bytes \
        == _nbytes(split[1])


def test_identity_plan_decode_step_has_no_cond(on_chip, kernels_on):
    """Every layer resident: the fetch indexes one stack, with no branch."""
    model, params, split = _remapped_tenant(on_chip, cycling=0)
    step = jax.jit(make_decode(model, paged=True, max_context=CONTEXT))
    hlo = step.lower(params, split, _paged_state(on_chip, model),
                     on_chip((BATCH,), jnp.int32)).compile().as_text()
    assert "paged_decode" in hlo
    assert " conditional(" not in hlo


def test_remapped_prefill_chunk_compiles(on_chip, kernels_on):
    model, params, split = _remapped_tenant(on_chip)
    state = _paged_state(on_chip, model)

    def chunk(params, split, state, slot, tokens, start):
        return model.impl.prefill_chunk_paged(
            params, state, slot, tokens, start, fetch=make_fetch(*split))
    compiled = jax.jit(chunk).lower(
        params, split, state, on_chip((), jnp.int32),
        on_chip((CHUNK,), jnp.int32), on_chip((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _stack_copies(hlo, split[0]) == []
    assert compiled.memory_analysis().host_argument_size_in_bytes \
        == _nbytes(split[1])
