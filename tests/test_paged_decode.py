"""Paged-pool decode data plane: identical outputs to the dense-cache path,
including with MIRAGE split-parameter fetch (kernel-backed on TPU; the jnp
oracle is exercised here)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, scaled_config
from repro.core import (
    RemapPlan, identity_plan, make_fetch, make_plan, split_blocks,
)
from repro.models import build_model

PAGE, NPAGES = 4, 24


@pytest.fixture(scope="module")
def setup():
    cfg = scaled_config(ARCHS["llama3-8b"], num_layers=4)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0, cfg.vocab_size)
    return cfg, m, params, prompt


def _dense_tokens(m, params, prompt, steps=6):
    lg, st = m.prefill(params, {"tokens": prompt}, 32)
    out = [int(jnp.argmax(lg[0]))]
    for _ in range(steps):
        lg, st = m.decode_step(params, st, jnp.asarray([out[-1]]), 32)
        out.append(int(jnp.argmax(lg[0])))
    return out


def _paged_state(m, params, prompt):
    lm = m.impl
    x = lm.embed(params, prompt)
    pos = jnp.broadcast_to(jnp.arange(prompt.shape[1])[None], prompt.shape)
    _, _, caches = lm.fwd_seq(params, x, {"positions": pos}, collect_cache=True)
    pt = jnp.asarray([[3, 4, 5, 6, 7]], jnp.int32)   # arbitrary page ids
    return lm.paged_state_from_prefill(
        caches, jnp.asarray([prompt.shape[1]]), pt, NPAGES, PAGE)


def test_paged_equals_dense(setup):
    cfg, m, params, prompt = setup
    dense = _dense_tokens(m, params, prompt)
    st = _paged_state(m, params, prompt)
    paged = [dense[0]]
    for _ in range(6):
        lg, st = m.impl.decode_step_paged(params, st, jnp.asarray([paged[-1]]))
        paged.append(int(jnp.argmax(lg[0])))
    assert paged == dense


def test_paged_with_remap_fetch(setup):
    cfg, m, params, prompt = setup
    dense = _dense_tokens(m, params, prompt)
    plan = make_plan(4, alpha=1, t_c=1.0, t_t=0.5)
    res, cyc, maps = split_blocks(params["blocks"], plan)
    fetch = make_fetch(res, cyc, maps)
    st = _paged_state(m, params, prompt)
    out = [dense[0]]
    for _ in range(6):
        lg, st = m.impl.decode_step_paged(
            params, st, jnp.asarray([out[-1]]), fetch=fetch)
        out.append(int(jnp.argmax(lg[0])))
    assert out == dense


def test_paged_pool_growth_preserves_content(setup):
    """Elastic segment growth (remap donates memory): pool padded with new
    pages, page table unchanged -> decode unaffected."""
    cfg, m, params, prompt = setup
    dense = _dense_tokens(m, params, prompt)
    st = _paged_state(m, params, prompt)
    out = [dense[0]]
    for i in range(6):
        if i == 3:   # grow the pool mid-stream (tier switch)
            st = dict(st,
                      pool_k=jnp.pad(st["pool_k"],
                                     ((0, 0), (0, 8), (0, 0), (0, 0), (0, 0))),
                      pool_v=jnp.pad(st["pool_v"],
                                     ((0, 0), (0, 8), (0, 0), (0, 0), (0, 0))))
        lg, st = m.impl.decode_step_paged(params, st, jnp.asarray([out[-1]]))
        out.append(int(jnp.argmax(lg[0])))
    assert out == dense


@pytest.mark.parametrize("kind", ["identity", "mixed", "all-cycle"])
def test_prefill_chunk_with_remap_fetch(setup, kind):
    """A prefill chunk through a ``make_fetch`` split leaves the logits and
    pools of the chunk run on the whole stack, whichever way the plan
    splits the layers."""
    cfg, m, params, prompt = setup
    n = m.repeats
    plan = {"identity": identity_plan(n),
            "mixed": make_plan(n, alpha=1, t_c=1.0, t_t=0.5),
            "all-cycle": RemapPlan(n, n, n, tuple(range(n)), ())}[kind]
    fetch = make_fetch(*split_blocks(params["blocks"], plan))
    st = _paged_state(m, params, prompt)
    chunk = jax.random.randint(jax.random.PRNGKey(2), (5,), 0, cfg.vocab_size)
    want, st_want = m.impl.prefill_chunk_paged(params, st, 0, chunk, 10)
    got, st_got = m.impl.prefill_chunk_paged(params, st, 0, chunk, 10,
                                             fetch=fetch)
    for key, a in [("logits", want)] + sorted(st_want.items()):
        b = got if key == "logits" else st_got[key]
        assert jnp.array_equal(a, b), key
