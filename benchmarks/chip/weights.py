"""Random weights of a dense GQA decoder, made by the benchmark from the seed.

The benchmark owns the weights so that the reference never reads anything
the program made: the program gets one copy, and after the window the
reference makes the same weights again from the same seed.

Layout (the benchmark's own, all stacked over layers ``L``)::

    embed [V, D]   out [D, V]   final_norm [D]
    norm1, norm2 [L, D]
    wq [L, D, H, hd]   wk, wv [L, D, Hkv, hd]   wo [L, H, hd, D]
    w_gate, w_up [L, D, F]   w_down [L, F, D]

Matrices are normal with standard deviation 1/sqrt(fan-in), in bf16; norm
scales are 1 + 0.1 * normal, in float32. All leaves come from one jitted
call on the device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def shapes(arch: dict) -> dict:
    """Leaf name -> (shape, fan-in); ``arch`` uses the published keys."""
    d, f = arch["hidden_size"], arch["intermediate_size"]
    h, hkv = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd, v = arch["head_dim"], arch["vocab_size"]
    n = arch["num_hidden_layers"]
    out = {
        "embed": ((v, d), d), "final_norm": ((d,), 0),
        "norm1": ((n, d), 0), "norm2": ((n, d), 0),
        "wq": ((n, d, h, hd), d), "wk": ((n, d, hkv, hd), d),
        "wv": ((n, d, hkv, hd), d), "wo": ((n, h, hd, d), h * hd),
        "w_gate": ((n, d, f), d), "w_up": ((n, d, f), d),
        "w_down": ((n, f, d), f),
    }
    if not arch["tie_word_embeddings"]:
        out["out"] = ((d, v), d)
    return out


def base_key(seed: int, tenant_index: int):
    """A key from a seed of up to 64 bits."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, tenant_index)


@partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    out = {}
    for i, (name, shape, fan) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        if fan == 0:
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * fan ** -0.5).astype(jnp.bfloat16)
    return out


def make(arch: dict, seed: int, tenant_index: int) -> dict:
    spec = tuple((n, s, f) for n, (s, f) in sorted(shapes(arch).items()))
    return _make(base_key(seed, tenant_index), spec)


def to_program(w: dict) -> dict:
    """The same arrays in the program's parameter tree
    (``repro.models.lm.LM.specs``: one pattern position, stacked blocks)."""
    tree = {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "blocks": ({
            "norm1": w["norm1"], "norm2": w["norm2"],
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn": {"w_gate": w["w_gate"], "w_in": w["w_up"],
                    "w_out": w["w_down"]},
        },),
    }
    if "out" in w:
        tree["out"] = w["out"]
    return tree
