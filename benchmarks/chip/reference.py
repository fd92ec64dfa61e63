"""Plain reference of a dense GQA decoder (Llama / Mistral family), in jnp.

Float32 activations, float32 matrix products at ``highest`` precision,
causal attention with an optional sliding window, no cache, no kernels,
no batching tricks. It reads the benchmark's own weight layout
(``weights.py``) and imports nothing of the program.

Forward pass per layer (x is the residual stream)::

    h = rmsnorm(x) * norm1;  q, k, v = h @ wq, h @ wk, h @ wv
    q, k = rope(q), rope(k)              # rotate-half, theta = rope_theta
    x = x + softmax(q k^T / sqrt(hd) + mask) v @ wo      # GQA: head i -> kv head i // (H / Hkv)
    h = rmsnorm(x) * norm2;  x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down
    logits = (rmsnorm(x) * final_norm) @ out

One departure from the published Llama-family pass, taken from the
program so that the two compute the same function: token embeddings are
multiplied by sqrt(hidden_size).

The control is the same pass computed in float8 e4m3, the precision step
below the configuration's bfloat16: every matrix (and the embedding table)
rounded with one scale per output channel, and the activation operand of
every matrix product rounded with one scale per row (``fp8=True``);
products still accumulate in float32. The weights are rounded layer by
layer inside the pass, so the control needs no second copy of them.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8_MAX = 448.0


def _mm(subs, a, b, fp8=False):
    if fp8:
        a = fp8_round(a, tuple(range(1, a.ndim)))
    return jnp.einsum(subs, a, b.astype(jnp.float32), precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate-half convention."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def fp8_round(w, axis):
    """Round to float8 e4m3 with one scale per slice reduced over ``axis``
    (the contracting dimensions): absmax / 448."""
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return ((wf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s)


# contracting axes of each matrix as one layer sees it (inside the scan)
F8_AXES = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1), "w_gate": 0,
           "w_up": 0, "w_down": 0}


@partial(jax.jit, static_argnames=("arch_items", "fp8"))
def _hidden(w, tokens, length, arch_items, fp8=False):
    """tokens [S] (padded) -> final hidden states [S, D], float32."""
    a = dict(arch_items)
    eps, theta = a["rms_norm_eps"], a["rope_theta"]
    h_q, h_kv, hd = (a["num_attention_heads"], a["num_key_value_heads"],
                     a["head_dim"])
    window = a.get("sliding_window") or 0
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    x = w["embed"][tokens].astype(jnp.float32)
    if fp8:
        x = fp8_round(x, 1)
    x = x * math.sqrt(a["hidden_size"])
    g = h_q // h_kv

    mm = partial(_mm, fp8=fp8)

    def layer(x, p):
        if fp8:
            p = {k: fp8_round(v, F8_AXES[k]) if k in F8_AXES else v
                 for k, v in p.items()}
        h = _rms(x, p["norm1"], eps)
        q = _rope(mm("sd,dhk->shk", h, p["wq"]), pos, theta)
        k = _rope(mm("sd,dhk->shk", h, p["wk"]), pos, theta)
        v = mm("sd,dhk->shk", h, p["wv"])
        outs = []
        for b0 in range(0, s, Q_BLOCK):
            qb = q[b0:b0 + Q_BLOCK].reshape(-1, h_kv, g, hd)
            sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HIGHEST)
            sc = sc / math.sqrt(hd)
            qp = pos[b0:b0 + Q_BLOCK][:, None]
            ok = (pos[None, :] <= qp) & (pos[None, :] < length)
            if window:
                ok &= pos[None, :] > qp - window
            sc = jnp.where(ok[None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            ob = jnp.einsum("kgqs,skd->qkgd", pr, v, precision=HIGHEST)
            outs.append(ob.reshape(-1, h_q, hd))
        o = jnp.concatenate(outs, 0)
        x = x + mm("shk,hkd->sd", o, p["wo"])
        h = _rms(x, p["norm2"], eps)
        f = jax.nn.silu(mm("sd,df->sf", h, p["w_gate"])) \
            * mm("sd,df->sf", h, p["w_up"])
        return x + mm("sf,fd->sd", f, p["w_down"]), None

    layers = {k: w[k] for k in ("norm1", "norm2", "wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, layers)
    return _rms(x, w["final_norm"], eps)


@partial(jax.jit, static_argnames=("fp8",))
def _logits(w, h_at, fp8=False):
    out = w["out"] if "out" in w else w["embed"].T
    if fp8:
        out = fp8_round(out, 0)
    return _mm("nd,dv->nv", h_at, out, fp8)


def padded_length(n: int) -> int:
    """Sequences are padded to a power of two of at least ``Q_BLOCK``
    tokens, so that few shapes compile."""
    return max(Q_BLOCK, 1 << (n - 1).bit_length())


def logits(w: dict, arch: dict, tokens: np.ndarray, at: np.ndarray,
           fp8: bool = False):
    """float32 logits [len(at), V] at positions ``at`` of ``tokens``."""
    n = len(tokens)
    buf = np.zeros((padded_length(n),), np.int32)
    buf[:n] = tokens
    items = tuple(sorted((k, v) for k, v in arch.items()
                         if isinstance(v, (int, float, bool))))
    h = _hidden(w, jnp.asarray(buf), jnp.int32(n), items, fp8)
    return _logits(w, h[jnp.asarray(at)], fp8)


def served_gaps(w: dict, arch: dict, prompt, served) -> np.ndarray:
    """Per served token: the reference's best logit minus the served
    token's logit, at the position where it was served (teacher-forced
    over the prompt and the tokens served before it)."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    at = len(prompt) - 1 + np.arange(len(served))
    lg = np.asarray(logits(w, arch, seq, at))
    return lg.max(-1) - lg[np.arange(len(served)), served]


def control_gaps(w: dict, arch: dict, prompt, served) -> np.ndarray:
    """Per position of the same prompt and served tokens: the reference's
    best logit minus the reference's logit of the token that the control
    (the same pass in float8) puts first."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    at = len(prompt) - 1 + np.arange(len(served))
    ref = np.asarray(logits(w, arch, seq, at))
    top = np.asarray(logits(w, arch, seq, at, fp8=True)).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(served)), top]
