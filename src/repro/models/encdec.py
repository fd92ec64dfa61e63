"""Encoder-decoder backbone (whisper-medium). Conv/mel frontend is a STUB:
inputs are precomputed frame embeddings [B, S_enc, D] from ``input_specs``.

Positions use RoPE in place of whisper's sinusoidal/learned embeddings —
identical shapes and FLOPs, documented in DESIGN.md. Cross-attention KV is
computed once at prefill and immutable during decode (like parameters —
DESIGN.md notes it is therefore remappable, a beyond-paper extension).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import with_sharding_constraint
from repro.models.blocks import Attention, SwiGLU, rms_norm, _einsum
from repro.models.common import (
    LayerFetch, Spec, dtype_of, is_spec, stack_specs, tree_init,
)

_SELF = Attention()
_CROSS = Attention(cross=True)
_FFN = SwiGLU()


class EncDec:
    def __init__(self, cfg: ModelConfig):
        assert cfg.is_encoder_decoder
        self.cfg = cfg
        self.repeats = cfg.num_layers            # decoder depth
        self.pattern = ["encdec"]                # single-position pattern

    # ------------------------------------------------------------------ specs
    def _enc_layer(self) -> Dict[str, Any]:
        d = self.cfg.d_model
        return {
            "norm1": Spec((d,), ("norm",), jnp.float32, "ones"),
            "attn": _SELF.specs(self.cfg),
            "norm2": Spec((d,), ("norm",), jnp.float32, "ones"),
            "ffn": _FFN.specs(self.cfg),
        }

    def _dec_layer(self) -> Dict[str, Any]:
        d = self.cfg.d_model
        return {
            "norm1": Spec((d,), ("norm",), jnp.float32, "ones"),
            "self": _SELF.specs(self.cfg),
            "norm_x": Spec((d,), ("norm",), jnp.float32, "ones"),
            "cross": _CROSS.specs(self.cfg),
            "norm2": Spec((d,), ("norm",), jnp.float32, "ones"),
            "ffn": _FFN.specs(self.cfg),
        }

    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        dt = dtype_of(cfg)
        return {
            "embed": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          dt, fan_in=cfg.d_model),
            "out": Spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                        dt, fan_in=cfg.d_model),
            "enc_norm": Spec((cfg.d_model,), ("norm",), jnp.float32, "ones"),
            "final_norm": Spec((cfg.d_model,), ("norm",), jnp.float32, "ones"),
            "encoder": stack_specs(self._enc_layer(), cfg.num_encoder_layers),
            "blocks": (stack_specs(self._dec_layer(), cfg.num_layers),),
        }

    def init(self, key):
        return tree_init(self.specs(), key)

    # ---------------------------------------------------------------- encoder
    def encode(self, params, frames):
        """frames [B, S_enc, D] (stub frontend output) -> enc_out."""
        cfg = self.cfg
        b, s, _ = frames.shape
        x = frames.astype(dtype_of(cfg))
        x = with_sharding_constraint(x, ("batch", "seq_cp", None))
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = {"positions": positions, "bidirectional": True}

        def body(x, p):
            h, _ = _SELF.fwd_seq(p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), ctx, cfg)
            x = x + h
            x = x + _FFN(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps))
            return x, None

        x, _ = jax.lax.scan(body, x, params["encoder"])
        return rms_norm(x, params["enc_norm"], cfg.norm_eps)

    # ---------------------------------------------------------------- decoder
    def embed(self, params, tokens, prefix_embeds=None):
        x = params["embed"][tokens].astype(dtype_of(self.cfg))
        return x * (self.cfg.d_model ** 0.5)

    def dec_seq(self, params, x, enc_out, remat_policy=None, collect_cache=False,
                fetch=None):
        """Decoder stack over ``params['blocks']``, or over the repeat index
        with ``fetch(r)`` supplying each layer (see ``LM.fwd_seq``)."""
        cfg = self.cfg
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = {"positions": positions, "enc_out": enc_out}

        def body(x, xs):
            (p,) = xs if fetch is None else fetch(xs)
            h, self_cache = _SELF.fwd_seq(
                p["self"], rms_norm(x, p["norm1"], cfg.norm_eps), ctx, cfg)
            x = x + h
            h, cross_cache = _CROSS.fwd_seq(
                p["cross"], rms_norm(x, p["norm_x"], cfg.norm_eps), ctx, cfg)
            x = x + h
            x = x + _FFN(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps))
            cache = {"self": self_cache, "cross": cross_cache} if collect_cache else None
            return x, cache

        if remat_policy and remat_policy != "none":
            body = jax.checkpoint(body) if remat_policy == "full" else jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)
        xs = params["blocks"] if fetch is None else jnp.arange(self.repeats)
        x, caches = jax.lax.scan(body, x, xs)
        return x, caches

    def loss(self, params, frames, tokens, targets, mask):
        enc_out = self.encode(params, frames)
        x = self.embed(params, tokens)
        x, _ = self.dec_seq(params, x, enc_out, remat_policy="dots_saveable")
        h = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = _einsum("bsd,dv->bsv", h, params["out"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return ((lse - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)

    # ------------------------------------------------------------ decode path
    def decode_state_specs(self, batch: int, max_context: int) -> Dict[str, Any]:
        cfg = self.cfg
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = dtype_of(cfg)
        src = cfg.max_source_len
        per_layer = {
            "mixer": {
                "self": {
                    "k": Spec((batch, max_context, hkv, hd),
                              ("batch", "kv_seq", None, None), dt, "zeros"),
                    "v": Spec((batch, max_context, hkv, hd),
                              ("batch", "kv_seq", None, None), dt, "zeros"),
                },
                "cross": {
                    "k": Spec((batch, src, hkv, hd), ("batch", None, None, None), dt, "zeros"),
                    "v": Spec((batch, src, hkv, hd), ("batch", None, None, None), dt, "zeros"),
                },
            }
        }
        return {
            "pos": Spec((batch,), ("batch",), jnp.int32, "zeros"),
            "blocks": (stack_specs(per_layer, self.repeats),),
        }

    def init_decode_state(self, batch: int, max_context: int):
        return jax.tree.map(
            lambda s: s.materialize(None),
            self.decode_state_specs(batch, max_context), is_leaf=is_spec)

    def prefill(self, params, frames, tokens, max_context: int, fetch=None):
        """Encode source, teacher-force prompt tokens, build decode state."""
        cfg = self.cfg
        b, s = tokens.shape
        enc_out = self.encode(params, frames)
        x = self.embed(params, tokens)
        x, caches = self.dec_seq(params, x, enc_out, collect_cache=True,
                                 fetch=fetch)
        h = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
        logits = _einsum("bd,dv->bv", h, params["out"])
        pad = max_context - s
        self_c = jax.tree.map(
            lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
            caches["self"])
        # cross KV length is the encoder length; pad/trim to max_source_len
        def fit_src(a):
            s_enc = a.shape[2]
            if s_enc >= cfg.max_source_len:
                return a[:, :, :cfg.max_source_len]
            return jnp.pad(a, ((0, 0), (0, 0), (0, cfg.max_source_len - s_enc),
                               (0, 0), (0, 0)))
        cross_c = jax.tree.map(fit_src, caches["cross"])
        state = {
            "pos": jnp.full((b,), s, jnp.int32),
            "blocks": ({"mixer": {"self": self_c, "cross": cross_c}},),
            "src_len": jnp.full((b,), min(frames.shape[1], cfg.max_source_len),
                                jnp.int32),
        }
        return logits, state

    def decode_step(self, params, state, tokens, max_context: int,
                    fetch=None, src_len=None, cross_transform=None):
        """``cross_transform(cross_slice)``: optional per-layer hook applied
        to the sliced cross-KV inside the scan body — the cross-KV remapping
        extension passes an explicit host->device ``device_put`` here (the
        cross cache is immutable after prefill, so it streams like
        parameters)."""
        cfg = self.cfg
        b = tokens.shape[0]
        x = self.embed(params, tokens[:, None])[:, 0]
        pos = state["pos"]
        s_c = max_context
        kv_pos = jnp.broadcast_to(jnp.arange(s_c, dtype=jnp.int32)[None], (b, s_c))
        if src_len is None:
            src_len = state.get("src_len", jnp.full((b,), cfg.max_source_len, jnp.int32))
        cross_pos = jnp.broadcast_to(
            jnp.arange(cfg.max_source_len, dtype=jnp.int32)[None],
            (b, cfg.max_source_len))
        shared = {
            "pos": pos,
            "slot": jnp.minimum(pos, s_c - 1),
            "kv_pos": kv_pos,
            "kv_valid": kv_pos <= pos[:, None],
            "cross_pos": cross_pos,
            "cross_valid": cross_pos < src_len[:, None],
        }

        if fetch is None:
            fetch = LayerFetch.stacked(params["blocks"])

        def body(x, xs):
            state_r, r = xs
            (p,) = fetch(r)
            st = state_r[0]["mixer"]
            cross = st["cross"] if cross_transform is None \
                else cross_transform(st["cross"])
            h, new_self = _SELF.fwd_dec(
                p["self"], rms_norm(x, p["norm1"], cfg.norm_eps),
                st["self"], shared, cfg)
            x = x + h
            h, _ = _CROSS.fwd_dec(
                p["cross"], rms_norm(x, p["norm_x"], cfg.norm_eps),
                cross, shared, cfg)
            x = x + h
            x = x + _FFN(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps))
            return x, ({"mixer": {"self": new_self, "cross": st["cross"]}},)

        x, new_blocks = jax.lax.scan(
            body, x, (state["blocks"], jnp.arange(self.repeats)))
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _einsum("bd,dv->bv", h, params["out"])
        new_state = dict(state, pos=pos + 1, blocks=new_blocks)
        return logits, new_state
