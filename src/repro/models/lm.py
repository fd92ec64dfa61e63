"""Decoder-only LM over a repeating pattern of heterogeneous blocks.

One module covers dense (llama/granite/phi3/danube), MoE (kimi/moonshot),
hybrid (jamba), SSM (xlstm) and VLM (llava: patch-embedding prefix) archs.

Layer stacking: the per-layer block kinds form a repeating *pattern* of
period p (p=1 homogeneous, p=8 jamba/xlstm); parameters are stored stacked
over the R = num_layers / p repeats so the forward pass is a single
``lax.scan`` whose body unrolls the p pattern positions. This keeps HLO size
independent of depth and gives parameter streaming (MIRAGE) a natural
remap unit: one repeat (= one layer for p=1 archs).

Decode uses an *index scan* (params fetched by dynamic index) so the same
code path supports MIRAGE's split resident/host parameter stacks via a
pluggable ``fetch`` function.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import with_sharding_constraint
from repro.models.blocks import (
    Attention, SwiGLU, MoE, Mamba, MLSTM, SLSTM, rms_norm, _einsum,
)
from repro.models.common import (
    LayerFetch, Spec, dtype_of, is_spec, stack_specs, tree_init,
)


@dataclasses.dataclass(frozen=True)
class LayerDef:
    mixer: str   # attn | mamba | mlstm | slstm
    ffn: str     # dense | moe | none


MIXERS = {"attn": Attention(), "mamba": Mamba(), "mlstm": MLSTM(), "slstm": SLSTM()}
_SWIGLU = SwiGLU()
_MOE = MoE()


def layer_defs(cfg: ModelConfig) -> List[LayerDef]:
    defs = []
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind.startswith("attn"):
            mixer = "attn"
        elif cfg.ssm is not None and cfg.ssm.kind == "mamba":
            mixer = "mamba"
        elif cfg.ssm is not None and cfg.ssm.slstm_period and \
                (i % cfg.ssm.slstm_period) == cfg.ssm.slstm_period - 1:
            mixer = "slstm"
        else:
            mixer = "mlstm"
        if kind.endswith("_moe"):
            ffn = "moe"
        elif cfg.d_ff:
            ffn = "dense"
        else:
            ffn = "none"
        defs.append(LayerDef(mixer, ffn))
    return defs


def block_pattern(cfg: ModelConfig) -> Tuple[List[LayerDef], int]:
    """(pattern, repeats): smallest period p with defs[i] == defs[i % p]."""
    defs = layer_defs(cfg)
    n = len(defs)
    for p in range(1, n + 1):
        if n % p == 0 and all(defs[i] == defs[i % p] for i in range(n)):
            return defs[:p], n // p
    return defs, 1


def _layer_specs(ld: LayerDef, cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    specs: Dict[str, Any] = {
        "norm1": Spec((d,), ("norm",), jnp.float32, "ones"),
        "mixer": MIXERS[ld.mixer].specs(cfg),
    }
    if ld.ffn != "none":
        specs["norm2"] = Spec((d,), ("norm",), jnp.float32, "ones")
        specs["ffn"] = (_MOE if ld.ffn == "moe" else _SWIGLU).specs(cfg)
    return specs


class LM:
    """Functional decoder-only LM; all methods are pure."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.pattern, self.repeats = block_pattern(cfg)

    # ------------------------------------------------------------------ specs
    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        dt = dtype_of(cfg)
        s: Dict[str, Any] = {
            "embed": Spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          dt, fan_in=cfg.d_model),
            "final_norm": Spec((cfg.d_model,), ("norm",), jnp.float32, "ones"),
            "blocks": tuple(
                stack_specs(_layer_specs(ld, cfg), self.repeats)
                for ld in self.pattern),
        }
        if not cfg.tie_embeddings:
            s["out"] = Spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"),
                            dt, fan_in=cfg.d_model)
        return s

    def init(self, key) -> Dict[str, Any]:
        return tree_init(self.specs(), key)

    # --------------------------------------------------------------- embed/out
    def embed(self, params, tokens, prefix_embeds=None):
        """tokens [B, St] (+ optional prefix [B, P, D]) -> x [B, S, D]."""
        x = params["embed"][tokens].astype(dtype_of(self.cfg))
        x = x * (self.cfg.d_model ** 0.5)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        return with_sharding_constraint(x, ("batch", None, None))

    def _out_w(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["out"]

    def logits_last(self, params, x_last):
        """x_last [B, D] -> [B, V]."""
        h = rms_norm(x_last, params["final_norm"], self.cfg.norm_eps)
        return _einsum("bd,dv->bv", h, self._out_w(params))

    def loss(self, params, x, targets, mask, chunk: int = 512):
        """Chunked CE so [B,S,V] logits never materialize.
        x [B,S,D]; targets/mask [B,S]. Returns (loss, aux dict)."""
        cfg = self.cfg
        b, s, d = x.shape
        h = rms_norm(x, params["final_norm"], cfg.norm_eps)
        w = self._out_w(params)
        chunk = min(chunk, s)
        while s % chunk:
            chunk -= 1
        n = s // chunk
        hs = jnp.moveaxis(h.reshape(b, n, chunk, d), 1, 0)
        ts = jnp.moveaxis(targets.reshape(b, n, chunk), 1, 0)
        ms = jnp.moveaxis(mask.reshape(b, n, chunk), 1, 0)

        def body(acc, xs):
            hc, tc, mc = xs
            logits = _einsum("bcd,dv->bcv", hc, w)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
            nll = (lse - gold) * mc
            return (acc[0] + nll.sum(), acc[1] + mc.sum()), None

        (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (hs, ts, ms))
        return tot / jnp.maximum(cnt, 1.0)

    # ------------------------------------------------------------ seq forward
    def fwd_seq(self, params, x, ctx, remat_policy: Optional[str] = None,
                collect_cache: bool = False,
                fetch: Optional[Callable[[jax.Array], Any]] = None):
        """x [B,S,D] -> (x, aux_loss, caches tuple-of-stacked | None).

        Scans ``params['blocks']``, or, with ``fetch``, the repeat index
        with ``fetch(r)`` supplying each repeat's layer params (serving
        with split resident/host stacks; see ``decode_step``)."""
        cfg = self.cfg

        inner_remat = bool(remat_policy and remat_policy != "none") \
            and len(self.pattern) > 1

        def apply_layer(ld, p, x):
            mixer = MIXERS[ld.mixer]
            h, cache = mixer.fwd_seq(
                p["mixer"], rms_norm(x, p["norm1"], cfg.norm_eps), ctx, cfg)
            x = x + h
            a = jnp.zeros((), jnp.float32)
            if ld.ffn != "none":
                hin = rms_norm(x, p["norm2"], cfg.norm_eps)
                if ld.ffn == "moe":
                    h2, a = _MOE(p["ffn"], hin, cfg)
                else:
                    h2 = _SWIGLU(p["ffn"], hin)
                x = x + h2
            return with_sharding_constraint(x, ("batch", None, None)), cache, a

        def body(carry, xs):
            x, aux = carry
            layer_params = xs if fetch is None else fetch(xs)
            caches = []
            for ld, p in zip(self.pattern, layer_params):
                fn = partial(apply_layer, ld)
                if inner_remat:
                    # nested remat: during the outer body's backward, only
                    # ONE pattern position's residuals are live at a time
                    # (jamba/xlstm 8-layer bodies otherwise hold all eight).
                    fn = jax.checkpoint(fn, static_argnums=())
                x, cache, a = fn(p, x)
                aux = aux + a
                caches.append(cache)
            return (x, aux), tuple(caches) if collect_cache else None

        if remat_policy and remat_policy != "none":
            if remat_policy == "full":
                body = jax.checkpoint(body)
            else:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.checkpoint_dots)
        xs = params["blocks"] if fetch is None else jnp.arange(self.repeats)
        (x, aux), caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs)
        return x, aux, caches

    # ------------------------------------------------------- decode state mgmt
    def decode_state_specs(self, batch: int, max_context: int) -> Dict[str, Any]:
        cfg = self.cfg
        st: Dict[str, Any] = {
            "pos": Spec((batch,), ("batch",), jnp.int32, "zeros"),
            "blocks": tuple(
                stack_specs(
                    {"mixer": MIXERS[ld.mixer].init_state(cfg, batch, max_context)},
                    self.repeats)
                for ld in self.pattern),
        }
        if cfg.sliding_window:
            w = min(max_context, cfg.sliding_window)
            st["kv_pos"] = Spec((batch, w), ("batch", "kv_seq"), jnp.int32, "neg_ones")
        return st

    def init_decode_state(self, batch: int, max_context: int):
        return jax.tree.map(
            lambda s: s.materialize(None), self.decode_state_specs(batch, max_context),
            is_leaf=is_spec)

    def _cache_len(self, max_context: int) -> int:
        cfg = self.cfg
        return min(max_context, cfg.sliding_window) if cfg.sliding_window else max_context

    def state_from_prefill(self, caches, positions_end, max_context: int):
        """Build decode state from fwd_seq caches (stacked [R, ...])."""
        cfg = self.cfg
        blocks = []
        for ld, cache in zip(self.pattern, caches):
            mixer = MIXERS[ld.mixer]
            if ld.mixer == "attn":
                conv = jax.vmap(
                    lambda c: mixer.seq_cache_to_state(cfg, c, max_context))
                blocks.append({"mixer": conv(cache)})
            else:
                blocks.append({"mixer": cache})
        st = {"pos": positions_end.astype(jnp.int32), "blocks": tuple(blocks)}
        if cfg.sliding_window:
            w = self._cache_len(max_context)
            s = positions_end[0]  # uniform prefill length
            idx = jnp.arange(w)
            kv_pos = jnp.where(
                idx[None, :] < positions_end[:, None],
                idx[None, :], -1).astype(jnp.int32)
            # ring layout when prefill longer than window: slot t%w holds t
            def ring(pe):
                base = jnp.maximum(pe - w, 0)
                tok = base + (idx - base % w) % w
                return jnp.where(tok < pe, tok, -1).astype(jnp.int32)
            kv_pos = jnp.where(
                (positions_end > w)[:, None], jax.vmap(ring)(positions_end), kv_pos)
            st["kv_pos"] = kv_pos
        return st

    def _decode_shared(self, state, max_context: int):
        cfg = self.cfg
        pos = state["pos"]
        b = pos.shape[0]
        s_c = self._cache_len(max_context)
        if cfg.sliding_window:
            slot = pos % s_c
            kv_pos = jax.vmap(lambda kp, sl, p: kp.at[sl].set(p))(
                state["kv_pos"], slot, pos)
            kv_valid = kv_pos >= 0
        else:
            slot = jnp.minimum(pos, s_c - 1)
            kv_pos = jnp.broadcast_to(
                jnp.arange(s_c, dtype=jnp.int32)[None], (b, s_c))
            kv_valid = kv_pos <= pos[:, None]
        return {"pos": pos, "slot": slot, "kv_pos": kv_pos, "kv_valid": kv_valid}

    # ------------------------------------------------------------- decode step
    def decode_step(
        self,
        params,
        state,
        tokens,                      # [B] int32
        max_context: int,
        fetch: Optional[Callable[[jax.Array], Any]] = None,
        extra_shared: Optional[dict] = None,
    ):
        """One token for every sequence. Returns (logits [B,V], new_state).

        ``fetch(r)`` returns the layer-params tuple for repeat r; default
        fetches by dynamic index from ``params['blocks']`` — MIRAGE passes a
        fetch that conds between resident (device) and remapped (host) stacks.
        """
        cfg = self.cfg
        x = self.embed(params, tokens[:, None])[:, 0]
        shared = self._decode_shared(state, max_context)
        if extra_shared:
            shared = {**shared, **extra_shared}

        if fetch is None:
            fetch = LayerFetch.stacked(params["blocks"])

        def body(x, xs):
            state_r, r = xs
            layer_params = fetch(r)
            new_states = []
            for ld, p, st in zip(self.pattern, layer_params, state_r):
                mixer = MIXERS[ld.mixer]
                h, new_st = mixer.fwd_dec(
                    p["mixer"], rms_norm(x, p["norm1"], cfg.norm_eps),
                    st["mixer"], shared, cfg)
                x = x + h
                if ld.ffn != "none":
                    hin = rms_norm(x, p["norm2"], cfg.norm_eps)
                    if ld.ffn == "moe":
                        h2, _ = _MOE(p["ffn"], hin, cfg)
                    else:
                        h2 = _SWIGLU(p["ffn"], hin)
                    x = x + h2
                new_states.append({"mixer": new_st})
            return x, tuple(new_states)

        x, new_blocks = jax.lax.scan(
            body, x, (state["blocks"], jnp.arange(self.repeats)))
        logits = self.logits_last(params, x)
        new_state = {"pos": state["pos"] + 1, "blocks": new_blocks}
        if cfg.sliding_window:
            new_state["kv_pos"] = shared["kv_pos"]
        return logits, new_state

    # ------------------------------------------------------- paged decode
    def decode_step_paged(self, params, state, tokens, fetch=None):
        """Decode against the elastic paged KV pool (vAttention-style data
        plane; kernels/paged_attention on TPU, jnp oracle on CPU).

        ``state``: pool_k/pool_v [R, P, page, Hkv, hd] (P grows when MIRAGE
        donates parameter segments), page_table [B, N] int32, ctx [B] int32
        (tokens already in each sequence's cache). Pure-attention stacks
        only (SWA/SSM tenants use the dense ring/recurrent state path).
        ``fetch`` (a ``LayerFetch``, default ``params['blocks']``) scans the
        layers.
        """
        cfg = self.cfg
        assert all(ld.mixer == "attn" for ld in self.pattern), \
            "paged decode supports attention stacks"
        from repro.kernels.paged_attention.ops import paged_decode_attention
        from repro.models.blocks import rope
        x = self.embed(params, tokens[:, None])[:, 0]
        pos = state["ctx"]                          # write position
        page = state["pool_k"].shape[2]
        pg = jnp.take_along_axis(
            state["page_table"], (pos // page)[:, None], axis=1)[:, 0]
        off = pos % page

        if fetch is None:
            fetch = LayerFetch.stacked(params["blocks"])

        def layer(layer_params, x, pool_k, pool_v):
            (p,) = layer_params
            attn = MIXERS["attn"]
            h = rms_norm(x, p["norm1"], cfg.norm_eps)
            q, k_new, v_new = attn._qkv(p["mixer"], h, cfg)
            q = rope(q, pos, cfg.rope_theta)
            k_new = rope(k_new, pos, cfg.rope_theta)
            pool_k = pool_k.at[pg, off].set(k_new)
            pool_v = pool_v.at[pg, off].set(v_new)
            out = paged_decode_attention(
                q, pool_k, pool_v, state["page_table"], pos + 1,
                window=cfg.sliding_window)
            y = _einsum("bhk,hkd->bd", out, p["mixer"]["wo"]).astype(x.dtype)
            x = x + y
            if self.pattern[0].ffn == "dense":
                x = x + _SWIGLU(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps))
            elif self.pattern[0].ffn == "moe":
                h2, _ = _MOE(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
                x = x + h2
            return x, (pool_k, pool_v)

        x, (pk, pv) = fetch.scan(layer, x, (state["pool_k"], state["pool_v"]))
        logits = self.logits_last(params, x)
        new_state = dict(state, pool_k=pk, pool_v=pv, ctx=pos + 1)
        return logits, new_state

    def prefill_chunk_paged(self, params, state, slot, tokens, start,
                            fetch=None, prefix_embeds=None):
        """One chunked-prefill step for batch row ``slot`` against the paged
        pool: run the transformer over ``tokens`` [S] at absolute positions
        ``start + arange(S)``, scattering each layer's K/V into the slot's
        pages *before* attending, so the chunk queries see the previously
        prefilled context (including CoW-shared prefix pages) plus the
        in-chunk causal block through one paged-context attention op.

        Returns (last_logits [V], new_state). ``state["ctx"][slot]`` is
        DEAD state while a slot is mid-prefill — the batched decode step
        bumps every row's cursor (and scatters a garbage row through the
        slot's pages, overwritten by the next chunk before it can become
        visible) — so all scatter positions and masks here derive from the
        ``start`` argument, never from ctx, and ctx is reset absolutely to
        ``start + S`` on exit.
        """
        cfg = self.cfg
        assert all(ld.mixer == "attn" for ld in self.pattern), \
            "paged chunked prefill supports attention stacks"
        from repro.kernels.paged_attention.ops import paged_prefill_attention
        from repro.models.blocks import rope
        x = self.embed(params, tokens[None, :], prefix_embeds)   # [1, S, D]
        s = x.shape[1]
        pos = start + jnp.arange(s, dtype=jnp.int32)             # [S]
        page = state["pool_k"].shape[2]
        pt_row = state["page_table"][slot]                       # [N]
        pg = pt_row[pos // page]                                 # [S]
        off = pos % page
        ctx_end = jnp.full((1,), start + s, jnp.int32)

        if fetch is None:
            fetch = LayerFetch.stacked(params["blocks"])

        def layer(layer_params, x, pool_k, pool_v):
            (p,) = layer_params
            attn = MIXERS["attn"]
            h = rms_norm(x, p["norm1"], cfg.norm_eps)
            q, k_new, v_new = attn._qkv(p["mixer"], h, cfg)      # [1,S,H,hd]
            q = rope(q, pos[None], cfg.rope_theta)
            k_new = rope(k_new, pos[None], cfg.rope_theta)
            pool_k = pool_k.at[pg, off].set(k_new[0])
            pool_v = pool_v.at[pg, off].set(v_new[0])
            out = paged_prefill_attention(
                q, pool_k, pool_v, pt_row[None],
                jnp.full((1,), start, jnp.int32), ctx_end,
                window=cfg.sliding_window)
            y = _einsum("bshk,hkd->bsd", out, p["mixer"]["wo"]).astype(x.dtype)
            x = x + y
            if self.pattern[0].ffn == "dense":
                x = x + _SWIGLU(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps))
            elif self.pattern[0].ffn == "moe":
                h2, _ = _MOE(p["ffn"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
                x = x + h2
            return x, (pool_k, pool_v)

        x, (pk, pv) = fetch.scan(layer, x, (state["pool_k"], state["pool_v"]))
        logits = self.logits_last(params, x[:, -1])
        new_state = dict(
            state, pool_k=pk, pool_v=pv,
            ctx=state["ctx"].at[slot].set(start + s))
        return logits[0], new_state

    def paged_state_from_prefill(self, caches, lengths, page_tables,
                                 num_pages: int, page_size: int,
                                 pool_k=None, pool_v=None):
        """Scatter dense prefill K/V caches into pool pages (into fresh
        zero pools, or into existing shared pools when given).
        caches: stacked [R, B, S, Hkv, hd]; page_tables [B, N]."""
        cfg = self.cfg
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        r, b, s, _, _ = caches[0]["k"].shape
        n = page_tables.shape[1]
        dt = caches[0]["k"].dtype
        if pool_k is None:
            pool_k = jnp.zeros((r, num_pages, page_size, hkv, hd), dt)
            pool_v = jnp.zeros((r, num_pages, page_size, hkv, hd), dt)
        # token t of sequence b lives at (page_tables[b, t//page], t%page).
        # Intended for batch=1 admissions (engine path): padded page-table
        # entries beyond a sequence's own pages must not appear.
        s_pad = -(-s // page_size) * page_size
        def scatter(pool, kv):
            kvp = jnp.pad(kv, ((0, 0), (0, 0), (0, s_pad - s), (0, 0), (0, 0)))
            kvp = kvp.reshape(r, b, s_pad // page_size, page_size, hkv, hd)
            pages = page_tables[:, :s_pad // page_size]        # [B, npg]
            return pool.at[:, pages].set(kvp)
        pool_k = scatter(pool_k, caches[0]["k"])
        pool_v = scatter(pool_v, caches[0]["v"])
        return {
            "pool_k": pool_k, "pool_v": pool_v,
            "page_table": page_tables.astype(jnp.int32),
            "ctx": lengths.astype(jnp.int32),
        }

    # --------------------------------------------------------------- prefill
    def prefill(self, params, tokens, max_context: int, prefix_embeds=None,
                lengths=None, fetch=None):
        """Returns (last_logits [B,V], decode_state)."""
        b, s_tok = tokens.shape
        x = self.embed(params, tokens, prefix_embeds)
        s = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        ctx = {"positions": positions}
        x, _aux, caches = self.fwd_seq(params, x, ctx, collect_cache=True,
                                       fetch=fetch)
        if lengths is None:
            lengths = jnp.full((b,), s, jnp.int32)
        last = jnp.clip(lengths - 1, 0, s - 1)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        logits = self.logits_last(params, x_last)
        state = self.state_from_prefill(caches, lengths, max_context)
        return logits, state
