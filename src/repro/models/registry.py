"""Uniform Model facade over LM / EncDec + per-(arch x shape) input specs."""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed.sharding import sharding_for
from repro.models.common import (
    Spec, dtype_of, tree_abstract, tree_init, tree_shardings, is_spec,
)
from repro.models.encdec import EncDec
from repro.models.lm import LM

WHISPER_DECODER_LEN = 448   # decoder-side target length for train/prefill


class Model:
    """Dispatches to LM or EncDec; every method is a pure function of params."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.impl = EncDec(cfg) if cfg.is_encoder_decoder else LM(cfg)

    # ----------------------------------------------------------------- params
    def specs(self):
        return self.impl.specs()

    def init(self, key):
        return self.impl.init(key)

    def abstract_params(self, mesh=None, rules=None):
        return tree_abstract(self.specs(), mesh, rules)

    def param_shardings(self, mesh, rules=None, memory_kind=None):
        return tree_shardings(self.specs(), mesh, rules, memory_kind)

    @property
    def repeats(self) -> int:
        return self.impl.repeats

    # ------------------------------------------------------------------ train
    def train_loss(self, params, batch, remat_policy: str = "dots_saveable"):
        cfg = self.cfg
        if cfg.is_encoder_decoder:
            return self.impl.loss(
                params, batch["frames"], batch["tokens"],
                batch["targets"], batch["mask"])
        x = self.impl.embed(params, batch["tokens"], batch.get("patch_embeds"))
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        x, aux, _ = self.impl.fwd_seq(
            params, x, {"positions": positions}, remat_policy=remat_policy)
        loss = self.impl.loss(params, x, batch["targets"], batch["mask"])
        return loss + 0.01 * aux

    # ---------------------------------------------------------------- serving
    def prefill(self, params, batch, max_context: int, fetch=None):
        if self.cfg.is_encoder_decoder:
            return self.impl.prefill(
                params, batch["frames"], batch["tokens"], max_context,
                fetch=fetch)
        return self.impl.prefill(
            params, batch["tokens"], max_context,
            prefix_embeds=batch.get("patch_embeds"),
            lengths=batch.get("lengths"), fetch=fetch)

    def decode_step(self, params, state, tokens, max_context: int, fetch=None):
        return self.impl.decode_step(params, state, tokens, max_context, fetch=fetch)

    def decode_state_specs(self, batch: int, max_context: int):
        return self.impl.decode_state_specs(batch, max_context)

    def init_decode_state(self, batch: int, max_context: int):
        return self.impl.init_decode_state(batch, max_context)

    def abstract_decode_state(self, batch: int, max_context: int, mesh=None, rules=None):
        return tree_abstract(
            self.decode_state_specs(batch, max_context), mesh, rules)

    @staticmethod
    def insert_slot(state, slot: int, new_state):
        """Insert a batch=1 prefill state into batch slot ``slot``.

        Layout-aware: ``blocks`` leaves are stacked [R, B, ...] (batch is
        dim 1); every other state leaf is batch-major [B, ...].
        """
        out = {}
        for key, val in state.items():
            if key == "blocks":
                out[key] = jax.tree.map(
                    lambda d, s: d.at[:, slot].set(s[:, 0]),
                    val, new_state[key])
            else:
                out[key] = jax.tree.map(
                    lambda d, s: d.at[slot].set(s[0]), val, new_state[key])
        return out

    # ------------------------------------------------------------ input specs
    def input_spec_tree(self, shape: ShapeConfig) -> Dict[str, Spec]:
        """Spec tree for the model inputs of one (arch x shape) cell."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        dt = dtype_of(cfg)
        tok = lambda *sh: Spec(tuple(sh), ("batch",) + (None,) * (len(sh) - 1),
                               jnp.int32, "zeros")
        if shape.kind in ("train", "prefill"):
            if cfg.is_encoder_decoder:
                d = {
                    "frames": Spec((b, s, cfg.d_model), ("batch", "seq_cp", None), dt, "normal"),
                    "tokens": tok(b, WHISPER_DECODER_LEN),
                }
                if shape.kind == "train":
                    d["targets"] = tok(b, WHISPER_DECODER_LEN)
                    d["mask"] = Spec((b, WHISPER_DECODER_LEN), ("batch", None),
                                     jnp.float32, "ones")
                return d
            d = {}
            s_text = s
            if cfg.num_image_patches:
                p = min(cfg.num_image_patches, s - 1)
                s_text = s - p
                d["patch_embeds"] = Spec(
                    (b, p, cfg.d_model), ("batch", None, None), dt, "normal")
            d["tokens"] = tok(b, s_text)
            if shape.kind == "train":
                d["targets"] = tok(b, s)
                d["mask"] = Spec((b, s), ("batch", None), jnp.float32, "ones")
            return d
        # decode: one new token against a cache of length s
        return {"tokens": Spec((b,), ("batch",), jnp.int32, "zeros")}

    def abstract_inputs(self, shape: ShapeConfig, mesh=None, rules=None):
        return tree_abstract(self.input_spec_tree(shape), mesh, rules)

    def concrete_inputs(self, shape: ShapeConfig, key):
        """Small random concrete batch (for smoke tests on reduced configs)."""
        cfg = self.cfg
        tree = self.input_spec_tree(shape)

        def mk(k, spec: Spec):
            if spec.dtype == jnp.int32:
                return jax.random.randint(k, spec.shape, 0, max(cfg.vocab_size, 2))
            return spec.materialize(k)

        leaves, treedef = jax.tree.flatten(tree, is_leaf=is_spec)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [mk(k, s) for k, s in zip(keys, leaves)])


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
