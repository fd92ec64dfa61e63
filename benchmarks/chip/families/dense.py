"""The dense GQA decoder family (Llama / Mistral): every layer is grouped
query attention followed by a SwiGLU FFN, all layers in one stacked scan.

Weights: ``weights.py``. Plain reference and its float8 control:
``reference.py``. FLOPs from shapes: ``flops.py``. Only
``program_config`` and ``to_program`` speak of the program.
"""
from __future__ import annotations

import dataclasses

import flops
import reference
import weights

# published config keys -> the program's ModelConfig fields
ARCH_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta", "sliding_window": "sliding_window",
    "tie_word_embeddings": "tie_embeddings", "torch_dtype": "dtype",
}

make = weights.make
to_program = weights.to_program
served_gaps = reference.served_gaps
control_gaps = reference.control_gaps
decode_step_flops = flops.decode_step_flops


def program_config(base, arch: dict):
    """``base`` (the program's ``ModelConfig`` of this model) with the
    published keys of ``arch`` in place; no sliding window is 0."""
    fields = {ARCH_FIELDS[k]: v for k, v in arch.items() if k in ARCH_FIELDS}
    fields["sliding_window"] = fields.get("sliding_window") or 0
    return dataclasses.replace(base, **fields)
