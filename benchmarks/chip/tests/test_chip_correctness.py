"""The comparison that decides ``correct`` fails when it should: a run
with the timed path broken underneath (the harness's look for a chip
skipped, a tiny configuration on the CPU), and the float8 control."""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from calibrate import control_reading  # noqa: E402
from test_chip_rehearsal import tiny  # noqa: E402

SEED = 2 ** 32 + 99
ARGS = ["--seed", str(SEED), "--seconds", "6", "--trace", "0"]


def run_tiny(capsys, workload="danube-4b.chat-fits"):
    rc = harness.run(["--workload", workload] + ARGS,
                     t_process=time.perf_counter(),
                     check_device=False, scale=tiny)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def state_unchanged(step):
    """The decode step returns the state it was given: no KV written, no
    cursor advanced."""
    def broken(params, split, state, tokens):
        logits, _ = step(params, split, state, tokens)
        return logits, state
    return broken


def token_altered(step):
    """Every decoded token is the next vocabulary id after the right one."""
    def broken(params, split, state, tokens):
        logits, new_state = step(params, split, state, tokens)
        return jnp.roll(logits, 1, axis=-1), new_state
    return broken


def check_broken(fault, workload, monkeypatch, capsys):
    from repro.serving import engine
    make = engine.make_decode
    monkeypatch.setattr(engine, "make_decode",
                        lambda *a: fault(make(*a)))
    res = run_tiny(capsys, workload)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
def test_a_broken_decode_step_is_not_correct(fault, monkeypatch, capsys):
    check_broken(fault, "danube-4b.chat-fits", monkeypatch, capsys)


@pytest.mark.parametrize("fault", [state_unchanged, token_altered])
def test_a_broken_remapped_decode_step_is_not_correct(fault, monkeypatch,
                                                      capsys):
    """The same faults in a remap cell, whose decode streams donated layers
    from host memory."""
    check_broken(fault, "danube-4b-remap.chat-remap", monkeypatch, capsys)


def test_float8_control_reads_above_the_program(monkeypatch, capsys):
    """The control goes through the same decision as the run's reading and
    comes out not correct, while the program's run is correct. On the
    chip, at the cell's size, the control's readings set the upper end of
    the limit (PERF.md)."""
    got = {}
    compare = harness.compare

    def both(config, seed, chosen):
        out = compare(config, seed, chosen)
        got.update(out, **control_reading(config, seed, chosen))
        return out

    monkeypatch.setattr(harness, "compare", both)
    res = run_tiny(capsys)
    assert res["correct"] is True
    assert got["tokens"] > 0
    assert got["control_max_logit_gap"] > got["max_logit_gap"]
    assert got["control_tokens"] == got["tokens"]
    assert got["control_correct"] is False
    assert got["control_max_logit_gap"] > got["limit"]
    jax.clear_caches()
