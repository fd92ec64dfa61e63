"""Trace reduction and the per-layer readers that use it, checked on a
hand-made trace with known answers and on a small trace recorded on one
TPU v5e (``data/v5e_chat_fits_events.json.gz``: the reduced events of a
53 s ``danube-4b.chat-fits`` window, one decode step among prefill
chunks)."""
from __future__ import annotations

import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import flops  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import tracered  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "v5e_chat_fits_events.json.gz")
DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def hand_made():
    """A 100 ms window: ops busy on [10, 30) and [50, 61) ms (two overlap),
    one decode program run of 20 ms, host spans around them."""
    ev = [
        (HOST, "main", "bench:window", 0, 100 * MS),
        (HOST, "main", "bench:step", 6 * MS, 59 * MS),
        (HOST, "main", "bench:decode", 8 * MS, 25 * MS),
        (HOST, "main", "bench:idle", 65 * MS, 35 * MS),
        (DEV, "XLA Modules", "jit_step(123)", 10 * MS, 20 * MS),
        (DEV, "XLA Ops", "while.1", 10 * MS, 20 * MS),
        (DEV, "XLA Ops", "paged_decode_attention.3", 10 * MS, 15 * MS),
        (DEV, "XLA Ops", "fusion.7", 25 * MS, 5 * MS),
        (DEV, "XLA Ops", "fusion.7", 50 * MS, 11 * MS),
        (DEV, "XLA Ops", "fusion.9", 55 * MS, 2 * MS),
        (DEV, "XLA Ops", "fusion.9", 120 * MS, 2 * MS),   # after the window
    ]
    return ev


def test_busy_idle_ops_and_gaps_on_a_hand_made_trace():
    red = tracered.reduce(hand_made())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.031)
    assert red["devices"] == 1
    # containers (while) are busy time but not rows of the op table
    assert set(red["ops"]) == {"paged_decode_attention.3", "fusion.7",
                               "fusion.9"}
    assert red["ops"]["fusion.7"] == pytest.approx(0.016)
    assert red["ops"]["fusion.9"] == pytest.approx(0.002)
    assert red["modules"] == {"jit_step(123)": [pytest.approx(0.02)]}
    # idle gaps [0,10), [30,50), [61,100) ms, each named by the innermost
    # host span around its middle
    gaps = {round(s, 6): name for name, s in red["gaps"]}
    assert gaps == {0.039: "idle", 0.02: "step", 0.01: "outside any span"}
    assert tracered.top_ops(red)[0][0] == "fusion.7"


def test_op_names_are_instruction_names():
    assert tracered.op_name("%fusion.12 = bf16[1,2]{1,0} fusion(%a)") \
        == "fusion.12"
    assert tracered.op_name("jit_step(42)") == "jit_step(42)"


def readings(trace, calls, out_tokens=100):
    rec = harness.Recorder()
    rec.decode_calls = calls
    arch = {"hidden_size": 3840, "intermediate_size": 10240,
            "num_hidden_layers": 24, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 120, "vocab_size": 32000}
    return harness.Readings(rec, {"out_tokens": out_tokens}, trace,
                            {"t": arch}, 16, peaks.peaks("TPU v5 lite"),
                            {"t": harness.load_family("dense")})


def test_readers_on_the_hand_made_trace():
    red = tracered.reduce(hand_made())
    calls = [{"tenant": "t", "m": 2, "host_bytes": 10, "ctx": [15, 100]}]
    r = readings(red, calls)
    idle = harness.load_reader("device_idle_share")(r)
    assert idle == pytest.approx(69.0)
    assert harness.load_reader("decode_step_ms")(r) == pytest.approx(20.0)
    arch = r.archs["t"]
    least, bound = flops.roofline_seconds(
        flops.paged_decode_flops(arch, [15, 100]),
        flops.paged_decode_bytes(arch, [15, 100], 16), r.peak)
    assert bound == "memory"
    roof = harness.load_reader("paged_decode_roofline")(r)
    assert roof == pytest.approx(100.0 * 24 * least / 0.015)
    mfu = harness.load_reader("decode_mfu")(r)
    assert mfu == pytest.approx(100.0 * flops.decode_step_flops(
        arch, [15, 100]) / (0.02 * 197e12))
    assert harness.load_reader("remapped_step_share")(r) == 100.0
    assert harness.load_reader("host_bytes_per_token")(r) == 0.1


def test_decode_mfu_reads_flops_through_the_tenant_family():
    """Two decode calls on a hand-made module table: the reading through
    the dense family is the one ``flops.decode_step_flops`` gives, to the
    last digit."""
    runs = [0.0213, 0.0197]
    calls = [{"tenant": "t", "m": 0, "host_bytes": 0, "ctx": [15, 100]},
             {"tenant": "t", "m": 0, "host_bytes": 0, "ctx": [16, 101, 7]}]
    r = readings({"modules": {"jit_step_decode(7)": runs}}, calls)
    want = 100.0 * sum(flops.decode_step_flops(r.archs["t"], c["ctx"])
                       for c in calls) / (sum(runs) * r.peak["bf16_flops"])
    assert harness.load_reader("decode_mfu")(r) == want


def test_readers_report_nothing_without_a_trace():
    r = readings(None, [])
    for name in ("device_idle_share", "decode_step_ms", "decode_mfu",
                 "paged_decode_roofline", "remapped_step_share",
                 "host_bytes_per_token"):
        assert harness.load_reader(name)(r) is None


def test_flops_and_bytes_from_shapes():
    arch = readings(None, []).archs["t"]
    # danube: 154,828,800 matmul parameters a layer (309,688,320 B with norms)
    assert flops.layer_matmul_params(arch) == 154_828_800
    assert flops.paged_decode_flops(arch, [0]) == 4 * 32 * 120
    # ctx 15 -> 16 keys -> one page of K and V, plus q and out
    assert flops.paged_decode_bytes(arch, [15], 16) == \
        2 * 16 * 8 * 120 * 2 + 2 * 32 * 120 * 2
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_recorded_v5e_trace():
    ev = tracered.load_events(RECORDED)
    red = tracered.reduce(ev)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    # the decode program and its paged kernel are there, and the kernel's
    # time sits inside the program's
    runs = tracered.select(red["modules"], "jit_step")
    kern = tracered.select(red["op_events"], "paged_decode")
    assert runs and kern
    assert 0 < sum(kern) < sum(runs)
    assert len(red["gaps"]) == 10
    assert all(g[1] > 0 for g in red["gaps"])
    # what this window showed: the device idle nearly all the time, the
    # decode program at 98.7 ms, host time in the eager prefill chunks
    assert red["busy_s"] / red["window_s"] < 0.02
    assert sum(runs) == pytest.approx(0.0987, rel=1e-3)
    assert red["gaps"][0][0] == "prefill_chunk"
