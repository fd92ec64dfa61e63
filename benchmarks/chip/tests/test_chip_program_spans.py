"""The per-layer readers of the program's own spans
(``prefill_chunk_host_ms``, ``step_host_ms``, ``admit_wait_p95_s``): known
answers on hand-made spans, None where the program records no spans or its
ring dropped some from the window, and numbers from a traced CPU
rehearsal."""
from __future__ import annotations

import json
import os
import sys
import time
import types

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import progspans  # noqa: E402
from test_chip_rehearsal import BIG_SEED, tiny  # noqa: E402

from repro.serving.tracing import Recorder, Span  # noqa: E402

READERS = ("prefill_chunk_host_ms", "step_host_ms", "admit_wait_p95_s")
MS = 1_000_000
T0 = 10_000 * MS                    # the window opens here (ns)


def readings(lo=T0, hi=T0 + 1000 * MS):
    return types.SimpleNamespace(e2e={"t_open": lo * 1e-9,
                                      "t_close": hi * 1e-9})


def hand_made():
    """Two 100 ms steps: the first holds a 50 ms prefill chunk (its 10 ms
    first-token wait inside) and a decode whose wait is 30 ms, the second
    a decode whose wait is 60 ms. Three requests are submitted in the
    window: waits 200 and 400 ms, and one still queued at the close."""
    def at(ms):
        return T0 + ms * MS
    spans = [
        Span("request.queued", at(-50), at(20), 1, 0, {"rid": "pre"}),
        Span("engine.prefill_wait", at(60), at(70), 4, 3, {}),
        Span("engine.prefill_chunk", at(20), at(70), 3, 2, {"rid": "a"}),
        Span("engine.decode_wait", at(75), at(105), 6, 5, {}),
        Span("engine.decode", at(72), at(106), 5, 2, {}),
        Span("engine.step", at(10), at(110), 2, 0, {"step": 1}),
        Span("engine.decode_wait", at(140), at(200), 9, 8, {}),
        Span("engine.decode", at(130), at(205), 8, 7, {}),
        Span("engine.step", at(120), at(220), 7, 0, {"step": 2}),
        Span("request.queued", at(100), at(300), 10, 0, {"rid": "b"}),
        Span("request.queued", at(300), at(700), 11, 0, {"rid": "c"}),
        Span("engine.step", at(990), at(1010), 12, 0, {"step": 3}),
    ]
    rec = Recorder()
    rec.spans.extend(spans)
    rec._open[("request.queued", "d")] = (at(900), {})
    return rec


def read(name, r):
    return harness.load_reader(name)(r)


def test_readers_on_hand_made_spans(monkeypatch):
    monkeypatch.setattr(progspans, "recorder", hand_made)
    r = readings()
    # the 50 ms chunk less its 10 ms first-token wait
    assert read("prefill_chunk_host_ms", r) == pytest.approx(40.0)
    # (100 - 50 - 30) and (100 - 60) ms; the third step ends after close
    assert read("step_host_ms", r) == pytest.approx(30.0)
    # 0.2, 0.4 and 0.1 s (still queued at the close); "pre" came before
    assert read("admit_wait_p95_s", r) == pytest.approx(
        harness.p95([0.2, 0.4, 0.1]))


def test_readers_give_none_without_the_window_spans(monkeypatch):
    r = readings()
    # a program without the module (the import fails)
    import repro.serving
    monkeypatch.delattr(repro.serving, "tracing")
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    assert progspans.recorder() is None
    assert all(read(n, r) is None for n in READERS)
    monkeypatch.undo()
    rec = hand_made()
    rec.dropped, rec.dropped_until_ns = 1, T0 + 5 * MS
    monkeypatch.setattr(progspans, "recorder", lambda: rec)
    assert all(read(n, r) is None for n in READERS)
    # spans dropped before the window opened leave it whole
    rec.dropped_until_ns = T0 - MS
    assert all(read(n, r) is not None for n in READERS)


def test_readers_report_in_a_traced_cpu_rehearsal(capsys):
    rc = harness.run(["--workload", "danube-4b.chat-fits", "--seed",
                      str(BIG_SEED), "--seconds", "6", "--trace", "1"],
                     t_process=time.perf_counter(), check_device=False,
                     scale=tiny)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert all(m[n] > 0 for n in READERS), m
    # every request the window admits waits less than the window
    assert m["admit_wait_p95_s"] < 6.0
    assert m["queue_wait_p95_s"] > 0
