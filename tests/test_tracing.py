"""Host-clock spans (``repro.serving.tracing``): the recorder's parent
links, attributes, window and ring; the span tree a few steps of a tiny
paged engine record; and the same spans, with their attributes as stats, in
a ``jax.profiler`` trace."""
import glob
import os
import time

import jax
import pytest

from repro.configs import ARCHS, scaled_config
from repro.core import packed_spec
from repro.models import build_model
from repro.serving import ServingEngine, TenantConfig, tracing
from repro.serving.traces import tiny_trace


def test_spans_link_to_their_parent_and_keep_their_attributes():
    rec = tracing.Recorder()
    with rec.span("outer", step=7, tenant="a") as outer:
        with rec.span("inner", rid="r1", tokens=4) as inner:
            inner.set(bytes=128)
        rec.instant("event.x", detail="d")
        outer.set(kv_used=3)
    inner_s, ev, outer_s = rec.spans           # recorded as each ends
    assert (inner_s.name, ev.name, outer_s.name) == ("inner", "event.x",
                                                     "outer")
    assert outer_s.parent == 0
    assert inner_s.parent == outer_s.sid and ev.parent == outer_s.sid
    assert outer_s.attrs == {"tenant": "a", "step": 7, "kv_used": 3}
    assert inner_s.attrs == {"rid": "r1", "tokens": 4, "bytes": 128}
    assert outer_s.start_ns <= inner_s.start_ns <= inner_s.end_ns \
        <= ev.start_ns == ev.end_ns <= outer_s.end_ns
    assert rec._stack == []


def test_a_span_closes_on_an_exception():
    rec = tracing.Recorder()
    with pytest.raises(ValueError):
        with rec.span("failing"):
            raise ValueError
    assert [s.name for s in rec.spans] == ["failing"] and rec._stack == []


def test_window_keeps_only_spans_inside_it():
    rec = tracing.Recorder()
    with rec.span("before"):
        pass
    lo = time.perf_counter_ns()
    with rec.span("straddles"):
        with rec.span("inside"):
            pass
        hi = time.perf_counter_ns()
    with rec.span("after"):
        pass
    assert [s.name for s in rec.window(lo, hi)] == ["inside"]
    assert {s.name for s in rec.window(0, time.perf_counter_ns())} == {
        "before", "straddles", "inside", "after"}


def test_the_ring_counts_its_drops_and_refuses_a_window_it_dropped_from():
    rec = tracing.Recorder(capacity=4)
    lo = time.perf_counter_ns()
    for i in range(6):
        with rec.span("s", i=i):
            pass
    assert rec.dropped == 2 and len(rec.spans) == 4
    assert [s.attrs["i"] for s in rec.spans] == [2, 3, 4, 5]
    assert rec.window(lo, time.perf_counter_ns()) is None
    # a window that opens after the newest dropped span is whole
    mid = rec.spans[0].start_ns
    assert [s.attrs["i"] for s in rec.window(mid, time.perf_counter_ns())] \
        == [2, 3, 4, 5]


def test_spans_across_steps_are_recorded_at_their_end_with_their_start():
    rec = tracing.Recorder()
    rec.begin("request.queued", "r1", tenant="a")
    rec.begin("request.queued", "r2", tenant="a")
    t1 = rec.opened("request.queued")["r1"]
    with rec.span("engine.step", step=1):
        rec.end("request.queued", "r1", slot=0)
    rec.end("request.queued", "nobody")         # never begun: nothing
    rec.discard("request.queued", "r2")
    assert rec.opened("request.queued") == {}
    (queued, step) = rec.spans
    assert queued.name == "request.queued" and queued.start_ns == t1
    assert queued.start_ns < step.start_ns and queued.parent == 0
    assert queued.attrs == {"tenant": "a", "slot": 0, "rid": "r1"}


def test_instants_and_span_ends_enter_a_zero_length_annotation(
        monkeypatch):
    marks = []

    class Annotation:
        def __init__(self, name, **attrs):
            self.name, self.attrs = name, attrs

        def __enter__(self):
            marks.append((self.name, self.attrs))

        def __exit__(self, *exc):
            pass

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    rec = tracing.Recorder()
    rec.begin("request.queued", "r1", tenant="a")
    rec.instant("event.finish", step=3, detail="r0")
    rec.end("request.queued", "r1", slot=2)
    (n1, a1), (n2, a2) = marks
    assert (n1, a1) == ("event.finish", {"step": 3, "detail": "r0"})
    queued = rec.spans[-1]
    assert n2 == "request.queued" and a2 == dict(
        queued.attrs, dur_ns=queued.end_ns - queued.start_ns)
    assert "dur_ns" not in queued.attrs


@pytest.fixture(scope="module")
def paged_tenant():
    cfg = scaled_config(ARCHS["llama3-8b"], num_layers=2)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return {"A": TenantConfig(cfg, params, max_batch=4, max_context=64,
                              paged=True)}


def _serve(tenants, base_pages):
    eng = ServingEngine(dict(tenants), mode="mirage", scheduler="temporal",
                        base_kv_pages=base_pages, page_size=4,
                        quantum_steps=4, prefill_chunk_tokens=8,
                        watermark_tokens=4)
    lo = time.perf_counter_ns()
    eng.submit(tiny_trace(list(tenants), n_per_model=3, prompt_len=18,
                          max_new=6, vocab=256))
    eng.run(max_steps=200)
    spans = tracing.RECORDER.window(lo, time.perf_counter_ns())
    assert spans is not None
    return eng, spans


def _ancestors(spans, s):
    by = {x.sid: x for x in spans}
    out = []
    while s.parent in by:
        s = by[s.parent]
        out.append(s.name)
    return out


@pytest.fixture(scope="module")
def served(paged_tenant):
    return _serve(paged_tenant, base_pages=64)


def test_engine_steps_record_the_span_tree(served):
    eng, spans = served
    steps = [s for s in spans if s.name == "engine.step"]
    assert [s.attrs["step"] for s in steps] == list(
        range(1, eng.step_idx + 1))
    assert all(s.parent == 0 and s.attrs["kv_total"] == 64 for s in steps)
    parents = {
        "engine.admit": ["engine.step"],
        "engine.prefill_chunk": ["engine.step"],
        "engine.prefill_wait": ["engine.prefill_chunk", "engine.step"],
        "engine.decode": ["engine.step"],
        "engine.page_table_sync": ["engine.decode", "engine.step"],
        "engine.decode_wait": ["engine.decode", "engine.step"],
        "engine.memory_control": ["engine.step"],
    }
    seen = {}
    for s in spans:
        if s.name in parents:
            assert _ancestors(spans, s) == parents[s.name], s
            seen[s.name] = seen.get(s.name, 0) + 1
    assert set(seen) == set(parents)
    assert seen["engine.memory_control"] == len(steps)
    chunks = [s for s in spans if s.name == "engine.prefill_chunk"]
    assert {(s.attrs["rid"], s.attrs["start"], s.attrs["tokens"])
            for s in chunks} == {(f"A-{i}", st, n) for i in range(3)
                                 for st, n in ((0, 8), (8, 8), (16, 2))}
    admitted = sum(s.attrs["admitted"] for s in spans
                   if s.name == "engine.admit")
    assert admitted == 3
    decodes = [s for s in spans if s.name == "engine.decode"]
    assert all(s.attrs["tenant"] == "A" and s.attrs["m"] == 0
               and s.attrs["host_bytes"] == 0 for s in decodes)
    assert sum(s.attrs["batch"] for s in decodes) == 3 * 5


def test_every_event_has_its_instant_and_requests_their_spans(served):
    eng, spans = served
    instants = [(s.attrs["step"], s.name[len("event."):], s.attrs["detail"])
                for s in spans if s.name.startswith("event.")]
    assert instants == eng.events
    rids = {r.rid for r in eng.finished}
    for name in ("request.queued", "request.prefill"):
        mine = {s.attrs["rid"]: s for s in spans if s.name == name}
        assert set(mine) == rids
    for rid in rids:
        q = next(s for s in spans if s.name == "request.queued"
                 and s.attrs["rid"] == rid)
        p = next(s for s in spans if s.name == "request.prefill"
                 and s.attrs["rid"] == rid)
        assert q.end_ns <= p.start_ns and p.attrs["tokens"] == 18
        first = next(s for s in spans if s.name == "event.prefill"
                     and s.attrs["detail"] == rid)
        assert p.start_ns <= first.start_ns


def test_a_tier_switch_records_its_outcome_and_pool(paged_tenant):
    """Under KV pressure the idle tenant's remap is an
    ``engine.tier_switch`` inside ``engine.memory_control``; its decode
    calls then stream the cycle layers, and the bytes on their spans are
    what ``stream_bytes`` counts."""
    two = {"A": paged_tenant["A"], "B": paged_tenant["A"]}
    eng, spans = _serve(two, base_pages=8)
    switches = [s for s in spans if s.name == "engine.tier_switch"]
    remaps = [s for s in switches if s.attrs["outcome"] == "remap"]
    assert remaps and len(remaps) == sum(
        1 for _, k, _d in eng.events if k == "remap")
    for s in remaps:
        assert _ancestors(spans, s)[0] == "engine.memory_control"
        assert s.attrs["pages_after"] > s.attrs["pages_before"]
    decodes = [s.attrs for s in spans if s.name == "engine.decode"]
    assert any(a["m"] > 0 for a in decodes)
    # m cycle layers, each one packed buffer of the layer's words
    layer = 4 * packed_spec(eng.xfer.host_copy["B"])[1]
    assert all(a["host_bytes"] == a["m"] * layer for a in decodes)
    assert sum(a["host_bytes"] for a in decodes) \
        == eng.xfer.stats.stream_bytes


def test_spans_reach_the_profiler_trace_with_their_attributes(
        paged_tenant, tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, _ = _serve(paged_tenant, base_pages=64)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "request.", "event.")):
                    found.setdefault(e.name, []).append(dict(e.stats))
    steps = found["engine.step"]
    assert sorted(s["step_num"] for s in steps) == list(
        range(1, eng.step_idx + 1))
    assert all(s["kv_total"] == 64 for s in steps)
    assert any(s["tenant"] == "A" and s["batch"] >= 1
               for s in found["engine.decode"])
    assert {s["rid"] for s in found["engine.prefill_chunk"]} == {
        "A-0", "A-1", "A-2"}
    assert {"engine.decode_wait", "engine.memory_control",
            "engine.admit"} <= set(found)
    # spans across steps and instants are moments in the trace: the
    # former carry how long before them they started
    rids = {r.rid for r in eng.finished}
    for name in ("request.queued", "request.prefill"):
        assert {s["rid"] for s in found[name]} == rids
        assert all(s["dur_ns"] > 0 for s in found[name])
    for kind in ("prefill", "finish"):
        marks = [(s["step"], s["detail"]) for s in found["event." + kind]]
        assert sorted(marks) == sorted(
            (st, d) for st, k, d in eng.events if k == kind)
