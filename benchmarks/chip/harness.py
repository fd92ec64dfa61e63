"""One benchmark run of one cell: set-up, an open-loop window, metrics and
the comparison with the plain reference.

Everything that belongs to one configuration, traffic mix, model family or
per-layer metric lives in its own file, found by the name that
``BENCHMARK.json`` (or a configuration) gives: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``families/<family>.py`` and
``metrics/<metric>.py``. This module is the same for every cell.

A model family is the code that one architecture needs outside the
program. A tenant of a configuration names it (``"family": "<name>"``;
``dense`` where the key is absent), and ``resolve`` refuses a cell, before
any set-up, whose family file is missing or lacks one of ``FAMILY_API``:

* ``make(arch, seed, tenant_index)``: the benchmark's own weights, made on
  the device from the seed, in the family's own layout;
* ``to_program(w)``: the same arrays in the program's parameter tree;
* ``program_config(base, arch)``: the program's ``ModelConfig`` for the
  published keys ``arch``, starting from ``base``
  (``repro.configs.get_arch(tenant["name"])``);
* ``served_gaps(w, arch, prompt, served)``: per served token, the plain
  float32 reference's best logit less the served token's logit;
* ``control_gaps(w, arch, prompt, served)``: the same gap for the token
  that the reference computed one precision step lower puts first;
* ``decode_step_flops(arch, ctx)``: the FLOPs of one whole decode step
  for live rows holding ``ctx`` tokens, from shapes.

Only ``to_program`` and ``program_config`` may speak of the program; the
weights and the reference import nothing of it. ``arch`` is the tenant's
published keys as its configuration file holds them. A per-layer reader
finds each tenant's family in ``Readings.families``.

What the harness reads of the program's state: a paged tenant's state
keeps its KV pool under ``pool_k``, pages on axis 1 (``shape_key``). A
family whose program keeps one latent pool (multi-head latent attention)
keeps it under that name.

A run:

1. set-up (``setup_s``, from process start to the window's opening):
   weights made on the device from the seed (the family's ``make``), the
   engine built with them, the remap tier the configuration names (if any)
   reached, every prefill chunk (length and start) of this seed's traffic
   and the decode step warmed, then a pre-roll of the cell's own traffic;
2. the window: an open loop that, between engine steps, submits every
   request that is due. A request is timed from when it was due; a token
   is stamped with the host time at which the ``ServingEngine.step`` that
   produced it returned (the step syncs on its argmax);
3. the comparison: a sample of finished requests, drawn from the seed,
   is run through the plain reference (the family's ``served_gaps``) once
   the engine is freed, and the widest gap by which a served token's logit
   lies below the reference's best is held against the configuration's
   limit.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from collections import deque
from statistics import quantiles
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))

import gen  # noqa: E402  (benchmark modules, beside this file)
import peaks  # noqa: E402
import tracered  # noqa: E402

FAMILIES = os.path.join(HERE, "families")
DEFAULT_FAMILY = "dense"
FAMILY_API = ("make", "to_program", "program_config", "served_gaps",
              "control_gaps", "decode_step_flops")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str, bench: Optional[dict] = None,
            scale=None) -> dict:
    """The cell, its configuration file, its traffic mix, each tenant's
    model family and its metrics, found by name. ``scale`` shrinks the
    configuration and mix for a rehearsal on the CPU (tests only)."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, conf["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if scale is not None:
        config, mix = scale(config, mix)
    return {
        "cell": cell,
        "config": config,
        "mix": mix,
        "families": {t["name"]: tenant_family(t)
                     for t in config["tenants"]},
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
    }


def tenant_family(tenant: dict):
    """The model family that a configuration's tenant names."""
    return load_family(tenant.get("family", DEFAULT_FAMILY))


def load_family(name: str):
    """``families/<name>.py``, loaded once a process; exits, naming the
    file and what it lacks, where it is missing or incomplete."""
    return _load_family(os.path.join(FAMILIES, name + ".py"))


@functools.lru_cache(maxsize=None)
def _load_family(path: str):
    if not os.path.isfile(path):
        raise SystemExit(f"{path}: no such model family file")
    name = "family_" + os.path.basename(path)[:-len(".py")]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"{path}: the model family lacks "
                         f"{', '.join(missing)}")
    return mod


def load_reader(name: str):
    """``metrics/<name>.py``; its ``read(r)`` returns a number or None."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def p95(values: List[float]) -> float:
    """95th percentile (``statistics.quantiles``, inclusive method)."""
    if len(values) == 1:
        return float(values[0])
    return float(quantiles(values, n=20, method="inclusive")[-1])


# --------------------------------------------------------------- recording
@dataclasses.dataclass
class Track:
    """Host-clock record of one request."""
    item: gen.Item
    req: object
    due: float                      # absolute host time
    submitted: float
    admitted: Optional[float] = None
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: bool = False

    def served(self) -> List[int]:
        r = self.req
        return list(np.asarray(r.prompt[len(self.item.prompt):])) \
            + list(r.generated)


class Recorder:
    """Counters and host times the metrics read. Only what happens inside
    the window is counted (``in_window``)."""

    def __init__(self):
        self.in_window = False
        self.tracks: Dict[str, Track] = {}
        self.live: List[Track] = []
        self.steps = 0
        self.kv_used: List[float] = []
        self.kv_used_max = 0
        self.decode_calls: List[dict] = []
        self.prefill_chunks = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.step_s: List[float] = []

    def on_compile(self, event, duration, **_kw):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        if event == BACKEND_COMPILE_EVENT and self.in_window:
            self.compiles += 1
            self.compile_s += duration

    def on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits" and self.in_window:
            self.cache_hits += 1

    def after_step(self, eng, t_start: float, t_end: float) -> None:
        keep = []
        for tr in self.live:
            r = tr.req
            n = len(r.prompt) - len(tr.item.prompt) + len(r.generated)
            if tr.admitted is None and (r.slot >= 0 or n or r.finished):
                tr.admitted = t_start
            tr.tokens.extend([t_end] * (n - len(tr.tokens)))
            tr.done = bool(r.finished)
            if not tr.done:
                keep.append(tr)
        self.live = keep
        if self.in_window:
            self.steps += 1
            self.step_s.append(t_end - t_start)
            a = eng.allocator
            self.kv_used.append(a.used_pages / max(a.total_pages, 1))
            self.kv_used_max = max(self.kv_used_max, a.used_pages)


def instrument(eng, rec: Recorder) -> None:
    """Host spans and counters around the calls the engine makes into its
    tenants: the jitted decode step and the eager prefill chunk."""
    import jax

    for name, t in eng.tenants.items():
        decode = t.decode

        def traced_decode(*a, _t=t, _d=decode, _n=name):
            if rec.in_window:
                split = eng.xfer.split[_n]
                cyc = split[1]
                m = eng.xfer.plans[_n].m
                per_layer = cyc.buf.nbytes // len(cyc) if len(cyc) else 0
                rec.decode_calls.append({
                    "tenant": _n, "m": m, "host_bytes": m * per_layer,
                    "ctx": [r.total_len - 1 for r in _t.running()
                            if not r.prefilling]})
            with jax.profiler.TraceAnnotation("bench:decode"):
                return _d(*a)
        t.decode = traced_decode

        lm = t.model.impl
        chunk = lm.prefill_chunk_paged

        def traced_chunk(params, state, slot, tokens, *a, _c=chunk, **k):
            if rec.in_window:
                rec.prefill_chunks += 1
            with jax.profiler.TraceAnnotation("bench:prefill_chunk"):
                return _c(params, state, slot, tokens, *a, **k)
        lm.prefill_chunk_paged = traced_chunk


# ------------------------------------------------------------------ engine
def model_config(tenant: dict):
    from repro.configs import get_arch
    return tenant_family(tenant).program_config(get_arch(tenant["name"]),
                                                tenant["arch"])


def build_engine(config: dict, seed: int, hw):
    """The engine with the benchmark's weights (the same construction as
    ``repro.launch.serve.build_engine``, which makes its own weights)."""
    import jax
    from repro.configs.base import RuntimeConfig
    from repro.models import build_model
    from repro.serving import ServingEngine, TenantConfig

    tenants = {}
    for i, t in enumerate(config["tenants"]):
        fam = tenant_family(t)
        cfg = model_config(t)
        params = fam.to_program(fam.make(t["arch"], seed, i))
        want = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise RuntimeError(f"{t['name']}: benchmark weights do not match "
                               "the program's parameter tree")
        tenants[t["name"]] = TenantConfig(
            cfg, params, max_batch=t["max_batch"],
            max_context=t["max_context"], paged=True)
        del params
    e = config["engine"]
    return ServingEngine(
        tenants, mode=e["mode"], scheduler=e["scheduler"], hw=hw,
        base_kv_pages=e["base_kv_pages"], page_size=e["page_size"],
        prefill_chunk_tokens=e["prefill_chunk_tokens"],
        runtime=dataclasses.replace(RuntimeConfig(), **e.get("runtime", {})))


def warm(eng, shapes: Dict[str, List[Tuple[int, int]]]) -> int:
    """Run every prefill chunk (length, start), the decode step and a slot
    release
    once at the engine's current pool size and plan; results are dropped.

    The state is committed to the device first. In the window it always
    is (every jitted step returns committed arrays), and JAX keys an eager
    op's program on whether its operands are committed, so warming an
    uncommitted state would leave the window's eager ops to compile."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    n = 0
    for name, t in eng.tenants.items():
        t.state = jax.device_put(t.state, dev)
        fetch = eng.xfer.fetch_for(name)
        for s, start in shapes[name]:
            out = t.model.impl.prefill_chunk_paged(
                t.params, t.state, 0, jnp.zeros((s,), jnp.int32), start,
                fetch=fetch)
            jax.block_until_ready(out)
            n += 1
        out = t.decode(t.params, eng.xfer.split[name], t.state,
                       jnp.zeros((t.max_batch,), jnp.int32))
        jax.block_until_ready(out)
        n += 1
        free = t.free_slot()
        if free is not None:
            # what a finishing request's release runs (an empty slot
            # already holds the released row, so nothing changes)
            t.clear_slot(free)
            jax.block_until_ready(t.state)
            n += 1
    return n


def force_tier(eng, want: Dict[str, int]) -> None:
    """Drive the engine's memory controller under KV pressure, as a decode
    step whose page allocation fails does, until each tenant in ``want``
    has donated that many layers; then finish each tier switch's
    host->device drain, so that set-up ends on the plan the window holds."""
    for _ in range(sum(want.values()) + 1):
        if all(eng.store.models[n].remapped_alpha >= a
               for n, a in want.items()):
            break
        eng._memory_control(True)
    for name in list(eng.xfer.pending):
        eng.xfer.advance(name, float("inf"))
    got = {n: eng.store.models[n].remapped_alpha for n in want}
    if got != want or eng.xfer.pending:
        raise RuntimeError(f"set-up reached remap levels {got}, not {want}")


def shape_key(eng):
    return tuple((n, t.state["pool_k"].shape[1], eng.xfer.plans[n].m,
                  eng.xfer.plans[n].cycle_layers)
                 for n, t in eng.tenants.items())


def submit_due(eng, pending: deque, rec: Recorder, t_open: float,
               now: float) -> None:
    """Hand the engine every request due by ``now`` (due times are seconds
    from ``t_open``), timed from when it was due."""
    from repro.serving.request import Request
    batch = []
    while pending and t_open + pending[0].due <= now:
        it = pending.popleft()
        r = Request(it.rid, it.tenant, it.prompt.copy(), it.max_new,
                    arrival=float(eng.step_idx))
        tr = Track(it, r, t_open + it.due, now)
        rec.tracks[it.rid] = tr
        rec.live.append(tr)
        batch.append(r)
    if batch:
        eng.submit(batch)


def serve(eng, pending: deque, rec: Recorder, t_open: float, t_end: float,
          clock) -> float:
    """Open loop until ``t_end``: submit what is due, then step. Returns
    the host time of the last step's return (or of the loop's end)."""
    import jax
    last = clock()
    while True:
        now = clock()
        if now >= t_end:
            return max(last, now)
        submit_due(eng, pending, rec, t_open, now)
        if eng.busy():
            ts = clock()
            with jax.profiler.TraceAnnotation("bench:step"):
                eng.step()
            last = clock()
            rec.after_step(eng, ts, last)
        else:
            nxt = t_open + pending[0].due if pending else t_end
            with jax.profiler.TraceAnnotation("bench:idle"):
                time.sleep(max(0.0, min(nxt, t_end) - clock()))
            last = clock()


# ----------------------------------------------------------------- metrics
def end_to_end(rec: Recorder, t_open: float, t_close: float) -> dict:
    window = t_close - t_open
    ttft, gaps, lateness = [], [], []
    out_tokens = 0
    for tr in rec.tracks.values():
        in_win = [t for t in tr.tokens if t_open <= t <= t_close]
        out_tokens += len(in_win)
        if t_open <= tr.due < t_close:
            lateness.append(tr.submitted - tr.due)
            first = tr.tokens[0] if tr.tokens else None
            ttft.append((first if first is not None and first <= t_close
                         else t_close) - tr.due)
        ts = [t for t in tr.tokens if t <= t_close]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if b >= t_open)
        if ts and not tr.done and ts[-1] >= t_open:
            gaps.append(t_close - ts[-1])
    return {"window_s": window, "t_open": t_open, "t_close": t_close,
            "ttft": ttft, "gaps": gaps, "out_tokens": out_tokens,
            "lateness": lateness}


class Readings:
    """What a per-layer metric reader gets."""

    def __init__(self, rec, e2e, trace, archs, page_size, peak,
                 families=None):
        self.rec = rec
        self.e2e = e2e
        self.trace = trace          # tracered.reduce(...) or None
        self.archs = archs          # tenant -> published arch keys
        self.page_size = page_size
        self.peak = peak            # peaks.peaks(device_kind)
        self.families = families or {}  # tenant -> model family module


# ------------------------------------------------------------- correctness
def sample(rec: Recorder, seed: int, want: dict) -> List[Track]:
    """Finished requests drawn from the seed, the longest answer first,
    until both ``requests`` and ``min_tokens`` are reached."""
    done = sorted((tr for tr in rec.tracks.values()
                   if tr.done and tr.served()), key=lambda tr: tr.item.rid)
    if not done:
        return []
    longest = max(done, key=lambda tr: len(tr.served()))
    rest = [tr for tr in done if tr is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    out, tokens = [longest], len(longest.served())
    for i in order:
        if len(out) >= want["requests"] and tokens >= want["min_tokens"]:
            break
        out.append(rest[i])
        tokens += len(rest[i].served())
    return out


def compare(config: dict, seed: int, chosen: List[Track]) -> dict:
    """Widest gap, over every sampled served token, between the
    reference's best logit and the served token's logit."""
    worst, n_tok, misses = 0.0, 0, 0
    for i, t in enumerate(config["tenants"]):
        mine = [tr for tr in chosen if tr.item.tenant == t["name"]]
        if not mine:
            continue
        fam = tenant_family(t)
        w = fam.make(t["arch"], seed, i)
        for tr in mine:
            g = fam.served_gaps(w, t["arch"], tr.item.prompt, tr.served())
            worst = max(worst, float(g.max()))
            n_tok += len(g)
            misses += int((g > 0).sum())
        del w
        gc.collect()
    return {"max_logit_gap": worst, "tokens": n_tok,
            "requests": len(chosen), "not_argmax": misses}


def judge(cmp: dict, limit: float) -> bool:
    """The decision on ``correct``: some served tokens were compared, and
    none lies further below the reference's best logit than ``limit``."""
    return cmp["tokens"] >= 1 and cmp["max_logit_gap"] <= limit


# -------------------------------------------------------------------- run
def run(argv=None, *, t_process: float, check_device: bool = True,
        scale=None) -> int:
    """One run; prints the result line last on stdout. ``scale`` shrinks
    the configuration and mix for a rehearsal on the CPU (tests only)."""
    args = parse_args(argv)
    info = resolve(args.workload, scale=scale)
    config, mix, cell = info["config"], info["mix"], info["cell"]

    import jax
    devs = jax.devices()
    if check_device and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        print(f"needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    dev = devs[0]
    kind = dev.device_kind
    if check_device:
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        from repro.serving.hw import spec_for_device_kind
        hw = spec_for_device_kind(kind)
        peak = peaks.peaks(kind)
    else:
        from repro.serving.hw import TPU_V5E as hw
        peak = peaks.PEAKS["TPU v5 lite"]

    clock = time.perf_counter
    rec = Recorder()
    jax.monitoring.register_event_duration_secs_listener(rec.on_compile)
    jax.monitoring.register_event_listener(rec.on_event)
    archs = {t["name"]: t["arch"] for t in config["tenants"]}
    vocab = {n: a["vocab_size"] for n, a in archs.items()}
    items = gen.generate(mix, args.seed, args.seconds, vocab)
    chunk = config["engine"]["prefill_chunk_tokens"]
    shapes = {n: gen.chunk_shapes([it for it in items if it.tenant == n],
                                  chunk) for n in archs}

    # -- set-up ---------------------------------------------------------
    phases = {"start": clock() - t_process}
    eng = build_engine(config, args.seed, hw)
    instrument(eng, rec)
    phases["build"] = clock() - t_process
    if config.get("setup_remap_alpha"):
        force_tier(eng, config["setup_remap_alpha"])
        phases["remap"] = clock() - t_process
    warmed = warm(eng, shapes)
    phases["warm"] = clock() - t_process
    key = shape_key(eng)
    # pre-roll: every pre-roll request at once, served until each has its
    # first token (or the cap), so that the window opens on a decoding batch
    pending = deque(items)
    t_open = clock() + 1.0
    submit_due(eng, pending, rec, t_open, clock())
    cap = clock() + mix["preroll"]["settle_cap_s"]
    while clock() < cap and any(not tr.tokens for tr in rec.live):
        t0 = clock()
        eng.step()
        rec.after_step(eng, t0, clock())
    phases["preroll"] = clock() - t_process
    if shape_key(eng) != key:
        warmed += warm(eng, shapes)
    phases["rewarm"] = clock() - t_process
    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    # the window opens now: pre-roll requests keep their due times, window
    # requests are due from here on
    now = clock()
    shift = now - t_open
    t_open = now
    for tr in rec.tracks.values():
        tr.due += shift
        tr.submitted += shift
        tr.tokens = [t + shift for t in tr.tokens]
        if tr.admitted is not None:
            tr.admitted += shift
    setup_s = t_open - t_process
    first_step = eng.step_idx
    print(f"[{kind}] set-up {setup_s:.3f} s (phases ended at "
          f"{ {k: round(v, 3) for k, v in phases.items()} }): {warmed} "
          f"warm-up calls, {len(rec.tracks)} pre-roll requests, pool/plan "
          f"{shape_key(eng)}", file=sys.stderr, flush=True)

    # -- window ---------------------------------------------------------
    rec.in_window = True
    with jax.profiler.TraceAnnotation("bench:window"):
        t_close = serve(eng, pending, rec, t_open, t_open + args.seconds,
                        clock)
    rec.in_window = False
    trace = None
    if args.trace:
        jax.profiler.stop_trace()
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    e2e = end_to_end(rec, t_open, t_close)
    counts: Dict[str, int] = {}
    for step, k, _d in eng.events:
        if step > first_step:
            counts[k] = counts.get(k, 0) + 1
    late = e2e["lateness"]
    print(f"[{kind}] window {e2e['window_s']:.3f} s: {len(e2e['ttft'])} "
          f"requests due, {e2e['out_tokens']} tokens, {rec.steps} steps, "
          f"{len(rec.decode_calls)} decode calls ("
          f"{sum(1 for c in rec.decode_calls if c['m'] > 0)} remapped), "
          f"{rec.prefill_chunks} "
          f"prefill chunks, {rec.compiles} compiles ({rec.compile_s:.3f} s, "
          f"{rec.cache_hits} from the persistent cache); step seconds "
          f"{np.percentile(rec.step_s, [50, 95, 100]).round(4).tolist() if rec.step_s else []}; "
          f"pool/plan {shape_key(eng)}; most KV pages used {rec.kv_used_max}; "
          f"generator lateness p95 {p95(late) if late else 0.0:.6f} s max "
          f"{max(late) if late else 0.0:.6f} s; engine events in the window "
          f"{counts}",
          file=sys.stderr, flush=True)
    print(f"[{kind}] tails (not bounded): ttft p95 "
          f"{p95(e2e['ttft']) if e2e['ttft'] else float('nan'):.4f} s over "
          f"{len(e2e['ttft'])} requests, tbt p95 "
          f"{p95(e2e['gaps']) if e2e['gaps'] else float('nan'):.4f} s over "
          f"{len(e2e['gaps'])} gaps", file=sys.stderr, flush=True)
    attempted = len(e2e["ttft"])
    failed = sum(1 for tr in rec.tracks.values()
                 if t_open <= tr.due < t_close and tr.done
                 and not tr.served())
    chosen = sample(rec, args.seed, mix["sample"])
    page_size = config["engine"]["page_size"]
    del eng, pending
    gc.collect()

    if args.trace:
        events = tracered.load_xplane(trace_dir)
        trace = tracered.reduce(events)
        shutil.rmtree(trace_dir, ignore_errors=True)
    readings = Readings(rec, e2e, trace, archs, page_size, peak,
                        info["families"])

    if args.trace:
        metrics = {}
        for m in info["per_layer"]:
            v = load_reader(m["name"])(readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {
            "ttft_p95_s": p95(e2e["ttft"]) if e2e["ttft"] else None,
            "tbt_p95_s": p95(e2e["gaps"]) if e2e["gaps"] else None,
            "tokens_per_s": e2e["out_tokens"] / e2e["window_s"],
            "setup_s": setup_s,
        }
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in info["end_to_end"]
                   if values.get(m["name"]) is not None}

    # -- correctness ----------------------------------------------------
    t_ref = clock()
    cmp = compare(config, args.seed, chosen)
    limit = config["limits"]["max_logit_gap"]
    correct = judge(cmp, limit)
    print(f"[{kind}] reference over {cmp['requests']} requests, "
          f"{cmp['tokens']} served tokens ({cmp['not_argmax']} not the "
          f"reference's argmax), in {clock() - t_ref:.3f} s", file=sys.stderr)
    checks = {"max_logit_gap": {"value": cmp["max_logit_gap"],
                                "limit": limit},
              "sampled_tokens": {"value": cmp["tokens"], "limit": 1}}
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    device = {"platform": dev.platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": tracered.top_ops(trace),
                               "idle_gaps": trace["gaps"]}
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
