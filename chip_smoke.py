#!/usr/bin/env python3
"""Chip smoke test: h2o-danube-3-4b at its published widths on one TPU v5e.

  python3 chip_smoke.py

Builds the serving engine through ``repro.launch.serve`` (weights random
from ``--seed``), serves 8 requests whose KV outgrows the base pool so the
Remapping Controller must move layers to pinned host memory, and checks:

  * at least one remap event fired;
  * the compiled remapped decode step holds the Pallas kernel
    (``tpu_custom_call``);
  * device-memory parameter bytes dropped by at least alpha x layer_bytes,
    and a drain before teardown brings every layer back;
  * every generated token is the argmax of a plain teacher-forced
    reference (``lm.fwd_seq``: jnp attention, no Pallas, dense params
    rebuilt from the host copy) or within BF16_ULPS bf16 ulps of its max
    logit.

Phase lines go first; the last line of stdout is one JSON object
``{"ok": true, "device": {...}}``. Without a TPU (or outside the repo) it
exits non-zero and prints no result. No exception of any phase is caught.
"""
from __future__ import annotations

import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

MODEL = "h2o-danube-3-4b"
# 8 prompts of 512-1024 tokens (whole 256-token chunks) over the full
# vocabulary, 32 new tokens each: ~430 KV pages against a 256-page base
# pool, so admission runs out of pages and the controller must remap
SERVE_ARGS = [
    "--tenants", MODEL, "--full-width", "--paged", "--page-size", "16",
    "--max-batch", "8", "--max-context", "2048", "--chunk-tokens", "256",
    "--mode", "mirage", "--requests", "8", "--max-new", "32",
    "--prompt-lens", "512,768,1024,768,1024,512,768,1024",
    "--base-pages", "256", "--seed", "0",
]
BF16_ULPS = 8          # token tolerance: gap to the max logit, in bf16 ulps
COPY_REPS = 5


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip smoke check failed: {what}")


def bytes_by_kind(arrays) -> dict:
    out: dict = {}
    for a in arrays:
        kind = a.sharding.memory_kind
        out[kind] = out.get(kind, 0) + a.nbytes
    return out


def param_bytes(eng, name) -> dict:
    resident, cycle, _ = eng.xfer.split[name]
    return bytes_by_kind(jax.tree.leaves(
        (eng.tenants[name].params, resident, cycle)))


def inspect_remap(eng) -> dict:
    """Layout and compiled decode step right after the first remap."""
    gc.collect()
    t = eng.tenants[MODEL]
    tc = time.perf_counter()
    compiled = t.decode.lower(
        t.params, eng.xfer.split[MODEL], t.state,
        jnp.zeros((t.max_batch,), jnp.int32)).compile()
    return dict(step=eng.step_idx,
                alpha=eng.store.models[MODEL].remapped_alpha,
                m=eng.xfer.plans[MODEL].m,
                after=param_bytes(eng, MODEL),
                live=bytes_by_kind(jax.live_arrays()),
                compile_s=time.perf_counter() - tc,
                kernel="tpu_custom_call" in compiled.as_text(),
                host_arg=compiled.memory_analysis()
                .host_argument_size_in_bytes)


def bf16_ulp(x: float) -> float:
    return 2.0 ** (math.floor(math.log2(max(abs(x), 2.0 ** -126))) - 7)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.hw import spec_for_device_kind
    from repro.core import pack_layers
    from repro.models import build_model

    kind = dev.device_kind
    tag = f"[{kind}]"
    hw = spec_for_device_kind(kind)
    print(f"{tag} hardware spec {hw.name}; compile cache "
          f"{enable_compile_cache()}", flush=True)

    # -- engine ---------------------------------------------------------
    args = serve.parse_args(SERVE_ARGS)
    t0 = time.perf_counter()
    eng = serve.build_engine(args, hw=hw)
    print(f"{tag} engine built in {time.perf_counter() - t0:.2f} s; "
          f"page_bytes={eng.store.memory.page_bytes} "
          f"layer_bytes={eng.store.models[MODEL].layer_bytes}", flush=True)
    gc.collect()
    before = param_bytes(eng, MODEL)
    live_before = bytes_by_kind(jax.live_arrays())
    print(f"{tag} before remap: param bytes by memory kind {before}; "
          f"live arrays by memory kind {live_before}", flush=True)

    # -- serve, stopping to inspect the first remap ----------------------
    reqs = serve.build_requests(args, eng)
    eng.submit(reqs)
    t0 = time.perf_counter()
    remap = None
    while eng.busy() and eng.step_idx < 2000:
        eng.step()
        if remap is None and any(k == "remap" for _, k, _d in eng.events):
            remap = inspect_remap(eng)
    serve_s = time.perf_counter() - t0
    check(not eng.busy(), "requests left unfinished")
    eng.allocator.check_invariants()
    counts: dict = {}
    for _, k, _d in eng.events:
        counts[k] = counts.get(k, 0) + 1
    print(f"{tag} served {len(eng.finished)} requests in {eng.step_idx} "
          f"steps, {serve_s:.2f} s wall (compiles included); "
          f"events {counts}", flush=True)
    print(f"{tag} transfer stats {eng.xfer.stats}", flush=True)
    check(remap is not None, "no remap event fired")
    lb = eng.store.models[MODEL].layer_bytes
    drop = before.get("device", 0) - remap["after"].get("device", 0)
    print(f"{tag} first remap at step {remap['step']}: alpha={remap['alpha']} "
          f"m={remap['m']}; param bytes by memory kind {remap['after']} "
          f"(device drop {drop} B, alpha*layer_bytes {remap['alpha'] * lb} "
          f"B); live arrays by memory kind {remap['live']}", flush=True)
    print(f"{tag} remapped decode step: compile {remap['compile_s']:.2f} s, "
          f"tpu_custom_call={remap['kernel']}, host argument bytes "
          f"{remap['host_arg']}", flush=True)
    check(remap["kernel"], "no tpu_custom_call in the remapped decode step")
    check(drop >= remap["alpha"] * lb,
          "device parameter bytes did not drop by alpha * layer_bytes")
    # -- reversion: every donated layer back before teardown ------------
    t0 = time.perf_counter()
    eng.drain_for_removal()
    while eng.draining():
        eng.step()
    gc.collect()
    reverted = param_bytes(eng, MODEL)
    print(f"{tag} reverted to the identity plan in "
          f"{time.perf_counter() - t0:.2f} s: param bytes by memory kind "
          f"{reverted}", flush=True)
    check(reverted.get("device") == before.get("device")
          and not reverted.get("pinned_host"),
          "reversion did not bring every layer back to device memory")
    print(f"{tag} peak_bytes_in_use "
          f"{dev.memory_stats()['peak_bytes_in_use']}", flush=True)

    # -- host -> HBM copy rate of one packed layer -----------------------
    host = eng.xfer.host_copy[MODEL]
    on_host = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    on_dev = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")
    layer = jax.device_put(pack_layers(host, [0]).buf, on_host)
    layer.block_until_ready()
    jax.device_put(layer, on_dev).block_until_ready()     # warm-up
    times = []
    for _ in range(COPY_REPS):
        tc = time.perf_counter()
        jax.device_put(layer, on_dev).block_until_ready()
        times.append(time.perf_counter() - tc)
    rate = layer.nbytes / float(np.median(times))
    print(f"{tag} pinned_host->HBM copy of one layer ({layer.nbytes} B): "
          f"median {np.median(times) * 1e3:.3f} ms over {COPY_REPS}, "
          f"{rate / 1e9:.3f} GB/s (HardwareSpec assumes "
          f"{hw.host_link_bw / 1e9:.0f} GB/s)", flush=True)
    del layer

    # -- reference: dense params from the host copy, teacher-forced ------
    cfg = eng.tenants[MODEL].cfg
    head = eng.tenants[MODEL].params
    done = sorted(eng.finished, key=lambda r: r.rid)
    check(len(done) == len(reqs) and all(
        len(r.generated) == args.max_new for r in done),
        "not every request generated max_new tokens")
    del eng, reqs
    gc.collect()
    lm = build_model(cfg).impl
    params = dict(head, blocks=jax.device_put(host, on_dev))
    length = max(r.prompt_len + args.max_new - 1 for r in done)
    length = -(-length // 512) * 512     # whole attention chunks

    @jax.jit
    def logits_at(params, tokens, at):
        x = lm.embed(params, tokens[None])
        pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)[None]
        xo, _, _ = lm.fwd_seq(params, x, {"positions": pos})
        return lm.logits_last(params, xo[0, at]).astype(jnp.float32)

    t0 = time.perf_counter()
    exact = total = 0
    worst = 0.0
    for r in done:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1])])
        tokens = np.zeros((length,), np.int32)
        tokens[:len(seq)] = seq
        at = r.prompt_len - 1 + np.arange(args.max_new)
        lg = np.asarray(logits_at(params, jnp.asarray(tokens),
                                  jnp.asarray(at)))
        for row, tok in zip(lg, r.generated):
            top = float(row.max())
            gap = top - float(row[tok])
            exact += int(row.argmax() == tok)
            total += 1
            worst = max(worst, gap / bf16_ulp(top))
            check(gap <= BF16_ULPS * bf16_ulp(top),
                  f"{r.rid}: token {tok} is {gap} below the max logit "
                  f"{top}, beyond {BF16_ULPS} bf16 ulps")
    print(f"{tag} reference agreement: {exact}/{total} tokens are the "
          f"reference argmax ({exact / total:.4f}); largest gap "
          f"{worst:.2f} bf16 ulps of the max logit (limit {BF16_ULPS}); "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
