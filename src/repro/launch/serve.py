"""Multi-tenant serving driver (functional engine, MIRAGE enabled).

  PYTHONPATH=src python -m repro.launch.serve \
      --tenants llama3-8b,h2o-danube-3-4b --mode mirage --requests 12

By default each tenant is a scaled (CPU-runnable) copy of its registry
config. ``--full-width`` runs the registry config at its published widths
and dtype (weights random from ``--seed``); ``--layers`` cuts depth in
either mode. Prints per-request outputs, remap/revert events and transfer
statistics. ``build_engine`` and ``build_requests`` are the one
construction path; ``chip_smoke.py`` drives the engine through them.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_arch, scaled_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving import ServingEngine, TenantConfig
from repro.serving.hw import HardwareSpec, TPU_V5E
from repro.serving.traces import tiny_trace


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", default="llama3-8b,h2o-danube-3-4b")
    ap.add_argument("--mode", default="mirage",
                    choices=["mirage", "vllm", "swap"])
    ap.add_argument("--scheduler", default="temporal",
                    choices=["temporal", "spatial"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--base-pages", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--prompt-lens", default="10",
                    help="comma-separated prompt lengths, cycled per request")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-context", type=int, default=48)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV pool (attention-stack tenants)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="chunked-prefill token budget (0 = monolithic)")
    ap.add_argument("--full-width", action="store_true",
                    help="published widths and dtype instead of scaled")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth cut (default: 4 scaled, published full)")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def tenant_config(name: str, args):
    """A tenant's ModelConfig: scaled, or at its published widths."""
    if args.full_width:
        cfg = get_arch(name)
        if args.layers:
            cfg = dataclasses.replace(cfg, num_layers=args.layers)
        return cfg
    return scaled_config(get_arch(name), num_layers=args.layers or 4)


def build_engine(args, hw: HardwareSpec = TPU_V5E) -> ServingEngine:
    """Tenants with random weights from ``--seed``, handed to the engine
    with no other reference kept, so a remap really frees their HBM."""
    tenants = {}
    for i, n in enumerate(args.tenants.split(",")):
        cfg = tenant_config(n, args)
        params = build_model(cfg).init(jax.random.PRNGKey(args.seed + i))
        tenants[n] = TenantConfig(cfg, params, max_batch=args.max_batch,
                                  max_context=args.max_context,
                                  paged=args.paged)
    return ServingEngine(
        tenants, mode=args.mode, scheduler=args.scheduler, hw=hw,
        base_kv_pages=args.base_pages, page_size=args.page_size,
        prefill_chunk_tokens=args.chunk_tokens)


def build_requests(args, eng: ServingEngine):
    """Requests over the smallest tenant vocabulary (256 when scaled)."""
    names = list(eng.tenants)
    vocab = min(t.cfg.vocab_size for t in eng.tenants.values()) \
        if args.full_width else 256
    return tiny_trace(names, n_per_model=args.requests // len(names),
                      prompt_len=[int(x) for x in args.prompt_lens.split(",")],
                      max_new=args.max_new, vocab=vocab, seed=args.seed)


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    eng = build_engine(args)
    eng.submit(build_requests(args, eng))
    eng.run(max_steps=2000)

    print(f"\n== {args.mode} / {args.scheduler} ==")
    for r in eng.finished:
        print(f"{r.rid:24s} prompt={r.prompt_len:3d} "
              f"out={r.generated[:6]}{'...' if len(r.generated) > 6 else ''} "
              f"preempt={r.preemptions}")
    kinds = {}
    for _, k, _d in eng.events:
        kinds[k] = kinds.get(k, 0) + 1
    print("events:", kinds)
    print("transfer stats:", eng.xfer.stats)
    print("pool segments:", [(s.source, s.num_pages)
                             for s in eng.allocator.segments])
    print("metrics:", eng.metrics().row())
    eng.allocator.check_invariants()
    return eng


if __name__ == "__main__":
    main()
