"""Multi-tenant serving engine: continuous batching + MIRAGE integration.

This is the *functional* runtime (it really executes the models — on CPU
with reduced configs in tests, on TPU unchanged): slot-based continuous
batching per tenant, a shared paged-KV control plane (`PagedKVAllocator`),
and per-iteration Remapping Controller hooks (Algorithm 1). Three memory
modes, matching the paper's comparison:

  * ``mirage`` — KV exhaustion grows the pool from remapped parameter
    memory; decode fetches cycling layers through the Transfer Engine.
  * ``vllm``   — fixed pool; exhaustion preempts the youngest running
    request and recomputes it later (PagedAttention recompute baseline).
  * ``swap``   — Pie-style: pool extends into host memory (functionally a
    growth; the bidirectional-transfer cost is charged by the simulator).

The step index is the engine's clock: arrivals, token times and SLO
targets count steps. Beside it the engine records host-clock spans
(``serving/tracing.py``): ``engine.step`` and, inside it, admission,
prefill chunks, decode (with its page-table round trip and its wait on the
device), memory control and tier switches; per-request ``queued`` and
``prefill`` spans, which split a request's time to first token into its
wait for a slot and its prefill, by rid; and one instant per ``events``
entry, which puts that log on the trace's timeline. Under
``jax.profiler`` all of them land in the trace beside the device's ops.
Output-equivalence of mirage vs vllm modes is what the integration tests
assert — remapping must never change results.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.core import (
    ControllerConfig, MetadataStore, MemoryInfo, ModelInfo,
    PagedKVAllocator, PrefixIndex, RemapDecision, RemappingController,
    TransferEngine, identity_plan, make_fetch,
)
from repro.models import build_model
from repro.models.common import tree_bytes
from repro.serving import tracing
from repro.serving.hw import HardwareSpec, TPU_V5E
from repro.serving.perf_model import PerfModel, kv_bytes_per_token
from repro.serving.request import (
    DECODE_WATERMARK_TOKENS, Request, ServingMetrics,
)
from repro.serving.scheduler import admission_watermark, make_scheduler
from repro.serving.slo import (
    SLOSpec, preemption_victim, runtime_tenant_slack, tier_rank,
)


def make_decode(model, paged: bool, max_context: int):
    """One decode step over a tenant's ``(resident, cycle, maps)`` split:
    every layer comes through ``make_fetch``, so cycle layers cross from
    ``pinned_host`` into HBM inside the step. Params hold no blocks."""
    def step_decode(params, split, state, tokens):
        fetch = make_fetch(*split)
        if paged:
            return model.impl.decode_step_paged(
                params, state, tokens, fetch=fetch)
        return model.decode_step(params, state, tokens, max_context,
                                 fetch=fetch)
    return step_decode       # the device program is named jit_step_decode


def execute_remap_decision(allocator, store, elastic_pages, d, *,
                           drop_cached=None) -> Optional[str]:
    """Execute one ``RemapDecision`` against the paged pool; shared by the
    engine and the controller-fuzz harness so the pool-side invariant is
    testable without tenants. Returns ``"remap"`` / ``"revert"`` when the
    decision took effect, ``"undone"`` when a doomed reversion was rolled
    back in the store, and ``None`` when the decision was a no-op at page
    granularity (e.g. a revert whose pages were already over-released by
    an earlier whole-segment shrink).

    Invariant maintained (asserted by tests after every decision):
    ``elastic_pages[m] == sum of segment pages sourced by m``. The undo
    path must NOT shrink-then-regrow: regrowing mints fresh page ids while
    ``total_pages`` stays put, drifting the segment map away from the
    accounting and leaking ids past any pool sized from it.
    """
    info = store.models[d.model]
    target_pages = d.new_alpha * (info.layer_bytes // store.memory.page_bytes)
    cur = elastic_pages[d.model]
    if target_pages > cur:
        allocator.grow(target_pages - cur, d.model)
        elastic_pages[d.model] = target_pages
        return "remap"
    if target_pages < cur:
        # cached prefix blocks parked in the donated segments would block
        # reversion forever; drop the unreferenced ones first
        if drop_cached is not None:
            drop_cached(d.model)
        if allocator.releasable_pages(d.model) < cur - target_pages:
            # pages still in use: undo the reversion (retry later)
            store.apply_remap(d.model, d.new_alpha + 1)
            return "undone"
        released = allocator.shrink(d.model)
        elastic_pages[d.model] = cur - released
        return "revert"
    return None


@dataclasses.dataclass
class TenantConfig:
    cfg: ModelConfig
    params: Any
    max_batch: int = 8
    max_context: int = 64
    priority: int = 0
    # per-tenant SLO: targets are in ENGINE STEPS (the functional engine's
    # clock); the tier drives victim selection and preemption order
    slo: SLOSpec = dataclasses.field(default_factory=SLOSpec)
    # paged=True: decode reads the elastic paged KV pool through
    # kernels/paged_attention (attention-stack archs only). Pool pages map
    # 1:1 to allocator page ids; a remap tier switch that grows the
    # allocator grows the pool (the donated-memory segments become pages).
    paged: bool = False


class Tenant:
    """Runtime state for one hosted model. ``params`` holds everything but
    the stacked blocks, which the engine's ``TransferEngine`` owns."""

    def __init__(self, name: str, tc: TenantConfig, hw: HardwareSpec):
        self.name = name
        self.cfg = tc.cfg
        self.model = build_model(tc.cfg)
        self.params = {k: v for k, v in tc.params.items() if k != "blocks"}
        self.max_batch = tc.max_batch
        self.max_context = tc.max_context
        self.perf = PerfModel(tc.cfg, hw)
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * tc.max_batch
        self.paged = tc.paged
        self.state = None if tc.paged else \
            self.model.init_decode_state(tc.max_batch, tc.max_context)
        self.decode = jax.jit(
            make_decode(self.model, tc.paged, tc.max_context))

    def init_paged_state(self, total_pages: int, page_size: int):
        """Pool covers every allocator page id + one scratch page (used by
        empty batch slots so their writes never touch live pages)."""
        import jax.numpy as jnp
        cfg = self.cfg
        hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        r = self.model.repeats
        n = -(-self.max_context // page_size)
        dt = jnp.dtype(cfg.dtype)
        scratch = total_pages
        self.state = {
            "pool_k": jnp.zeros((r, total_pages + 1, page_size, hkv, hd), dt),
            "pool_v": jnp.zeros((r, total_pages + 1, page_size, hkv, hd), dt),
            "page_table": jnp.full((self.max_batch, n), scratch, jnp.int32),
            "ctx": jnp.zeros((self.max_batch,), jnp.int32),
        }

    def grow_pool(self, new_total_pages: int):
        import jax.numpy as jnp
        cur = self.state["pool_k"].shape[1] - 1
        add = new_total_pages - cur
        if add <= 0:
            return
        # scratch page stays last: insert new pages before it
        def grow(pool):
            body, scratch = pool[:, :-1], pool[:, -1:]
            pad = jnp.zeros((pool.shape[0], add) + pool.shape[2:], pool.dtype)
            return jnp.concatenate([body, pad, scratch], axis=1)
        # scratch index moves: rewrite empty-slot table entries
        old_scratch, new_scratch = cur, new_total_pages
        pt = self.state["page_table"]
        pt = jnp.where(pt == old_scratch, new_scratch, pt)
        self.state = dict(
            self.state, pool_k=grow(self.state["pool_k"]),
            pool_v=grow(self.state["pool_v"]), page_table=pt)

    def page_row(self, pages) -> np.ndarray:
        """Scratch-padded page-table row: ``pages`` first, every other
        entry the scratch page. THE one encoding of the slot-lifecycle
        invariant (unused entries must absorb writes harmlessly) — all
        row installs and resets go through here."""
        scratch = self.state["pool_k"].shape[1] - 1
        n = self.state["page_table"].shape[1]
        row = np.full((n,), scratch, np.int32)
        row[:len(pages)] = pages
        return row

    # ------------------------------------------------------------- batching
    def free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def running(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def write_slot_state(self, slot: int, new_state) -> None:
        """Insert a prefill result (batch=1 state) into batch slot."""
        self.state = self.model.insert_slot(self.state, slot, new_state)

    def clear_slot(self, slot: int) -> None:
        """Release a batch slot. For paged tenants the slot's page-table
        row and write cursor MUST be reset: the freed pages may be handed
        to another request immediately, and a stale row would make every
        subsequent ``decode_step_paged`` write the dead slot's garbage KV
        into pages the survivor now owns (the slot-lifecycle invariant:
        an empty slot always points at the scratch page with ctx == 0)."""
        self.slots[slot] = None
        if self.paged and self.state is not None:
            self.state = dict(
                self.state,
                page_table=self.state["page_table"].at[slot].set(
                    jnp.asarray(self.page_row([]))),
                ctx=self.state["ctx"].at[slot].set(0),
            )


class ServingEngine:
    def __init__(
        self,
        tenants: Dict[str, TenantConfig],
        *,
        mode: str = "mirage",                      # mirage | vllm | swap
        scheduler: str = "temporal",
        hw: HardwareSpec = TPU_V5E,
        base_kv_pages: int = 64,
        page_size: int = 16,
        runtime: RuntimeConfig = RuntimeConfig(),
        quantum_steps: int = 8,
        prefix_sharing: bool = False,
        prefill_chunk_tokens: int = 0,
        step_tokens: int = 0,
        watermark_tokens: int = DECODE_WATERMARK_TOKENS,
        slack_margin: float = 0.0,
    ):
        """``prefill_chunk_tokens``: > 0 enables token-budget chunked
        prefill for paged tenants — an admitted prompt is computed in
        chunks of at most this many tokens per engine step, interleaved
        with decode of the other slots (0 = monolithic prefill, the
        original behaviour). ``step_tokens``: scheduler-visible per-step
        token budget; decode tokens are charged first, prefill chunks
        consume the remainder (0 = unlimited). ``watermark_tokens``:
        decode headroom reserved per running request at admission, shared
        with the simulator via ``DECODE_WATERMARK_TOKENS``.
        ``scheduler="slo"`` enables slack-driven scheduling over each
        tenant's ``TenantConfig.slo`` (targets in engine steps);
        ``slack_margin`` is the urgency threshold in steps."""
        assert mode in ("mirage", "vllm", "swap")
        self.mode = mode
        self.hw = hw
        self.runtime = runtime
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        self.watermark_tokens = int(watermark_tokens)
        self.slo_specs: Dict[str, SLOSpec] = {
            n: tc.slo for n, tc in tenants.items()}
        # slack is only worth computing when some tenant has a real SLO:
        # with every spec at the all-inf default, every slack is inf and
        # both consumers (scheduler urgency, victim ordering) ignore it
        self._slo_enabled = any(
            s != SLOSpec() for s in self.slo_specs.values())
        self.tenants = {n: Tenant(n, tc, hw) for n, tc in tenants.items()}
        self.allocator = PagedKVAllocator(base_kv_pages, page_size)
        # HBM one allocator page id costs: every paged tenant holds a pool
        # over every page id (with none, the KV a page of tokens would
        # take in the dense tenants' caches)
        kv_owners = [t for t in self.tenants.values() if t.paged] \
            or list(self.tenants.values())
        page_bytes = page_size * sum(
            kv_bytes_per_token(t.cfg, t.cfg.dtype_bytes) for t in kv_owners)
        self.store = MetadataStore(MemoryInfo(
            hbm_bytes=hw.hbm_bytes, page_bytes=max(page_bytes, 1),
            base_kv_pages=base_kv_pages))
        self.xfer = TransferEngine()
        for n, t in self.tenants.items():
            unit_bytes = max(tree_bytes(
                t.model.specs()["blocks"]) // t.model.repeats, 1)
            self.store.register(ModelInfo(
                name=n, num_layers=t.model.repeats, layer_bytes=unit_bytes,
                priority=tenants[n].priority,
                max_remap_fraction=runtime.max_remap_fraction,
                slo_tier=tenants[n].slo.tier))
            self.xfer.register(n, tenants[n].params["blocks"], unit_bytes)
        self.controller = RemappingController(
            self.store,
            ControllerConfig(
                victim_policy=runtime.victim_policy,
                double_buffer=runtime.double_buffer,
                dynamic_reversion=runtime.dynamic_reversion,
                reversion_hysteresis=runtime.reversion_hysteresis,
            ),
            {n: t.perf.t_transfer_unit for n, t in self.tenants.items()},
        )
        self.scheduler = make_scheduler(
            scheduler, list(self.tenants), quantum_steps=quantum_steps,
            step_tokens=step_tokens, specs=self.slo_specs,
            slack_margin=slack_margin)
        self._reversion_base = self.controller.cfg.dynamic_reversion
        self.step_idx = 0
        self._incoming: deque[Request] = deque()
        self.finished: List[Request] = []
        self.events: List[Tuple[int, str, str]] = []   # (step, kind, detail)
        self._elastic_pages: Dict[str, int] = {n: 0 for n in self.tenants}
        # prefix sharing rides the paged pool only: dense tenants keep
        # per-slot KV state, which has nothing shareable.
        self.prefix: Dict[str, PrefixIndex] = {
            n: PrefixIndex(page_size) for n, tc in tenants.items()
            if prefix_sharing and tc.paged}
        self._prefix_path: Dict[str, list] = {}   # rid -> acquired trie path
        # fleet prefix cache hooks (cluster layer): publish listener and
        # a sequence for synthetic import-allocation request ids
        self._prefix_listener = None
        self._import_seq = 0
        for t in self.tenants.values():
            if t.paged:
                from repro.models.lm import layer_defs
                assert all(ld.mixer == "attn" for ld in
                           layer_defs(t.cfg)), \
                    f"paged mode needs an attention stack: {t.name}"
                t.init_paged_state(self.allocator.total_pages, page_size)

    # --------------------------------------------- API (ServingRuntime)
    def submit(self, reqs: List[Request]) -> None:
        """Enqueue arrivals (append-safe incremental ``merge_arrivals``:
        the cluster router feeds requests as their steps come due)."""
        from repro.serving.runtime import merge_arrivals
        for r in reqs:
            tracing.begin("request.queued", r.rid, tenant=r.model)
        self._incoming = merge_arrivals(self._incoming, reqs)

    def busy(self) -> bool:
        return bool(self._incoming or any(
            t.queue or t.running() for t in self.tenants.values()))

    def tick(self) -> float:
        """Advance one scheduling iteration; returns the elapsed steps —
        1.0 normally, more when the idle fast-forward jumped the clock
        across an arrival gap."""
        before = self.step_idx
        self.step()
        return float(self.step_idx - before)

    def _idle_jump(self) -> int:
        """Steps the idle fast-forward would skip before the next step:
        with no queued/running work and no pending transfer drain (each
        step drains one unit — skipping steps would freeze it), empty
        steps are unobservable and the clock may jump so the next step
        admits the head arrival at its usual ceil(arrival) step index."""
        if self._incoming and not self.xfer.pending and not any(
                t.queue or t.running() for t in self.tenants.values()):
            nxt = int(np.ceil(self._incoming[0].arrival)) - 1
            if nxt > self.step_idx:
                return nxt - self.step_idx
        return 0

    def horizon(self) -> float:
        """Arrival horizon of the next tick: ``step()`` advances the
        clock (through the idle fast-forward, if it applies) *before*
        admitting, so requests with arrival <= that post-advance clock
        are admitted in the upcoming iteration."""
        return float(self.step_idx + self._idle_jump()) + 1.0

    def pressure(self) -> float:
        """KV pool pressure in [0, 1] (used page fraction)."""
        return self.allocator.used_pages / max(self.allocator.total_pages, 1)

    def inflight(self) -> int:
        """Requests submitted but not finished (cluster-router load)."""
        return len(self._incoming) + sum(
            len(t.queue) + len(t.running()) for t in self.tenants.values())

    def draining(self) -> bool:
        """A remap/revert tier switch is mid-drain in the TransferEngine."""
        return bool(self.xfer.pending)

    def tenant_slacks(self) -> Dict[str, float]:
        """Live per-tenant SLO slack in ENGINE STEPS."""
        return self._slo_slack(float(self.step_idx))

    def set_reversion_enabled(self, enabled: bool) -> None:
        """Gate *new* Dynamic Reversion decisions (coordinated remap:
        a cluster policy staggers revert drains across replicas). The
        gate can only RESTRICT: a runtime built with reversion disabled
        stays disabled no matter what a cluster policy grants."""
        self.controller.cfg.dynamic_reversion = \
            enabled and self._reversion_base

    # ------------------------------------------- fleet prefix cache hooks
    def set_prefix_listener(self, cb) -> None:
        """Install ``cb(model, tokens, now)``, invoked on every prefix
        publish (the cluster layer points this at
        ``FleetPrefixCache.publish``; ``now`` is in engine steps)."""
        self._prefix_listener = cb

    def prefix_probe(self, model: str, tokens) -> int:
        """Non-mutating longest-cached-prefix length in tokens — what a
        fleet fetch verifies against before trusting a possibly-stale
        fleet index entry."""
        idx = self.prefix.get(model)
        return idx.peek(tokens) if idx is not None else 0

    def prefix_costs(self, model: str, span_tokens: int,
                     prompt_tokens: int):
        """(bytes, t_fetch_s, t_recompute_s) for importing a cached
        ``span_tokens`` prefix of a ``prompt_tokens`` prompt
        (``PerfModel.prefix_transfer_costs``)."""
        return self.tenants[model].perf.prefix_transfer_costs(
            span_tokens, prompt_tokens)

    def export_prefix(self, model: str, tokens, n_tokens: int):
        """Gather the real KV of the leading cached blocks of ``tokens``
        (up to ``n_tokens``) for a peer replica: returns ``(k, v)`` page
        arrays of shape ``(repeats, blocks, page_size, kv_heads, head_dim)``
        or None when nothing is cached. Uses ``match`` (LRU-refreshing,
        stats-free): an export IS a use of those blocks."""
        idx = self.prefix.get(model)
        t = self.tenants.get(model)
        if idx is None or t is None or not t.paged or t.state is None:
            return None
        ps = self.allocator.page_size
        nblk = max(int(n_tokens), 0) // ps
        if nblk <= 0:
            return None
        m = idx.match(tokens, max_tokens=nblk * ps, record=False)
        if not m.pages:
            return None
        pages = np.asarray(m.pages[:nblk])
        return (np.asarray(t.state["pool_k"][:, pages]),
                np.asarray(t.state["pool_v"][:, pages]))

    def import_prefix(self, model: str, tokens, n_tokens: int,
                      kv=None) -> int:
        """Install a peer's exported prefix KV into the local paged pool
        as refcounted CoW cache pages — exactly like a local prefix fork:
        fresh pages are allocated, the KV bytes land in ``pool_k/pool_v``,
        the blocks enter the prefix index, and the cache takes the one
        reference that keeps them alive (``cache_hold``). Blocks already
        cached locally are skipped (only the delta is imported). Returns
        the tokens imported."""
        idx = self.prefix.get(model)
        t = self.tenants.get(model)
        if idx is None or t is None or not t.paged or t.state is None \
                or kv is None:
            return 0
        k, v = kv
        ps = self.allocator.page_size
        nblk = min(max(int(n_tokens), 0), len(tokens),
                   k.shape[1] * ps) // ps
        have = idx.peek(tokens, max_tokens=nblk * ps) // ps
        if nblk <= have:
            return 0
        new_blocks = nblk - have
        self._import_seq += 1
        rid = f"__prefix_import_{self._import_seq}"
        pages = self.allocator.allocate(rid, new_blocks * ps)
        if pages is None:
            self._reclaim(new_blocks - self.allocator.free_pages)
            pages = self.allocator.allocate(rid, new_blocks * ps)
            if pages is None:
                return 0
        arr = jnp.asarray(np.asarray(pages))
        t.state = dict(
            t.state,
            pool_k=t.state["pool_k"].at[:, arr].set(
                jnp.asarray(k[:, have:nblk])),
            pool_v=t.state["pool_v"].at[:, arr].set(
                jnp.asarray(v[:, have:nblk])),
        )
        # the trie path beyond block ``have`` cannot exist locally (trie
        # property: a missing block severs every deeper node on the path),
        # so insert consumes exactly our fresh pages
        page_seq = [-1] * have + list(pages)
        new_pages, _path = idx.insert(tokens, page_seq,
                                      max_tokens=nblk * ps)
        assert new_pages == list(pages), (new_pages, pages)
        self.allocator.cache_hold(new_pages)
        self.allocator.free(rid)
        self._event("prefix-import", f"{model} blocks={len(new_pages)}")
        return len(new_pages) * ps

    def prefix_snapshot(self, max_blocks: int = 0):
        """Every maximal cached prefix as ``(model, tokens)`` pairs — the
        donor side of scale-out pre-warm (non-mutating; ``max_blocks``
        bounds the total blocks, 0 = unbounded). The joining replica
        imports each span through ``export_prefix``/``import_prefix``, so
        the real KV pages cross with it."""
        out = []
        budget = max_blocks if max_blocks > 0 else None
        for n, idx in self.prefix.items():
            paths = idx.paths(budget)
            if budget is not None:
                budget -= sum(len(p) // idx.page_size for p in paths)
            out.extend((n, p) for p in paths)
        return out

    # ------------------------------------------- replica lifecycle hooks
    def withdraw_pending(self) -> List[Request]:
        """Pull back every submitted-but-not-yet-admitted arrival so the
        cluster layer can respill it to another replica at scale-in.
        Requests already admitted (queued/running) finish here."""
        out = list(self._incoming)
        self._incoming.clear()
        return out

    def drain_for_removal(self) -> None:
        """Force reversion of every donated parameter segment before
        teardown (the drain-before-teardown invariant): pages are
        released back level by level — exactly the controller's one-step
        revert semantics, including the cached-prefix drop and the
        pages-in-use undo — and the restored layers' host->device traffic
        drains through the TransferEngine one unit per step. Idempotent;
        call once the replica's inflight work is gone."""
        if self.mode != "mirage":
            return
        for name, info in self.store.models.items():
            progressed = False
            while info.remapped_alpha > 0:
                target = info.remapped_alpha - 1
                self.store.apply_remap(name, target)
                d = RemapDecision(name, target,
                                  identity_plan(info.num_layers),
                                  reverted=True)
                out = execute_remap_decision(
                    self.allocator, self.store, self._elastic_pages, d,
                    drop_cached=self._drop_cached_in_segments
                    if self.prefix else None)
                if out == "undone":
                    break       # pages still in use: retry next tick
                progressed = True
            if progressed:
                self.xfer.submit_plan(name, identity_plan(info.num_layers))
                self._event("revert-teardown", name)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        while self.step_idx < max_steps and self.busy():
            self.step()
        if self.busy():
            warnings.warn(
                f"ServingEngine.run: step budget ({max_steps}) exhausted "
                f"with {self.inflight()} requests still unfinished — they "
                "are not in the returned list; see metrics().unfinished",
                RuntimeWarning, stacklevel=2)
        return self.finished

    # ----------------------------------------------------------------- step
    def step(self) -> None:
        # idle fast-forward (mirrors the simulator's): empty steps are
        # unobservable, so jump the clock across an arrival gap —
        # admission lands on the same ceil(arrival) step index the
        # one-by-one walk would reach, and a lagging cluster replica
        # heals in one tick instead of gating fleet dispatch. Gated on
        # xfer.pending: a pending tier switch drains one unit per step,
        # so the gap is walked normally until the drain completes.
        self.step_idx += self._idle_jump()
        self.step_idx += 1
        with tracing.span("engine.step", step=self.step_idx) as sp:
            self._step(float(self.step_idx))
            # the allocator gauge, read on the host after the step
            sp.set(kv_used=self.allocator.used_pages,
                   kv_total=self.allocator.total_pages)

    def _step(self, now: float) -> None:
        # 1. admit arrivals (functional time: step index)
        while self._incoming and self._incoming[0].arrival <= now:
            r = self._incoming.popleft()
            self.tenants[r.model].queue.append(r)
        # 2. schedule — live SLO slack feeds both the scheduler (EDF
        # urgency) and the MetadataStore (victim/reversion ordering)
        if self._slo_enabled:
            slacks = self._slo_slack(now)
            self.store.note_slack(slacks)
            self.scheduler.observe_slack(slacks)
        pending = {n: len(t.queue) for n, t in self.tenants.items()}
        running = {n: len(t.running()) for n, t in self.tenants.items()}
        active = self.scheduler.schedule(pending, running, now)
        self.store.mark_active(active)
        self.store.note_kv_usage(self.allocator.used_pages)
        # 3. per active tenant: admit prefills, run prefill chunks under
        # the scheduler's token budget, then decode one token
        pressure = False
        for name in active:
            pressure |= self._admit(self.tenants[name])
        # decode tokens are charged against the step budget first so a
        # chunking tenant can never starve decode-heavy tenants
        decode_tokens = sum(
            1 for name in active for r in self.tenants[name].running()
            if not r.prefilling)
        budget = self.scheduler.prefill_budget(decode_tokens)
        for name in active:
            budget -= self._prefill_step(self.tenants[name], budget)
        for name in active:
            pressure |= self._decode(self.tenants[name])
        # 4. MIRAGE / baseline memory management
        self._memory_control(pressure)
        # 5. async apply queue: pending tier switches drain one remap unit
        # per step (the link carries about one layer per iteration), so a
        # decision's first decode step never serializes on the whole plan
        for n, info in self.store.models.items():
            if n in self.xfer.pending:
                with tracing.span("xfer.advance", tenant=n) as sp:
                    sp.set(bytes=self.xfer.advance(n, info.layer_bytes))

    def _event(self, kind: str, detail: str) -> None:
        """One entry of the step-indexed ``events`` log, and its host-clock
        instant."""
        self.events.append((self.step_idx, kind, detail))
        tracing.instant("event." + kind, step=self.step_idx, detail=detail)

    # ------------------------------------------------------------- internals
    def _slo_slack(self, now: float) -> Dict[str, float]:
        """Per-tenant slack in ENGINE STEPS: one decode == one step, and a
        chunked prefill takes ceil(remaining prompt / chunk) steps to first
        token — lowered into the shared ``runtime_tenant_slack`` helper
        (the simulator lowers PerfModel seconds into the same helper;
        slack ordering is unit-invariant)."""
        chunk = self.prefill_chunk_tokens
        out = {}
        for n, t in self.tenants.items():
            def steps_left(remaining_tokens, chunked=t.paged and chunk > 0):
                if chunked:
                    return float(-(-max(remaining_tokens, 1) // chunk))
                return 1.0

            head = t.queue[0] if t.queue else None
            running = t.running()
            out[n] = runtime_tenant_slack(
                self.slo_specs[n], now, t.queue,
                [r for r in running if not r.prefilling],
                [r for r in running if r.prefilling],
                t_first_head=steps_left(head.prompt_len)
                if head is not None else 1.0,
                t_next=1.0,
                t_first_remaining=lambda r, sl=steps_left: sl(
                    r.prompt_len - r.prefill_pos))
        return out

    def _t_compute(self) -> Dict[str, float]:
        """Per-model T_c fed to the controller's pipeline-feasibility cap
        (§5.3). Uses the LIVE mean context of the running batch — a fixed
        ``max_context / 2`` guess would freeze the α cap while contexts
        grow and decode actually slows down."""
        out = {}
        for n, t in self.tenants.items():
            running = t.running()
            batch = max(len(running), 1)
            info = self.store.models[n]
            if info.active:
                ctx = (sum(r.total_len for r in running) / len(running)) \
                    if running else t.max_context / 2
                out[n] = t.perf.decode_step_time(batch, ctx) \
                    / t.model.repeats
            else:
                out[n] = t.perf.prefill_time(512) / t.model.repeats
        return out

    def _memory_control(self, pressure: bool) -> None:
        if self.mode == "vllm":
            return  # recompute handled at allocation sites
        with tracing.span("engine.memory_control", pressure=int(pressure)):
            if self.mode == "swap":
                if pressure:
                    seg = self.allocator.grow(16, "host-swap")
                    for t in self.tenants.values():
                        if t.paged:
                            t.grow_pool(self.allocator.page_id_bound)
                    self._event("swap-grow", f"{seg.num_pages}")
                return
            decisions = self.controller.step(
                kv_pressure=pressure, t_compute=self._t_compute())
            for d in decisions:
                self._apply_decision(d)

    def _drop_cached_in_segments(self, model: str) -> None:
        cand = [p for seg in self.allocator.segments
                if seg.source == model
                for p in self.allocator.segment_cached(seg)]
        for idx in self.prefix.values():
            dropped = idx.evict_pages(cand, evictable=self._cache_only)
            if dropped:
                self.allocator.cache_drop(dropped)

    def _apply_decision(self, d: RemapDecision) -> None:
        with tracing.span("engine.tier_switch", tenant=d.model,
                          alpha=d.new_alpha,
                          pages_before=self.allocator.total_pages) as sp:
            outcome = execute_remap_decision(
                self.allocator, self.store, self._elastic_pages, d,
                drop_cached=self._drop_cached_in_segments
                if self.prefix else None)
            sp.set(outcome=str(outcome))
            if outcome not in ("remap", "revert"):
                return
            self.xfer.submit_plan(d.model, d.plan)
            if outcome == "remap":
                for t in self.tenants.values():  # donated memory: pages
                    if t.paged:
                        t.grow_pool(self.allocator.page_id_bound)
            sp.set(pages_after=self.allocator.total_pages)
            self._event(outcome, f"{d.model} a={d.new_alpha}")

    # -------------------------------------------------------------- prefill
    def _admit(self, t: Tenant) -> bool:
        if not t.queue:
            return False
        with tracing.span("engine.admit", tenant=t.name) as sp:
            n = len(t.running())
            pressure = self._admit_queue(t)
            sp.set(admitted=len(t.running()) - n)
        return pressure

    def _admit_queue(self, t: Tenant) -> bool:
        pressure = False
        idx = self.prefix.get(t.name)
        while t.queue:
            r = t.queue[0]
            slot = t.free_slot()
            if slot is None:
                break
            # longest cached prefix (full pages; at least the final token is
            # always recomputed so prefill produces the first logits).
            # Acquiring the path pins it against our own cache eviction
            # below; released again if admission fails on capacity.
            # a preempt-inflated prompt can outgrow a fixed pool entirely;
            # mirage/swap pools can still grow, but in vllm mode the
            # request is unserveable — drop it (simulator's starvation
            # guard, mirrored) instead of livelocking the tenant
            if self.mode == "vllm" and \
                    self.allocator.pages_needed(r.prompt_len + 1) \
                    > self.allocator.total_pages:
                t.queue.popleft()
                r.finished = True
                self.finished.append(r)
                tracing.discard("request.queued", r.rid)
                self._event("drop-unserveable", r.rid)
                continue
            match = None
            if idx is not None:
                match = idx.match(r.prompt, max_tokens=r.prompt_len - 1,
                                  record=False)
                idx.acquire(match.nodes)
            matched_pages = len(match.pages) if match else 0
            # shared admission watermark (scheduler.admission_watermark):
            # decode headroom per running request, lowered to allocator
            # pages here and to KV bytes in the simulator
            reserve = admission_watermark(
                sum(len(x.running()) for x in self.tenants.values()),
                self.watermark_tokens, self.allocator.pages_needed)
            need = self.allocator.pages_needed(r.prompt_len + 1) \
                - matched_pages + reserve
            if need > self.allocator.free_pages:
                # unreferenced cached blocks are the low-pressure free-page
                # source, reclaimed before the controller escalates
                self._reclaim(need - self.allocator.free_pages)
            if need > self.allocator.free_pages and match and match.tokens:
                # the pinned match may hold the only reclaimable pages:
                # give up the match (prefix recomputes) and reclaim again
                idx.release(match.nodes)
                match = None
                need = self.allocator.pages_needed(r.prompt_len + 1) + reserve
                self._reclaim(need - self.allocator.free_pages)
            if need > self.allocator.free_pages:
                if match:
                    idx.release(match.nodes)
                pressure = True
                break
            if match:
                idx.record_lookup(match.tokens, r.prompt_len)
            if match and match.tokens:
                self.allocator.fork(r.rid, match.pages, match.tokens)
                self._prefix_path[r.rid] = list(match.nodes)
                r.prefix_matched_tokens += match.tokens
            elif match:
                idx.release(match.nodes)
            assert self.allocator.allocate(
                r.rid, r.prompt_len + 1 - (match.tokens if match else 0)
            ) is not None
            t.queue.popleft()
            # chunked prefill needs the paged pool to hold partial-prompt
            # KV between steps; multimodal prefixes (patch embeds / audio
            # frames) shift positions and keep the monolithic path.
            if t.paged and self.prefill_chunk_tokens > 0 \
                    and not t.cfg.num_image_patches \
                    and not t.cfg.is_encoder_decoder:
                self._begin_chunked_prefill(t, r, slot)
            else:
                self._prefill(t, r, slot)
        return pressure

    def _cache_only(self, p: int) -> bool:
        """Page is held by the prefix cache alone (no live request maps it)."""
        return self.allocator.refs.get(p) == 1 and p in self.allocator.cached

    def _reclaim(self, need_pages: int) -> int:
        """Evict unreferenced cached prefix blocks (leaf-first LRU) to free
        pages — tried before remapping (mirage) or preemption (vllm).
        Best-effort tenants' caches are drained before latency-critical
        ones: a cold cache miss is the cheapest place to take pressure,
        and the best-effort tier is who should take it."""
        freed = 0
        by_tier = sorted(self.prefix.items(), key=lambda kv: (
            tier_rank(self.slo_specs[kv[0]].tier), kv[0]))
        for name, idx in by_tier:
            if freed >= need_pages:
                break
            pages = idx.evict(need_pages - freed, evictable=self._cache_only)
            if pages:
                freed += self.allocator.cache_drop(pages)
                self._event("cache-evict", f"{name} n={len(pages)}")
        return freed

    def _prefill(self, t: Tenant, r: Request, slot: int) -> None:
        self._slot_taken(r, slot)
        prompt = jnp.asarray(r.prompt[None, :])
        batch = {"tokens": prompt}
        if t.cfg.is_encoder_decoder:
            rng = np.random.default_rng(abs(hash(r.rid)) % (2**31))
            frames = rng.standard_normal(
                (1, min(t.cfg.max_source_len, 32), t.cfg.d_model)) * 0.02
            batch["frames"] = jnp.asarray(frames, jnp.float32)
        if t.cfg.num_image_patches:
            rng = np.random.default_rng(abs(hash(r.rid)) % (2**31))
            batch["patch_embeds"] = jnp.asarray(rng.standard_normal(
                (1, t.cfg.num_image_patches, t.cfg.d_model)) * 0.02, jnp.float32)
        if t.paged:
            logits = self._prefill_paged(t, r, slot, batch)
        else:
            logits, state1 = t.model.prefill(
                t.params, batch, t.max_context,
                fetch=self.xfer.fetch_for(t.name))
        tok = int(jnp.argmax(logits[0]))
        t.slots[slot] = r
        r.slot = slot
        if not t.paged:
            t.write_slot_state(slot, state1)
        r.generated.append(tok)
        r.t_first_token = float(self.step_idx)
        r.token_times.append(float(self.step_idx))
        tracing.end("request.prefill", r.rid, tokens=r.prompt_len)
        self._event("prefill", r.rid)

    @staticmethod
    def _slot_taken(r: Request, slot: int) -> None:
        """A request leaves the queue for a batch slot: its wait ends and
        its prefill begins."""
        tracing.end("request.queued", r.rid, slot=slot)
        tracing.begin("request.prefill", r.rid, tenant=r.model)

    def _prefill_paged(self, t: Tenant, r: Request, slot: int, batch):
        """Prefill and scatter the KV into this request's allocator pages.

        With prefix sharing, the leading ``seq_shared`` pages were forked
        from the cache and already hold this prefix's KV (same tokens, same
        params, same absolute positions => identical values); only the
        unmatched suffix is scattered, and shared pages are never written
        (the CoW invariant). The forward itself still runs full-length —
        the functional engine owns correctness, the simulator owns the
        prefill-FLOP savings."""
        lm = t.model.impl
        prompt = batch["tokens"]
        x = lm.embed(t.params, prompt, batch.get("patch_embeds"))
        b, s = prompt.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        xo, _, caches = lm.fwd_seq(t.params, x, {"positions": positions},
                                   collect_cache=True,
                                   fetch=self.xfer.fetch_for(t.name))
        logits = lm.logits_last(t.params, xo[:, -1])
        pages = self.allocator.seq_pages[r.rid]
        page_size = self.allocator.page_size
        pt_row = t.page_row(pages)
        shared = self.allocator.seq_shared.get(r.rid, 0)
        if shared:
            m = shared * page_size
            caches = ({"k": caches[0]["k"][:, :, m:],
                       "v": caches[0]["v"][:, :, m:]},)
            scat_row = t.page_row(pages[shared:])
        else:
            scat_row = pt_row
        st1 = lm.paged_state_from_prefill(
            caches, jnp.full((1,), s, jnp.int32), jnp.asarray(scat_row[None]),
            t.state["pool_k"].shape[1], page_size,
            pool_k=t.state["pool_k"], pool_v=t.state["pool_v"])
        t.state = dict(
            t.state,
            pool_k=st1["pool_k"], pool_v=st1["pool_v"],
            page_table=t.state["page_table"].at[slot].set(jnp.asarray(pt_row)),
            ctx=t.state["ctx"].at[slot].set(s),
        )
        self._publish(t, r, np.asarray(r.prompt))
        return logits

    # ----------------------------------------------------- chunked prefill
    def _begin_chunked_prefill(self, t: Tenant, r: Request, slot: int) -> None:
        """Admit ``r`` into ``slot`` without running any compute yet: pages
        are already allocated for the full prompt (+1 decode token), the
        slot's page-table row is installed, and the write cursor starts at
        the CoW-shared prefix (those pages already hold this prefix's KV).
        ``_prefill_step`` then advances the prompt in bounded chunks."""
        self._slot_taken(r, slot)
        t.slots[slot] = r
        r.slot = slot
        r.prefilling = True
        r.prefill_pos = self.allocator.seq_shared.get(r.rid, 0) \
            * self.allocator.page_size
        row = t.page_row(self.allocator.seq_pages[r.rid])
        t.state = dict(
            t.state,
            page_table=t.state["page_table"].at[slot].set(jnp.asarray(row)),
            ctx=t.state["ctx"].at[slot].set(r.prefill_pos),
        )

    def _prefill_step(self, t: Tenant, budget: int) -> int:
        """Advance every prefilling request of ``t`` by one chunk of at
        most ``prefill_chunk_tokens`` (and at most the remaining scheduler
        token budget). Returns the prompt tokens consumed. A request whose
        last chunk completes emits its first token here and — since
        ``_decode`` runs after this in the same ``step()`` with
        ``prefilling`` now cleared — decodes its second token in the same
        step, exactly like the monolithic path (prefill + first decode in
        one step); that same-step decode is required for bit-identity."""
        spent = 0
        for r in [x for x in t.slots if x is not None and x.prefilling]:
            chunk = min(self.prefill_chunk_tokens, budget - spent,
                        r.prompt_len - r.prefill_pos)
            if chunk <= 0:
                continue
            with tracing.span("engine.prefill_chunk", tenant=t.name,
                              rid=r.rid, start=r.prefill_pos, tokens=chunk):
                tokens = jnp.asarray(
                    r.prompt[r.prefill_pos:r.prefill_pos + chunk])
                logits, t.state = t.model.impl.prefill_chunk_paged(
                    t.params, t.state, r.slot, tokens, r.prefill_pos,
                    fetch=self.xfer.fetch_for(t.name))
                r.prefill_pos += chunk
                spent += chunk
                if r.prefill_pos < r.prompt_len:
                    continue
                r.prefilling = False
                with tracing.span("engine.prefill_wait"):
                    tok = int(jnp.argmax(logits))
                r.generated.append(tok)
                r.t_first_token = float(self.step_idx)
                r.token_times.append(float(self.step_idx))
                tracing.end("request.prefill", r.rid, tokens=r.prompt_len)
                self._publish(t, r, np.asarray(r.prompt))
                self._event("prefill", r.rid)
        return spent

    def _publish(self, t: Tenant, r: Request, tokens: np.ndarray) -> None:
        """Register this request's fully written KV pages in the prefix
        index so later requests can fork them (cache takes one reference
        per newly published page)."""
        idx = self.prefix.get(t.name)
        if idx is None:
            return
        pages = self.allocator.seq_pages.get(r.rid, [])
        new_pages, path = idx.insert(tokens, pages)
        if new_pages:
            self.allocator.cache_hold(new_pages)
        # the request now depends on its full path (matched + own blocks)
        old = self._prefix_path.pop(r.rid, None)
        if old:
            idx.release(old)
        if path:
            idx.acquire(path)
            self._prefix_path[r.rid] = path
            if self._prefix_listener is not None:
                self._prefix_listener(t.name, tokens, float(self.step_idx))

    # --------------------------------------------------------------- decode
    def _decode(self, t: Tenant) -> bool:
        # mid-prefill slots hold no decodable token yet: they are skipped
        # here and advanced by _prefill_step instead
        reqs = [r for r in t.running() if not r.prefilling]
        if not reqs:
            return False
        pressure = False
        # page for the next token of every running request
        for r in reqs:
            if r.slot < 0 or t.slots[r.slot] is not r:
                # evicted by a _preempt_one earlier in this same loop
                # (vllm victim): it is queued again — allocating for it
                # here would leave a stale 1-token mapping behind
                continue
            if self.allocator.allocate(r.rid, 1) is None:
                # cached prefix blocks are the cheapest pages to reclaim —
                # drop cold ones before remapping/preempting
                if self._reclaim(1) and \
                        self.allocator.allocate(r.rid, 1) is not None:
                    continue
                pressure = True
                if self.mode == "vllm":
                    if self._preempt_one(exclude=r.rid) and \
                            self.allocator.allocate(r.rid, 1) is not None:
                        pressure = False
                        continue
                    self._preempt(r)  # could not make room: preempt r itself
                else:
                    # mirage/swap: grow synchronously then retry once
                    self._memory_control(True)
                    if self.allocator.allocate(r.rid, 1) is not None:
                        continue
                    self._preempt(r)
        reqs = [r for r in t.running() if not r.prefilling]
        if not reqs:
            return pressure
        tokens = np.zeros((t.max_batch,), np.int32)
        for r in reqs:
            tokens[r.slot] = r.generated[-1]
        # the interim plan mid-drain keeps pending layers in the cycle set,
        # so the remapped fetch path stays consistent through a tier switch
        host_bytes = self.xfer.note_stream(t.name)
        with tracing.span("engine.decode", tenant=t.name, batch=len(reqs),
                          m=self.xfer.plans[t.name].m,
                          host_bytes=host_bytes):
            if t.paged:
                # per-token page allocations land in the allocator; sync
                # the running slots' page-table rows before the step
                with tracing.span("engine.page_table_sync"):
                    pt = np.asarray(t.state["page_table"]).copy()
                    for r in reqs:
                        pt[r.slot] = t.page_row(
                            self.allocator.seq_pages[r.rid])
                    t.state = dict(t.state, page_table=jnp.asarray(pt))
            logits, t.state = t.decode(
                t.params, self.xfer.split[t.name], t.state,
                jnp.asarray(tokens))
            with tracing.span("engine.decode_wait"):
                choices = np.asarray(jnp.argmax(logits, axis=-1))
        for r in list(reqs):
            r.generated.append(int(choices[r.slot]))
            r.token_times.append(float(self.step_idx))
            if len(r.generated) >= r.max_new_tokens or \
                    r.total_len >= t.max_context - 1:
                self._finish(t, r)
        return pressure

    # ------------------------------------------------------------ preemption
    def _preempt_one(self, exclude: str = "") -> bool:
        """vLLM recompute baseline: the shared ``preemption_victim``
        choice (youngest running, best-effort tenants first)."""
        r = preemption_victim(
            (r for t in self.tenants.values() for r in t.running()
             if r.rid != exclude), self.slo_specs)
        if r is None:
            return False
        self._preempt(r)
        return True

    def _release_prefix(self, r: Request) -> None:
        idx = self.prefix.get(r.model)
        path = self._prefix_path.pop(r.rid, None)
        if idx is not None and path:
            idx.release(path)

    def _preempt(self, r: Request) -> None:
        t = self.tenants[r.model]
        self._release_prefix(r)
        self.allocator.free(r.rid)
        t.clear_slot(r.slot)
        r.preemptions += 1
        # recompute: prompt + generated becomes the new prompt
        r.prompt = np.concatenate(
            [r.prompt, np.asarray(r.generated, np.int32)])
        r.generated = []
        r.slot = -1
        # a mid-prefill victim restarts its prompt from scratch (the
        # partially scattered KV died with its pages)
        r.prefilling = False
        r.prefill_pos = 0
        t.queue.appendleft(r)
        tracing.discard("request.prefill", r.rid)
        tracing.begin("request.queued", r.rid, tenant=r.model)
        self._event("preempt", r.rid)

    def _finish(self, t: Tenant, r: Request) -> None:
        # publish the conversation so the next turn's prompt (this prompt +
        # this response) forks the whole history. KV exists for the prompt
        # plus all generated tokens except the last (emitted, never fed
        # back); only fully written pages are publishable.
        if self.prefix.get(t.name) is not None and len(r.generated) > 1:
            toks = np.concatenate([
                np.asarray(r.prompt, np.int64),
                np.asarray(r.generated[:-1], np.int64)])
            self._publish(t, r, toks)
        self._release_prefix(r)
        self.allocator.free(r.rid)
        t.clear_slot(r.slot)
        r.finished = True
        self.finished.append(r)
        self._event("finish", r.rid)

    # ---------------------------------------------------------------- report
    def metrics(self) -> ServingMetrics:
        m = ServingMetrics.from_requests(
            self.finished, makespan=float(self.step_idx))
        m.unfinished = self.inflight()
        return m

    def tier_metrics(self) -> Dict[str, ServingMetrics]:
        """Tail metrics per SLO tier (engine-step clock)."""
        return ServingMetrics.per_tier(
            self.finished, self.slo_specs, makespan=float(self.step_idx))

    def prefix_stats(self) -> Dict[str, Any]:
        """Per-tenant prefix-cache counters (empty when sharing is off)."""
        return {n: dataclasses.asdict(idx.stats)
                | {"cached_blocks": idx.num_blocks}
                for n, idx in self.prefix.items()}
