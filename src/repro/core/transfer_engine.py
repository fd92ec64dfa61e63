"""Async Transfer Engine (paper §4.1/§6) — JAX/TPU realization.

Two cooperating mechanisms:

1. **Compiled in-step streaming** (the TPU-native pipeline): a model's
   stacked layer parameters are split into a *resident* stack (device HBM)
   and a *cycle* stack held in ``pinned_host`` memory. The layer scans run
   each repeat through ``make_fetch``: resident layers dynamic-index the
   device stack; cycling layers dynamic-index the host stack and
   ``jax.device_put`` the slice into device memory *inside* the jitted
   step, and the layer runs inside the branch that fetched it. A copy is
   issued inside its own layer's branch, so it cannot overlap the previous
   layers' compute: the paper's per-layer prefetch pipeline (β buffer
   slots; β is enforced by the feasibility check in ``layer_selection``)
   is not built on the device.

2. **Host-side tier switching**: increasing α *drops* device layers (the
   host copy is the only full copy, as in vLLM) and donates their bytes to
   the KV allocator; Dynamic Reversion restores them over the host link.
   Each switch re-places the stacks from the host copy after releasing the
   old ones, so HBM never holds two resident stacks of one model.
   ``TransferEngine`` does this bookkeeping and accounts every byte moved
   (the benchmarks read these counters).

   Tier switches are **asynchronous**: ``submit_plan`` records the target
   and applies the free direction (drops) immediately; the layers that
   must cross the host link (cycle->resident restores, including
   re-spacing moves when α changes) drain incrementally via
   ``advance(budget_bytes)``, which the serving engine drives once per
   decode step. Mid-drain, ``plans[name]`` / ``fetch_for`` reflect the
   *interim* plan (pending layers stay in the cycle set), so decode stays
   correct at every point of the transition — the first decode step after
   a remap decision no longer serializes on the whole plan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.core.expert_remap import (
    EXPERT_PARAM_KEYS, ExpertPlan, identity_expert_plan, residency_states,
    unit_expert,
)
from repro.core.layer_selection import RemapPlan
from repro.core.transfer_pipeline import PlanDrain, identity_plan
from repro.models.common import LayerFetch


# ---------------------------------------------------------------------------
# stacked-tree split / fetch
# ---------------------------------------------------------------------------

WORD_TILE = 8 * 128     # uint32 words in one (8, 128) TPU tile


def packed_spec(blocks):
    """Static unpacking spec for stacked ``blocks`` (leaves [R, ...]; arrays
    or shapes): each leaf's word offset inside one layer's buffer, padded
    to whole tiles. Returns ((treedef, leaf specs), words per layer)."""
    leaves, treedef = jax.tree.flatten(blocks)
    spec, off = [], 0
    for a in leaves:
        nbytes = int(np.prod(a.shape[1:])) * np.dtype(a.dtype).itemsize
        assert nbytes % 4 == 0, (a.shape, a.dtype)
        spec.append((off, tuple(a.shape[1:]), np.dtype(a.dtype)))
        off += -(-(nbytes // 4) // WORD_TILE) * WORD_TILE
    return (treedef, tuple(spec)), off


@jax.tree_util.register_pytree_node_class
class PackedLayers:
    """Stacked layers held as one uint32 buffer per layer, ``buf`` [m, rows,
    128]. A cycled layer crosses host->HBM as one contiguous copy. The
    plain 2-D tiling also keeps the TPU compiler from giving the host and
    device sides of a leaf such as [d, Hkv, 120] different layouts, which
    it rejects when the stacks are held leaf by leaf."""

    def __init__(self, buf, spec):
        self.buf = buf
        self.spec = spec

    def tree_flatten(self):
        return (self.buf,), self.spec

    @classmethod
    def tree_unflatten(cls, spec, children):
        return cls(children[0], spec)

    def __len__(self) -> int:
        return self.buf.shape[0]

    def layer(self, words):
        """One layer's [rows, 128] words (on device) -> its params tree."""
        flat = words.reshape(-1)
        treedef, leaves = self.spec
        out = []
        for off, shape, dtype in leaves:
            n = int(np.prod(shape)) * dtype.itemsize // 4
            out.append(jax.lax.bitcast_convert_type(
                flat[off:off + n], dtype).reshape(shape))
        return jax.tree.unflatten(treedef, out)


def pack_layers(blocks, sel) -> PackedLayers:
    """Host-side packing of layers ``sel`` of stacked ``blocks``."""
    spec, words = packed_spec(blocks)
    buf = np.zeros((len(sel), words), np.uint32)
    for leaf, (off, _shape, _dt) in zip(jax.tree.leaves(blocks), spec[1]):
        for j, c in enumerate(sel):
            w = np.ascontiguousarray(leaf[c]).reshape(-1).view(np.uint32)
            buf[j, off:off + w.size] = w
    return PackedLayers(buf.reshape(len(sel), words // 128, 128), spec)


def split_blocks(blocks, plan: RemapPlan):
    """Place stacked layer params (leaves [R, ...]) per ``plan``: resident
    layers in the default device's HBM as a stacked tree, cycle layers
    packed (``PackedLayers``) in its ``pinned_host`` memory. ``blocks`` is the
    host copy (numpy); a resident selection of the whole stack in order
    (the identity plan) is placed as is, so registering a tenant makes no
    second device copy. Returns (resident, cycle, index_maps)."""
    device = jax.devices()[0]
    res = np.array(plan.resident_layers, np.int32)
    cyc = np.array(plan.cycle_layers, np.int32)
    r_total = plan.n
    is_resident = np.zeros(r_total, bool)
    is_resident[res] = True
    # position of repeat r inside its stack
    idx_in_stack = np.zeros(r_total, np.int32)
    idx_in_stack[res] = np.arange(len(res))
    idx_in_stack[cyc] = np.arange(len(cyc))

    on_device = SingleDeviceSharding(device, memory_kind="device")
    if np.array_equal(res, np.arange(r_total)):
        resident = jax.device_put(blocks, on_device)
    else:
        resident = jax.tree.map(
            lambda a: jax.device_put(np.asarray(a)[res], on_device), blocks)
    cycle = jax.device_put(
        pack_layers(blocks, cyc),
        SingleDeviceSharding(device, memory_kind="pinned_host"))
    maps = {
        "is_resident": jnp.asarray(is_resident),
        "idx_in_stack": jnp.asarray(idx_in_stack),
    }
    return resident, cycle, maps


def make_fetch(resident, cycle: PackedLayers,
               maps: Dict[str, jax.Array]) -> LayerFetch:
    """Build the per-repeat parameter fetch for the layer scans.

    Resident layers dynamic-index the device stack. A cycle layer's words
    are dynamic-indexed out of the ``pinned_host`` buffer and
    ``jax.device_put`` into device memory inside the traced step, on every
    backend, then unpacked there. A split with both kinds picks the source
    per repeat with a ``lax.cond``; ``LayerFetch.scan`` runs the layer
    inside it, so the resident branch reads its layer in place.
    """
    is_resident = maps["is_resident"]
    idx = maps["idx_in_stack"]

    def from_resident(r):
        i = idx[r]
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            resident)

    def from_cycle(r):
        i = idx[r]
        # the scope names the host->HBM copy and its unpacking in the
        # compiled program's op metadata (and so in the device trace)
        with jax.named_scope("remap_fetch"):
            words = jax.lax.dynamic_index_in_dim(cycle.buf, i,
                                                 keepdims=False)
            return cycle.layer(jax.device_put(words, jax.memory.Space.Device))

    if len(cycle) == 0:                 # degenerate: single-stack fetch
        return LayerFetch(from_resident)
    if _repeats(resident) == 0:
        return LayerFetch(from_cycle)
    return LayerFetch(from_cycle, from_resident, pick=is_resident)


# ---------------------------------------------------------------------------
# host-side engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TransferStats:
    remap_drops_bytes: int = 0          # device bytes donated to KV
    revert_bytes: int = 0               # donation-level restore debt (Δα)
    stream_bytes: int = 0               # per-token cycling transfers
    tier_switches: int = 0
    drain_bytes: int = 0                # host->device bytes moved by advance()


class TransferEngine:
    """Owns per-model (resident, cycle) stacks + the full host copy.

    The host copy (numpy, host DRAM) is the only full copy of a model's
    stacked blocks. The device holds just the resident stack; the cycle
    stack sits in ``pinned_host`` memory and reaches HBM one layer at a
    time inside the step (``make_fetch``).

    ``plans[name]`` is always the plan the *split reflects right now* —
    the interim plan while a submitted tier switch drains, the target once
    ``advance`` has paid for every cycle->resident load.
    """

    def __init__(self):
        self.host_copy: Dict[str, Any] = {}        # full stacked blocks (host)
        self.split: Dict[str, Tuple[Any, Any, Dict[str, jax.Array]]] = {}
        self.plans: Dict[str, RemapPlan] = {}
        self.layer_bytes: Dict[str, int] = {}
        self.pending: Dict[str, PlanDrain] = {}
        self.stats = TransferStats()
        self._target_alpha: Dict[str, int] = {}
        # expert-granular state (MoE tenants; unit = one expert's weights)
        self.expert_host: Dict[str, Any] = {}
        self.expert_unit_bytes: Dict[str, int] = {}
        self.expert_dims: Dict[str, Tuple[int, int]] = {}
        self.expert_plans: Dict[str, ExpertPlan] = {}
        self.expert_pending: Dict[str, PlanDrain] = {}
        self._expert_flat: Dict[str, RemapPlan] = {}

    def register(self, name: str, blocks, layer_bytes: int) -> None:
        """Take a model's stacked blocks. Under the identity plan the
        caller's device stack becomes the resident stack as is; once a
        remap drops it, its HBM is free only if the caller keeps no other
        reference to it."""
        # a copy, never a view: a view would keep the device buffer alive
        self.host_copy[name] = jax.tree.map(np.array, blocks)
        self.layer_bytes[name] = layer_bytes
        self._target_alpha[name] = 0
        self._install(name, identity_plan(_repeats(blocks)), blocks)

    def _install(self, name: str, plan: RemapPlan, blocks=None) -> None:
        """Place the stacks for ``plan`` from ``blocks`` (default: the
        host copy). The old stacks are released first, so HBM never holds
        two resident stacks of one model (at full width two do not fit)."""
        self.plans[name] = plan
        self.split.pop(name, None)
        self.split[name] = split_blocks(
            self.host_copy[name] if blocks is None else blocks, plan)

    def submit_plan(self, name: str, plan: RemapPlan) -> None:
        """Begin an async tier switch. Drops (resident->cycle) happen now;
        loads (cycle->resident) queue behind ``advance``. Re-submitting
        mid-drain transitions from the current interim plan (in-flight
        drain progress is discarded — the superseded loads are re-queued
        if the new target still wants them resident)."""
        cur = self.pending[name].current_plan if name in self.pending \
            else self.plans[name]
        lb = self.layer_bytes[name]
        old_alpha = self._target_alpha[name]
        if plan.alpha > old_alpha:
            self.stats.remap_drops_bytes += (plan.alpha - old_alpha) * lb
        elif plan.alpha < old_alpha:
            self.stats.revert_bytes += (old_alpha - plan.alpha) * lb
        self._target_alpha[name] = plan.alpha
        self.stats.tier_switches += 1
        drain = PlanDrain(cur, plan, lb)
        if drain.done:
            self.pending.pop(name, None)
        else:
            self.pending[name] = drain
        # a reversion's interim IS the current plan — skip the no-op
        # re-split (and the cold-start restart) in that case
        if drain.current_plan != self.plans[name]:
            self._install(name, drain.current_plan)

    def advance(self, name: str, budget_bytes) -> int:
        """Drain up to ``budget_bytes`` of the pending tier switch over the
        host link. The split stays at the interim plan until the LAST
        layer is paid for, then hops to the target in one re-split —
        paid-but-uninstalled layers keep streaming from host (correct,
        conservatively timed) instead of forcing a full re-split and a
        fresh jit executable per layer. Returns the bytes consumed."""
        drain = self.pending.get(name)
        if drain is None:
            return 0
        used, _completed = drain.advance(budget_bytes)
        self.stats.drain_bytes += used
        if drain.done:
            del self.pending[name]
            self._install(name, drain.target)
        return used

    def pending_bytes(self, name: str) -> int:
        """Host->device bytes still owed by an in-flight tier switch."""
        drain = self.pending.get(name)
        return drain.remaining_bytes if drain is not None else 0

    def apply_plan(self, name: str, plan: RemapPlan) -> None:
        """Synchronous tier switch: submit + drain the whole transition."""
        self.submit_plan(name, plan)
        self.advance(name, float("inf"))

    def fetch_for(self, name: str):
        return make_fetch(*self.split[name])

    def note_stream(self, name: str) -> int:
        """Count one decode step's streaming: each of the plan's m cycle
        layers crosses host->HBM as one packed buffer. Returns the bytes
        (from shapes; nothing is read from the device)."""
        n = self.split[name][1].buf.nbytes
        self.stats.stream_bytes += n
        return n

    # ------------------------------------------------------------------
    # expert-granular remapping (MoE tenants)
    # ------------------------------------------------------------------
    # The same PlanDrain state machine and byte counters, at the unit
    # ``unit = moe_layer * num_experts + expert`` (one expert's 3*d*d_expert
    # SwiGLU weights). Residency plans have m == alpha (a donated expert
    # only streams on the steps it is routed to).

    def register_experts(self, name: str, moe_blocks, expert_bytes: int,
                         num_moe_layers: int, num_experts: int) -> None:
        """Register a model's expert-stacked MoE params: tree whose
        EXPERT_PARAM_KEYS leaves have shape [num_moe_layers, num_experts,
        ...] (the ``p["ffn"]`` sub-tree of the stacked blocks)."""
        self.expert_host[name] = moe_blocks
        self.expert_unit_bytes[name] = int(expert_bytes)
        self.expert_dims[name] = (num_moe_layers, num_experts)
        plan = identity_expert_plan(num_moe_layers, num_experts)
        self.expert_plans[name] = plan
        self._expert_flat[name] = plan.to_remap_plan()

    def submit_expert_plan(self, name: str, plan: ExpertPlan) -> None:
        """Begin an async expert-residency switch. Donations (resident ->
        remapped) are free drops; restores queue behind
        ``advance_experts``. Re-submitting mid-drain retargets from the
        interim plan, exactly like ``submit_plan``."""
        L, E = self.expert_dims[name]
        if (plan.num_moe_layers, plan.num_experts) != (L, E):
            raise ValueError("plan shape mismatch")
        flat = plan.to_remap_plan()
        cur = self.expert_pending[name].current_plan \
            if name in self.expert_pending else self._expert_flat[name]
        eb = self.expert_unit_bytes[name]
        old_alpha = cur.alpha if name not in self.expert_pending \
            else self.expert_pending[name].target.alpha
        if flat.alpha > old_alpha:
            self.stats.remap_drops_bytes += (flat.alpha - old_alpha) * eb
        elif flat.alpha < old_alpha:
            self.stats.revert_bytes += (old_alpha - flat.alpha) * eb
        self.stats.tier_switches += 1
        drain = PlanDrain(cur, flat, eb)
        if drain.done:
            self.expert_pending.pop(name, None)
        else:
            self.expert_pending[name] = drain
        if drain.current_plan != self._expert_flat[name]:
            self._expert_flat[name] = drain.current_plan
        self.expert_plans[name] = plan

    def advance_experts(self, name: str, budget_bytes) -> int:
        """Drain up to ``budget_bytes`` of the pending expert restores."""
        drain = self.expert_pending.get(name)
        if drain is None:
            return 0
        used, _ = drain.advance(budget_bytes)
        self.stats.drain_bytes += used
        if drain.done:
            del self.expert_pending[name]
            self._expert_flat[name] = drain.target
        return used

    def expert_residency(self, name: str) -> Dict[str, set]:
        """Partition of flattened expert units into exactly one of
        {resident, remapped, in_flight} under the live interim plan."""
        states = residency_states(self._expert_flat[name],
                                  self.expert_pending.get(name))
        out = {"resident": set(), "remapped": set(), "in_flight": set()}
        for u, s in states.items():
            out[s].add(u)
        return out

    def expert_params_for(self, name: str, absent: str = "host"):
        """Effective MoE params under the live residency. ``absent='host'``
        returns values identical to the dense tree (cold experts stream
        from the host copy — production semantics, bit-exact).
        ``absent='zero'`` zeroes every non-resident expert instead: any
        routed-to cold expert then perturbs the output, so bit-identity
        against the dense run *proves* no routed expert was victimized."""
        tree = self.expert_host[name]
        if absent == "host":
            return tree
        L, E = self.expert_dims[name]
        flat = self._expert_flat[name]
        cold = [unit_expert(u, E) for u in flat.cycle_layers]

        def zero(a):
            out = np.array(a)
            for l, e in cold:
                out[l, e] = 0
            return out

        def walk(t):
            if isinstance(t, dict):
                return {k: (zero(v) if k in EXPERT_PARAM_KEYS else walk(v))
                        for k, v in t.items()}
            if isinstance(t, (tuple, list)):
                out = [walk(v) for v in t]
                return tuple(out) if isinstance(t, tuple) else out
            return t
        return walk(tree)


def _repeats(blocks) -> int:
    leaves = jax.tree.leaves(blocks)
    return leaves[0].shape[0] if leaves else 0
