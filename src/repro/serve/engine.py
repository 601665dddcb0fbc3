"""Continuous-batching decode engine with LISA-VILLA session caching.

The serving data path is device-resident (the serving-layer analogue of the
paper's "move data over wide internal paths, not the narrow channel"):

  * ``step`` — ONE jitted dispatch and ONE device→host read of the decoded
    tokens per decode step, regardless of how ragged the slot positions are:
    per-slot positions and the active mask are traced data
    (``models/lm.decode_step_batched``), greedy sampling runs in-graph, and
    the KV cache is donated so XLA updates it in place instead of copying it
    every token.  The host reads the device at two more points, each counted
    in ``Engine.stats``: the VILLA policy tags the scheduler's cost scoring
    consults once a tick (``tag_reads``) and each prefill's first token
    (``prefill_reads``); ``host_transfers`` counts the decode reads alone.
  * suspend / resume — planned movement: each is a ``movement.Transfer``
    between the compute tier and the VILLA slow tier, lowered once at engine
    construction by ``movement.plan`` into pack + tier legs and executed
    inside the jitted bodies by ``movement.execute``.  Snapshots live as
    dtype-preserving uint8 *pages* (``serve/paged_store``); the tier legs
    run the paper's exact promotion policy and move pages through the Pallas
    RBM kernels (scalar-prefetched page tables, LIP double buffering).
    ``resume_many`` executes ONE fused wave plan (``movement.fuse``) — a
    whole burst of resumes is still a single dispatch.
  * prefill — lengths are bucketed (next power of two) where the architecture
    permits, bounding compilation count; pads carry sentinel positions so
    they stay causally invisible forever.

The movement is also *accounted*: every plan carries a ``MovementCost``
priced by the engine's :class:`~repro.core.dram.spec.DramSpec` under the
``lisa`` vs ``memcpy`` mechanisms, and each suspend/resume charges its
plan's cost — the serving-level view of Table 1's gap.

Every entry point runs inside a :func:`repro.obs.host.span`
(``repro.submit``, ``repro.step_begin``, ``repro.step_end``, ...), every
jitted call inside ``repro.dispatch`` and every device→host read inside
``repro.read``: with the profiler on, the trace puts the host's time
beside the device's.  Each jit carries its function's name, so its
programs read ``jit_<fn>`` on the device.

Pure-JAX state; greedy sampling; CPU-runnable at reduced configs.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import movement as MV
from repro.configs.base import ModelConfig
from repro.core.dram.spec import DDR3_1600, DramSpec
from repro.core.dram.villa import VillaConfig
from repro.core.lisa import villa_cache as VC
from repro.models import lm
from repro.obs.host import span
from repro.serve import paged_store as PS

POS_SENTINEL = 2**30     # matches the cache init sentinel in models/lm.py


def _named(fn, *args):
    """``partial(fn, *args)`` under ``fn``'s name, so the program a jit of
    it lowers reads ``jit_<fn>`` (a bare partial lowers as
    ``jit__unknown``).  Only the name is copied: ``update_wrapper`` would
    also set ``__wrapped__``, through which ``inspect.signature`` reports
    the parameters the partial has already bound."""
    p = partial(fn, *args)
    p.__name__ = p.__qualname__ = fn.__name__
    return p


def _quiet(fn, *args):
    """Run one donated-buffer dispatch (a ``repro.dispatch`` span), without
    the 'donated buffers were not usable' warning on the CPU backend only:
    CPU XLA cannot honor donation.  On every other backend a dropped
    donation is a real copy of the buffer, so its warning stays visible.
    Scoped per call so other code keeps the diagnostic for its own donation
    mistakes."""
    with span("repro.dispatch", program=fn.__name__):
        if jax.default_backend() != "cpu":
            return fn(*args)
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return fn(*args)


class EngineFull(RuntimeError):
    """No free slot: the caller should drain a slot (or queue) and retry."""


class UnknownSession(KeyError):
    """resume() of a uid that was never suspended (or has been evicted)."""


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new: int
    generated: Optional[List[int]] = None
    # scheduling metadata: when the request entered the system, its priority
    # class (0 = most urgent) and its latency SLO.  Round-trips through
    # repro.sched — `Scheduler.submit_request` admits by these fields, and
    # scheduler-placed requests carry them back out.  Defaults make plain
    # engine use unchanged.
    arrival_ns: float = 0.0
    priority: int = 0
    slo_ns: float = float("inf")


def prefill_insert(cfg: ModelConfig, max_len: int, params, cache, tokens,
                   positions, true_len, slot):
    """Prefill one request and insert its KV into ``slot``: the body of
    :class:`Engine`'s prefill dispatch, returns (next_token scalar, cache).
    ``tokens`` may be right-padded to a bucket length; pads carry sentinel
    positions."""
    cache1 = lm.init_cache(cfg, 1, max_len=max_len)
    logits, cache1 = lm.prefill(cfg, params, tokens, cache1,
                                positions=positions)
    nxt = jnp.argmax(logits[0, true_len - 1]).astype(jnp.int32)
    cache = jax.tree.map(
        lambda full, p: jax.lax.dynamic_update_slice_in_dim(
            full, p.astype(full.dtype), slot, axis=1), cache, cache1)
    return nxt, cache


def default_villa(n_sessions: int) -> VillaConfig:
    """The session store's VILLA tiering when :class:`Engine` is given
    none: a fast tier of a quarter of the sessions (at least 2)."""
    return VillaConfig(n_counters=n_sessions, n_hot=max(n_sessions // 4, 2),
                       n_slots=max(n_sessions // 4, 2), epoch_len=8)


def session_plans(page_spec: PS.PageSpec, villa: VillaConfig,
                  spec: DramSpec):
    """(suspend plan, resume plan): one session's snapshot moving from the
    compute tier into the VILLA slow tier and back, lowered against
    ``spec``."""
    layout = MV.Layout.pages(page_spec)
    return tuple(MV.plan(MV.Transfer(MV.Tier(a), MV.Tier(b), layout,
                                     policy=villa), spec)
                 for a, b in (("compute", "slow"), ("slow", "compute")))


def rounds_f32_dots_to_bf16(device: jax.Device, rows: int) -> bool:
    """Whether a float32 dot of ``rows`` activation rows against a weight
    runs on ``device`` as one bfloat16 pass with float32 accumulation: on a
    TPU at JAX's default matmul precision, when ``rows`` > 1.  XLA turns a
    one-row dot into a float32 multiply-reduce over the float32 weight, so
    a one-slot decode is exact float32 there.  Where it rounds, holding the
    matmul weights in bfloat16 gives the same results without casting them
    from float32 on every step; elsewhere (the CPU's dot is a true float32
    dot) the engine holds them as given."""
    return (device.platform == "tpu" and rows > 1 and
            jax.config.jax_default_matmul_precision in (None, "default",
                                                        "bfloat16"))


def _held_bytes(params, dtype) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(params)
               if leaf.dtype == dtype)


# ---- jitted bodies of Engine (traced slot/store indices; donated buffers) --
def _suspend_body(plan, cache, store, sums, slot, idx):
    env = MV.execute(plan, cache=cache, slot=slot, store=store, item=idx)
    # the pack leg emitted per-page checksums; persist them in the sidecar
    # row for this store index (donated: updated in place)
    return env["store"], sums.at[idx].set(env["sums"])


def _resume_body(plan, cache, store, sums, failed, slot, idx):
    env = MV.execute(plan, cache=cache, store=store, slot=slot, item=idx,
                     sums=sums[idx])
    return env["cache"], env["store"], failed + env["verify_fail"]


def _fused_wave(wave_plans: Dict[tuple, MV.MovementPlan],
                single: MV.MovementPlan, k: int) -> MV.MovementPlan:
    """A whole wave as ONE fused plan (k identical transfers -> one
    pack / batched tier access / scanned unpack: one dispatch), cached in
    ``wave_plans``."""
    key = (id(single), k)
    if key not in wave_plans:
        wave_plans[key] = MV.fuse([single] * k)
    return wave_plans[key]


def _suspend_many_body(plan, wave_plans, cache, store, sums, slots, idxs):
    env = MV.execute(_fused_wave(wave_plans, plan, slots.shape[0]),
                     cache=cache, slots=slots, store=store, items=idxs)
    return env["store"], sums.at[idxs].set(env["sums"])


def _resume_many_body(plan, wave_plans, cache, store, sums, failed, slots,
                      idxs):
    env = MV.execute(_fused_wave(wave_plans, plan, slots.shape[0]),
                     cache=cache, store=store, slots=slots, items=idxs,
                     sums=sums[idxs])
    return env["cache"], env["store"], failed + env["verify_fail"]


def _clone_body(store, sums, src, dst):
    """Shared-row demotion body: clone slow row src -> dst (pages AND
    checksum sidecar) in one dispatch; the fork table repoints the aliases
    right after."""
    return VC.clone_item(store, src, dst), sums.at[dst].set(sums[src])


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_len: int = 128, n_sessions: int = 64,
                 villa: Optional[VillaConfig] = None,
                 spec: DramSpec = DDR3_1600, replica_id: int = 0,
                 device: Optional[jax.Device] = None):
        # every buffer this engine owns is committed to ONE device (default:
        # the first), so its dispatches run there and a replica fleet
        # (serve/cluster.py) spreads one engine per chip
        self.device = device if device is not None else jax.devices()[0]
        put = partial(jax.device_put, device=self.device)
        self.cfg = cfg
        self.params = put(params)
        if rounds_f32_dots_to_bf16(self.device, slots):
            # decode's dots have ``slots`` rows; a prefill's have the
            # prompt's, at least 16 where it is bucketed
            self.params = lm.bf16_matmul_weights(cfg, self.params)
        self.spec = spec
        self.slots = slots
        self.max_len = max_len
        self.n_sessions = n_sessions
        # which replica of a serving fleet this engine is (0 for standalone
        # use); the cluster layer (serve/cluster.py) keys session residence
        # and migration routes on it
        self.replica_id = replica_id
        self.active: Dict[int, Request] = {}        # slot -> request
        self.pos = np.zeros(slots, np.int32)

        with jax.default_device(self.device):
            self.cache = put(lm.init_cache(cfg, slots, max_len=max_len))
        # ONE jitted decode for the whole ragged batch; the cache buffer is
        # donated — XLA writes the new KV in place instead of copying it.
        self._decode = jax.jit(_named(lm.decode_step_batched, cfg),
                               donate_argnums=(1,))
        self._decode_legacy = None      # built on first step_unbatched()

        # Prefill-length bucketing is sound when every layer's cache slot for
        # token t is position-addressed (full attention / MLA): right-padded
        # tokens carry sentinel positions and stay causally invisible, and
        # later decodes overwrite exactly the pad slots.  Ring-buffer windows,
        # scan states (mamba/rwkv), enc-dec and m-rope address by array index
        # or consume pads statefully — those fall back to exact lengths.
        self._can_bucket = (not cfg.encdec and not cfg.mrope and
                            all(k in ("attn_full", "mla")
                                for k in cfg.layer_kinds()))
        self._prefill = jax.jit(_named(prefill_insert, cfg, max_len),
                                donate_argnums=(1,))

        # Session store: suspended KV snapshots as dtype-preserving uint8
        # pages in a VILLA tiered store (movement via the RBM page kernels).
        self.page_spec = PS.PageSpec.for_cache(self.cache)
        self.villa_cfg = villa or default_villa(n_sessions)
        with jax.default_device(self.device):
            self.sessions = put(PS.make_session_store(
                self.page_spec, n_sessions, self.villa_cfg))
        self.session_pos: Dict[int, int] = {}       # uid -> next position
        self.session_tok: Dict[int, int] = {}       # uid -> last emitted token
        self.store_uid: Dict[int, int] = {}         # phys row -> owner uid
        # CoW alias ledger (repro/fork): logical uids -> physical store
        # rows, refcounted.  Forked sessions alias ONE row until a writer
        # diverges; all alias mutation goes through its API (the
        # `unrefcounted-alias` lint rule).  store_uid tracks one
        # representative owner per physical row (the last writer).
        self.forks = PS.make_fork_table()
        # Detection sidecar: per-page checksums of every suspended snapshot,
        # written by the pack leg at suspend time and verified at unpack on
        # resume.  ``verify_failed`` accumulates ON DEVICE — the verdict
        # rides whichever sync a caller already performs (bench/test
        # surface), never adding one to the tick loop.
        self.session_sums = put(jnp.zeros(
            (n_sessions, self.page_spec.n_pages), jnp.uint32))
        self.verify_failed = put(jnp.zeros((), jnp.int32))
        self.fast_degraded = False

        # Every suspend/resume is a planned movement between the compute
        # tier and the VILLA slow tier, lowered ONCE here against the spec;
        # the jitted bodies execute the plans, and each call charges its
        # plan's modeled MovementCost (lisa hop chain vs channel memcpy).
        self.plan_suspend, self.plan_resume = session_plans(
            self.page_spec, self.villa_cfg, spec)
        self._wave_plans: Dict[tuple, MV.MovementPlan] = {}
        # the jitted bodies close over the plans and the wave-plan cache,
        # never over the engine: a jit that held a bound method would tie
        # the engine (and, through adopt_jits, every replica sharing it) in
        # a reference cycle, keeping its device buffers alive until the
        # cyclic collector happens to run
        self._suspend = jax.jit(_named(_suspend_body, self.plan_suspend),
                                donate_argnums=(1, 2))
        self._suspend_many = jax.jit(
            _named(_suspend_many_body, self.plan_suspend, self._wave_plans),
            donate_argnums=(1, 2))
        self._resume = jax.jit(_named(_resume_body, self.plan_resume),
                               donate_argnums=(0, 1, 3))
        self._resume_many = jax.jit(
            _named(_resume_many_body, self.plan_resume, self._wave_plans),
            donate_argnums=(0, 1, 3))
        # shared-row demotion: device-clone one slow row (store + checksum
        # sidecar) so a shared snapshot can yield its index without being
        # destroyed
        self._clone = jax.jit(_clone_body, donate_argnums=(0, 1))
        # Fork fast path: a same-replica ``fork``-kind transfer lowers to
        # ONE page_alias leg — host bookkeeping priced as RowClone FPM on
        # the lisa arm vs the per-session copy it avoids on the memcpy arm
        # (cost.bytes = bytes NOT copied).  A shared-row demotion moves one
        # row's real bytes within the pool, priced under the same alias
        # mechanism (an in-subarray RowClone of one row).
        self.plan_fork = MV.plan(MV.Transfer(
            MV.Tier("slow"), MV.Tier("slow"), MV.Layout.pages(self.page_spec),
            kind="fork"), spec)
        self.plan_demote = self.plan_fork
        self.snapshot_bytes = self.page_spec.total_bytes
        # host_transfers: decode-token reads (one per step); tag_reads and
        # prefill_reads: the scheduler's policy-tag reads and each
        # prefill's first token — every device→host read the engine makes;
        # weight_bytes_*: the params' bytes held in each dtype
        self.stats = {"decoded_tokens": 0, "prefills": 0, "suspends": 0,
                      "resumes": 0,
                      "decode_dispatches": 0, "host_transfers": 0,
                      "tag_reads": 0, "prefill_reads": 0,
                      "evictions": 0, "demotions": 0,
                      "forks": 0, "bytes_not_copied": 0,
                      "modeled_move_ns_lisa": 0.0,
                      "modeled_move_ns_memcpy": 0.0,
                      "weight_bytes_bf16": _held_bytes(self.params,
                                                       jnp.bfloat16),
                      "weight_bytes_f32": _held_bytes(self.params,
                                                      jnp.float32)}
        # observability (repro.obs): fork/CoW/demotion/eviction events are
        # marked as trace instants when a tracer is attached — host
        # bookkeeping only, zero device dispatches
        self.tracer = None
        self.trace_lane = 0

    def attach_tracer(self, tracer, lane: Optional[int] = None) -> None:
        """Attach a :class:`repro.obs.Tracer`; session lifecycle events
        (fork / demotion / eviction) become instants on ``lane`` (the
        scheduler's replica lane convention: ``1 + replica_id``; the
        single-engine scheduler passes nothing and events share lane 0)."""
        self.tracer = tracer
        self.trace_lane = lane if lane is not None else 0

    def _wave_plan(self, single: MV.MovementPlan, k: int) -> MV.MovementPlan:
        """A whole wave as ONE fused plan (see :func:`_fused_wave`)."""
        return _fused_wave(self._wave_plans, single, k)

    # ---- scheduling -------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [s for s in range(self.slots) if s not in self.active]

    def _take_slot(self) -> int:
        free = self.free_slots()
        if not free:
            raise EngineFull(
                f"all {self.slots} slots busy; suspend or finish a request "
                f"first (active uids: {[r.uid for r in self.active.values()]})")
        return free[0]

    def _bucket_len(self, n: int) -> int:
        if not self._can_bucket:
            return n
        return min(max(16, 1 << (n - 1).bit_length()), self.max_len)

    def submit(self, req: Request) -> int:
        slot = self._take_slot()
        n = len(req.prompt)
        if n > self.max_len:
            raise ValueError(f"prompt length {n} exceeds max_len={self.max_len}")
        lb = self._bucket_len(n)
        with span("repro.submit", uid=req.uid, bucket=lb):
            req.generated = []
            toks = np.zeros(lb, np.int32)
            toks[:n] = req.prompt
            if self.cfg.mrope:  # (3,B,S) layout — unbucketed, default arange
                positions = None
            else:
                pos_arr = np.full(lb, POS_SENTINEL, np.int32)
                pos_arr[:n] = np.arange(n)
                positions = jnp.asarray(pos_arr)[None]
            nxt, self.cache = _quiet(
                self._prefill, self.params, self.cache,
                jnp.asarray(toks)[None], positions, jnp.int32(n),
                jnp.int32(slot))
            with span("repro.read", what="prefill_token"):
                req.generated.append(int(nxt))
            self.stats["prefill_reads"] += 1
            self.stats["prefills"] += 1
            self.active[slot] = req
            self.pos[slot] = n
            if len(req.generated) >= req.max_new:
                # a max_new=1 request is completed by the prefill token
                # itself — suspend now instead of letting the next step_end
                # overshoot the budget by one decoded token
                self.suspend(slot)
        return slot

    def step_begin(self):
        """Issue the tick's ONE fused decode dispatch and return the
        in-flight device handle (None when idle).  The dispatch is async:
        the host is free to plan the next scheduling wave while the device
        decodes — the serving analogue of LISA-LIP's linked precharge
        (:mod:`repro.sched.scheduler` overlaps exactly this way).  Pair
        with :meth:`step_end`."""
        if not self.active:
            return None
        with span("repro.step_begin", active=len(self.active)):
            toks = np.zeros(self.slots, np.int32)
            mask = np.zeros(self.slots, bool)
            for s, req in self.active.items():
                toks[s] = req.generated[-1]
                mask[s] = True
            nxt_dev, self.cache = _quiet(
                self._decode, self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(self.pos), jnp.asarray(mask))
            self.stats["decode_dispatches"] += 1
        return nxt_dev

    def step_end(self, handle) -> List:
        """Read one in-flight decode's tokens (the step's device→host read,
        counted in ``host_transfers``), run token bookkeeping, and suspend
        completed requests — a burst completes as ONE fused
        ``suspend_many`` wave.  Returns the ``(slot, request)`` pairs that
        completed this step."""
        if handle is None:
            return []
        with span("repro.step_end") as sp:
            with span("repro.read", what="tokens"):
                nxt = np.asarray(handle)    # waits for the decode to finish
            self.stats["host_transfers"] += 1
            with span("repro.bookkeep"):
                for s in self.active:
                    self.active[s].generated.append(int(nxt[s]))
                    self.pos[s] += 1
                    self.stats["decoded_tokens"] += 1
                done = [s for s, req in self.active.items()
                        if len(req.generated) >= req.max_new]
                completed = [(s, self.active[s]) for s in done]
            sp.set(completed=len(done))
            if len(done) == 1:
                self.suspend(done[0])
            elif done:                    # burst completion: ONE fused wave
                self.suspend_many(done)
        return completed

    def step(self) -> List:
        """Decode one token for every active slot: ONE jitted dispatch and
        ONE device→host read of its tokens, however ragged the slot
        positions are.  Equivalent to ``step_end(step_begin())`` with
        nothing overlapped."""
        return self.step_end(self.step_begin())

    def step_unbatched(self) -> None:
        """A/B-ONLY path — never serve production traffic with it.  Kept
        solely so benchmarks can compare against the pre-batching design:
        splits slots into uniform-position groups — one dispatch per group
        plus one sync per slot.  Equivalent to :meth:`step` ONLY at uniform
        positions: with ragged positions each group's cache write lands in
        every batch row and corrupts the other slots (the latent bug the
        active-mask path fixes).  The drift guard for the real path is
        tests/test_decode_consistency.py::
        test_batched_ragged_decode_parity_with_unbatched, which pins
        ``decode_step_batched`` at ragged positions to per-request
        ``decode_step`` truth (tokens AND cache state)."""
        if not self.active:
            return
        if self._decode_legacy is None:
            self._decode_legacy = jax.jit(_named(lm.decode_step, self.cfg))
        groups: Dict[int, List[int]] = {}
        for s in self.active:
            groups.setdefault(int(self.pos[s]), []).append(s)
        for pos, ss in groups.items():
            toks = np.zeros((self.slots, 1), np.int32)
            for s in ss:
                toks[s, 0] = self.active[s].generated[-1]
            logits, self.cache = _quiet(
                self._decode_legacy, self.params, self.cache,
                jnp.asarray(toks), jnp.int32(pos))
            self.stats["decode_dispatches"] += 1
            for s in ss:
                with span("repro.read", what="tokens"):
                    nxt = int(jnp.argmax(logits[s, 0]))
                self.stats["host_transfers"] += 1
                self.active[s].generated.append(nxt)
                self.pos[s] += 1
                self.stats["decoded_tokens"] += 1
        for s, req in list(self.active.items()):
            if len(req.generated) >= req.max_new:
                self.suspend(s)

    def adopt_jits(self, other: "Engine") -> None:
        """Share ``other``'s jitted entry points and wave-plan cache.

        A replica fleet (serve/cluster.py) runs N engines with identical
        config and geometry; without sharing, each replica would recompile
        the same decode/prefill/suspend/resume programs.  After adoption
        every hot path compiles ONCE for the whole fleet — the serving-
        layer analogue of one shared row-buffer program driving many
        subarrays."""
        if (self.cfg is not other.cfg or self.slots != other.slots
                or self.max_len != other.max_len
                or self.n_sessions != other.n_sessions
                or self.page_spec != other.page_spec
                or self.villa_cfg != other.villa_cfg
                or self.spec != other.spec):
            raise ValueError(
                "adopt_jits needs an identically-configured engine (same "
                "cfg object, slots, max_len, n_sessions, page layout, "
                "villa config and DramSpec — the shared suspend/resume "
                "programs bake in the tier policy and movement pricing)")
        self._decode = other._decode
        self._prefill = other._prefill
        self._suspend = other._suspend
        self._suspend_many = other._suspend_many
        self._resume = other._resume
        self._resume_many = other._resume_many
        self._clone = other._clone
        self._wave_plans = other._wave_plans

    # ---- VILLA session tiering (fork-aware row allocation) ----------------
    def _claim_row(self, uid: int) -> int:
        """Free the home index (uid % n_sessions) for ``uid``'s next write
        and return it.  An EXCLUSIVE occupant is destroy-evicted (legacy
        collision semantics); a SHARED occupant is *demoted* — its bytes
        device-cloned to a free row and every alias repointed — never
        destroyed.  Also the ``alloc`` callback of
        :meth:`~repro.fork.ForkPageTable.write_break`."""
        idx = uid % self.n_sessions
        owner = self.store_uid.get(idx)
        if owner is not None and owner != uid:
            if self.forks.refs.get(idx, 0) > 1:
                self._demote_row(idx)
            else:
                self._evict_row(idx)
        elif owner == uid and idx in self.forks.refs:
            # uid's own home is the shared row it is detaching from:
            # demote it (uid's alias moves along; write_break re-resolves)
            self._demote_row(idx)
        assert idx not in self.forks.refs, (idx, self.forks.refs)
        return idx

    def _evict_row(self, idx: int) -> None:
        """Destroy the exclusive snapshot occupying ``idx``."""
        old = self.store_uid.pop(idx)
        self.session_pos.pop(old, None)
        self.session_tok.pop(old, None)
        if old in self.forks and self.forks.resolve(old) == idx:
            self.forks.release(old)
        self.stats["evictions"] += 1
        if self.tracer is not None:
            self.tracer.instant("evict", lane=self.trace_lane, cat="fork",
                                attrs={"uid": old, "row": idx})

    def _demote_row(self, src: int) -> None:
        """Migrate a SHARED row out of the way: device-clone its pages and
        checksum sidecar to a free row, repoint every alias as one unit
        (refcount preserved).  Shared snapshots are never destroyed by a
        collision — the fork-aware eviction contract."""
        free = [i for i in range(self.n_sessions)
                if i not in self.forks.refs and i not in self.store_uid]
        if not free:
            raise RuntimeError(
                f"store full: cannot demote shared row {src} "
                f"(aliases {self.forks.aliases(src)}); drop a session first")
        dst = free[0]
        self.sessions, self.session_sums = _quiet(
            self._clone, self.sessions, self.session_sums,
            jnp.int32(src), jnp.int32(dst))
        self.forks.repoint(src, dst)
        self.store_uid[dst] = self.store_uid.pop(src)
        self.stats["demotions"] += 1
        self._charge_move(self.plan_demote)
        if self.tracer is not None:
            self.tracer.instant(
                "cow_demote", lane=self.trace_lane, cat="fork",
                attrs={"src_row": src, "dst_row": dst,
                       "ns_lisa": self.plan_demote.cost.ns_lisa,
                       "ns_memcpy": self.plan_demote.cost.ns_memcpy})

    def _own_row(self, uid: int, idx: int) -> None:
        """Post-write bookkeeping: a fresh uid binds its claimed row; any
        row ``uid`` no longer backs is handed to a surviving alias so
        ``store_uid`` always names a live alias of every owned row."""
        if uid not in self.forks:
            self.forks.bind(uid, idx)
        for phys in [p for p, o in self.store_uid.items()
                     if o == uid and p != idx]:
            alts = [a for a in self.forks.aliases(phys) if a != uid]
            if alts:
                self.store_uid[phys] = alts[0]
            else:
                del self.store_uid[phys]
        self.store_uid[idx] = uid

    def _release_row(self, uid: int) -> Optional[int]:
        """Drop ``uid``'s alias claim; returns the physical row iff it was
        the last alias (now reclaimable), else None.  Ownership of a still-
        shared row passes to a surviving alias."""
        phys = self.forks.resolve(uid)
        freed = self.forks.release(uid)
        if freed is not None:
            self.store_uid.pop(freed, None)
        elif self.store_uid.get(phys) == uid:
            self.store_uid[phys] = self.forks.aliases(phys)[0]
        return freed

    # ---- session residence metadata (migration support) -------------------
    def session_meta(self, uid: int) -> tuple:
        """(next position, last emitted token) of a suspended session —
        the host-side bookkeeping a migration must carry along with the
        snapshot pages."""
        if uid not in self.session_pos:
            raise UnknownSession(f"uid {uid} has no suspended session on "
                                 f"replica {self.replica_id}")
        return self.session_pos[uid], self.session_tok[uid]

    def adopt_session(self, uid: int, pos: int, tok: int) -> int:
        """Register an inbound migrated session and return the store index
        its pages must be scattered into (an EXCLUSIVE row — an inbound
        snapshot is materialized bytes, not an alias).  Collisions evict or
        demote explicitly, exactly like a local suspend."""
        if uid in self.forks:
            self._release_row(uid)      # stale claim: re-adoption replaces
        idx = self._claim_row(uid)
        self.forks.bind(uid, idx)
        self.store_uid[idx] = uid
        self.session_pos[uid] = int(pos)
        self.session_tok[uid] = int(tok)
        return idx

    def adopt_alias(self, uid: int, pos: int, tok: int,
                    owner_uid: int) -> int:
        """Register a session as an ALIAS of an already-resident owner
        (snapshot restore of a forked family: the owner's row was restored
        ONCE; each alias re-attaches by bookkeeping alone — zero device
        work, one repair heals every alias).  Returns the shared row."""
        if uid in self.forks:
            self._release_row(uid)
        phys = self.forks.fork_child(owner_uid, uid)
        self.session_pos[uid] = int(pos)
        self.session_tok[uid] = int(tok)
        return phys

    def drop_session(self, uid: int) -> int:
        """Forget a suspended session (its pages migrated away); returns
        the PHYSICAL row the snapshot occupied — for a forked alias that is
        the shared row, which survives for the other aliases.  The bytes in
        the pool are left as-is; an exclusive row is dead until a new
        session claims it."""
        pos = self.session_pos.pop(uid, None)
        if pos is None:
            raise UnknownSession(f"uid {uid} has no suspended session on "
                                 f"replica {self.replica_id}")
        self.session_tok.pop(uid, None)
        if uid not in self.forks:
            return uid % self.n_sessions      # pre-fork legacy bookkeeping
        idx = self.forks.resolve(uid)
        self._release_row(uid)
        return idx

    def _suspend_bookkeep(self, slot: int) -> int:
        """Pop the request off ``slot`` and record its session state;
        returns the uid (row allocation is the caller's CoW write-break)."""
        req = self.active.pop(slot)
        self.session_pos[req.uid] = int(self.pos[slot])
        self.session_tok[req.uid] = req.generated[-1] if req.generated else 0
        self.stats["suspends"] += 1
        return req.uid

    def suspend(self, slot: int) -> None:
        if slot not in self.active:
            raise ValueError(f"slot {slot} has no active request to suspend "
                             f"(active slots: {sorted(self.active)})")
        with span("repro.suspend", uids=self.active[slot].uid, width=1):
            uid = self._suspend_bookkeep(slot)
            # CoW write-break BEFORE the scatter: the `unrefcounted-alias`
            # lint rule requires the refcount API in any function that
            # drives the _suspend scatter.
            idx = (self.forks.write_break(uid, alloc=self._claim_row)
                   if uid in self.forks else self._claim_row(uid))
            self._own_row(uid, idx)
            self.sessions, self.session_sums = _quiet(
                self._suspend, self.cache, self.sessions, self.session_sums,
                jnp.int32(slot), jnp.int32(idx))
            self._charge_move(self.plan_suspend)

    def suspend_many(self, slots: Sequence[int]) -> None:
        """Suspend a wave of slots in ONE dispatch (the dual of
        :meth:`resume_many`): one vmapped page pack + one batched
        write-through through the fused suspend plan."""
        if not slots:
            return
        bad = [s for s in slots if s not in self.active]
        if bad or len(set(slots)) != len(slots):
            raise ValueError(f"suspend wave needs distinct active slots "
                             f"(got {list(slots)}; active: "
                             f"{sorted(self.active)})")
        with span("repro.suspend_many", width=len(slots)) as sp:
            uids = [self._suspend_bookkeep(s) for s in slots]
            sp.set(uids=uids)
            # per-uid CoW write-break (host bookkeeping; the scatter below
            # stays ONE fused dispatch for the whole wave)
            idxs = []
            for uid in uids:
                idx = (self.forks.write_break(uid, alloc=self._claim_row)
                       if uid in self.forks else self._claim_row(uid))
                self._own_row(uid, idx)
                idxs.append(idx)
            self.sessions, self.session_sums = _quiet(
                self._suspend_many, self.cache, self.sessions,
                self.session_sums, jnp.asarray(slots, jnp.int32),
                jnp.asarray(idxs, jnp.int32))
            self._charge_move(self._wave_plan(self.plan_suspend,
                                              len(slots)))

    def _check_resumable(self, uid: int, extra_new: int) -> int:
        for slot, r in self.active.items():
            if r.uid == uid:
                raise ValueError(
                    f"uid {uid} is already active in slot {slot}; suspend it "
                    f"before resuming it again (a second resume would fork a "
                    f"stale snapshot and corrupt suspend bookkeeping)")
        if uid not in self.session_pos:
            raise UnknownSession(
                f"uid {uid} has no suspended session (never suspended, or "
                f"evicted by a store-index collision)")
        pos = self.session_pos[uid]
        if pos + extra_new - 1 > self.max_len:
            # decode step k writes the cache at position pos+k: past max_len
            # the scatter is silently dropped (JAX OOB semantics) and later
            # tokens would attend over a hole — refuse instead of corrupting
            raise ValueError(
                f"uid {uid} is at position {pos}: decoding {extra_new - 1} "
                f"more tokens would write past max_len={self.max_len}; "
                f"clamp extra_new to the context envelope (repro.sched "
                f"truncates follow-ups this way)")
        # the PHYSICAL row: a forked child resumes by gathering straight
        # from the parent's shared row (read-through aliasing)
        return self.forks.resolve(uid)

    def _activate(self, slot: int, uid: int, extra_new: int) -> None:
        req = Request(uid=uid, prompt=np.zeros(0, np.int32), max_new=extra_new)
        req.generated = [self.session_tok[uid]]
        self.active[slot] = req
        self.pos[slot] = self.session_pos[uid]
        if len(req.generated) >= req.max_new:
            # extra_new <= 1: the restored seed token already meets the
            # budget — suspend instead of overshooting by one decode (the
            # resume-path mirror of submit()'s max_new=1 guard)
            self.suspend(slot)

    def resume(self, uid: int, extra_new: int) -> int:
        """Bring a suspended session back: the tiered-store access promotes
        hot sessions to the fast tier (paper policy) — hit rate is the
        serving-level VILLA metric.  One jitted dispatch, no host sync."""
        idx = self._check_resumable(uid, extra_new)
        slot = self._take_slot()
        self.cache, self.sessions, self.verify_failed = _quiet(
            self._resume, self.cache, self.sessions, self.session_sums,
            self.verify_failed, jnp.int32(slot), jnp.int32(idx))
        self._activate(slot, uid, extra_new)
        self.stats["resumes"] += 1
        self._charge_move(self.plan_resume)
        return slot

    def resume_many(self, uids: Sequence[int], extra_new) -> List[int]:
        """Resume a wave of sessions in ONE dispatch: the page tables of all
        sessions drive one batched tiered-store access.  ``extra_new`` is an
        int applied to every session, or a per-uid sequence (the scheduler
        resumes jobs owing different token counts in one fused wave —
        ``extra_new`` is host bookkeeping, never traced, so ragged budgets
        share the single dispatch)."""
        if not uids:
            return []
        if len(set(uids)) != len(uids):
            raise ValueError(f"duplicate uids in resume wave: {list(uids)}")
        extras = ([int(extra_new)] * len(uids)
                  if isinstance(extra_new, (int, np.integer))
                  else [int(e) for e in extra_new])
        if len(extras) != len(uids):
            raise ValueError(f"extra_new sequence has {len(extras)} entries "
                             f"for {len(uids)} uids")
        idxs = [self._check_resumable(u, e) for u, e in zip(uids, extras)]
        free = self.free_slots()
        if len(free) < len(uids):
            raise EngineFull(f"{len(uids)} resumes requested but only "
                             f"{len(free)} slots free")
        slots = free[:len(uids)]
        with span("repro.resume_many", uids=uids, width=len(uids)):
            self.cache, self.sessions, self.verify_failed = _quiet(
                self._resume_many, self.cache, self.sessions,
                self.session_sums, self.verify_failed,
                jnp.asarray(slots, jnp.int32), jnp.asarray(idxs, jnp.int32))
            for slot, uid, extra in zip(slots, uids, extras):
                self._activate(slot, uid, extra)
                self.stats["resumes"] += 1
            self._charge_move(self._wave_plan(self.plan_resume, len(uids)))
        return slots

    def _charge_move(self, plan: MV.MovementPlan) -> None:
        """Account one executed plan under both mechanisms: the running
        totals expose the modeled LISA-vs-memcpy gap at serving
        granularity."""
        self.stats["modeled_move_ns_lisa"] += plan.cost.ns_lisa
        self.stats["modeled_move_ns_memcpy"] += plan.cost.ns_memcpy

    # ---- zero-copy session forking (RowClone analogue) --------------------
    def fork_many(self, parent_uid: int, child_uids: Sequence[int],
                  seed_tokens: Optional[Sequence[int]] = None) -> None:
        """Fork N children off a SUSPENDED parent: each child aliases the
        parent's physical snapshot row (refcount += 1) and inherits its
        position — pure host bookkeeping, ZERO device dispatches (pinned by
        repro.analysis.testlib).  The shared prefix is prefilled once, ever.

        ``seed_tokens`` overrides each child's first decode input (the
        divergence point); default is the parent's last emitted token.  The
        real copy is deferred: a child's first post-fork decode scatters
        only its slot cache, and its next suspend write-breaks onto a row
        of its own — still one fused dispatch per wave.

        Charges one ``fork``-kind movement plan per child (RowClone FPM on
        the lisa arm vs the avoided full-snapshot copy on the memcpy arm)
        and credits ``stats["bytes_not_copied"]``.
        """
        if not child_uids:
            return
        if parent_uid not in self.session_pos:
            raise UnknownSession(
                f"uid {parent_uid} has no suspended session to fork "
                f"(suspend the parent first — fork aliases its snapshot)")
        for slot, r in self.active.items():
            if r.uid == parent_uid:
                raise ValueError(
                    f"parent uid {parent_uid} is active in slot {slot}; "
                    f"suspend it before forking (the snapshot row must be "
                    f"quiescent)")
        if len(set(child_uids)) != len(child_uids):
            raise ValueError(f"duplicate child uids: {list(child_uids)}")
        taken = [c for c in child_uids
                 if c == parent_uid or c in self.session_pos
                 or c in self.forks
                 or any(r.uid == c for r in self.active.values())]
        if taken:
            raise ValueError(f"child uids already in use: {taken}")
        seeds = (list(seed_tokens) if seed_tokens is not None
                 else [self.session_tok[parent_uid]] * len(child_uids))
        if len(seeds) != len(child_uids):
            raise ValueError(f"{len(seeds)} seed tokens for "
                             f"{len(child_uids)} children")
        for child, seed in zip(child_uids, seeds):
            self.forks.fork_child(parent_uid, child)
            self.session_pos[child] = self.session_pos[parent_uid]
            self.session_tok[child] = int(seed)
        fplan = self._wave_plan(self.plan_fork, len(child_uids))
        self._charge_move(fplan)
        self.stats["forks"] += len(child_uids)
        self.stats["bytes_not_copied"] += fplan.cost.bytes
        if self.tracer is not None:
            self.tracer.instant(
                "fork", lane=self.trace_lane, cat="fork",
                attrs={"parent": parent_uid, "children": len(child_uids),
                       "bytes_not_copied": fplan.cost.bytes,
                       "ns_lisa": fplan.cost.ns_lisa,
                       "ns_memcpy": fplan.cost.ns_memcpy})

    def fork(self, parent_uid: int, child_uid: int,
             seed_token: Optional[int] = None) -> None:
        """Fork ONE child — see :meth:`fork_many`."""
        self.fork_many(parent_uid, [child_uid],
                       None if seed_token is None else [seed_token])

    def reseed(self, uid: int, token: int) -> None:
        """Override a suspended session's next decode input (host
        bookkeeping only): the benchmark's fork-OFF arm drives identical
        divergence tokens through independent sessions this way."""
        if uid not in self.session_pos:
            raise UnknownSession(f"uid {uid} has no suspended session")
        for slot, r in self.active.items():
            if r.uid == uid:
                raise ValueError(f"uid {uid} is active in slot {slot}")
        self.session_tok[uid] = int(token)

    def shared_uids(self) -> frozenset:
        """uids whose snapshot row is aliased by at least one other session
        (host dicts only — no device read).  The scheduler treats these as
        the WORST eviction victims and their replicas as preferred fork
        placements."""
        return frozenset(u for u, p in self.forks.phys_of.items()
                         if self.forks.refs[p] > 1)

    def fast_resident_uids(self) -> frozenset:
        """uids whose snapshots are resident in the VILLA fast tier right
        now (one small device→host read of the policy tags, counted in
        ``tag_reads``; it waits for the last dispatch that wrote the store).
        The scheduler consults this for occupancy-aware cost scoring: a
        resident resume is a fast-subarray read, a resident suspend pays the
        write-through to both pools.  A degraded fast tier reports empty —
        every movement is priced at slow-tier cost."""
        if self.fast_degraded:
            return frozenset()
        with span("repro.read", what="tags"):
            tags = np.asarray(self.sessions.policy.tags)
        self.stats["tag_reads"] += 1
        out = set()
        for t in tags:
            t = int(t)
            if t < 0:
                continue
            if t in self.forks.refs:
                # a resident SHARED row makes every alias fast-resident —
                # they all read the same physical pages
                out.update(self.forks.aliases(t))
            elif t in self.store_uid:
                out.add(self.store_uid[t])
        return frozenset(out)

    def hit_rate(self) -> float:
        return float(VC.hit_rate(self.sessions))

    # ---- chaos surface ----------------------------------------------------
    def degrade_fast(self) -> None:
        """Take the VILLA fast tier offline for pricing purposes: tags are
        dropped (nothing to write back — the store is write-through, so the
        slow tier is already current, the fault-model analogue of
        LISA-VILLA's dirty-line writeback being a no-op) and
        :meth:`fast_resident_uids` reports empty from now on, rerouting
        every scheduler cost estimate to slow-tier prices.  Data-path
        correctness is untouched; only the pricing surface degrades."""
        self.fast_degraded = True
        st = self.sessions
        self.sessions = st._replace(policy=st.policy._replace(
            tags=jnp.full_like(st.policy.tags, -1)))

    def corrupt_stored(self, idx: int, page: int, byte: int,
                       xor: int) -> None:
        """Chaos hook: XOR one byte of suspended snapshot ``idx`` at rest —
        in the slow pool AND, if the snapshot is fast-resident, in the fast
        copy (both tiers hold the same rotted bits, as one failing subarray
        would).  The checksum sidecar is deliberately NOT updated: the next
        resume's unpack verify must catch this.  Pure device ops, no host
        sync."""
        P, d = self.page_spec.page_rows, self.page_spec.page_lanes
        row, lane = byte // d, byte % d
        if not (0 <= page < self.page_spec.n_pages and 0 <= row < P):
            raise ValueError(f"corrupt_stored target out of range: "
                             f"page={page}, byte={byte}")
        x = jnp.uint8(xor)
        st = self.sessions
        slow = st.slow.at[idx, page, row, lane].set(
            st.slow[idx, page, row, lane] ^ x)
        tags = st.policy.tags
        hit = jnp.any(tags == idx)
        f = jnp.argmax(tags == idx)
        fast = jnp.where(hit, st.fast.at[f, page, row, lane].set(
            st.fast[f, page, row, lane] ^ x), st.fast)
        self.sessions = st._replace(slow=slow, fast=fast)

    def verify_store(self) -> jax.Array:
        """Scrub: recompute every LIVE suspended snapshot's checksums
        against the sidecar; returns the ON-DEVICE int32 count of corrupt
        PHYSICAL rows.  A shared row is checked ONCE however many sessions
        alias it — one corruption, one detection, and the one repair that
        follows heals every alias.  Callers (the chaos bench's end-of-run
        audit, tests) sync it explicitly — the tick loop never calls
        this."""
        idxs = sorted(i for i, u in self.store_uid.items()
                      if u in self.session_pos
                      or any(a in self.session_pos
                             for a in self.forks.aliases(i)))
        if not idxs:
            return jnp.zeros((), jnp.int32)
        ii = jnp.asarray(idxs, jnp.int32)
        cs = PS.page_checksums(self.sessions.slow[ii])
        mismatch = jnp.any(cs != self.session_sums[ii], axis=-1)
        return jnp.sum(mismatch.astype(jnp.int32))

    def verify_failure_count(self) -> int:
        """Sync the device-side resume-verify counter (bench/test surface —
        one explicit read, outside the tick loop)."""
        return int(self.verify_failed)

    def compile_counts(self) -> Dict[str, int]:
        """Jit-cache sizes of the hot-path entry points (compilations
        seen)."""
        return {name: fn._cache_size() for name, fn in [
            ("decode", self._decode), ("prefill", self._prefill),
            ("suspend", self._suspend), ("resume", self._resume),
            ("suspend_many", self._suspend_many),
            ("resume_many", self._resume_many), ("clone", self._clone)]}
