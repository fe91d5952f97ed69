"""Continuous-batching request scheduler over the Engine decode path.

State machine (docs/serving.md): requests enter a FIFO **admission
queue** stamped with arrival ticks; a free decode **slot** triggers a
single-request **prefill** (batch 1, the request's actual prompt length)
whose cache is inserted into the pooled decode cache at that slot; all
occupied slots then advance together through batched **decode** steps
with per-slot positions and per-slot kv lengths — the ragged
``grouped_matmul``/``valid_rows`` path bills exactly the valid rows, so
a half-empty batch is visibly half-billed.  A sequence that has emitted
its budget **drains**: one final step absorbs its last token's KV (the
cache-consistency invariant ``generate`` relies on), then the slot frees
for the next queued request mid-flight.

Time is a virtual clock: one tick per batched decode step,
``prefill_ticks`` per prefill.  Everything host-side is deterministic —
FIFO by ``(arrival, rid)``, lowest free slot wins, greedy argmax decode —
so a seeded arrival trace pins the full admit/prefill/finish event log.

Spans (docs/serving.md): ``step`` and the work inside it run under
``jax.profiler.TraceAnnotation`` spans named ``serve.*`` (the ``SPAN_*``
constants), which land in the profiler's host plane on the device trace's
clock when a profiler runs, and cost about a microsecond each when none
does.  Each request's ``RequestResult`` carries its host-clock queue wait
(``queue_s``), which its ``serve.prefill`` span repeats as ``queued_ms``.

Resilience layer (docs/serving.md failure model): requests may carry a
``deadline_ticks`` budget — expired work is evicted whether queued or
mid-decode (the drain invariant makes mid-flight eviction safe: the
cache is always consistent with the emitted sequence, so freeing the
slot never poisons the pool).  ``SchedulerConfig.max_queue`` bounds the
admission queue, rejecting overflow with a structured
:class:`~repro.serving.resilience.Rejection` carrying a ``retry_after``
backpressure hint, and an optional
:class:`~repro.serving.resilience.ShedPolicy` deterministically drops
deadline-infeasible / lowest-priority queued work under overload.  A
:class:`~repro.runtime.fault_tolerance.FailureInjector` with a serving
mode exercises the detectors: a per-step NaN/inf guard on decode logits
and per-slot KV checksums audited every ``audit_every`` decode steps.
Recovery quarantines the poisoned slot and rebuilds its cache by
re-prefilling ``prompt + emitted_tokens`` — sufficient by the drain
invariant, and bit-identical on FP16 because the decode-built cache
equals the full-prefill cache bitwise (pinned by
``test_generate_cache_consistent_with_emitted_sequence``).  Recovery
overlaps the virtual clock (co-resident ticks are unaffected); its cost
is billed as waste slot-ticks in the
:class:`~repro.serving.resilience.ServeGoodputMeter`.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.models import transformer
from repro.runtime import sharding
from repro.serving import kv_cache, resilience

__all__ = [
    "Request", "SchedulerConfig", "RequestResult", "Scheduler",
    "instrumented_decode_events",
]

_span = jax.profiler.TraceAnnotation
# Span names; the benchmark's trace readers match them, so a rename
# silences them.
SPAN_STEP = "serve.step"             # all of Scheduler.step()
SPAN_ADMIT = "serve.admit"           # evict/admit/shed; queued = queue after
SPAN_PREFILL = "serve.prefill"       # one request: prefill, insert, 1st token
SPAN_DECODE = "serve.decode"         # one batched decode step
SPAN_FETCH = "serve.decode.fetch"    # waits for decode, logits to the host
SPAN_SAMPLE = "serve.decode.sample"  # NaN guard, argmax, slot bookkeeping
SPAN_RECOVER = "serve.recover"       # one slot's cache rebuild


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    arrival: float          # ticks
    prompt: np.ndarray      # (P,) int32 token ids
    max_new_tokens: int
    deadline_ticks: Optional[float] = None  # budget relative to arrival
    priority: int = 0       # higher survives load shedding longer

    @property
    def deadline(self) -> Optional[float]:
        if self.deadline_ticks is None:
            return None
        return self.arrival + self.deadline_ticks


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    n_slots: int = 4
    max_len: int = 64
    storage_dtype: Optional[str] = None  # e.g. "float8_e4m3fn" (FP8 KV cache)
    prefill_ticks: float = 1.0
    max_queue: Optional[int] = None      # bounded admission; None = unbounded
    audit_every: int = 0                 # KV checksum cadence; 0 = off
    shed: Optional[resilience.ShedPolicy] = None


@dataclasses.dataclass
class RequestResult:
    rid: int
    arrival: float
    first_token_tick: Optional[float] = None
    finish_tick: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    final_logits: Optional[np.ndarray] = None  # P(next token | full sequence)
    status: str = "pending"  # pending|finished|rejected|expired|shed
    submitted_s: Optional[float] = None  # host perf_counter() at submit
    queue_s: Optional[float] = None      # host s, submit to prefill start

    @property
    def ttft(self) -> float:
        if self.first_token_tick is None:
            return float("nan")
        return self.first_token_tick - self.arrival

    @property
    def tokens_per_tick(self) -> float:
        if self.finish_tick is None:
            return float("nan")
        return len(self.tokens) / max(self.finish_tick - self.arrival, 1e-9)


@dataclasses.dataclass
class _Slot:
    rid: int
    pos: int        # next cache write position == rows currently valid
    emitted: int    # tokens emitted so far
    fed: int        # emitted tokens whose KV has been absorbed
    max_new: int
    last_token: int
    prompt: np.ndarray = dataclasses.field(default_factory=lambda: np.zeros(0))
    deadline: Optional[float] = None    # absolute tick
    priority: int = 0


class Scheduler:
    """FIFO admission → per-request prefill → pooled continuous decode."""

    def __init__(self, params, cfg, scfg: SchedulerConfig,
                 rules: Optional[sharding.Rules] = None,
                 injector=None):
        if cfg.block_kind not in ("attn", "moe"):
            raise ValueError(
                f"the serving scheduler drives attn/moe decode caches, "
                f"not {cfg.block_kind!r}")
        if scfg.n_slots < 1:
            raise ValueError("need at least one decode slot")
        if (injector is not None and injector.mode == "kv_corrupt"
                and scfg.audit_every < 1):
            raise ValueError(
                "kv_corrupt injection needs audit_every >= 1 — silent "
                "corruption with the checksum audit off is undetectable")
        self.params, self.cfg, self.scfg = params, cfg, scfg
        self.rules = rules
        self.injector = injector
        self.clock = 0.0
        self.decode_steps = 0
        self.prefill_count = 0
        self.compute_dtype = cfg.policy.compute_dtype
        self.cache = transformer.init_cache(
            cfg, scfg.n_slots, scfg.max_len, dtype=self.compute_dtype,
            storage_dtype=scfg.storage_dtype)
        self.slots: List[Optional[_Slot]] = [None] * scfg.n_slots
        self.pending: List[Request] = []       # submitted, arrival in future
        self.queue: deque = deque()            # admitted, waiting for a slot
        self.trace: List[Tuple] = []           # (event, tick, rid, ...)
        self.health: List[Dict[str, float]] = []
        self.results: Dict[int, RequestResult] = {}
        self.rejections: List[resilience.Rejection] = []
        self.guards: Dict[int, resilience.SlotGuard] = {}
        self.goodput = resilience.ServeGoodputMeter(n_slots=scfg.n_slots)
        self._prefills: Dict[int, Any] = {}
        self._recover_prefills: Dict[int, Any] = {}

        def _decode(params_, cache_, tokens_, pos_, sizes_):
            with sharding.use_rules(rules), engine.op_scope("serve_decode"):
                return transformer.serve_step(
                    params_, cfg, tokens_, cache_, pos_,
                    kv_group_sizes=sizes_)

        def _insert(pool_, single_, slot_):
            with engine.op_scope("serve_admit"):
                return kv_cache.insert_slot(
                    pool_, single_, slot_, self.compute_dtype)

        def _recover_decode(params_, cache_, tokens_, pos_, sizes_):
            # batch-1 replay of the poisoned step over the rebuilt cache
            with sharding.use_rules(rules), engine.op_scope("serve_recover"):
                return transformer.serve_step(
                    params_, cfg, tokens_, cache_, pos_,
                    kv_group_sizes=sizes_)

        def _recover_insert(pool_, single_, slot_):
            with engine.op_scope("serve_recover"):
                return kv_cache.insert_slot(
                    pool_, single_, slot_, self.compute_dtype)

        self._decode = jax.jit(_decode, donate_argnums=(1,))
        self._insert = jax.jit(_insert, donate_argnums=(0,))
        self._recover_decode = jax.jit(_recover_decode)
        self._recover_insert = jax.jit(_recover_insert, donate_argnums=(0,))

    # ----------------------------------------------------------------- #
    # Admission
    # ----------------------------------------------------------------- #
    def _reject(self, r: Request, reason: str,
                retry_after: Optional[float]) -> None:
        self.rejections.append(resilience.Rejection(
            rid=r.rid, tick=self.clock, reason=reason,
            retry_after=retry_after))
        self.results[r.rid] = RequestResult(
            rid=r.rid, arrival=r.arrival, status="rejected")
        self.goodput.on_reject()
        self.trace.append(("reject", self.clock, r.rid, reason))

    def submit(self, requests: Sequence[Request]) -> None:
        """Validate and enqueue; an invalid request is rejected per-request
        (structured ``Rejection``, ``retry_after=None`` — retrying cannot
        help) and never aborts the rest of the batch."""
        accepted = []
        for r in requests:
            if r.max_new_tokens < 1:
                self._reject(r, "invalid", None)
                continue
            if len(r.prompt) + r.max_new_tokens > self.scfg.max_len:
                self._reject(r, "oversized", None)
                continue
            self.results[r.rid] = RequestResult(
                rid=r.rid, arrival=r.arrival, submitted_s=time.perf_counter())
            accepted.append(r)
        self.pending.extend(accepted)
        self.pending.sort(key=lambda r: (r.arrival, r.rid))

    def _expire(self, r: Request, where: str) -> None:
        res = self.results[r.rid]
        res.status = "expired"
        self.goodput.on_expire(0)
        self.trace.append(("expire", self.clock, r.rid, where))

    def _admit(self) -> None:
        while self.pending and self.pending[0].arrival <= self.clock:
            r = self.pending.pop(0)
            if r.deadline is not None and self.clock >= r.deadline:
                self._expire(r, "pending")
                continue
            if self.scfg.max_queue is not None:
                # free slots count toward capacity: _start drains the queue
                # into them this very step, so only truly waiting work is
                # held against the bound
                cap = self.scfg.max_queue + sum(
                    1 for s in self.slots if s is None)
                if len(self.queue) >= cap:
                    self._reject(r, "queue_full", resilience.retry_after_hint(
                        len(self.queue), self.scfg.prefill_ticks))
                    continue
            self.queue.append(r)
            self.trace.append(("admit", self.clock, r.rid))

    def _admission(self, *work) -> None:
        """Run admission work under one ``serve.admit`` span."""
        with _span(SPAN_ADMIT) as span:
            for fn in work:
                fn()
            span.set_metadata(queued=len(self.queue))

    def _shed(self) -> None:
        # runs after _start: only work still *waiting* once the free slots
        # were handed out is candidate shed material
        if self.scfg.shed is None or not self.queue:
            return
        victims = self.scfg.shed.select_shed(
            list(self.queue), self.clock, self.scfg.prefill_ticks)
        if not victims:
            return
        vids = {r.rid for r in victims}
        self.queue = deque(r for r in self.queue if r.rid not in vids)
        for r in sorted(victims, key=lambda v: v.rid):
            res = self.results[r.rid]
            res.status = "shed"
            self.goodput.on_shed()
            self.trace.append(("shed", self.clock, r.rid))

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _evict_expired(self) -> None:
        """Deadline enforcement: drop expired work, queued or mid-decode.

        Mid-flight eviction is safe under the drain invariant — the slot's
        cache rows always equal ``prompt + emitted[:fed]``, so freeing it
        leaves the pool consistent; tokens already emitted are billed as
        waste."""
        for i, s in enumerate(self.slots):
            if s is None or s.deadline is None or self.clock < s.deadline:
                continue
            res = self.results[s.rid]
            res.status = "expired"
            self.goodput.on_expire(len(res.tokens))
            self.trace.append(("evict", self.clock, s.rid, i))
            self.slots[i] = None
            self.guards.pop(i, None)
        if self.queue:
            keep: deque = deque()
            for r in self.queue:
                if r.deadline is not None and self.clock >= r.deadline:
                    self._expire(r, "queued")
                else:
                    keep.append(r)
            self.queue = keep

    # ----------------------------------------------------------------- #
    # Prefill (disaggregated: batch 1, the request's real prompt length)
    # ----------------------------------------------------------------- #
    def _prefill_fn(self, plen: int, *, recover: bool = False):
        table = self._recover_prefills if recover else self._prefills
        if plen not in table:
            cfg, scfg, rules = self.cfg, self.scfg, self.rules
            scope = "serve_recover" if recover else "serve_prefill"

            def pre(params_, prompt_):
                with sharding.use_rules(rules), engine.op_scope(scope):
                    return transformer.prefill(
                        params_, cfg, {"inputs": prompt_}, scfg.max_len,
                        storage_dtype=scfg.storage_dtype)

            table[plen] = jax.jit(pre)
        return table[plen]

    def _guarded_prefill(self, prompt: jax.Array, rid: int):
        """One prefill dispatch with crash-injection + single retry.

        ``prefill_crash`` counts prefill attempts; the injector's one-shot
        latch guarantees the retry runs clean, so a crashed prefill costs
        one extra prefill's worth of waste slot-ticks and nothing else."""
        self.prefill_count += 1
        pre = self._prefill_fn(prompt.shape[1])
        try:
            if (self.injector is not None and self.injector.fires(
                    self.prefill_count, "prefill_crash")):
                raise RuntimeError("injected prefill crash")
            return pre(self.params, prompt)
        except RuntimeError:
            self.trace.append(("prefill_retry", self.clock, rid))
            self.goodput.on_recovery(self.scfg.prefill_ticks)
            return pre(self.params, prompt)

    def _start(self) -> None:
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                return
            r = self.queue.popleft()
            if r.deadline is not None and self.clock >= r.deadline:
                # expired while a co-resident prefill moved the clock
                self._expire(r, "queued")
                continue
            res = self.results[r.rid]
            res.queue_s = time.perf_counter() - res.submitted_s
            prompt_np = np.asarray(r.prompt, np.int32)
            with _span(SPAN_PREFILL, rid=r.rid, plen=len(prompt_np),
                       queued_ms=1e3 * res.queue_s):
                prompt = jnp.asarray(prompt_np)[None]
                logits, single = self._guarded_prefill(prompt, r.rid)
                self.cache = self._insert(self.cache, single, jnp.int32(slot))
                tok = int(jnp.argmax(logits[0]))
                self.clock += self.scfg.prefill_ticks
                res.first_token_tick = self.clock
                res.tokens.append(tok)
                self.slots[slot] = _Slot(
                    rid=r.rid, pos=prompt.shape[1], emitted=1, fed=0,
                    max_new=r.max_new_tokens, last_token=tok,
                    prompt=prompt_np, deadline=r.deadline,
                    priority=r.priority)
                self._arm_guards()
                self.trace.append(
                    ("prefill", self.clock, r.rid, slot, prompt.shape[1]))
            # the clock moved; later arrivals may be due now
            self._admission(self._admit)

    # ----------------------------------------------------------------- #
    # Integrity: checksum guards, quarantine, slot rebuild
    # ----------------------------------------------------------------- #
    def _arm_guards(self) -> None:
        """(Re)checksum every occupied slot after a cache mutation.

        Re-arming must be global, not per-slot: under FP8 ratcheted
        delayed scaling any insert may requantize the *whole* pool, so a
        guard armed before someone else's admission would false-positive.
        """
        if self.scfg.audit_every < 1:
            return
        self.guards = {
            i: resilience.SlotGuard(
                rid=s.rid, length=s.pos,
                checksum=kv_cache.slot_checksum(self.cache, i, s.pos))
            for i, s in enumerate(self.slots) if s is not None}

    def _audit_slots(self) -> None:
        """Compare every armed guard; quarantine + rebuild mismatches.

        All checksums are compared *before* any rebuild: a rebuild's
        insert may ratchet the FP8 pool scale and requantize co-resident
        slots, which would trip their still-armed guards spuriously."""
        bad = []
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            g = self.guards.get(i)
            if g is None or g.rid != s.rid:
                continue
            if kv_cache.slot_checksum(self.cache, i, g.length) != g.checksum:
                bad.append(i)
        for i in bad:
            s = self.slots[i]
            self.trace.append(("kv_quarantine", self.clock, s.rid, i))
            self._rebuild_slot(i, s, rerun_decode=False)
            self.goodput.on_recovery(self.scfg.prefill_ticks)
            self.trace.append(("recover", self.clock, s.rid, i))
        if bad:
            self._arm_guards()

    def _rebuild_slot(self, slot: int, s: _Slot,
                      rerun_decode: bool) -> Optional[np.ndarray]:
        """Rebuild one slot's cache from scratch and re-insert it.

        Re-prefills ``prompt + emitted[:fed]`` — exactly the tokens whose
        KV the slot holds (rows valid ``[0, pos)``, ``pos == P + fed``) —
        which reproduces the decode-built cache bitwise on FP16.  With
        ``rerun_decode`` the poisoned decode step is replayed batch-1
        (feed ``last_token`` at ``pos``) and the recovered logits row is
        returned to replace the poisoned one; without it (checksum audit,
        which fires *before* the corrupt rows are ever read) the rebuilt
        cache alone restores the invariant.  The virtual clock does not
        advance — recovery overlaps the pool and is billed as waste
        slot-ticks by the caller."""
        with _span(SPAN_RECOVER, rid=s.rid):
            res = self.results[s.rid]
            absorbed = np.concatenate(
                [np.asarray(s.prompt, np.int32),
                 np.asarray(res.tokens[:s.fed], np.int32)])
            assert absorbed.shape[0] == s.pos, "slot rows out of sync"
            seq = jnp.asarray(absorbed)[None]
            _, single = self._prefill_fn(seq.shape[1], recover=True)(
                self.params, seq)
            row = None
            if rerun_decode:
                logits1, single = self._recover_decode(
                    self.params, single,
                    jnp.asarray([[s.last_token]], np.int32),
                    jnp.asarray([s.pos], np.int32),
                    jnp.asarray([s.pos + 1], np.int32))
                row = np.asarray(logits1[0])
            self.cache = self._recover_insert(
                self.cache, single, jnp.int32(slot))
            return row

    def _victim_slot(self) -> Optional[int]:
        active = self._active()
        if not active:
            return None
        target = getattr(self.injector, "target", None)
        if target is not None:
            for i in active:
                if self.slots[i].rid == target:
                    return i
        return active[0]

    # ----------------------------------------------------------------- #
    # Decode (the whole slot pool, ragged over per-slot kv lengths)
    # ----------------------------------------------------------------- #
    def _active(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _decode_once(self) -> None:
        if (self.scfg.audit_every >= 1
                and self.decode_steps % self.scfg.audit_every == 0):
            self._audit_slots()
        n = self.scfg.n_slots
        toks = np.zeros((n, 1), np.int32)
        pos = np.zeros((n,), np.int32)
        sizes = np.zeros((n,), np.int32)
        for i, s in enumerate(self.slots):
            if s is None:
                # parked: rewrites a row its next occupant overwrites anyway
                pos[i] = self.scfg.max_len - 1
                continue
            toks[i, 0] = s.last_token
            pos[i] = s.pos
            sizes[i] = s.pos + 1  # valid kv rows after this step's append
        logits, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(sizes))
        self.clock += 1.0
        self.decode_steps += 1
        self.goodput.on_decode_step()
        with _span(SPAN_FETCH):
            logits = np.array(logits)  # host copy: rows may be replaced below
        with _span(SPAN_SAMPLE):
            self._sample(logits)

    def _sample(self, logits: np.ndarray) -> None:
        """The host's half of a decode step: NaN guard, greedy tokens, slot
        bookkeeping, checksum guards and the health record."""
        n = self.scfg.n_slots
        if (self.injector is not None
                and self.injector.mode == "nan_logits"
                and self._active()
                and self.injector.fires(self.decode_steps, "nan_logits")):
            logits[self._victim_slot(), :] = np.nan
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            if not np.all(np.isfinite(logits[i])):
                # NaN/inf guard: the slot's freshly appended KV row is as
                # suspect as the logits — quarantine, rebuild, replay.
                self.trace.append(("nan_detect", self.clock, s.rid, i))
                logits[i] = self._rebuild_slot(i, s, rerun_decode=True)
                self.goodput.on_recovery(self.scfg.prefill_ticks + 1.0)
                self.trace.append(("recover", self.clock, s.rid, i))
        active = 0
        for i, s in enumerate(self.slots):
            if s is None:
                continue
            active += 1
            s.fed += 1
            s.pos += 1
            res = self.results[s.rid]
            if s.emitted < s.max_new:
                tok = int(np.argmax(logits[i]))
                s.emitted += 1
                s.last_token = tok
                res.tokens.append(tok)
            if s.emitted >= s.max_new and s.fed >= s.emitted:
                # the last emitted token's KV was absorbed this step: the
                # cache is consistent with the emitted sequence at eviction
                res.finish_tick = self.clock
                res.final_logits = logits[i]
                res.status = "finished"
                self.goodput.on_finish(len(res.tokens))
                self.trace.append(("finish", self.clock, s.rid, i))
                self.slots[i] = None
                self.guards.pop(i, None)
        self._arm_guards()
        if (self.injector is not None
                and self.injector.mode == "kv_corrupt"
                and self._active()
                and self.injector.fires(self.decode_steps, "kv_corrupt")):
            # silent bit flips after the guards armed; the next audit
            # (before the corrupt rows are read) must flag exactly this slot
            v = self._victim_slot()
            sv = self.slots[v]
            self.cache = kv_cache.corrupt_slot_rows(
                self.cache, v, [0, max(sv.pos - 1, 0)])
        self.health.append({
            "tick": self.clock,
            "queue_depth": len(self.queue),
            "pending": len(self.pending),
            "active_slots": active,
            "batch_fill": active / n,
        })

    # ----------------------------------------------------------------- #
    # Drive
    # ----------------------------------------------------------------- #
    def step(self) -> bool:
        """Advance one scheduler event; False once fully drained."""
        with _span(SPAN_STEP):
            self._admission(self._evict_expired, self._admit)
            self._start()
            self._admission(self._shed)
            active = len(self._active())
            if active:
                with _span(SPAN_DECODE, active=active,
                           slots=self.scfg.n_slots):
                    self._decode_once()
                return True
            if self.pending:  # idle until the next arrival
                self.clock = max(self.clock, self.pending[0].arrival)
                return True
            return False

    def run(self) -> List[RequestResult]:
        while self.step():
            pass
        return [self.results[rid] for rid in sorted(self.results)]


# --------------------------------------------------------------------- #
# Instrumented (abstract) decode trace: exact ragged billing
# --------------------------------------------------------------------- #
def instrumented_decode_events(params, cfg, scfg: SchedulerConfig,
                               kv_lengths: Sequence[int]):
    """Trace one continuous-batching decode step abstractly and return the
    Engine events, tagged under the ``serve_decode`` op scope.

    ``kv_lengths`` are the per-slot valid kv rows *including* the token
    appended by the step (what the scheduler passes as group sizes; 0 for
    a parked slot).  Passing them concrete gives the grouped score GEMMs
    static ``valid_rows`` billing — the runtime path traces the same ops
    with traced sizes and falls back to dense billing.
    """
    n = scfg.n_slots
    sizes = np.asarray(kv_lengths, np.int32)
    if sizes.shape != (n,):
        raise ValueError(f"need {n} per-slot lengths, got {sizes.shape}")
    cabs = jax.eval_shape(lambda: transformer.init_cache(
        cfg, n, scfg.max_len, dtype=cfg.policy.compute_dtype,
        storage_dtype=scfg.storage_dtype))
    tok = jax.ShapeDtypeStruct((n, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((n,), jnp.int32)
    with engine.instrument() as events, engine.op_scope("serve_decode"):
        jax.eval_shape(
            lambda p_, c_, t_, q_: transformer.serve_step(
                p_, cfg, t_, c_, q_, kv_group_sizes=sizes),
            params, cabs, tok, pos)
    return events
