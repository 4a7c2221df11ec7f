"""Scheduler layer: admission / prefill-ordering / preemption policy.

The engine (`serving/engine.py`) owns the *mechanisms* — placing a
request into a slot, running chunks and decode steps, preempting a slot
to the host swap tier, restoring it — and delegates every *decision* to
a `Scheduler`:

  * which queued (or swapped-out) request enters which free slot, and
    under which allocator admission mode (watermark vs optimistic);
  * which mid-prefill slot runs the step's one prompt chunk;
  * which victim to preempt when the pool runs dry.

Two policies:

  * `FifoScheduler` (default) — bit-identical to the historical engine:
    strict FIFO admission under watermark (worst-case reserve-ahead)
    admission, no skip past a blocked head, prompt chunks in admission
    (uid) order, never preempts. Any schedule this policy produces is
    preemption-free by construction, so greedy outputs are bit-identical
    to the pre-scheduler engine.

  * `SloScheduler` — priority classes (lower number = more urgent;
    `submit(priority=...)`), *optimistic* admission (only the pages
    written now must be free, nothing reserved ahead — worst-case
    reservation strands exactly the capacity SAL-PIM says decode is
    starved for), no head-of-line blocking (a blocked candidate is
    skipped, not waited on), and preempt-and-swap when the pool runs
    dry: the lowest-priority / youngest victim's pages are gathered to
    the host swap tier (`kvcache.swap_out_slot`) and the request is
    re-admitted later, resuming bit-identically. Admission-triggered
    preemption only claims victims of *strictly lower* priority, so a
    class never thrashes itself; capacity-triggered preemption (decode
    needs a page and the free list is dry) may claim anyone — victim
    choice cannot create pages, only choose who waits.

Safety rules the policies must respect (enforced by the engine helpers):

  * A mid-prefill victim is *aborted* (requeued, cursor reset, its
    incompletely-written registered pages unregistered), never swapped —
    a partial prompt's pages are not all fully written, so a blob could
    capture garbage. Abort is cheap: prefill is recomputed on
    re-admission (and may re-hit the prefix cache).
  * A mid-prefill slot whose *registered* pages have sharers
    (refcount > 1 past its borrowed prefix) must not be preempted at
    all: sharers mapped those pages at admission and are waiting for
    the donor to write them (`ServingEngine._preemptable`).
  * Under prefix sharing, a sharer's first chunk must not run before
    its donor finished writing the shared pages. `FifoScheduler` gets
    this from strict uid (= admission) order; `SloScheduler` admits out
    of uid order, so it checks the actual page-writer relation
    (`ServingEngine._prefix_ready`) instead. The earliest-admitted
    prefilling slot is always ready, so prefill never livelocks.
  * An infeasible candidate — one that cannot fit even after evicting
    every eligible victim — must not evict anyone: futile evictions
    re-preempt the same victims every step (livelock). `SloScheduler`
    guards every eviction with `BlockAllocator.admission_probe`.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, runtime_checkable


@dataclasses.dataclass
class SwappedRequest:
    """A preempted request parked off-device, awaiting re-admission.

    blob is None for aborted mid-prefill victims (they re-admit fresh
    and re-run prefill); decoding victims carry their exact KV payload
    in the engine's `HostSwapTier` keyed by uid, plus the saved logits
    row (`logits`) sampling resumes from.
    """

    req: object                       # engine.Request
    n_kv: int                         # resident tokens at swap-out
    logits: Optional[object] = None   # np.ndarray (vocab,) or None
    has_blob: bool = False


@runtime_checkable
class Scheduler(Protocol):
    """Policy interface the engine calls at step boundaries.

    Attributes:
      name            short policy id (stats / bench labels)
      preemptive      True enables the engine's capacity-ensure hooks
      reserve         allocator admission mode for this policy's
                      admissions (True = watermark, False = optimistic)
      pin_budget_pages  prefix-cache pages allowed to survive refcount 0
    """

    name: str
    preemptive: bool
    reserve: bool
    pin_budget_pages: int

    def schedule_admissions(self, eng) -> None:
        """Fill free slots from eng.queue / eng.swapped."""
        ...

    def select_prefill_slot(self, eng, cand: list[tuple[int, int]]) -> int:
        """Pick the slot for this step's prompt chunk from non-empty
        `cand` = [(uid, slot), ...] of mid-prefill slots."""
        ...

    def pick_victim(self, eng, below_priority: Optional[int],
                    protect: frozenset = frozenset()) -> Optional[int]:
        """Pick a slot to preempt (None = no legal victim). With
        `below_priority` set, only slots of strictly lower priority
        (larger number) qualify — admission must not thrash its own
        class; capacity-driven calls pass None."""
        ...


class FifoScheduler:
    """The historical policy, extracted: strict-FIFO watermark admission,
    uid-ordered prefill, no preemption. Bit-identical to the
    pre-scheduler engine by construction."""

    name = "fifo"
    preemptive = False
    reserve = True
    pin_budget_pages = 0

    def schedule_admissions(self, eng) -> None:
        tel = eng.telemetry
        for slot in range(eng.slots):
            if eng.active[slot] is None and eng.queue:
                req = eng.queue[0]
                # Watermark admission: worst-case pages (net of any shared
                # prefix pages) must be reservable, else the whole FIFO
                # waits (no skip — later short requests must not starve
                # the head). admit_tokens mutates no state on refusal, so
                # a waiting head reserves nothing.
                res = eng.allocator.admit_tokens(
                    req.uid, req.prompt, req.max_new_tokens)
                if res is None:
                    # One blocked-step event per engine step the FIFO head
                    # waits at the watermark (head-of-line blocking,
                    # visible in the snapshot).
                    tel.count("admission.blocked_steps")
                    if not any(r is not None for r in eng.active):
                        # Nothing holds pages, yet the head still doesn't
                        # fit: it never will (submit() bounds gross worst
                        # case, so this is a safety net).
                        worst = eng.allocator.pages_for(
                            eng.allocator.worst_case_tokens(
                                len(req.prompt), req.max_new_tokens))
                        raise ValueError(
                            f"request {req.uid} needs {worst} pages; "
                            f"pool has {eng.allocator.num_pages - 1}")
                    break
                eng.queue.pop(0)
                eng._place(slot, req, res[1])
        eng.peak_pages = max(eng.peak_pages, eng.allocator.used_pages)

    def select_prefill_slot(self, eng, cand: list[tuple[int, int]]) -> int:
        # Strict admission (uid) order: the donor-before-sharer safety
        # argument for registration-at-admission prefix pages.
        return min(cand)[1]

    def pick_victim(self, eng, below_priority, protect=frozenset()):
        return None


class SloScheduler:
    """SLO-aware policy: priority classes, optimistic admission,
    preempt-and-swap.

    Admission order is (priority, uid): urgent classes first, FIFO
    within a class, swapped-out requests compete in the same order (so
    a preempted request is restored as soon as its class is up).
    Blocked candidates are skipped — no head-of-line blocking. When a
    candidate does not fit, the policy first reclaims pinned prefix
    pages, then preempts victims of strictly lower priority until the
    candidate fits or no victim remains.

    `pin_budget_pages` > 0 keeps that many hot prefix pages alive at
    refcount 0, so a recurring system prompt survives the gap between
    the requests that use it.
    """

    name = "slo"
    preemptive = True
    reserve = False

    def __init__(self, pin_budget_pages: int = 0):
        self.pin_budget_pages = pin_budget_pages

    # -- admission ----------------------------------------------------------
    def schedule_admissions(self, eng) -> None:
        if not eng.queue and not eng.swapped:
            return
        cands = sorted(
            [(e.req.priority, e.req.uid, e) for e in list(eng.swapped)]
            + [(r.priority, r.uid, r) for r in list(eng.queue)],
            key=lambda c: (c[0], c[1]))
        for prio, _uid, item in cands:
            slot = next((i for i, r in enumerate(eng.active) if r is None),
                        None)
            if slot is None:
                # All slots busy: a strictly-lower-priority victim may
                # yield its slot (and its pages) — but only for a
                # candidate that can actually fit afterwards; evicting
                # for one that never will would thrash the victims
                # every step.
                if not self._feasible(eng, item, prio):
                    eng.telemetry.count("admission.blocked_steps")
                    continue
                victim = self.pick_victim(eng, below_priority=prio)
                if victim is None:
                    break   # later candidates have prio >= this one
                eng._preempt(victim)
                slot = victim
            if not self._admit_with_evictions(eng, item, slot, prio):
                eng.telemetry.count("admission.blocked_steps")
                if not any(r is not None for r in eng.active):
                    # Nothing holds pages, yet the candidate still does
                    # not fit: it never will (submit() bounds the gross
                    # worst case, so this is a safety net).
                    r = item.req if isinstance(item, SwappedRequest) else item
                    raise ValueError(
                        f"request {r.uid} cannot fit: pool has "
                        f"{eng.allocator.num_pages - 1} pages")
                continue
        eng.peak_pages = max(eng.peak_pages, eng.allocator.used_pages)

    def _admit_with_evictions(self, eng, item, slot, prio) -> bool:
        """Try to place `item` (Request or SwappedRequest) into `slot`,
        preempting strictly-lower-priority victims while it does not
        fit. Pinned-page reclaim happens inside the allocator's admit
        paths; eviction only frees *mapped* pages. The feasibility
        guard runs before every eviction: once the candidate provably
        cannot fit even after evicting every remaining eligible victim,
        give up without touching them."""
        protect = frozenset((slot,))
        while True:
            if isinstance(item, SwappedRequest):
                ok = eng._swap_in(item, slot, reserve=self.reserve)
            else:
                ok = eng._admit_queued(item, slot, reserve=self.reserve)
            if ok:
                return True
            if not self._feasible(eng, item, prio, protect=protect):
                return False
            victim = self.pick_victim(eng, below_priority=prio,
                                      protect=protect)
            if victim is None:
                return False
            eng._preempt(victim)

    def _feasible(self, eng, item, prio, protect=frozenset()) -> bool:
        """Can `item` possibly be admitted, counting the free list,
        reclaimable pinned pages, and every page that evicting every
        eligible (strictly-lower-priority, preemptable, unprotected)
        victim would release? If not, no eviction for it is justified."""
        a = eng.allocator
        if isinstance(item, SwappedRequest) and item.has_blob:
            need = a.pages_for(item.n_kv)
            reclaimable = a.pinned_pages
        else:
            r = item.req if isinstance(item, SwappedRequest) else item
            need, reclaimable = a.admission_probe(
                r.prompt, r.max_new_tokens, reserve=self.reserve)
        attainable = a.free_pages + reclaimable
        if attainable >= need:
            return True
        for i, r in enumerate(eng.active):
            if (r is None or i in protect or r.priority <= prio
                    or not eng._preemptable(i)):
                continue
            # refcount-1 pages are the ones eviction actually frees (or
            # pins — reclaimable either way); shared pages survive their
            # sharers.
            attainable += sum(1 for p in a.pages_of(r.uid)
                              if a.refcount(p) == 1)
            if attainable >= need:
                return True
        return False

    # -- chunk ordering -----------------------------------------------------
    def select_prefill_slot(self, eng, cand: list[tuple[int, int]]) -> int:
        """Most-urgent class first, FIFO within a class — among slots
        whose borrowed prefix pages are fully written
        (`ServingEngine._prefix_ready`): a sharer must not run a chunk
        while the donor that registered its borrowed pages is still
        mid-prefill, or it would attend over garbage. uid order is NOT
        a safe proxy here (unlike FIFO): SLO admission can seat a
        high-priority donor with a *larger* uid than its sharer. Page
        ownership is unique and acyclic in admission time, so the
        earliest-admitted prefilling slot is always ready — no
        livelock; the unfiltered fallback is a safety net only."""
        eligible = [(eng.active[slot].priority, uid, slot)
                    for uid, slot in cand if eng._prefix_ready(slot)]
        if not eligible:
            eligible = [(eng.active[slot].priority, uid, slot)
                        for uid, slot in cand]
        return min(eligible)[2]

    # -- preemption ---------------------------------------------------------
    def pick_victim(self, eng, below_priority,
                    protect=frozenset()) -> Optional[int]:
        """Lowest-priority, then youngest (largest uid) preemptable slot;
        None when no slot qualifies."""
        best, best_key = None, None
        for i, r in enumerate(eng.active):
            if r is None or i in protect:
                continue
            if below_priority is not None and r.priority <= below_priority:
                continue
            if not eng._preemptable(i):
                continue
            key = (r.priority, r.uid)
            if best is None or key > best_key:
                best, best_key = i, key
        return best
