"""Continuous-batching scheduler: FCFS admission, token-budget packing
of mixed prefill+decode steps, preemption-by-recompute.

The scheduler owns request queues and KV-block accounting; the engine
owns the ONE compiled unified step (ISSUE 8). Each engine iteration asks
for a :class:`StepPlan` that packs work into the engine's fixed
``step_tokens`` budget: **every** running sequence decodes one token
(decode is planned FIRST, so a streaming long prefill can never starve
running decoders), then prefill chunks fill the remaining budget FCFS —
several sequences' chunks may ride one step, each capped at
``prefill_chunk`` tokens per iteration (chunked prefill). Slots are the
engine's fixed metadata rows — a finished request's slot is handed to
the next waiting request between steps, which (together with the fixed
token budget) keeps the unified executable's shapes, and therefore its
single compilation, constant.

Prefix-cache-aware admission (ISSUE 15): when the engine's
``PagedKVCache`` carries a :class:`~.kv_cache.PrefixCache`, a request
entering a slot first matches its prompt's longest registered
full-block prefix — matched blocks are claimed (incref / resurrection
through the allocator) straight into its block table and only the
uncached tail prefills. A prompt whose FULL length is cached is capped
at ``len(prompt) - 1`` matched tokens (at least one token must run to
produce the sampling logits); because that cap lands mid-block, the
last matched block becomes a **copy-on-write source**: the scheduler
holds one claimed reference on it (``cow_src``) and the engine copies
its contents into the sequence's freshly-allocated private block before
the step runs, so the final-token write can never touch shared state.

When the block pool can't cover a needed allocation, the sequence with
the LATEST arrival is preempted (vLLM's recompute policy, protecting
FCFS order): its blocks are freed, and it re-enters the waiting queue
with ``prompt + generated-so-far`` as its new prefill text. On
readmission the recompute-prefill rebuilds its KV state and the sampled
continuation picks up exactly where it left off — under greedy decoding
the final output is identical to the unpreempted run. With the prefix
cache on, a preempted sequence's committed blocks park as reclaimable
instead of being erased, so readmission's prefix match recovers them
and only the genuinely uncached tail recomputes. Because decode is
planned before prefill and victims are always strictly YOUNGER than the
sequence needing blocks, a plan can never direct the engine at a
sequence whose blocks a later planning stage of the same plan took: an
already-planned victim is knocked back to WAITING (slot released), and
the engine filters such stale entries before acting — the
protected-victim guarantee (no chunk is ever written through an
all-null block table).

The engine's run loop plans a step while the step before is still on the
device (ISSUE 32): a sequence's ``num_sampled`` may then run ahead of
``len(generated)``. Nothing of a plan needs a token's value but the
recompute text of a victim: ``preempt`` raises :class:`Unharvested`
before it touches a sequence whose newest token is not harvested, what
the plan did until then stands, and the engine plans again after the
harvest. A sequence sampled to its length (``all_sampled``) is not
planned again.
"""
from __future__ import annotations

import bisect
import itertools
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, List, Optional, Sequence, Tuple

from .kv_cache import NULL_BLOCK, PagedKVCache

__all__ = ["RequestState", "Request", "StepPlan", "Scheduler", "Unharvested"]

_req_counter = itertools.count()


class Unharvested(Exception):
    """The plan wants to preempt a sequence whose newest token the engine
    has not read back from the device: its recompute text cannot be
    written yet. Raised before the sequence is touched; what the plan did
    until then stands (admissions, grown block tables), and planning again
    after the harvest picks up from there."""


class RequestState(Enum):
    WAITING = "waiting"    # queued (fresh or preempted), no slot
    PREFILL = "prefill"    # slot assigned, prompt not fully cached
    RUNNING = "running"    # decoding one token per engine step
    FINISHED = "finished"
    FAILED = "failed"


@dataclass
class Request:
    """One generation request plus its runtime sequence state."""

    prompt_tokens: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    #: per-token streaming callback ``(request, token_id) -> None``
    on_token: Optional[Callable] = None
    req_id: int = field(default_factory=lambda: next(_req_counter))
    arrival_time: float = field(default_factory=time.perf_counter)
    #: W3C trace id (32 lowercase hex) — client-supplied via
    #: ``traceparent`` or engine-generated at submit; stamped into every
    #: trace span of this request so ``trace merge --requests`` can
    #: stitch the cross-process chain
    trace_id: Optional[str] = None
    #: LoRA adapter slot applied to this request's rows (ISSUE 20);
    #: 0 = the bare base model
    adapter_id: int = 0

    # -- runtime state (engine/scheduler managed) --------------------------
    state: RequestState = RequestState.WAITING
    slot: Optional[int] = None
    #: first admission into a batch slot — the end of the queue-wait span
    slot_time: Optional[float] = None
    #: the block-table rows, one list a layer group of the cache (sized
    #: by ``Scheduler.add``); a page a window group released is the null
    #: block there
    tables: List[List[int]] = field(default_factory=list)
    #: tokens to (re)prefill — the prompt, or prompt+generated after a
    #: preemption (recompute)
    pending_tokens: List[int] = field(default=None)
    prefill_pos: int = 0     # pending tokens already cached
    #: tokens whose K/V the cache holds for good: the **confirmed** length.
    #: A step with a draft row writes one row beyond it (the written
    #: length); the row counts here once the draft is accepted
    num_cached: int = 0
    generated: List[int] = field(default_factory=list)
    #: tokens the compiled step has sampled for this request; those beyond
    #: ``len(generated)`` (at most two) are on the device, not harvested
    num_sampled: int = 0
    #: the row of the newest sampled token in its step's token array
    token_row: int = -1
    #: the drafter's guess of the token after the newest confirmed one
    #: (an engine with ``draft_tokens``; None until a step has made one)
    draft: Optional[int] = None
    #: draft rows this sequence rides in the step being planned or run
    draft_rows: int = 0
    #: draft rows of its steps in flight: each may yet add one to
    #: ``num_cached`` and ``num_sampled``, which are lower bounds until
    #: those steps are harvested
    pending_drafts: int = 0
    # -- prefix-cache state (ISSUE 15) -------------------------------------
    #: prompt tokens recovered from the prefix cache at the LAST admission
    cached_prompt_tokens: int = 0
    #: prompt tokens actually prefilled over the request's whole life
    #: (incl. preemption recompute) — the recompute-tail test's subject
    prefilled_tokens: int = 0
    #: lifetime accumulators across every admission: pending-token demand
    #: and cache-matched tokens — ``prefilled_tokens ≤ admitted_pending
    #: − cached_tokens_total`` is the "recompute only the uncached tail"
    #: invariant tests pin
    admitted_pending_total: int = 0
    cached_tokens_total: int = 0
    #: full blocks already registered in the prefix index + the chain
    #: digest of the last one (the next block's hash parent)
    committed_blocks: int = 0
    committed_hash: Optional[bytes] = None
    #: prefix-cache chain root (ISSUE 20): non-base adapters hash their
    #: blocks under an adapter-specific seed so one tenant's KV never
    #: answers another tenant's identical prompt; ``None`` = base model
    cache_seed: Optional[bytes] = None
    #: copy-on-write: claimed source block + the logical index of the
    #: private destination block the engine copies it into pre-step
    cow_src: Optional[int] = None
    cow_index: Optional[int] = None
    first_token_time: Optional[float] = None
    last_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    preemptions: int = 0
    finish_reason: Optional[str] = None
    error: Optional[str] = None

    def __post_init__(self):
        self.prompt_tokens = [int(t) for t in self.prompt_tokens]
        if self.pending_tokens is None:
            self.pending_tokens = list(self.prompt_tokens)

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.FAILED)

    def holds_blocks(self) -> bool:
        return any(self.tables)

    def blocks_held(self) -> int:
        """Pages held over every group (a released page is null)."""
        return sum(len(t) - t.count(NULL_BLOCK) for t in self.tables)

    def last_token(self) -> int:
        """The decode-step input: the newest sampled, not-yet-cached
        token (prefill completion always samples one before decoding)."""
        return self.generated[-1]

    @property
    def unharvested(self) -> int:
        """Sampled tokens still on the device."""
        return self.num_sampled - len(self.generated)

    @property
    def all_sampled(self) -> bool:
        """Every token it asked for is sampled: a finish by length, known
        without a token's value."""
        return self.num_sampled >= self.max_new_tokens

    def ttft(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    def latency(self) -> Optional[float]:
        if self.finish_time is None:
            return None
        return self.finish_time - self.arrival_time


@dataclass
class StepPlan:
    #: prefill chunks packed into this step's token budget, FCFS order:
    #: (sequence, number of prompt tokens to prefill)
    prefills: List[Tuple[Request, int]] = field(default_factory=list)
    #: running sequences to advance: one row each, and ``draft_rows``
    #: rows of drafts to verify
    decode: List[Request] = field(default_factory=list)

    @property
    def empty(self) -> bool:
        return not self.prefills and not self.decode

    @property
    def decode_tokens(self) -> int:
        return sum(1 + s.draft_rows for s in self.decode)

    @property
    def total_tokens(self) -> int:
        return self.decode_tokens + sum(n for _, n in self.prefills)


class Scheduler:
    """FCFS continuous-batching policy over ``max_batch`` engine slots
    and a ``step_tokens`` per-step token budget."""

    def __init__(self, cache: PagedKVCache, max_batch: int,
                 prefill_chunk: int, step_tokens: Optional[int] = None,
                 draft_tokens: int = 0):
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.cache = cache
        self.max_batch = max_batch
        self.prefill_chunk = prefill_chunk
        #: draft rows a greedy decoding sequence rides a step (0 or 1)
        self.draft_tokens = int(draft_tokens)
        decode_rows = max_batch * (1 + self.draft_tokens)
        # default budget: every decode slot plus one full chunk — the
        # worst mix the old two-executable engine could run per
        # iteration, now in one step
        self.step_tokens = int(step_tokens if step_tokens is not None
                               else decode_rows + prefill_chunk)
        if self.step_tokens < decode_rows + 1:
            raise ValueError(
                f"step_tokens {self.step_tokens} can't cover "
                f"{max_batch} decode slots plus any prefill")
        self.waiting: List[Request] = []   # sorted by arrival_time
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.num_preemptions = 0

    # -- queue state -------------------------------------------------------
    def slotted(self) -> List[Request]:
        return [s for s in self.slots if s is not None]

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.slotted())

    def has_work(self) -> bool:
        return bool(self.waiting or self.slotted())

    def add(self, req: Request):
        """FCFS enqueue (kept sorted by arrival so a preempted earlier
        request resumes ahead of later arrivals)."""
        if len(req.tables) != len(self.cache.groups):
            req.tables = [[] for _ in self.cache.groups]
        bisect.insort(self.waiting, req, key=lambda r: r.arrival_time)

    # -- planning ----------------------------------------------------------
    def schedule(self) -> StepPlan:
        """Admit, collect the decode batch, then pack prefill chunks
        into the remaining token budget (preempting by recompute where
        the block pool falls short). Decode plans FIRST — running
        requests advance every step no matter how many prompts are
        streaming (starvation-freedom), and FCFS-senior prefill
        allocations that evict a younger just-planned decode sequence
        merely turn its plan entry stale (the engine filters on
        slot/state before acting — the protected-victim guarantee)."""
        self._admit()
        plan = StepPlan()
        plan.decode = self._plan_decode()
        plan.prefills = self._plan_prefills(
            self.step_tokens - plan.decode_tokens)
        return plan

    def _admit(self):
        for i, s in enumerate(self.slots):
            if s is not None or not self.waiting:
                continue
            req = self.waiting.pop(0)
            req.slot = i
            self.slots[i] = req
            req.state = RequestState.PREFILL
            if req.slot_time is None:
                req.slot_time = time.perf_counter()
            req.admitted_pending_total += len(req.pending_tokens)
            self._prefix_admit(req)

    def _prefix_admit(self, seq: Request):
        """Match the longest cached prefix of ``seq.pending_tokens`` and
        seed its block table with the claimed blocks. Fully-cached
        prompts are capped at ``len - 1`` tokens (the last token must
        prefill to produce sampling logits); the cap lands mid-block, so
        the final matched block turns into a held COW source instead of
        a table entry."""
        pc = self.cache.groups[0].prefix_cache
        if pc is None or seq.holds_blocks():
            return
        tokens = seq.pending_tokens
        if len(tokens) <= self.cache.block_size:
            return  # no full block can match under the one-token cap
        tables, digests = self.cache.match(tokens, seed=seq.cache_seed)
        if not digests:
            return
        matched = len(digests) * self.cache.block_size
        if matched >= len(tokens):
            # fully-cached aligned prompt (a one-group cache's: several
            # groups' match leaves a token): the last matched block is the
            # COW source (we hold its claimed reference until the engine
            # copies it); usable cache shrinks to len - 1 tokens
            seq.cow_src = tables[0].pop()
            seq.cow_index = len(tables[0])
            digests.pop()
            matched = len(tokens) - 1
        seq.tables = tables
        seq.prefill_pos = matched
        seq.num_cached = matched
        seq.cached_prompt_tokens = matched
        seq.cached_tokens_total += matched
        seq.committed_blocks = len(digests)
        seq.committed_hash = digests[-1] if digests else seq.cache_seed
        pc.hit_tokens += matched

    def _release_cow(self, seq: Request):
        """Drop a held COW source reference (preempt/finish/abort before
        the engine performed the copy — or after: the engine clears
        ``cow_src`` once the copy ran)."""
        if seq.cow_src is not None:
            self.cache.groups[0].allocator.free([seq.cow_src])
            seq.cow_src = None
        seq.cow_index = None

    def _plan_prefills(self, budget: int) -> List[Tuple[Request, int]]:
        """FCFS prefill packing: each PREFILL-state sequence gets up to
        ``prefill_chunk`` tokens (chunked prefill — long prompts stream
        across steps), as many sequences as the budget covers. Stops at
        the first sequence the pool can't serve even after preemption:
        letting a YOUNGER prompt's chunk jump it would invert FCFS with
        the pool under pressure, exactly when order matters."""
        out: List[Tuple[Request, int]] = []
        cands = sorted((s for s in self.slotted()
                        if s.state is RequestState.PREFILL),
                       key=lambda r: r.arrival_time)
        for seq in cands:
            if budget <= 0:
                break
            if seq.slot is None or seq.state is not RequestState.PREFILL:
                # preempted mid-loop by a senior candidate's allocation:
                # planning it anyway would attach fresh blocks to a
                # slotless WAITING request (unreclaimable by
                # _pick_victim) or spuriously evict a third sequence
                continue
            n = min(self.prefill_chunk, budget,
                    len(seq.pending_tokens) - seq.prefill_pos)
            if n <= 0:
                continue
            if not self._ensure_blocks(seq, seq.prefill_pos + n):
                break  # pool contended; retry later, keep FCFS order
            out.append((seq, n))
            budget -= n
        return out

    def _plan_decode(self) -> List[Request]:
        batch = []
        # earliest arrivals first: preemption victims come from the tail,
        # so a seq preempted mid-planning is simply never reached
        for seq in sorted(self.slotted(), key=lambda r: r.arrival_time):
            if seq.state is not RequestState.RUNNING or seq.slot is None \
                    or seq.all_sampled:
                continue
            # pages for the written length at its longest: the row, a
            # draft's row, and a draft in flight that may be accepted
            # (never past the request's whole length: at least one token
            # is still to come, two for a draft's row)
            seq.draft_rows = self._draft_rows(seq)
            if self._ensure_blocks(seq, seq.num_cached + seq.pending_drafts
                                   + 1 + seq.draft_rows):
                batch.append(seq)
        return batch

    def _draft_rows(self, seq: Request) -> int:
        """A greedy sequence that has a draft (on the host, or coming from
        the step in flight) takes it along, unless the one token it may
        still want needs no second."""
        return int(
            self.draft_tokens > 0 and seq.temperature == 0
            and (seq.draft is not None or seq.unharvested > 0)
            and seq.max_new_tokens - seq.num_sampled >= 2)

    # -- block management --------------------------------------------------
    def _free_blocks(self, seq: Request):
        """Every page ``seq`` holds goes back to its group's allocator."""
        for g, t in zip(self.cache.groups, seq.tables):
            g.allocator.free([b for b in t if b != NULL_BLOCK])
        seq.tables = [[] for _ in self.cache.groups]

    def release_behind_window(self, seq: Request) -> dict:
        """Once a step is dispatched and ``num_cached`` advanced: a window
        group gives back every page of ``seq`` whose last key no token
        still to come can see (it lies at or before ``num_cached -
        window``; the step in flight reads it off the table it was handed). A page the prefix cache has
        registered parks reclaimable and keeps its contents; the table
        entry becomes the null block. Returns ``{group name: pages}``."""
        out = {}
        for g, t in zip(self.cache.groups, seq.tables):
            if g.window is None:
                continue
            i = min(g.first_visible_page(seq.num_cached), len(t)) - 1
            gone = []
            while i >= 0 and t[i] != NULL_BLOCK:   # released: a prefix
                gone.append(t[i])
                t[i] = NULL_BLOCK
                i -= 1
            if gone:
                g.allocator.free(gone)
                out[g.name] = len(gone)
        return out

    def _ensure_blocks(self, seq: Request, total_tokens: int) -> bool:
        """Grow ``seq``'s block table to cover ``total_tokens`` cached
        positions in every layer group or in none, preempting
        latest-arrival sequences as needed.
        Victims are always strictly younger than ``seq`` (FCFS-senior
        requests are never evicted for junior ones). A victim that was
        already planned this step is knocked to WAITING with its slot
        released, which is exactly what the engine's stale-entry filter
        checks — it can never be executed against freed blocks."""
        groups, tables = self.cache.groups, seq.tables
        want = self.cache.blocks_for(total_tokens)
        need = [max(0, want - len(t)) for t in tables]
        if not any(need):
            return True
        while not all(g.allocator.can_allocate(n)
                      for g, n in zip(groups, need)):
            victim = self._pick_victim(after=seq)
            if victim is None:
                holders = [s for s in self.slotted()
                           if s is not seq and s.holds_blocks()]
                if (holders and seq.slot is not None and seq.holds_blocks()
                        and all(h.arrival_time < seq.arrival_time
                                for h in holders)):
                    # only FCFS-senior sequences hold the pool: hand our
                    # blocks back so the head can finish sooner
                    self.preempt(seq)
                # else: a protected (or senior) holder will become
                # evictable/finish on a later step — just wait
                return False
            self.preempt(victim)
        for g, t, n in zip(groups, tables, need):
            if n:
                t.extend(g.allocator.allocate(n))
        return True

    def _pick_victim(self, after: Request) -> Optional[Request]:
        """Latest-arrival slotted sequence strictly younger than
        ``after`` — preemption never evicts an earlier (FCFS-senior)
        request."""
        cands = [s for s in self.slotted()
                 if s is not after and s.holds_blocks()
                 and s.arrival_time > after.arrival_time]
        if not cands:
            return None
        return max(cands, key=lambda r: r.arrival_time)

    def preempt(self, seq: Request):
        """Preemption-by-recompute: free every block, requeue with
        prompt+generated as the new prefill text. Greedy decoding makes
        the resumed continuation token-identical. With the prefix cache
        on, the freed committed blocks PARK as reclaimable — readmission
        re-matches them and recomputes only the uncached tail. The text
        holds every generated token: a sequence with one still on the
        device raises :class:`Unharvested`, untouched."""
        if seq.unharvested > 0:
            raise Unharvested(seq.req_id)
        from paddle_tpu.observability import requests as obs_requests
        led = obs_requests._active
        if led is not None:
            # close out the occupancy interval at the pre-free level —
            # the request holds zero blocks until readmission
            led.note_occupancy(seq, time.monotonic())
        self._release_cow(seq)
        self._free_blocks(seq)
        self.release_slot(seq)
        seq.pending_tokens = list(seq.prompt_tokens) + list(seq.generated)
        seq.prefill_pos = 0
        seq.num_cached = 0
        seq.draft, seq.draft_rows = None, 0    # the recompute drafts anew
        seq.cached_prompt_tokens = 0
        seq.committed_blocks = 0
        seq.committed_hash = seq.cache_seed
        seq.state = RequestState.WAITING
        seq.preemptions += 1
        self.num_preemptions += 1
        from paddle_tpu.observability import trace
        trace.mark("serving", "preempted",
                   args={"req": seq.req_id, "trace": seq.trace_id,
                         "preemptions": seq.preemptions,
                         "generated": len(seq.generated)})
        self.add(seq)

    def release_slot(self, seq: Request):
        if seq.slot is not None:
            self.slots[seq.slot] = None
            seq.slot = None

    def release(self, seq: Request):
        """Return the slot and every page (nothing where they are gone).
        Registered blocks park in the reclaimable tier — a finished
        request's prompt stays servable from cache. Apart from ``finish``:
        a sequence whose last token is sampled and not yet harvested needs
        neither any more."""
        from paddle_tpu.observability import requests as obs_requests
        led = obs_requests._active
        if led is not None:
            # bill the final holding interval before the blocks go back
            led.note_occupancy(seq, time.monotonic())
        self._release_cow(seq)
        self._free_blocks(seq)
        self.release_slot(seq)

    def finish(self, seq: Request, state: RequestState,
               reason: str = "stop"):
        """Return every resource; the engine records metrics/callbacks."""
        self.release(seq)
        seq.state = state
        seq.finish_reason = reason
        seq.finish_time = time.perf_counter()
