"""Continuous-batching request scheduler: submit/poll queue + age-fair
admission over a slot-ring engine.

``SlotScheduler`` is the backend-agnostic half: it owns the slot free-list,
the per-prompt-shape FIFO buckets, the completion table, and the step loop
(advance in-flight admissions → fill free slots → one multi-slot engine step →
collect finished slots). Backends specialize the admission and collection
hooks: the LM ``Scheduler`` admits via (optionally chunked) prefill and
finishes slots on EOS / ``max_new``; the HDC scheduler
(``repro.serving.hdc.HDCScheduler``) admits query batches into tenant slots
and finishes every running slot each step (one banked similarity launch
answers all of them).

Admission is age-fair: each free slot takes the globally oldest pending
request — re-evaluated per slot — rather than draining the oldest request's
whole bucket first. Same-shape requests still share one compiled prefill per
bucket, but a sustained stream of long prompts can no longer starve a short
prompt that arrived in between (the bucket-drain policy kept picking the long
bucket because its head stayed oldest while the drained entries were
refilled behind it).

Each ``step`` is one host span ``scheduler.step`` (the step's index and the
slots it runs) enclosing ``scheduler.admit``, ``scheduler.dispatch`` and
``scheduler.collect``: ``jax.profiler.TraceAnnotation``s, which a profiler
trace records on the clock of the device's ops and which do nothing else
while no trace is recording. ``slot_steps`` counts the running slots of
every engine step and ``computed_slot_steps`` the slots those steps computed,
so their ratio is the share of the device's slot work that served a request.

Eviction is step-granular: a finished slot is freed immediately and refilled
on the next admission pass while the remaining slots keep going — no drain
barrier, no recompile.

Slot-leak guard: a request that never finishes (a decode loop that never hits
EOS under a huge ``max_new``, or a backend bug) used to pin its slot forever —
``run`` would spin until its wall-clock timeout raised with the slot still
held. ``max_slot_steps`` bounds the steps any single admission may consume;
an expired slot is force-evicted (freed + ``engine.on_evict``), and its
request is requeued at the head of its bucket up to ``max_requeues`` times
before being failed with an ``"evicted"`` completion — the queue always
drains.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.engine import ChunkedPrefill, ContinuousEngine, _prompt_sig


@dataclasses.dataclass
class Request:
    rid: int
    batch: dict                  # B=1 model inputs incl. 'tokens' [1, S]
    prompt_len: int
    max_new: int
    key: Any
    t_submit: float


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: list[int]            # generated tokens (incl. the final EOS, if any)
    finish_reason: str           # "length" | "eos"
    prompt_len: int
    t_submit: float
    t_admit: float
    t_finish: float

    @property
    def latency(self) -> float:
        """Submit-to-finish wall time (includes queueing)."""
        return self.t_finish - self.t_submit


class SlotScheduler:
    """Backend-agnostic queue + slot bookkeeping over a ``SlotRingEngine``.

    Subclasses implement:

    * ``_start_admission(req, slot) -> list[Completion]`` — begin serving
      ``req`` on ``slot``: either fully admit (register it in ``running``,
      possibly finishing immediately) or park an in-flight multi-step
      admission in ``self.admitting[slot]``;
    * ``_advance_admissions() -> list[Completion]`` — make one unit of
      progress on every in-flight admission (default: none exist);
    * ``_collect(emitted) -> list[Completion]`` — consume one engine step's
      per-slot emissions, finishing and freeing slots as the backend dictates;
    * ``_step_params()`` — the params pytree handed to ``engine.step``
      (default: the ``params`` given at construction).

    A backend whose admissions are cheap scatters (HDC) may instead override
    ``_admit_free_slots`` wholesale to fill every free slot in one batched
    engine call.
    """

    def __init__(self, engine, params, clock: Callable[[], float] = time.monotonic,
                 *, max_slot_steps: int | None = None, max_requeues: int = 1):
        if max_slot_steps is not None and max_slot_steps < 1:
            raise ValueError("max_slot_steps must be >= 1")
        self.engine = engine
        self.params = params
        self.clock = clock
        self.state = engine.init_state()
        self.free: list[int] = list(range(engine.num_slots))
        # slot -> backend-defined running record (LM: (request, tokens, t_admit))
        self.running: dict[int, Any] = {}
        # slot -> backend-defined in-flight admission (LM: (request, ChunkedPrefill))
        self.admitting: dict[int, Any] = {}
        self.buckets: dict[Any, collections.deque] = collections.defaultdict(
            collections.deque
        )
        self.results: dict[int, Completion] = {}
        self.steps = 0
        self.slot_steps = 0            # running slots, summed over engine steps
        self.computed_slot_steps = 0   # slots computed, summed over engine steps
        self._next_rid = 0
        self.max_slot_steps = max_slot_steps
        self.max_requeues = max_requeues
        self._slot_steps: dict[int, int] = {}   # slot -> steps consumed in-flight
        self._requeues: dict[int, int] = {}     # rid -> deadline evictions so far

    # -- queue ---------------------------------------------------------------

    def poll(self, rid: int) -> Completion | None:
        return self.results.get(rid)

    @property
    def pending(self) -> int:
        return sum(len(q) for q in self.buckets.values())

    @property
    def active(self) -> int:
        return len(self.running) + len(self.admitting)

    # -- admission / eviction ------------------------------------------------

    def _pop_oldest(self) -> Any | None:
        """Pop the globally oldest pending request across all buckets."""
        live = [(q[0].t_submit, q[0].rid, s) for s, q in self.buckets.items() if q]
        if not live:
            return None
        return self.buckets[min(live)[2]].popleft()

    def _admit_free_slots(self) -> list[Completion]:
        finished = []
        while self.free:
            # age-fair: re-pick the globally oldest request for EACH free slot
            req = self._pop_oldest()
            if req is None:
                break
            slot = self.free.pop(0)
            finished.extend(self._start_admission(req, slot))
        return finished

    # -- backend hooks --------------------------------------------------------

    def _start_admission(self, req, slot: int) -> list[Completion]:
        raise NotImplementedError

    def _advance_admissions(self) -> list[Completion]:
        return []

    def _collect(self, emitted) -> list[Completion]:
        raise NotImplementedError

    def _step_params(self):
        return self.params

    def _bucket_key(self, req) -> Any:
        """Bucket a (re)queued request lands in — backends with shape buckets
        override (the LM scheduler keys on the prompt signature)."""
        return 0

    def _fail_eviction(self, slot: int, record) -> Completion:
        """Build the failure completion for a deadline-evicted slot record."""
        raise NotImplementedError

    # -- slot-leak guard ------------------------------------------------------

    def _evict_slot(self, slot: int) -> list[Completion]:
        """Force-evict a deadline-expired slot: free it, notify the engine,
        requeue the request at the HEAD of its bucket (it is the oldest — the
        age-fair pop must see it first) or fail it after ``max_requeues``."""
        record = self.running.pop(slot)
        req = record[0]
        self.free.append(slot)
        self._slot_steps.pop(slot, None)
        self.engine.on_evict(slot)
        n = self._requeues.get(req.rid, 0)
        if n < self.max_requeues:
            self._requeues[req.rid] = n + 1
            self.buckets[self._bucket_key(req)].appendleft(req)
            return []
        done = self._fail_eviction(slot, record)
        self.results[req.rid] = done
        return [done]

    def _enforce_deadlines(self, stepped: list[int]) -> list[Completion]:
        """Charge one step to every slot that ran and evict the expired ones."""
        finished = []
        for slot in stepped:
            if slot not in self.running:      # finished normally this step
                self._slot_steps.pop(slot, None)
                continue
            n = self._slot_steps.get(slot, 0) + 1
            self._slot_steps[slot] = n
            if n >= self.max_slot_steps:
                finished.extend(self._evict_slot(slot))
        return finished

    # -- drive ---------------------------------------------------------------

    def step(self) -> list[Completion]:
        """Advance in-flight admissions one unit, fill free slots, run one
        multi-slot engine step, collect finished slots. Returns the requests
        completed during this call."""
        with TraceAnnotation("scheduler.step", step=self.steps) as span:
            with TraceAnnotation("scheduler.admit"):
                finished = self._advance_admissions()
                finished.extend(self._admit_free_slots())
            if not self.running:
                return finished
            stepped = list(self.running)
            span.set_metadata(slots=len(stepped))
            with TraceAnnotation("scheduler.dispatch"):
                self.state, emitted = self.engine.step(self._step_params(),
                                                       self.state)
            self.steps += 1
            self.slot_steps += len(stepped)
            self.computed_slot_steps += self.engine.num_slots
            with TraceAnnotation("scheduler.collect"):
                finished.extend(self._collect(emitted))
            if self.max_slot_steps is not None:
                finished.extend(self._enforce_deadlines(stepped))
            return finished

    def run(self, timeout: float | None = None) -> dict[int, Completion]:
        """Step until the queue and all slots drain. Returns {rid: Completion}."""
        t0 = self.clock()
        while self.pending or self.running or self.admitting:
            self.step()
            if timeout is not None and self.clock() - t0 > timeout:
                raise TimeoutError(
                    f"scheduler did not drain within {timeout}s "
                    f"(pending={self.pending}, active={self.active})"
                )
        return self.results


class Scheduler(SlotScheduler):
    """LM request scheduler over a ``ContinuousEngine``.

    Short prompts admit with one whole-prompt prefill; prompts longer than the
    engine's ``prefill_chunk`` (when chunking is enabled) reserve their slot
    and run one prefill chunk per scheduler step, interleaved with the other
    slots' decode steps — the long admission no longer stalls the step loop
    for a whole-prompt prefill.
    """

    def __init__(self, engine: ContinuousEngine, params,
                 clock: Callable[[], float] = time.monotonic,
                 *, max_slot_steps: int | None = None, max_requeues: int = 1):
        super().__init__(engine, params, clock,
                         max_slot_steps=max_slot_steps,
                         max_requeues=max_requeues)

    def submit(self, tokens, *, extras: dict | None = None,
               max_new: int | None = None, key: jax.Array | None = None) -> int:
        """Queue one request. `tokens` [S] or [1, S]; `extras` holds additional
        B=1 model inputs (patch_embeds, positions, frames). Returns request id."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        batch = {"tokens": tokens, **(extras or {})}
        max_new = self.engine.cfg.max_new if max_new is None else max_new
        if not 1 <= max_new <= self.engine.cfg.max_new:
            raise ValueError(f"max_new must be in [1, {self.engine.cfg.max_new}]")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid, batch, tokens.shape[1], max_new,
            key if key is not None else jax.random.PRNGKey(rid), self.clock(),
        )
        self.buckets[_prompt_sig(batch)].append(req)
        return rid

    def _bucket_key(self, req: Request):
        return _prompt_sig(req.batch)

    def _fail_eviction(self, slot: int, record) -> Completion:
        req, toks, t_admit = record
        return Completion(
            req.rid, toks, "evicted", req.prompt_len, req.t_submit, t_admit,
            self.clock(),
        )

    def _finish(self, slot: int, reason: str) -> Completion:
        req, toks, t_admit = self.running.pop(slot)
        done = Completion(
            req.rid, toks, reason, req.prompt_len, req.t_submit, t_admit, self.clock()
        )
        self.results[req.rid] = done
        self.free.append(slot)
        return done

    def _register(self, req: Request, slot: int, tok0: int) -> list[Completion]:
        """Record a freshly admitted request; finish immediately on instant EOS
        or max_new == 1."""
        self.running[slot] = (req, [tok0], self.clock())
        eos = self.engine.cfg.eos_id
        if eos is not None and tok0 == eos:
            return [self._finish(slot, "eos")]
        if req.max_new <= 1:
            return [self._finish(slot, "length")]
        return []

    def _start_admission(self, req: Request, slot: int) -> list[Completion]:
        if self.engine.supports_chunked_prefill(req.batch):
            job = self.engine.begin_chunked_prefill(self.params, req.batch, req.key)
            # run the first chunk now so a reserved slot always has progress
            job = self.engine.advance_chunked_prefill(self.params, job)
            self.admitting[slot] = (req, job)
            return []
        self.state, tok0 = self.engine.prefill_into_slot(
            self.params, self.state, req.batch, slot, req.key
        )
        return self._register(req, slot, tok0)

    def _advance_admissions(self) -> list[Completion]:
        finished = []
        for slot in sorted(self.admitting):
            req, job = self.admitting[slot]
            if not job.done:
                job = self.engine.advance_chunked_prefill(self.params, job)
                self.admitting[slot] = (req, job)
            if job.done:
                del self.admitting[slot]
                self.state, tok0 = self.engine.admit_chunked(self.state, job, slot)
                finished.extend(self._register(req, slot, tok0))
        return finished

    def _collect(self, emitted) -> list[Completion]:
        finished = []
        em = np.asarray(emitted)    # device sync: this is the step barrier
        eos = self.engine.cfg.eos_id
        for slot in sorted(self.running):
            req, toks, _ = self.running[slot]
            tok = int(em[slot])
            toks.append(tok)
            if eos is not None and tok == eos:
                finished.append(self._finish(slot, "eos"))
            elif len(toks) >= req.max_new:
                finished.append(self._finish(slot, "length"))
        return finished
