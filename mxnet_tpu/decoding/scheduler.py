"""Continuous-batching scheduler: the control loop of the decode tier.

One scheduler thread per decoder model drives a fixed-shape
`DecodeEngine` step loop. Unlike the one-shot batcher (which forms a
batch, runs it, and replies), the decode batch is a ROLLING set: every
step the scheduler

  1. resolves per-sequence deadlines (mid-generation, not just at
     admission — a stuck client's sequence frees its pages promptly),
  2. admits waiting requests into free batch rows (prefill: one
     bucket-padded prompt pass that scatters K/V into fresh pages),
  3. grows each live sequence's page table by one page when its next
     token crosses a page boundary — preempting the lowest-priority
     (ties: most recently admitted) sequence when the pool is
     exhausted, never crashing (CI gate iii),
  4. runs ONE fixed-shape decode step over the full (max_batch,
     pages_bucket) grid and streams each live row's token out.

Preemption drops a sequence's pages but keeps its token history; on
readmission the scheduler re-prefills prompt + generated-so-far and
the continuation is bit-identical to the uninterrupted run (the
XLA-level prefix stability tests/test_decoding.py pins) — including
sampled runs, whose randomness is a pure function of (request seed,
position) and so replays exactly.

Two work-avoidance layers ride the same loop (ROADMAP item 1):

  * a `PrefixCache` (prefix.py) lets admission map full prompt pages
    already prefilled by live or recently-finished sequences instead
    of recomputing them — only the tail past the cached prefix is
    prefilled. Cached-but-unreferenced pages are evicted LRU under
    pool pressure BEFORE any live sequence is preempted.
  * with a draft model loaded, `_step` runs the engine's speculative
    propose+verify pair and can emit up to spec_k+1 tokens per target
    step (speculative.py proves output equivalence).

With `run_ahead` = D the loop keeps D plain steps launched beyond the
one whose tokens it waits for (`_turn_ahead`): the device goes from
step to step without the host; a request for a free row is admitted
with the steps still in flight, and every other decision of the list
above is made with nothing in flight.

Tokens reach callers through `DecodeFuture`: `result()` is the full
generated list (the serving Future contract), `stream()` returns a
`TokenStream` iterating tokens as steps complete. The stream OWNS the
request: closing it (context-manager exit, `close()`, or GC) cancels
an unfinished request so its pages return to the pool instead of
decoding on to max_tokens for a reader that left.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading
import time

import numpy as np

from ..serving.batcher import (DeadlineExceededError, ServerBusyError,
                               ServerClosedError, ServingError,
                               pick_bucket)
from ..telemetry import trace as _trace
from . import config as _cfg
from .blocks import SCRATCH_PAGE, PagePoolExhausted, pages_needed, \
    release_behind
from .blocks import cover as _cover
from .engine import DecodeEngine
from .prefix import PrefixCache
from .sampling import SamplingParams
from .stats import DecodeStats

_DONE = object()


class RequestHandedOff(ServingError):
    """Raised into a request's future/stream when a draining decoder
    hands the request off instead of finishing it locally. `.state`
    is the JSON-ready resume record (prompt, tokens generated so far,
    sampling seed + position, remaining deadline) that
    `admit_resumed` on any other replica accepts — under counter-based
    sampling the continuation there is bit-identical to the
    uninterrupted run, so a caller (normally the fleet router) loses
    nothing but a little latency."""

    def __init__(self, state):
        super().__init__(
            "request handed off mid-decode; resume elsewhere with "
            "admit_resumed(exc.state)")
        self.state = state


class DecodeFuture:
    """Handle for one decode request: both a future and a stream.

    `result(timeout)` blocks for the COMPLETE generated token list
    (EOS excluded) or raises the request's failure. `stream(timeout)`
    returns a TokenStream iterating tokens as the scheduler emits
    them — the first token arrives right after prefill — and raises
    the failure mid-iteration if one lands. `finish_reason` is
    "eos" | "max_tokens" | "length" | "cancelled" after completion.

    `cancel()` asks the scheduler to stop the request at its next
    sweep: the future resolves with reason "cancelled" holding the
    tokens generated so far, and the sequence's pages go back to the
    pool. No-op once done.
    """

    def __init__(self, trace_id=None):
        self.trace_id = trace_id
        self.finish_reason = None
        self._q = queue.Queue()
        self._done = threading.Event()
        self._cancel = threading.Event()
        self._tokens = None
        self._exc = None

    # ---------------------------------------------- scheduler side
    def _emit(self, tok):
        self._q.put(int(tok))

    def _finish(self, tokens, reason):
        self.finish_reason = reason
        self._tokens = list(tokens)
        self._done.set()
        self._q.put(_DONE)

    def _fail(self, exc):
        self._exc = exc
        self._done.set()
        self._q.put(exc)

    # ------------------------------------------------- caller side
    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("decode request still running")
        if self._exc is not None:
            raise self._exc
        return list(self._tokens)

    def exception(self, timeout=None):
        if not self._done.wait(timeout):
            raise TimeoutError("decode request still running")
        return self._exc

    def cancel(self):
        """Request cancellation; returns True if the request was still
        running (the scheduler will resolve it with reason
        "cancelled"), False if it had already finished."""
        if self._done.is_set():
            return False
        self._cancel.set()
        return True

    def stream(self, timeout=None):
        """A TokenStream over generated tokens (see class docstring:
        the stream owns the request — close it to cancel)."""
        return TokenStream(self, timeout=timeout)


class TokenStream:
    """Iterator over one request's tokens that OWNS the request.

    Abandoning a stream used to leak the whole tail of the request:
    the scheduler kept decoding to max_new_tokens, holding pages and a
    batch row for a reader that left. TokenStream closes that hole —
    `close()`, `with`-exit, and garbage collection all cancel the
    underlying request if it has not finished. Iterating to the end
    makes close a no-op.
    """

    def __init__(self, future, timeout=None):
        self.future = future
        self._timeout = timeout

    def __iter__(self):
        return self

    def __next__(self):
        item = self.future._q.get(timeout=self._timeout)
        if item is _DONE:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item

    def close(self):
        """Cancel the request unless it already finished."""
        self.future.cancel()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _Sequence:
    """Scheduler-internal state of one in-flight request."""

    __slots__ = ("prompt", "max_new", "priority", "deadline", "future",
                 "trace_id", "order", "sampling", "use_draft",
                 "generated", "table", "length", "last_token",
                 "preempted", "t_submit_pc", "t_queued", "pending_tail",
                 "tail_meta", "ahead", "side")

    def __init__(self, prompt, max_new, priority, deadline, future,
                 trace_id, order, sampling, use_draft):
        self.prompt = list(prompt)
        self.max_new = max_new
        self.priority = priority
        self.deadline = deadline       # absolute monotonic, or None
        self.future = future
        self.trace_id = trace_id
        self.order = order             # admission tiebreak (FIFO)
        self.sampling = sampling       # SamplingParams (resolved)
        self.use_draft = use_draft     # speculative opt-in for this row
        self.generated = []
        self.table = None              # page ids while active
        # the tables of the engine's further page groups, indexed like
        # `table` (a windowed group's released entries hold the scratch
        # page); empty for a model with one group
        self.side = []
        self.length = 0                # tokens materialized in cache
        self.last_token = -1
        self.preempted = False
        self.t_submit_pc = _trace.now()
        # since when it waits for a row: its submit, or its preemption
        self.t_queued = self.t_submit_pc
        # merged-step tail prefill (engine.merged_step_enabled): the
        # uncached prompt tail still to be fed through step() rows,
        # and (t0, n_ctx, start, need_total, n_matched) bookkeeping
        # for the note_prefill/span record at completion
        self.pending_tail = None
        self.tail_meta = None
        # steps launched for this row whose tokens are not out yet
        # (ContinuousScheduler.run_ahead)
        self.ahead = 0

    def context_tokens(self):
        """Tokens the KV cache must hold for this sequence: the prompt
        plus everything generated EXCEPT the newest token (whose K/V
        is appended by the next decode step)."""
        return self.prompt + self.generated[:-1] \
            if self.generated else list(self.prompt)


class ContinuousScheduler:
    """The rolling-batch control loop over one DecodeEngine."""

    def __init__(self, engine, stats, key, queue_cap=None,
                 max_tokens=None, eos_id=None, run_ahead=None):
        self.engine = engine
        self.stats = stats
        self.key = key
        # steps kept in flight beyond the one whose tokens the loop
        # waits for (see _turn_ahead); 0 is the turn that launches a
        # step and waits for it. Unset, the backend decides
        # (decoding.config.run_ahead)
        self.run_ahead = int(run_ahead) if run_ahead is not None \
            else _cfg.run_ahead(engine)
        if self.run_ahead and (engine.spec_enabled
                               or engine.merged_step_enabled):
            raise ServingError(
                "run_ahead keeps plain decode steps in flight: not "
                "with speculation or the merged step")
        self._ahead = collections.deque()   # launched, tokens not out
        self._t_retired = 0.0
        # prefills launched and launched steps taken out, ever: what a
        # `decoding.admit` span counts of each inside it
        self._n_prefills = 0
        self._n_retired = 0
        self.queue_cap = queue_cap if queue_cap is not None \
            else _cfg.queue_cap()
        self.default_max_tokens = max_tokens if max_tokens is not None \
            else _cfg.max_tokens()
        self.eos_id = eos_id if eos_id is not None \
            else engine.cfg.eos_id
        # prompt-prefix page cache: admission-side work avoidance; the
        # cache inherits the engine's kv dtype so its advertised
        # digests can never match pages stored at another precision
        self.cache = PrefixCache(engine.allocator,
                                 kv_dtype=engine.kv_dtype) \
            if engine.prefix_cache_enabled else None
        self._cond = threading.Condition()
        self._waiting = []
        self._rows = [None] * engine.max_batch
        self._tail_plan = []           # (seq, chunk) for this step
        self._order = itertools.count()
        self._closed = False
        self._drain = True
        self._draining = False         # drain(): admission closed
        self._handoff = False          # leftovers hand off, not fail
        self._handoff_states = []      # loop/backstop-thread only
        self._thread = None

    # ------------------------------------------------------ public API
    def start(self):
        self._thread = threading.Thread(
            target=self._loop, name=f"decoding-{self.key}", daemon=True)
        self._thread.start()
        return self

    def depth(self):
        """(waiting, active) — the stats view's queue-depth probe."""
        with self._cond:
            return (len(self._waiting),
                    sum(1 for s in self._rows if s is not None))

    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_ms=None, sampling=None, seed=None, draft=None):
        """Enqueue one autoregressive request; returns a DecodeFuture.

        `priority`: higher values survive page-pool pressure longer
        (preemption victims are chosen lowest-priority-first).
        `deadline_ms` is end-to-end and checked EVERY step, not only
        at admission — a mid-generation miss resolves the future with
        DeadlineExceededError and frees the sequence's pages.
        `sampling`/`seed`: a SamplingParams (or None for the env
        defaults; `seed` overrides just the stream seed). Greedy
        (temperature<=0) needs no seed. `draft`: per-request
        speculative opt-in/out; defaults to "on when a draft model is
        loaded".
        """
        prompt = [int(t) for t in prompt]
        sp = SamplingParams.resolve(sampling, seed)
        sp.validate(self.engine.cfg.vocab)
        if draft is None:
            use_draft = self.engine.spec_enabled
        else:
            use_draft = bool(draft)
            if use_draft and not self.engine.spec_enabled:
                raise ServingError(
                    "speculative decoding requested but no draft "
                    "model is loaded")
        if not prompt:
            raise ServingError("empty prompt")
        if any(t < 0 or t >= self.engine.cfg.vocab for t in prompt):
            raise ServingError("prompt token outside vocab")
        if len(prompt) > self.engine.max_context:
            raise ServingError(
                f"prompt of {len(prompt)} tokens exceeds the decode "
                f"context capacity {self.engine.max_context}")
        max_new = int(max_new_tokens) if max_new_tokens is not None \
            else self.default_max_tokens
        if max_new < 1:
            raise ServingError("max_new_tokens must be >= 1")
        tid = _trace.new_trace_id()
        with _trace.span("decoding.submit", trace_id=tid,
                         model=self.key):
            deadline = (time.monotonic() + deadline_ms / 1e3
                        if deadline_ms is not None else None)
            fut = DecodeFuture(tid)
            with self._cond:
                if self._closed or self._draining:
                    raise ServerClosedError("decoder is shut down")
                if len(self._waiting) >= self.queue_cap:
                    self.stats.note_rejected()
                    raise ServerBusyError(
                        f"decode queue full ({self.queue_cap}); "
                        "retry with backoff")
                seq = _Sequence(prompt, max_new, int(priority),
                                deadline, fut, tid, next(self._order),
                                sp, use_draft)
                self._waiting.append(seq)
                self._cond.notify()
        self.stats.note_submitted()
        return fut

    def admit_resumed(self, state):
        """Admit a request handed off by another scheduler's `drain()`
        (or rebuilt by the fleet router from its own token record
        after a replica died). The resumed future's STREAM emits only
        NEW tokens — everything in state["generated"] was already
        delivered by the original replica — while `result()` returns
        the full list. Counter-based sampling (token at position P is
        a pure function of the request seed and P) plus the XLA
        prefix-stability property make the continuation bit-identical
        to the uninterrupted run; internally this rides the exact
        readmission path preemption uses."""
        prompt = [int(t) for t in state["prompt"]]
        generated = [int(t) for t in state.get("generated", ())]
        sp = SamplingParams.resolve(state.get("sampling"), None)
        sp.validate(self.engine.cfg.vocab)
        max_new = int(state["max_new_tokens"])
        if not prompt:
            raise ServingError("empty prompt in resume state")
        if any(t < 0 or t >= self.engine.cfg.vocab
               for t in prompt + generated):
            raise ServingError("resume state token outside vocab")
        if len(generated) >= max_new:
            raise ServingError(
                "resume state is already at max_new_tokens; nothing "
                "left to decode")
        if len(prompt) + len(generated) > self.engine.max_context:
            raise ServingError(
                "resume state exceeds the decode context capacity "
                f"{self.engine.max_context}")
        use_draft = bool(state.get("draft")) and self.engine.spec_enabled
        deadline_ms = state.get("deadline_ms")
        deadline = (time.monotonic() + float(deadline_ms) / 1e3
                    if deadline_ms is not None else None)
        tid = _trace.new_trace_id()
        fut = DecodeFuture(tid)
        with self._cond:
            if self._closed or self._draining:
                raise ServerClosedError("decoder is shut down")
            if len(self._waiting) >= self.queue_cap:
                self.stats.note_rejected()
                raise ServerBusyError(
                    f"decode queue full ({self.queue_cap}); "
                    "retry with backoff")
            seq = _Sequence(prompt, max_new,
                            int(state.get("priority", 0)), deadline,
                            fut, tid, next(self._order), sp, use_draft)
            seq.generated = generated
            if generated:
                # the preemption-readmission contract: _admit restores
                # last_token without re-emitting the replayed token
                seq.preempted = True
                seq.last_token = generated[-1]
            self._waiting.append(seq)
            self._cond.notify()
        self.stats.note_submitted()
        return fut

    def _handoff_state(self, seq, now=None):
        """JSON-ready resume record for one unfinished sequence (the
        payload of RequestHandedOff / input of admit_resumed)."""
        sp = seq.sampling
        st = {
            "prompt": list(seq.prompt),
            "generated": list(seq.generated),
            "max_new_tokens": seq.max_new,
            "priority": seq.priority,
            "position": len(seq.generated),
            "draft": bool(seq.use_draft),
            "sampling": {"temperature": sp.temperature,
                         "top_k": sp.top_k, "top_p": sp.top_p,
                         "seed": sp.seed},
        }
        if seq.deadline is not None:
            if now is None:
                now = time.monotonic()
            st["deadline_ms"] = max(0.0, (seq.deadline - now) * 1e3)
        return st

    def drain(self, timeout=30):
        """Graceful shutdown with zero request loss: stop admitting,
        let live decodes run to completion for up to `timeout`
        seconds, then hand off whatever is still unfinished — each
        leftover future resolves with RequestHandedOff carrying the
        resume record, and the full list of records is returned so a
        control plane (the fleet router) can re-admit them elsewhere.
        With timeout=0 everything in flight hands off immediately."""
        deadline = time.monotonic() + max(0.0, float(timeout))
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        while time.monotonic() < deadline:
            with self._cond:
                busy = bool(self._waiting) or any(
                    s is not None for s in self._rows)
            if not busy:
                break
            time.sleep(0.01)        # poll outside the lock
        with self._cond:
            self._closed = True
            self._drain = False
            self._handoff = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._fail_leftovers()
        if self.cache is not None:
            self.cache.release_all()
        return [dict(st) for st in self._handoff_states]

    def stop(self, drain=True, timeout=30):
        """Close admission; drain=True finishes in-flight sequences,
        drain=False fails them fast with ServerClosedError."""
        with self._cond:
            self._closed = True
            self._drain = drain
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        self._fail_leftovers()
        if self.cache is not None:
            # the loop is down: flush the cache's page refs so the
            # pool drains to empty (pages_in_use == 0 after close)
            self.cache.release_all()

    def _fail_leftovers(self):
        """Backstop against stranded futures: if the loop thread is
        down (never started, died on a persistent engine error, or
        outlived its join timeout and then exited) any request still
        queued or rowed would otherwise wait forever. Sweep them into
        a terminal state — handoff records when draining, a
        ServerClosedError otherwise. No-op while the loop is alive
        (it owns the sweep then)."""
        if self._thread is not None and self._thread.is_alive():
            return
        with self._cond:
            leftovers = self._waiting[:]
            self._waiting.clear()
            leftovers.extend(s for s in self._rows if s is not None)
            handoff = self._handoff
        for s in leftovers:
            if s.future.done():
                continue
            if handoff:
                st = self._handoff_state(s)
                self._handoff_states.append(st)
                self.stats.note_cancelled()
                self._resolve(s, exc=RequestHandedOff(st))
            else:
                self.stats.note_failed()
                self._resolve(s, exc=ServerClosedError(
                    "decoder stopped"))

    # ---------------------------------------------------- loop helpers
    def _active(self):
        return [s for s in self._rows if s is not None]

    def _resolve(self, seq, *, exc=None, reason=None):
        """Terminal transition: free pages, clear the row, settle the
        future exactly once."""
        if seq.table is not None:
            self._drop_pages(seq)
        with self._cond:
            # rows are loop-thread-owned but read under the cond by
            # depth(); publish the clear through the same lock
            for row, s in enumerate(self._rows):
                if s is seq:
                    self._rows[row] = None
        # the span is the hand-off alone (settling the future), as
        # serving.reply is; the request's lifetime rides as an
        # attribute — a span that long would cover, and so name, every
        # device idle gap of a capture
        t_r0 = _trace.now()
        if exc is not None:
            seq.future._fail(exc)
        else:
            self.stats.note_completed()
            seq.future._finish(seq.generated, reason)
        t_r1 = _trace.now()
        _trace.record_span(
            "decoding.reply", seq.trace_id, t_r0, t_r1,
            {"model": self.key,
             "outcome": reason or type(exc).__name__,
             "tokens": len(seq.generated),
             "latency_us": round((t_r1 - seq.t_submit_pc) * 1e6, 1)})

    def _drop_pages(self, seq):
        """A sequence's pages of every group back to their allocators
        (a table's scratch entries are no one's)."""
        for alloc, table in zip(self.engine.allocators,
                                [seq.table] + seq.side):
            alloc.free(table)
        seq.table, seq.side = None, []

    def _preempt(self, seq):
        """Evict for pages: drop the sequence's pages but keep its
        token history; it re-prefills on readmission (bit-identical
        continuation — the XLA prefix-stability property)."""
        self._settle()      # its tokens in flight come out first
        if seq.table is None:
            return          # one of them was its last
        self._drop_pages(seq)
        seq.preempted = True
        # a merged-step tail in flight dies with the pages: readmission
        # re-plans the whole prompt (possibly re-matching the cache)
        seq.pending_tail = None
        seq.tail_meta = None
        seq.t_queued = _trace.now()
        with self._cond:
            for row, s in enumerate(self._rows):
                if s is seq:
                    self._rows[row] = None
            self._waiting.append(seq)
        self.stats.note_preempted()

    def _reclaim_one(self, requester):
        """Free pages by preempting ONE victim: the lowest-priority
        active sequence, ties broken most-recently-admitted-first.
        The requester itself is a candidate (it may BE the lowest
        priority). Returns the victim, or None when nothing is
        preemptible."""
        self._settle()      # a victim's history must be whole
        victims = self._active()
        if requester is not None and requester.table is None:
            # an admission candidate competes at its own priority
            victims = [s for s in victims
                       if (s.priority, -s.order)
                       < (requester.priority, -requester.order)]
        if not victims:
            return None
        victim = min(victims, key=lambda s: (s.priority, -s.order))
        self._preempt(victim)
        return victim

    def _check_deadlines(self, now):
        """Per-step deadline resolution for BOTH queued and active
        sequences (the decode half of the serving deadline fix)."""
        with self._cond:
            expired = [s for s in self._waiting
                       if s.deadline is not None and now > s.deadline]
            for s in expired:
                self._waiting.remove(s)
        for s in self._active():
            if s.deadline is not None and now > s.deadline:
                expired.append(s)
        for s in expired:
            self.stats.note_expired()
            self._resolve(s, exc=DeadlineExceededError(
                f"deadline passed after {len(s.generated)} tokens"))

    def _check_cancelled(self):
        """Resolve requests whose future (or owning TokenStream) was
        cancelled: queued ones never admit, active ones free their
        pages now instead of decoding to max_tokens."""
        with self._cond:
            doomed = [s for s in self._waiting
                      if s.future._cancel.is_set()]
            for s in doomed:
                self._waiting.remove(s)
        for s in self._active():
            if s.future._cancel.is_set():
                doomed.append(s)
        for s in doomed:
            self.stats.note_cancelled()
            self._resolve(s, reason="cancelled")

    def _free_one_page(self, requester):
        """Make at least one page reclaimable, cheapest source first:
        evict a cached-but-idle prefix run before preempting any live
        sequence (the cache must never cause a preemption). Returns
        False when neither source can yield."""
        if self.cache is not None and self.cache.evict_lru():
            return True
        return self._reclaim_one(requester) is not None

    def _handle_token(self, seq, tok):
        """Post-step bookkeeping for one live row's emitted token."""
        if tok == self.eos_id:
            self._resolve(seq, reason="eos")
            return
        seq.generated.append(tok)
        seq.last_token = tok
        seq.future._emit(tok)
        if len(seq.generated) >= seq.max_new:
            self._resolve(seq, reason="max_tokens")
        elif seq.length >= self.engine.max_context:
            # no page can hold the next position: capacity stop
            self._resolve(seq, reason="length")

    def _finish_tail(self, seq, first_tok):
        """Merged-step tail completion: the bookkeeping a dedicated
        tail-prefill dispatch would have done at admission — prefill
        stats, span record, cache publish, first-token handling —
        deferred to the decode step that wrote the final tail token
        (so cached pages are only published once actually filled)."""
        t0, n_ctx, start, need_total, n_matched = seq.tail_meta
        seq.tail_meta = None
        seq.pending_tail = None
        dt = _trace.now() - t0
        self.stats.note_prefill(n_ctx - start, dt,
                                readmission=seq.preempted)
        _trace.record_span(
            "decoding.prefill", seq.trace_id, t0, t0 + dt,
            {"model": self.key, "tokens": n_ctx,
             "cached_tokens": start, "pages": need_total,
             "pages_reused": n_matched,
             "readmission": seq.preempted, "merged": True})
        if self.cache is not None:
            P = self.engine.page_size
            n_full = len(seq.prompt) // P
            if n_full:
                self.cache.insert(seq.prompt[:n_full * P],
                                  seq.table[:n_full])
        was_preempted, seq.preempted = seq.preempted, False
        if was_preempted and seq.generated:
            # tail replay of a preempted run reproduces the token
            # already emitted; restore, don't re-emit (see _admit)
            seq.last_token = seq.generated[-1]
        else:
            self._handle_token(seq, first_tok)

    # -------------------------------------------------------- admission
    def _admit(self):
        """Fill free batch rows from the waiting queue in (priority,
        FIFO) order. Admission prefers free pages but will preempt
        strictly-lower-priority active sequences to make room.

        With the prefix cache on, admission first maps every full
        prompt page already cached for this token prefix (allocator
        `ref`, the fork path — zero compute) and prefills ONLY the
        tail. The match is capped one page short of the prompt so at
        least one tail token always runs (the prefill program needs a
        position to emit from) — which also keeps cached pages out of
        every write range. After prefill the sequence's own full
        prompt pages are inserted, making them reusable by the next
        request while this one is still decoding.
        """
        alloc = self.engine.allocator
        P = self.engine.page_size
        while None in self._rows:
            with self._cond:
                if not self._waiting:
                    return
                seq = min(self._waiting,
                          key=lambda s: (-s.priority, s.order))
                self._waiting.remove(seq)
            queued = _trace.now() - seq.t_queued
            tokens = seq.context_tokens()
            need_total = pages_needed(len(tokens), P)
            matched, start = [], 0
            if self.cache is not None:
                matched, start = self.cache.match(
                    tokens, (len(tokens) - 1) // P)
                self.stats.note_prefix_reuse(len(matched))
            need = need_total - len(matched)
            # further page groups are covered chunk by chunk as the
            # prompt is prefilled: what they need at most must be free
            side_need = self.engine.prefill_pages(len(tokens))[1:]
            ok = True
            while alloc.free_pages() < need or any(
                    a.free_pages() < n for a, n in zip(
                        self.engine.allocators[1:], side_need)):
                if not self._free_one_page(seq):
                    # nothing reclaimable below this priority: requeue
                    # and stop admitting (pages may free up later)
                    ok = False
                    break
            if not ok:
                if matched:
                    alloc.free(matched)
                with self._cond:
                    self._waiting.append(seq)
                return
            seq.table = matched + alloc.alloc(need)
            seq.side = [[] for _ in side_need]
            with self._cond:
                row = self._rows.index(None)
                self._rows[row] = seq
            if start and self.engine.merged_step_enabled:
                # merged-step deferral: no tail-prefill dispatch here —
                # the uncached tail rides the next decode step(s) as
                # ragged rows (_grow plans the chunks, _step packs
                # them). length stays at the cached prefix until those
                # rows actually write; the cache insert and the
                # note_prefill/first-token bookkeeping happen at tail
                # completion (_finish_tail), when the pages are real.
                seq.pending_tail = list(tokens[start:])
                seq.length = start
                seq.tail_meta = (_trace.now(), len(tokens), start,
                                 need_total, len(matched))
                continue
            behind, launched = 0.0, None
            if self._ahead:
                # the prefill queues on the device behind the steps in
                # flight: their tokens go out as they arrive, and the
                # span is the prefill's own time
                launched, launch_s = self._launch_prefill(seq, tokens,
                                                          start)
                t_launched = _trace.now()
                self._settle()
                behind = _trace.now() - t_launched
            with _trace.span(
                    "decoding.prefill", seq.trace_id, model=self.key,
                    tokens=len(tokens), cached_tokens=start,
                    pages=need_total, pages_reused=len(matched),
                    readmission=seq.preempted) as fill:
                t0 = _trace.now()
                if launched is None:
                    launched, launch_s = self._launch_prefill(
                        seq, tokens, start)
                first = self.engine.fetch_prefill(launched)
                dt = _trace.now() - t0
                # how many programs the prompt took, and what a block
                # that counts (engine.cfg.step_counters) counted over
                # them; how long the request waited for a row, the
                # dispatch's host time, the wait behind the steps in
                # flight
                counted = self.engine.last_prefill
                fill.note(queued_us=round(queued * 1e6),
                          launch_us=round(launch_s * 1e6),
                          behind_us=round(behind * 1e6), **counted)
            self._n_prefills += 1
            self.stats.note_prefill(len(tokens) - start, dt,
                                    readmission=seq.preempted)
            self.stats.note_counters(counted)
            if seq.side:
                self.stats.note_released(self.engine.last_released)
            seq.length = len(tokens)
            if self.cache is not None:
                # publish this prompt's full pages (existing runs keep
                # their pages; only the new suffix takes cache refs)
                n_full = len(seq.prompt) // P
                if n_full:
                    self.cache.insert(seq.prompt[:n_full * P],
                                      seq.table[:n_full])
            was_preempted, seq.preempted = seq.preempted, False
            if was_preempted and seq.generated:
                # the re-prefill reproduces the token already emitted
                # (prefix stability — sampled streams are (seed,
                # position)-pure); restore, don't re-emit. A sequence
                # preempted mid-tail (merged-step mode) may have no
                # token yet — its first token is genuinely new.
                seq.last_token = seq.generated[-1]
            else:
                self._handle_token(seq, int(first))

    def _launch_prefill(self, seq, tokens, start):
        """A sequence's prefill dispatched, every chunk of it: returns
        what `fetch_prefill` takes and the dispatch's host seconds."""
        t0 = _trace.now()
        launched = self.engine.launch_prefill(
            tokens, [seq.table] + seq.side if seq.side else seq.table,
            start=start,
            seed=seq.sampling.seed,
            temperature=seq.sampling.temperature,
            top_k=seq.sampling.top_k, top_p=seq.sampling.top_p)
        return launched, _trace.now() - t0

    def _admit_turn(self):
        """A turn's decisions and admissions as one `decoding.admit`
        span: deadlines, cancels, admission, page growth. The span says
        how many prefills it launched and how many launched steps it
        took out (`prefills`, `drained`)."""
        prefills, retired = self._n_prefills, self._n_retired
        with _trace.span("decoding.admit") as admit_span:
            self._check_deadlines(time.monotonic())
            self._check_cancelled()
            self._admit()
            self._grow()
            admit_span.note(prefills=self._n_prefills - prefills,
                            drained=self._n_retired - retired)

    # ------------------------------------------------------------ growth
    def _grow(self):
        """Before each step, make every live row's WHOLE write range
        backed by exclusively-owned pages: positions length..length+K
        (K = spec_k in speculative mode, else 0). Allocates across
        page boundaries (evicting cached pages, then preempting,
        under pressure) and breaks COW aliases on every page the step
        may write — rejected speculative entries land in owned pages,
        so rollback-by-truncation never corrupts a shared page."""
        alloc = self.engine.allocator
        P = self.engine.page_size
        k = self.engine.spec_k if self.engine.spec_enabled else 0
        side = list(zip(self.engine.groups,
                        self.engine.allocators))[1:]
        if side:
            self._release_windows()
        for seq in self._active():
            if seq.table is None or seq.pending_tail:
                continue    # tail seqs: write range planned below
            # pages covering the step's write positions (clamped to
            # capacity: the host stops at max_context before any
            # clamped write could be read back)
            cover = min(seq.length + seq.ahead + k + 1,
                        self.engine.max_context)
            need = pages_needed(cover, P)
            while seq.table is not None and len(seq.table) < need:
                try:
                    seq.table.extend(alloc.alloc(1))
                except PagePoolExhausted:
                    if self.cache is not None and self.cache.evict_lru():
                        continue
                    victim = self._reclaim_one(None)
                    if victim is None:
                        break
            if side and seq.table is not None:
                self._grow_side(seq, side, cover)
            if seq.table is None or len(seq.table) < need or (
                    side and any(len(t) < need for t in seq.side)):
                continue    # preempted itself; back in the queue
            first = (seq.length + seq.ahead) // P
            last = min((cover - 1) // P, len(seq.table) - 1)
            for idx in range(first, last + 1):
                page, copy_from = None, None
                while seq.table is not None:
                    try:
                        page, copy_from = alloc.make_writable(
                            seq.table, idx)
                        break
                    except PagePoolExhausted:
                        # COW needs one free page: cheapest first
                        if (self.cache is not None
                                and self.cache.evict_lru()):
                            continue
                        self._preempt(seq)
                if seq.table is None or page is None:
                    break
                if copy_from is not None:
                    self.engine.copy_page(copy_from, page)
        # merged-step tail plan: split this step's tail_budget extra
        # rows across sequences still holding a pending prompt tail,
        # sizing each one's page table for the chunk it will write.
        # Tail pages sit past the cached prefix (the cache matches
        # full pages only), so they are exclusively owned — the
        # make_writable pass below is the same COW discipline as
        # above and never copies in practice.
        self._tail_plan = []
        if not self.engine.merged_step_enabled:
            return
        budget = self.engine.tail_budget
        for seq in self._active():
            if budget <= 0:
                break
            if seq.table is None or not seq.pending_tail:
                continue
            chunk = min(len(seq.pending_tail), budget)
            cover = min(seq.length + chunk, self.engine.max_context)
            need = pages_needed(cover, P)
            while seq.table is not None and len(seq.table) < need:
                try:
                    seq.table.extend(alloc.alloc(1))
                except PagePoolExhausted:
                    if self.cache is not None and self.cache.evict_lru():
                        continue
                    if self._reclaim_one(None) is None:
                        break
            if seq.table is None or len(seq.table) < need:
                continue
            first = seq.length // P
            last = min((cover - 1) // P, len(seq.table) - 1)
            ok = True
            for idx in range(first, last + 1):
                page, copy_from = None, None
                while seq.table is not None:
                    try:
                        page, copy_from = alloc.make_writable(
                            seq.table, idx)
                        break
                    except PagePoolExhausted:
                        if (self.cache is not None
                                and self.cache.evict_lru()):
                            continue
                        self._preempt(seq)
                if seq.table is None or page is None:
                    ok = False
                    break
                if copy_from is not None:
                    self.engine.copy_page(copy_from, page)
            if ok and seq.table is not None:
                self._tail_plan.append((seq, chunk))
                budget -= chunk

    def _grow_side(self, seq, side, cover):
        """`_grow` for a row's tables of the further page groups: the
        same `cover` positions as the first group's (no page is shared
        there: nothing to make writable)."""
        P = self.engine.page_size
        need = pages_needed(cover, P)
        for (g, a), table in zip(side, list(seq.side)):
            while seq.table is not None and len(table) < need:
                try:
                    _cover(a, table, cover,
                           g.first_page(seq.length + seq.ahead, P))
                except PagePoolExhausted:
                    if self._reclaim_one(None) is None:
                        break

    def _release_windows(self):
        """Windowed groups: every live row's pages that lie wholly
        behind the window of its next query go back to the allocator.
        Steps in flight were launched with the tables they read, and
        whoever gets a page next writes it in a program launched after
        them."""
        P = self.engine.page_size
        released = 0
        for seq in self._active():
            if seq.table is None or self._steps_left(seq) <= 0:
                continue    # takes no further step: resolved as it is
            at = seq.length + seq.ahead
            for g, a, table in zip(self.engine.groups[1:],
                                   self.engine.allocators[1:], seq.side):
                if g.window:
                    released += release_behind(a, table,
                                               g.first_page(at, P))
        self.stats.note_released(released)

    # -------------------------------------------------------------- step
    def _step(self):
        """One engine step over the live rows, as three spans that
        partition it: `decoding.pack` (row arrays), `decoding.step`
        (the engine call: its `engine.launch` and `engine.fetch`),
        `decoding.emit` (tokens out to the streams, stats). One span
        each per turn — never per row or per token."""
        engine = self.engine
        live = [(row, s) for row, s in enumerate(self._rows)
                if s is not None]
        if not live:
            return
        spec = engine.spec_enabled
        k = engine.spec_k if spec else 0
        with _trace.span("decoding.pack"):
            (tokens, table, lengths, active, use_draft, *samp), \
                tail_rows, bucket = self._pack(live)
            step_attrs = self._step_attrs(live, bucket, lengths, active,
                                          samp[1], k + 1)
        with _trace.span("decoding.step", in_flight=0, queued=0,
                         **step_attrs) as step_span:
            t0 = _trace.now()
            if spec:
                out, n_emit = engine.spec_step(
                    tokens, table, lengths, active, use_draft, *samp)
            else:
                out = engine.step(tokens, table, lengths, active, *samp)
            dt = _trace.now() - t0
            if engine.cfg.step_counters:
                # came back with the tokens, in the step's one fetch
                step_span.note(**engine.last_step_counters)
                self.stats.note_counters(engine.last_step_counters)
        with _trace.span("decoding.emit") as emit_span:
            emitted = self._emit(live, tail_rows, out,
                                 n_emit if spec else None)
            emit_span.note(tokens=emitted)
            self._note_step(emitted, dt, step_attrs)
            self.stats.note_pool()
            if engine._guard and self.stats.steps % 16 == 0:
                # interval drain of the numerics guard (one fetch per
                # 16 steps); nonfinite rows surface in nonfinite_*,
                # dequant-overflow clips in quant_clip_*
                # (decodingStats view)
                for nf, clips in engine.drain_guard():
                    if nf:
                        self.stats.note_nonfinite(nf)
                    if clips:
                        self.stats.note_quant_clips(clips)

    def _step_attrs(self, live, bucket, lengths, active, temps, new):
        """What a `decoding.step` span says of the step, from its packed
        rows (`new` positions a row are written and read beside its
        context)."""
        engine = self.engine
        reach = lengths[active] + new
        attrs = {
            "trace_ids": tuple(s.trace_id for _, s in live),
            "model": self.key, "live": len(live), "bucket": bucket,
            # context positions this step's attention reads: what a
            # roofline of the attention kernel is owed
            "ctx_tokens": int(reach.sum()),
            # pages those positions lie in: what the in-place kernel
            # copies, of the `rows x bucket` it is given
            "live_pages": int(
                (-(-reach // engine.page_size)).sum()),
            # pages every group's allocator has handed out
            "pages_held": tuple(a.pages_in_use()
                                for a in engine.allocators),
            "program": engine.step_program(bucket),
            # rows that sample: with none the step's sampler takes its
            # argmax branch (sampling.sample_rows)
            "sampled_rows": int((active & (temps > 0)).sum())}
        for g in engine.groups:
            if g.window:
                # the positions of them a windowed group's layers read
                attrs["window_tokens"] = int(
                    np.minimum(reach, g.window).sum())
        return attrs

    def _note_step(self, emitted, seconds, attrs):
        """A finished step into the stats' running sums."""
        self.stats.note_step(
            emitted, seconds, attrs["live_pages"],
            self.engine.step_rows * attrs["bucket"],
            ctx_tokens=attrs["ctx_tokens"],
            window_tokens=attrs.get("window_tokens", 0),
            pages_held=attrs["pages_held"],
            greedy=not attrs["sampled_rows"])

    def _pack(self, live):
        """The step's fixed-shape row arrays: returns ((tokens, table,
        lengths, active, use_draft, seeds, temps, top_ks, top_ps),
        tail_rows, bucket)."""
        engine = self.engine
        b = engine.max_batch
        r = engine.step_rows        # == b + tail_budget when merged
        # _grow already sized every table for the full write range;
        # span over table lengths keeps the bucket consistent with it
        span = max(len(s.table) for _, s in live)
        bucket = pick_bucket(span, engine.page_buckets)
        tokens = np.zeros((r,), np.int32)
        table = np.full(engine.table_shape(r, bucket), SCRATCH_PAGE,
                        np.int32)
        lengths = np.zeros((r,), np.int32)
        active = np.zeros((r,), bool)
        use_draft = np.zeros((r,), bool)
        seeds = np.zeros((r,), np.uint32)
        temps = np.zeros((r,), np.float32)
        top_ks = np.zeros((r,), np.int32)
        top_ps = np.ones((r,), np.float32)
        for row, s in live:
            if s.pending_tail:
                continue    # fed through the ragged tail rows below
            tokens[row] = s.last_token
            if s.side:
                for gi, t in enumerate([s.table] + s.side):
                    table[gi, row, :len(t)] = t
            else:
                table[row, :len(s.table)] = s.table
            lengths[row] = s.length + s.ahead
            active[row] = True
            use_draft[row] = s.use_draft
            seeds[row] = s.sampling.seed & 0xFFFFFFFF
            temps[row] = s.sampling.temperature
            top_ks[row] = s.sampling.top_k
            top_ps[row] = s.sampling.top_p
        # ragged rows b..r-1: planned prompt-tail chunks ride the same
        # fixed-shape step. Row j of a chunk holds prompt token at
        # absolute position lengths[row] (= count of context tokens
        # already written); the kernel's per-row length masking gives
        # intra-chunk causality for free, and the chunk's LAST row
        # samples the sequence's first token at its true position —
        # bit-identical to the dedicated tail-prefill program.
        tail_rows = []
        next_row = b
        for seq, chunk in self._tail_plan:
            if seq.table is None or not seq.pending_tail \
                    or seq.future.done():
                continue    # resolved/preempted after planning
            chunk = min(chunk, len(seq.pending_tail))
            for j in range(chunk):
                row = next_row
                next_row += 1
                tokens[row] = seq.pending_tail[j]
                table[row, :len(seq.table)] = seq.table
                lengths[row] = seq.length + j
                active[row] = True
                seeds[row] = seq.sampling.seed & 0xFFFFFFFF
                temps[row] = seq.sampling.temperature
                top_ks[row] = seq.sampling.top_k
                top_ps[row] = seq.sampling.top_p
            tail_rows.append((seq, next_row - 1, chunk))
        return (tokens, table, lengths, active, use_draft, seeds, temps,
                top_ks, top_ps), tail_rows, bucket

    def _emit(self, live, tail_rows, out, n_emit):
        """Post-step bookkeeping of every live row: lengths advance,
        tokens go out to the streams, finished requests resolve.
        `n_emit` is the speculative step's per-row count (None for a
        plain step). Returns the tokens emitted."""
        emitted = 0
        if n_emit is not None:
            k = self.engine.spec_k
            for row, s in live:
                n = int(n_emit[row])
                if s.use_draft:
                    self.stats.note_spec(k, n - 1)
                for j in range(n):
                    if s.table is None or s.future.done():
                        break   # resolved mid-run (eos/max_tokens)
                    s.length += 1
                    emitted += 1
                    self._handle_token(s, int(out[row, j]))
        else:
            for row, s in live:
                if s.pending_tail:
                    continue    # decode row was inactive this step
                s.length += 1
                emitted += 1
                self._handle_token(s, int(out[row]))
            for seq, last_row, chunk in tail_rows:
                if seq.table is None or seq.future.done():
                    continue
                seq.length += chunk
                del seq.pending_tail[:chunk]
                if not seq.pending_tail:
                    # tail tokens are prefill work, not emitted tokens:
                    # counted via note_prefill in _finish_tail
                    self._finish_tail(seq, int(out[last_row]))
        return emitted

    # ------------------------------------------------- steps in flight
    # With `run_ahead` = D the loop keeps D steps launched beyond the
    # one whose tokens it waits for, so the device goes from one step
    # to the next without the host: a step's input tokens are the step
    # before's output, taken on the device (`engine.next_tokens`); its
    # lengths, page table and live rows the host knows without the
    # tokens (a row's budget of steps is `max_new` and the context's
    # capacity; only an `eos` ends a row unannounced, and what was
    # launched for it after that is dropped unread). Tokens still go
    # out one step at a time, as each step's output arrives. Every
    # DECISION about a live row — cancellation, deadlines, preemption,
    # shutdown — is made with nothing in flight: what needs one makes
    # the loop take out all that is launched first (`_settle`), so the
    # rest of the scheduler never sees a sequence whose `generated`
    # lags its pages. A request for a FREE row is admitted with the
    # steps still in flight (`_turn_ahead`). The price is that a
    # finished row's slot waits D steps for its successor's prefill.

    def _steps_left(self, seq):
        """Steps this row can still take beyond those in flight."""
        return min(seq.max_new - len(seq.generated),
                   self.engine.max_context - seq.length) - seq.ahead

    def _pending(self, now):
        """What waits for a decision of the loop: None; "admit", a
        request for a free row and nothing else; or "settle"."""
        def flagged(s):
            return s.future._cancel.is_set() or (
                s.deadline is not None and now > s.deadline)

        with self._cond:
            if self._closed or self._draining:
                return "settle"
            waiting = list(self._waiting)
            free = None in self._rows
        if any(flagged(s) for s in waiting) \
                or any(flagged(s) for s in self._active()):
            return "settle"
        return "admit" if waiting and free else None

    def _launch_ahead(self):
        """Launch the next step of every row that has one left to take;
        False where no row has, or where the pool could not back every
        row's next position without reclaiming pages (a decision: the
        loop then falls back to one step at a time)."""
        engine = self.engine
        live = [(row, s) for row, s in enumerate(self._rows)
                if s is not None and s.table is not None
                and self._steps_left(s) > 0]
        if not live:
            return False
        if self._ahead:
            # _grow without pressure: a page to grow by and a page to
            # copy into for each row at most
            if engine.allocator.free_pages() < 2 * len(live):
                return False
            if len(engine.groups) > 1:
                # a further group's pool is sized to its rows' windows:
                # count what this step really takes, after the releases
                self._release_windows()
                P = engine.page_size
                for gi, a in enumerate(engine.allocators[1:]):
                    short = sum(
                        len(s.side[gi]) * P <= s.length + s.ahead
                        for _, s in live)
                    if a.free_pages() < short:
                        return False
            self._grow()
        with _trace.span("decoding.pack"):
            (tokens, table, lengths, active, _draft, *samp), _tails, \
                bucket = self._pack(live)
            attrs = self._step_attrs(live, bucket, lengths, active,
                                     samp[1], 1)
            if self._ahead:
                tokens = engine.next_tokens(self._ahead[-1][1])
        out = engine.launch_step(tokens, table, lengths, active, *samp)
        for _, s in live:
            s.ahead += 1
        self._ahead.append((live, out, attrs, _trace.now()))
        return True

    def _retire(self, step_span):
        """Take the oldest launched step's tokens out: the fetch, then
        the rows' bookkeeping and streams. Its attributes go on the
        `decoding.step` span that holds it."""
        engine = self.engine
        live, out, attrs, t_launch = self._ahead.popleft()
        self._n_retired += 1
        host = engine.fetch_step(out, engine.max_batch)
        t_out = _trace.now()
        # steps still launched while these tokens came out
        step_span.note(in_flight=len(self._ahead), **attrs)
        if engine.cfg.step_counters:
            step_span.note(**engine.last_step_counters)
            self.stats.note_counters(engine.last_step_counters)
        with _trace.span("decoding.emit") as emit_span:
            emitted = 0
            for row, s in live:
                s.ahead -= 1
                if s.table is None or s.future.done():
                    continue    # ended by an eos while this was in flight
                s.length += 1
                emitted += 1
                self._handle_token(s, int(host[row]))
            emit_span.note(tokens=emitted)
            # the step's own seconds: from its launch, or from the
            # step before's tokens where it waited behind that
            self._note_step(
                emitted, t_out - max(t_launch, self._t_retired), attrs)
            self._t_retired = t_out
            self.stats.note_pool()
            if engine._guard and self.stats.steps % 16 == 0:
                for nf, clips in engine.drain_guard():
                    if nf:
                        self.stats.note_nonfinite(nf)
                    if clips:
                        self.stats.note_quant_clips(clips)

    def _settle(self):
        """Take out everything that is launched, oldest first."""
        while self._ahead:
            with _trace.span("decoding.step") as step_span:
                self._retire(step_span)

    def _turn_ahead(self):
        """One turn with steps in flight: decide what waits for a
        decision, launch up to `run_ahead` steps beyond the oldest,
        take the oldest's tokens out. The `decoding.step` span holds
        the turn's launches, its fetch and its emit, so that the spans
        of successive turns tile the device's time as the device goes
        from step to step.

        A request for a free row is admitted WITH the launched steps
        still in flight: the row is free, so nothing launched reads or
        writes what the admission touches, the host's share of it runs
        while the device works, and its prefill queues behind the
        steps (what would need a victim's whole history, a preemption,
        takes everything out first: `_reclaim_one`, `_preempt`). When
        the prefill's token is back all that was launched is done, and
        comes out before the next launch. Anything else that waits
        for a decision is decided with nothing in flight."""
        pending = self._pending(time.monotonic()) if self._ahead \
            else "settle"
        if pending == "settle":
            self._settle()
        if pending is not None:
            self._admit_turn()
            self._settle()
        if not any(self._rows):
            return
        # `queued`: the steps already in flight at the turn's first
        # launch; 0 where the device waits for this turn's pack and
        # launch
        with _trace.span("decoding.step",
                         queued=len(self._ahead)) as step_span:
            while len(self._ahead) <= self.run_ahead \
                    and self._launch_ahead():
                pass
            if self._ahead:
                self._retire(step_span)

    # -------------------------------------------------------------- loop
    def _loop(self):
        while True:
            with self._cond:
                while (not self._closed and not self._waiting
                       and not any(self._rows)):
                    # bounded wait so queued-only deadline expiry is
                    # still timely under an idle engine
                    self._cond.wait(0.05)
                if self._closed:
                    if not self._drain:
                        doomed = self._waiting[:]
                        self._waiting.clear()
                    elif not self._waiting and not any(self._rows):
                        return
            if self._closed and not self._drain:
                doomed.extend(self._active())
                if self._handoff:
                    # drain() timed out with work in flight: every
                    # leftover resolves with its resume record
                    now = time.monotonic()
                    for s in doomed:
                        st = self._handoff_state(s, now)
                        self._handoff_states.append(st)
                        self.stats.note_cancelled()
                        self._resolve(s, exc=RequestHandedOff(st))
                else:
                    for s in doomed:
                        self.stats.note_failed()
                        self._resolve(s, exc=ServerClosedError(
                            "decoder stopped"))
                return
            try:
                # one turn = decoding.admit, then _step's three spans;
                # each prefill _admit launches is a decoding.prefill
                # span inside it
                if self.run_ahead:
                    self._turn_ahead()
                    continue
                self._admit_turn()
                self._step()
            except Exception as exc:  # never kill the loop silently
                # what was launched is dropped unread with its rows
                self._ahead.clear()
                for s in self._active():
                    s.ahead = 0
                for s in self._active():
                    self.stats.note_failed()
                    self._resolve(s, exc=exc)
                with self._cond:
                    bail = self._closed
                    stranded = self._waiting[:] if bail else []
                    if bail:
                        self._waiting.clear()
                if bail:
                    # shutting down on a persistently-raising engine:
                    # spinning admit->fail forever would outlive the
                    # join timeout and strand the queue — fail it and
                    # exit (stop()/drain() backstops anything admitted
                    # between the sweep above and this return)
                    for s in stranded:
                        self.stats.note_failed()
                        self._resolve(s, exc=exc)
                    return


class DecodedModel:
    """One loaded decoder: engine + scheduler + stats (the decode-tier
    sibling of registry.ServedModel; `ModelServer.load_decoder` is the
    usual way to construct one)."""

    def __init__(self, name, version, params, cfg, *, max_batch=None,
                 page_size=None, num_pages=None, page_buckets=None,
                 kernel=None, ring_prefill=None, queue_cap=None,
                 max_tokens=None, warmup=True, draft=None,
                 draft_cfg=None, spec_k=None, prefix_cache=None,
                 merged_step=None, kv_dtype=None, chunk_buckets=None,
                 context_buckets=None, run_ahead=None):
        self.name = name
        self.version = int(version)
        self.cfg = cfg
        # draft spec: a params dict (with draft_cfg), the string
        # "self" (self-draft: the target drafts for itself — useful
        # for tests/CI where acceptance is then ~1), or None to read
        # MXNET_DECODE_SPEC_DRAFT
        if draft is None and _cfg.spec_draft():
            draft = _cfg.spec_draft()
        draft_params = None
        if isinstance(draft, str):
            if draft == "self":
                draft_params, draft_cfg = params, cfg
            elif draft:
                raise ServingError(
                    f"unknown draft spec {draft!r} (expected 'self' "
                    "or a params dict)")
        elif draft is not None:
            draft_params = draft
            draft_cfg = draft_cfg if draft_cfg is not None else cfg
        self.engine = DecodeEngine(
            params, cfg, max_batch=max_batch, page_size=page_size,
            num_pages=num_pages, page_buckets=page_buckets,
            kernel=kernel, ring_prefill=ring_prefill,
            draft_params=draft_params, draft_cfg=draft_cfg,
            spec_k=spec_k, prefix_cache=prefix_cache,
            merged_step=merged_step, kv_dtype=kv_dtype,
            chunk_buckets=chunk_buckets, context_buckets=context_buckets)
        self.stats = DecodeStats(
            key=self.key, traces_fn=self.engine.traces,
            pool_fn=self.engine.pool_stats)
        self.scheduler = ContinuousScheduler(
            self.engine, self.stats, self.key, queue_cap=queue_cap,
            max_tokens=max_tokens, run_ahead=run_ahead)
        self.stats._depth_fn = self.scheduler.depth
        if self.scheduler.cache is not None:
            self.stats._prefix_fn = self.scheduler.cache.stats
        self._started = False
        if warmup:
            self.warmup()

    @property
    def key(self):
        return f"{self.name}:{self.version}"

    def warmup(self):
        """Pre-trace the full decode grid and latch the trace floor;
        the scheduler thread starts only once the model is warm (the
        ServedModel readiness contract)."""
        self.engine.warmup()
        self.stats.mark_warmup_done()
        if not self._started:
            self.scheduler.start()
            self._started = True
        return self

    # -------------------------------------------------------- data path
    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_ms=None, sampling=None, seed=None, draft=None):
        return self.scheduler.submit(prompt,
                                     max_new_tokens=max_new_tokens,
                                     priority=priority,
                                     deadline_ms=deadline_ms,
                                     sampling=sampling, seed=seed,
                                     draft=draft)

    def generate(self, prompt, max_new_tokens=None, priority=0,
                 deadline_ms=None, timeout=None, sampling=None,
                 seed=None, draft=None):
        """Sync decode: the full generated token list."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           priority=priority, deadline_ms=deadline_ms,
                           sampling=sampling, seed=seed,
                           draft=draft).result(timeout)

    def stream(self, prompt, max_new_tokens=None, priority=0,
               deadline_ms=None, timeout=None, sampling=None,
               seed=None, draft=None):
        """Streaming decode: a TokenStream yielding tokens as steps
        complete. Close it (or exit its `with` block) to cancel an
        unfinished request and free its pages."""
        fut = self.submit(prompt, max_new_tokens=max_new_tokens,
                          priority=priority, deadline_ms=deadline_ms,
                          sampling=sampling, seed=seed, draft=draft)
        return fut.stream(timeout=timeout)

    def admit_resumed(self, state):
        """Admit a handed-off request (see ContinuousScheduler
        .admit_resumed): returns a DecodeFuture whose stream emits
        only the tokens not yet delivered elsewhere."""
        return self.scheduler.admit_resumed(state)

    def drain(self, timeout=30):
        """Stop admitting, finish live decodes (up to `timeout` s),
        hand off the rest; returns the handoff records."""
        return self.scheduler.drain(timeout=timeout)

    def close(self, drain=True, timeout=30):
        self.scheduler.stop(drain=drain, timeout=timeout)
