"""Decode-tier counters — `decodingStats` in profiler dumps, /metrics
and /statusz (via the PR 7 registry/view machinery).

The one-shot serving tier counts requests; the decode tier counts
TOKENS and PAGES, the units continuous batching actually schedules:

  prefill/decode tokens/s   the two throughput regimes, separately —
                            prefill is compute-bound batch work,
                            decode is latency-bound steady state
  kv_occupancy              owned pages / pool capacity (the paged
                            cache's answer to padding_waste)
  free_low_watermark        fewest free pages ever seen: how close
                            the pool came to forcing preemption
  preemptions/readmissions  sequences evicted for pages and brought
                            back (re-prefilled) — nonzero is healthy
                            under overload, a crash is not
  live_page_share           live_pages / bucket_pages: pages the
                            steps' contexts lay in over the page slots
                            (rows x bucket) their programs were given —
                            how much of a step's bucket the in-place
                            attention kernel has to read
  p50/p95/p99_token_ms      per-token decode latency
  traces_since_warmup       decode/prefill traces after warmup —
                            MUST stay 0 in steady state (the decode
                            extension of the PR 2 discipline)

Registered as a separate `decodingStats` view (omit_empty) rather
than folded into `servingStats`, so the serving snapshot's key shape
— which tests pin byte-for-byte — is untouched.
"""
from __future__ import annotations

import threading
import time
from collections import deque

from ..serving.stats import _percentile
from ..telemetry import register_view as _register_view
from ..telemetry import registry as _treg

_registry_lock = threading.Lock()
_registry: "dict[str, DecodeStats]" = {}

_LATENCY_KEEP = 4096

# native instruments (Prometheus-typed companions of the snapshot)
_TOKENS = _treg.counter(
    "mxnet_tpu_decode_tokens_total",
    "Tokens processed by the decode tier (phase=prefill|decode)")
_PREEMPTIONS = _treg.counter(
    "mxnet_tpu_decode_preemptions_total",
    "Sequences preempted for KV pages (re-prefilled on readmission)")
_OCCUPANCY = _treg.gauge(
    "mxnet_tpu_decode_kv_occupancy",
    "Fraction of the KV page pool currently owned by sequences")
_TOKEN_LATENCY_MS = _treg.histogram(
    "mxnet_tpu_decode_token_latency_ms",
    "Per-token decode-step latency",
    buckets=(0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000))
_PREFILL_LATENCY_MS = _treg.histogram(
    "mxnet_tpu_decode_prefill_latency_ms",
    "Per-prompt prefill latency (time-to-first-token's device half)",
    buckets=(1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000))
_NONFINITE = _treg.counter(
    "mxnet_tpu_decode_nonfinite_logits_total",
    "Active rows whose decode logits held NaN/Inf "
    "(MXNET_NUMERICS_DECODE_GUARD)")
_PREFIX_PAGES = _treg.counter(
    "mxnet_tpu_decode_prefix_pages_reused_total",
    "Prompt KV pages mapped from the prefix cache instead of "
    "prefilled (each one is page_size tokens of avoided compute)")
_SPEC_TOKENS = _treg.counter(
    "mxnet_tpu_decode_spec_tokens_total",
    "Speculative decoding draft tokens (phase=proposed|accepted)")
_QUANT_CLIPS = _treg.counter(
    "mxnet_tpu_decode_quant_clip_values_total",
    "KV values clipped at int8 quantization because the row held "
    "NaN/Inf or saturated its own scale (MXNET_NUMERICS_DECODE_GUARD "
    "dequant-overflow watermark; 0 under healthy numerics)")
_KV_BYTES = _treg.gauge(
    "mxnet_tpu_decode_kv_bytes_per_token",
    "Pool bytes per cached token position, K+V combined (4x head_dim "
    "x layers at float32; int8 shrinks it ~capacity_ratio-fold)")


def _register(key, stats):
    with _registry_lock:
        _registry[key] = stats


def _unregister(key):
    with _registry_lock:
        _registry.pop(key, None)


def decoding_stats():
    """Snapshot of every live decode model: {"name:version": {...}}."""
    with _registry_lock:
        items = list(_registry.items())
    return {key: st.snapshot() for key, st in items}


def reset_decoding_stats():
    with _registry_lock:
        items = list(_registry.values())
    for st in items:
        st.reset()


_register_view("decodingStats", decoding_stats, prom_prefix="decoding",
               omit_empty=True, label_name="model")


class DecodeStats:
    """Counters for one decode model. `traces_fn` reads the engine's
    trace counter; `pool_fn` reads the allocator; `depth_fn` reads the
    scheduler's (waiting, active) — all live at snapshot time."""

    def __init__(self, key=None, traces_fn=None, pool_fn=None,
                 depth_fn=None, prefix_fn=None):
        self._key = key or ""
        self._lock = threading.Lock()
        self._traces_fn = traces_fn
        self._pool_fn = pool_fn
        self._depth_fn = depth_fn
        self._prefix_fn = prefix_fn
        self.reset()

    def reset(self):
        with self._lock:
            self.submitted = 0
            self.completed = 0
            self.failed = 0
            self.rejected = 0
            self.expired = 0
            self.cancelled = 0
            self.spec_proposed = 0
            self.spec_accepted = 0
            self.preemptions = 0
            self.readmissions = 0
            self.prefills = 0
            self.prefill_tokens = 0
            self.decode_tokens = 0
            self.steps = 0
            # steps launched with no row above temperature 0: their
            # sampler took the argmax branch
            self.greedy_steps = 0
            # pages the steps' contexts lay in, of the page slots
            # (rows x bucket) their programs were given
            self.live_pages = 0
            self.bucket_pages = 0
            # running sums over the steps: context positions their
            # attention read, those of them a windowed page group's
            # layers read, the pages each page group had handed out;
            # and the pages a windowed group got back from live rows
            self.ctx_tokens = 0
            self.window_tokens = 0
            self.pages_held = []
            self.window_pages_released = 0
            self.nonfinite_logit_steps = 0
            self.nonfinite_logits = 0
            self.quant_clip_steps = 0
            self.quant_clip_values = 0
            # running sums of what a counting block's programs report
            # (engine.cfg.step_counters; a `*_max` counter keeps its
            # largest): empty for a block that counts nothing
            self.counters = {}
            self.prefill_chunks = 0
            self.traces_at_warmup = None
            self._prefill_s = 0.0
            self._decode_s = 0.0
            self._token_lat = deque(maxlen=_LATENCY_KEEP)
            self._t0 = time.monotonic()

    # ------------------------------------------------------ recording
    def note_submitted(self):
        with self._lock:
            self.submitted += 1

    def note_rejected(self):
        with self._lock:
            self.rejected += 1

    def note_expired(self, n=1):
        with self._lock:
            self.expired += n

    def note_cancelled(self, n=1):
        with self._lock:
            self.cancelled += n

    def note_spec(self, proposed, accepted):
        """One speculative step's draft accounting for one row."""
        with self._lock:
            self.spec_proposed += proposed
            self.spec_accepted += accepted
        _SPEC_TOKENS.inc(proposed, phase="proposed", model=self._key)
        _SPEC_TOKENS.inc(accepted, phase="accepted", model=self._key)

    def note_failed(self, n=1):
        with self._lock:
            self.failed += n

    def note_completed(self, n=1):
        with self._lock:
            self.completed += n

    def note_prefix_reuse(self, pages):
        """Prompt pages mapped from the prefix cache at admission
        (the snapshot's hit/miss detail comes from prefix_fn; this
        just feeds the native Prometheus counter)."""
        if pages:
            _PREFIX_PAGES.inc(pages, model=self._key)

    def note_prefill(self, tokens, seconds, readmission=False):
        with self._lock:
            self.prefills += 1
            self.prefill_tokens += tokens
            self._prefill_s += seconds
            if readmission:
                self.readmissions += 1
        _TOKENS.inc(tokens, phase="prefill", model=self._key)
        _PREFILL_LATENCY_MS.observe(seconds * 1e3, model=self._key)

    def note_step(self, live_rows, seconds, live_pages=0,
                  bucket_pages=0, ctx_tokens=0, window_tokens=0,
                  pages_held=(), greedy=False):
        """One continuous-decode step: `live_rows` tokens emitted, its
        context of `ctx_tokens` positions (`window_tokens` of them in
        reach of a windowed group's layers) in `live_pages` pages of
        the program's `bucket_pages` slots, with `pages_held` pages
        out of each page group's allocator; `greedy` where no row of
        it sampled."""
        with self._lock:
            self.steps += 1
            self.greedy_steps += bool(greedy)
            self.decode_tokens += live_rows
            self.live_pages += live_pages
            self.bucket_pages += bucket_pages
            self.ctx_tokens += ctx_tokens
            self.window_tokens += window_tokens
            if len(self.pages_held) < len(pages_held):
                self.pages_held = [0] * len(pages_held)
            for i, n in enumerate(pages_held):
                self.pages_held[i] += n
            self._decode_s += seconds
            if live_rows:
                per_tok = seconds / live_rows
                self._token_lat.append(per_tok)
        if live_rows:
            _TOKEN_LATENCY_MS.observe(
                seconds / live_rows * 1e3, model=self._key)

    def note_counters(self, counted):
        """One step's or one prefill's counters ({name: int}, with
        `chunks` for a prefill) into the running sums."""
        with self._lock:
            for name, v in counted.items():
                if name == "chunks":
                    self.prefill_chunks += v
                elif name.endswith("_max"):
                    self.counters[name] = max(
                        self.counters.get(name, 0), v)
                else:
                    self.counters[name] = self.counters.get(name, 0) + v

    def note_released(self, pages):
        """Pages a windowed page group got back from rows still live
        (behind their windows)."""
        if pages:
            with self._lock:
                self.window_pages_released += pages

    def note_nonfinite(self, rows, steps=1):
        """Guard trip: `rows` active rows produced NaN/Inf logits
        across `steps` decode steps (MXNET_NUMERICS_DECODE_GUARD)."""
        with self._lock:
            self.nonfinite_logit_steps += steps
            self.nonfinite_logits += rows
        _NONFINITE.inc(rows, model=self._key)

    def note_quant_clips(self, values, steps=1):
        """Guard trip, quantization flavor: `values` K/V entries were
        clipped at int8 scatter time across `steps` decode steps —
        the dequant-overflow watermark. Healthy numerics quantize with
        zero clips (each row's scale comes from its own maxabs), so
        any count means NaN/Inf or saturation reached the cache."""
        with self._lock:
            self.quant_clip_steps += steps
            self.quant_clip_values += values
        _QUANT_CLIPS.inc(values, model=self._key)

    def note_preempted(self, n=1):
        with self._lock:
            self.preemptions += n
        _PREEMPTIONS.inc(n, model=self._key)

    def mark_warmup_done(self):
        """Latch the trace floor: anything above it in steady state is
        a retrace the fixed-shape decode grid failed to prevent."""
        with self._lock:
            self.traces_at_warmup = (
                self._traces_fn() if self._traces_fn else 0)

    def note_pool(self):
        """Refresh the occupancy/bytes gauges (called per step)."""
        if self._pool_fn:
            pool = self._pool_fn()
            _OCCUPANCY.set(pool.get("kv_occupancy", 0.0),
                           model=self._key)
            _KV_BYTES.set(pool.get("kv_bytes_per_token", 0.0),
                          model=self._key)

    # ------------------------------------------------------- snapshot
    def snapshot(self):
        traces_now = self._traces_fn() if self._traces_fn else 0
        pool = self._pool_fn() if self._pool_fn else {}
        prefix = self._prefix_fn() if self._prefix_fn else {}
        waiting, active = self._depth_fn() if self._depth_fn else (0, 0)
        with self._lock:
            lat = sorted(self._token_lat)
            out = {
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "rejected": self.rejected,
                "expired": self.expired,
                "cancelled": self.cancelled,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_acceptance_rate": round(
                    self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else 0.0,
                "tokens_per_target_step": round(
                    self.decode_tokens / self.steps, 3)
                if self.steps else 0.0,
                "preemptions": self.preemptions,
                "readmissions": self.readmissions,
                "prefills": self.prefills,
                "prefill_tokens": self.prefill_tokens,
                "decode_tokens": self.decode_tokens,
                "steps": self.steps,
                "greedy_steps": self.greedy_steps,
                "live_pages": self.live_pages,
                "bucket_pages": self.bucket_pages,
                "live_page_share": round(
                    self.live_pages / self.bucket_pages, 4)
                if self.bucket_pages else 0.0,
                "ctx_tokens": self.ctx_tokens,
                "window_tokens": self.window_tokens,
                "pages_held": list(self.pages_held),
                "window_pages_released": self.window_pages_released,
                "nonfinite_logit_steps": self.nonfinite_logit_steps,
                "nonfinite_logits": self.nonfinite_logits,
                "quant_clip_steps": self.quant_clip_steps,
                "quant_clip_values": self.quant_clip_values,
                "prefill_chunks": self.prefill_chunks,
                **self.counters,
                "prefill_tokens_per_s": round(
                    self.prefill_tokens / self._prefill_s, 1)
                if self._prefill_s else 0.0,
                "decode_tokens_per_s": round(
                    self.decode_tokens / self._decode_s, 1)
                if self._decode_s else 0.0,
                "p50_token_ms": round(_percentile(lat, 0.50) * 1e3, 3),
                "p95_token_ms": round(_percentile(lat, 0.95) * 1e3, 3),
                "p99_token_ms": round(_percentile(lat, 0.99) * 1e3, 3),
                "traces_since_warmup": (
                    traces_now - self.traces_at_warmup
                    if self.traces_at_warmup is not None else None),
                "waiting": waiting,
                "active": active,
            }
        out.update(pool)
        out.update(prefix)
        return out
