"""DecodeEngine: the device half of continuous batching.

Owns the paged pool — one pre-allocated (layers, pages, page_size,
width) buffer per PLANE the model's configuration states (`cfg.planes`:
`k` and `v` of heads*head_dim for the dense block, a latent row and an
index key for the sparse latent block, a `k`/`v` pair for each kind of
layer of a block that mixes window and full layers; quant.py says why
that order), each at the layers the plane covers — a block allocator
and a page table a sequence for every PAGE GROUP the configuration
states (`cfg.page_groups`: one for all planes, unless layers keep
different positions), and a FIXED grid of jitted programs. The engine
names no block: it takes the planes, the groups, the step functions and
the program names from the configuration object (the model contract,
`model.DecoderConfig`). With several groups `num_pages` gives each
group's pages, a step's page table is the groups' tables stacked,
(groups, rows, bucket), and a prefill's is a list of their lists:

  prefill  one program per prompt length bucket (batch 1, dense causal
           attention — optionally ring attention for long buckets —
           that scatters K/V into the sequence's pages)
  tail     one tail-prefill program per length bucket (prefix-cache
           hits: compute only the uncached prompt tail, attending over
           the shared pages — page table padded to the largest bucket
           for one static shape per tail bucket). NOT BUILT in
           merged-step mode (MXNET_DECODE_MERGED_STEP, the default
           with the prefix cache on): tail tokens ride the decode
           step as extra ragged rows instead, one program family
           fewer in the warmup grid
  decode   one program per pages-per-sequence bucket; the step shape
           is a function ONLY of (step_rows, bucket) — step_rows =
           max_batch plus, in merged mode, page_size tail rows —
           never of real lengths or batch composition — so `warmup()`
           pre-traces the full grid and steady-state decode adds zero
           traces. In merged mode the rows mix decode queries and
           tail-prefill prompt tokens through the ragged paged
           attention kernel (decoding/attention.py)
  draft/   with a draft model configured, one K-token draft proposer
  verify   and one K+1-position target verifier per pages bucket —
           the speculative pair joins the same pinned trace grid, and
           the draft keeps parallel K/V pools indexed by the SAME
           page ids (see speculative.py)
  chunk    where the configuration prefills through the pages
           (`cfg.prefill_chunk`): INSTEAD of the prefill and tail
           families, one program per (chunk tokens bucket, context
           pages bucket) that writes a chunk's rows and attends its
           queries over the pages — a long prompt is that program
           taken repeatedly, a prefix-cache tail taken once or twice.
           No whole-prompt program is built (its score matrix grows
           with the square of the prompt)
  copy     one page-copy program (copy-on-write fork support; traced
           once more for the draft pool shape when it differs)

Trace accounting: every impl body bumps a python-side counter as its
first statement. Python runs at TRACE time only, so the counter counts
traces, not calls — `traces()` after `warmup()` is the decode tier's
`traces_since_warmup` evidence (the PR 2 discipline, extended to a
workload exec_cache never sees because decode jits are raw jax.jit).

The engine is NOT thread-safe: exactly one scheduler thread drives it
(the serving-lane convention — an Executor is single-threaded too).
"""
from __future__ import annotations

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler as _profiler
from .. import utils as _utils
from ..serving.batcher import pick_bucket
from ..telemetry import trace as _trace
from . import config as _cfg
from . import attention as _attn
from . import quant as _quant
from . import speculative as _spec

# warn-once latch for calibration-harvest failures (the serving
# registry's convention: one WARN per process, not one per bucket)
_calibration_warned = False
from .blocks import SCRATCH_PAGE, BlockAllocator, PageError, cover, \
    pages_needed, release_behind


class DecodeEngine:
    def __init__(self, params, cfg, *, max_batch=None, page_size=None,
                 num_pages=None, page_buckets=None, kernel=None,
                 ring_prefill=None, draft_params=None, draft_cfg=None,
                 spec_k=None, prefix_cache=None, merged_step=None,
                 kv_dtype=None, chunk_buckets=None,
                 context_buckets=None):
        self.cfg = cfg
        # KV storage precision (MXNET_DECODE_KV_DTYPE): the page pools
        # — target AND draft — store at this dtype; int8 pools carry
        # per-(slot, head) scale planes through every pytree hop
        self.kv_dtype = _quant.canonical(
            kv_dtype if kv_dtype is not None else _cfg.kv_dtype())
        self.max_batch = max_batch if max_batch is not None \
            else _cfg.max_batch()
        self.page_size = page_size if page_size is not None \
            else _cfg.page_size()
        # the page groups (cfg.page_groups): the first holds every
        # position of a live row, and is what `allocator`, `num_pages`
        # and the page buckets speak of
        self.groups = tuple(cfg.page_groups)
        if self.groups[0].window:
            raise PageError("the first page group keeps every position")
        if num_pages is None:
            num_pages = _cfg.num_pages()
        self.group_pages = tuple(
            int(n) for n in (num_pages if isinstance(num_pages, (
                tuple, list)) else [num_pages] * len(self.groups)))
        if len(self.group_pages) != len(self.groups):
            raise PageError(
                f"num_pages {num_pages!r} for {len(self.groups)} page "
                f"groups {[g.name for g in self.groups]}")
        self.num_pages = self.group_pages[0]
        if page_buckets is None:
            page_buckets = _cfg.page_buckets()
        if page_buckets is None:
            # a sequence can never own more pages than the pool (or
            # than max_len covers) — cap the default grid there
            cap = min(self.num_pages - 1,
                      cfg.max_len // self.page_size)
            page_buckets = _cfg.default_page_buckets(max(1, cap))
        self.page_buckets = tuple(sorted(set(int(b)
                                             for b in page_buckets)))
        self.kernel_name = kernel if kernel is not None \
            else _cfg.kernel()
        self.ring_prefill = ring_prefill if ring_prefill is not None \
            else _cfg.ring_prefill()
        if self.page_buckets[-1] * self.page_size > cfg.max_len:
            raise PageError(
                f"largest page bucket {self.page_buckets[-1]} x "
                f"page_size {self.page_size} exceeds the model's "
                f"max_len {cfg.max_len}")
        if self.page_buckets[-1] > self.num_pages - 1:
            raise PageError(
                f"page bucket {self.page_buckets[-1]} exceeds pool "
                f"capacity {self.num_pages - 1}")

        windowed = [g for g in self.groups if g.window]
        if windowed and (prefix_cache or draft_params is not None):
            # a hit (or a rolled-back draft) resumes a row at a position
            # whose window lies in pages a live row has already given
            # back: decoding/prefix.py would have to keep them at every
            # hit boundary. Unasked, the cache is off for such a model
            raise PageError(
                f"page group {windowed[0].name!r} keeps a row's last "
                f"{windowed[0].window} positions only: neither the "
                "prefix cache nor a draft can resume a row from pages "
                "it has released")
        if windowed:
            prefix_cache = False

        # chunked prefill (cfg.prefill_chunk): the chunk programs'
        # token buckets, and the page buckets they gather over (a
        # document's early chunks need not score and sort the largest
        # context)
        self.chunk_buckets = self.context_buckets = ()
        if cfg.prefill_chunk:
            self.chunk_buckets = tuple(sorted(set(
                int(b) for b in chunk_buckets or (cfg.prefill_chunk,))))
            self.context_buckets = tuple(sorted(set(
                int(b) for b in context_buckets
                or self.page_buckets[-1:])))
            if self.chunk_buckets[-1] != cfg.prefill_chunk:
                raise PageError(
                    f"largest chunk bucket {self.chunk_buckets[-1]} != "
                    f"the configuration's prefill_chunk "
                    f"{cfg.prefill_chunk}")
            if self.context_buckets[-1] != self.page_buckets[-1]:
                raise PageError(
                    f"largest context bucket {self.context_buckets[-1]} "
                    f"!= largest page bucket {self.page_buckets[-1]}")
            if draft_params is not None:
                raise PageError(
                    "speculation verifies with the dense block's "
                    "multi-query kernel: not for a chunk-prefilled "
                    "configuration")
        elif len(self.groups) > 1:
            raise PageError(
                "several page groups are filled by chunked prefill "
                "(cfg.prefill_chunk)")
        self.allocators = tuple(BlockAllocator(n, self.page_size)
                                for n in self.group_pages)
        self.allocator = self.allocators[0]
        self.allocator.groups = self.allocators
        self._attn = _attn.get_kernel(self.kernel_name)
        self._attn_multi = _attn.get_multi_kernel(self.kernel_name)
        self._params = jax.tree_util.tree_map(jnp.asarray, dict(params))
        names = [g.name for g in self.groups]
        self._plane_group = tuple(names.index(plane.group)
                                  for plane in cfg.planes)
        self._pools = tuple(
            _quant.make_plane(plane.layers or cfg.n_layers,
                              self.group_pages[gi], self.page_size, plane,
                              self.kv_dtype)
            for plane, gi in zip(cfg.planes, self._plane_group))
        self.prefix_cache_enabled = prefix_cache if prefix_cache \
            is not None else _cfg.prefix_cache()
        self.spec_k = int(spec_k) if spec_k is not None \
            else _cfg.spec_k()
        self.draft_cfg = None
        self._draft_params = None
        if draft_params is not None and self.spec_k > 0:
            dcfg = draft_cfg if draft_cfg is not None else cfg
            if dcfg.vocab != cfg.vocab:
                raise PageError(
                    f"draft vocab {dcfg.vocab} != target {cfg.vocab}: "
                    "speculative decoding needs one token space")
            if dcfg.max_len < cfg.max_len:
                raise PageError(
                    f"draft max_len {dcfg.max_len} < target "
                    f"{cfg.max_len}: the draft must cover every "
                    "position the target can reach")
            self.draft_cfg = dcfg
            self._draft_params = jax.tree_util.tree_map(
                jnp.asarray, dict(draft_params))
            dshape = (dcfg.n_layers, self.num_pages, self.page_size,
                      dcfg.n_heads, dcfg.head_dim)
            self._dk = _quant.make_pool(dshape, self.kv_dtype)
            self._dv = _quant.make_pool(dshape, self.kv_dtype)
        # merged ragged step (MXNET_DECODE_MERGED_STEP): prefix-cache
        # tail-prefill tokens ride the decode step as extra rows
        # through the ragged paged kernel — the per-length-bucket tail
        # programs are never built and the warmup grid shrinks by one
        # program per prefill bucket. Requires the prefix cache (the
        # only producer of tails) and no speculation (the verify pair
        # owns its own multi-query shape).
        want_merged = merged_step if merged_step is not None \
            else _cfg.merged_step()
        self.merged_step_enabled = bool(
            want_merged and self.prefix_cache_enabled
            and not self.spec_enabled and not cfg.prefill_chunk)
        # the block's choice of paged attention, handed to its steps
        self._kernels = SimpleNamespace(
            attn=(_attn.get_ragged_kernel(self.kernel_name)
                  if self.merged_step_enabled else self._attn),
            attn_multi=self._attn_multi)
        # extra step rows available for tail tokens each merged step;
        # one page's worth keeps the row overhead bounded while a tail
        # still advances a full page per step
        self.tail_budget = self.page_size if self.merged_step_enabled \
            else 0
        self.step_rows = self.max_batch + self.tail_budget
        # donation lets XLA update the pool in place; CPU falls back
        # with a warning, so only donate where it pays
        self._donate = jax.default_backend() != "cpu"
        self._decode_fns = {}
        self._prefill_fns = {}
        self._tail_fns = {}
        self._chunk_fns = {}
        self._probe_fns = {}
        # what the newest step and the newest prefill counted
        # (cfg.step_counters), for the scheduler's spans and stats
        self.last_step_counters = {}
        self.last_prefill = {"chunks": 1}
        # pages a windowed group got back during the newest prefill
        self.last_released = 0
        self._draft_prefill_fns = {}
        self._draft_tail_fns = {}
        self._propose_fns = {}
        self._verify_fns = {}
        self._copy_fn = None
        # a step's tokens cut from its output, for the step after it
        rows = self.step_rows
        self._next_tokens_fn = jax.jit(lambda out: out[:rows])
        self._trace_counts = {}
        self._warm = False
        # MXNET_NUMERICS_DECODE_GUARD: each decode step also returns a
        # device scalar counting active rows with NaN/Inf logits;
        # scalars accumulate here and drain in one fetch (drain_guard)
        self._guard = bool(_utils.getenv("MXNET_NUMERICS_DECODE_GUARD"))
        self._guard_pending = []
        # executable-accounting key: the decode grid is a function of
        # (model config, batch, paging layout, kernel) — deterministic
        # within a process, which is all deviceStats needs. The pool's
        # storage order is part of it: an AOT bundle's programs take
        # the pools as arguments, so one compiled around another order
        # must not match
        import hashlib as _hashlib

        self._digest = _hashlib.sha1(repr(
            (cfg, self.max_batch, self.page_size,
             self.num_pages if len(self.groups) == 1
             else self.group_pages,
             self.kernel_name, self.draft_cfg,
             self.spec_k if self.spec_enabled else 0,
             self.step_rows if self.merged_step_enabled else 0,
             self.kv_dtype, _quant.POOL_LAYOUT)
            + ((self.chunk_buckets, self.context_buckets)
               if cfg.prefill_chunk else ())
        ).encode()).hexdigest()[:12]

    # the dense block's two planes by name (speculation, page reads)
    @property
    def _k(self):
        return self._pools[0]

    @property
    def _v(self):
        return self._pools[1]

    def _jit(self, impl, name, kind, donate):
        """jit one grid program under a name of its own and route it
        through profiling's executable accounting (deviceStats).

        The closure is renamed before `jax.jit`, so the compiled module
        is `jit_<name>` — what a profiler capture's `XLA Modules` line
        and `profiling.scope_map` key on (instruction names such as
        `fusion.67` repeat across programs; module names must not).
        The accounting wrapper is transparent: it dispatches through
        the SAME compiled executable a raw jit would build, so trace
        counts (`_note_trace`) are unchanged."""
        impl.__name__ = impl.__qualname__ = name
        fn = jax.jit(impl, donate_argnums=donate if self._donate else ())
        try:
            from .. import profiling as _profiling

            return _profiling.instrument(fn, digest=self._digest,
                                         kind=kind)
        except Exception:
            return fn

    # ------------------------------------------------------ properties
    @property
    def spec_enabled(self):
        """True when a draft model is loaded and K > 0: the scheduler
        routes steps through spec_step instead of step."""
        return self._draft_params is not None and self.spec_k > 0

    @property
    def max_context(self):
        """Tokens the largest bucket covers — the hard length cap."""
        return self.page_buckets[-1] * self.page_size

    @property
    def prefill_buckets(self):
        """Prompt length buckets: one per page bucket (the decode
        extension of the serving tier's MXNET_SERVING_LENGTH_BUCKETS
        grid, derived instead of hand-configured)."""
        return tuple(b * self.page_size for b in self.page_buckets)

    def step_program(self, bucket):
        """Module name of the program that takes a step's device time
        at this pages bucket, as a capture's `XLA Modules` line and
        `profiling.scope_map` have it (see `_jit`)."""
        kind = "verify" if self.spec_enabled else "decode"
        return f"jit_{self.cfg.program_family}{kind}_p{bucket}"

    def traces(self):
        """Total prefill/decode/copy traces so far (see docstring)."""
        return sum(self._trace_counts.values())

    def trace_counts(self):
        return dict(self._trace_counts)

    def pool_stats(self):
        st = self.allocator.stats()
        # measured bytes per pooled token position over every plane
        # (scale planes included): the float32/int8 ratio of this
        # number is the capacity multiplier ci/check_quant.py
        # reports
        per_tok = sum(_quant.kv_bytes_per_token(p) for p in self._pools)
        more = {}
        for g, a in zip(self.groups[1:], self.allocators[1:]):
            gs = a.stats()
            more.update({f"{g.name}_pages_total": gs["pages_total"],
                         f"{g.name}_pages_free": gs["pages_free"],
                         f"{g.name}_free_low_watermark":
                             gs["free_low_watermark"]})
        return {
            **more,
            "pages_total": st["pages_total"],
            "pages_free": st["pages_free"],
            "kv_occupancy": round(
                st["pages_in_use"] / max(1, st["pages_total"]), 4),
            "free_low_watermark": st["free_low_watermark"],
            "pages_allocated": st["pages_allocated"],
            "kv_dtype": self.kv_dtype,
            "kv_bytes_per_token": round(per_tok, 2),
            "pool_capacity_tokens": (self.num_pages - 1)
            * self.page_size,
        }

    def _note_trace(self, name):
        # first statement of every impl body: executes under tracing
        # only, so this COUNTS TRACES (see module docstring)
        self._trace_counts[name] = self._trace_counts.get(name, 0) + 1

    # -------------------------------------------------- numerics guard
    _GUARD_CAP = 1024  # device scalars between drains

    def _run_decode(self, fn, *args):
        """Dispatch one decode program; absorb the guard vector (still
        on device — zero sync) when the guard is enabled."""
        res = fn(*args)
        if not self._guard:
            out, self._pools = res
            return out
        out, self._pools, bad = res
        self._guard_pending.append(bad)
        if len(self._guard_pending) > self._GUARD_CAP:
            del self._guard_pending[:-self._GUARD_CAP]
        return out

    def drain_guard(self):
        """Pending guard vectors -> host in ONE blocking fetch
        (counted in hostSyncStats); [] (no fetch) when empty or the
        guard is off. Each entry is an (nonfinite_rows, quant_clips)
        pair per drained step — NaN/Inf logit rows and dequant-
        overflow clip events of the step's quantized K/V writes. The
        scheduler drains on an interval and feeds nonzero counts into
        DecodeStats (`decodingStats` view)."""
        if not self._guard_pending:
            return []
        pending, self._guard_pending = self._guard_pending, []
        host = jax.device_get(pending)
        _profiler.count_host_sync("blocking_fetches")
        _profiler.count_host_sync("metric_fetches")
        return [(int(v[0]), int(v[1])) for v in host]

    # -------------------------------------------------------- builders
    def _build_decode_fn(self, bucket):
        # merged mode routes through the ragged entry: same per-row
        # contract, named for what the mixed batch actually is
        cfg, kernels, guard = self.cfg, self._kernels, self._guard

        def impl(params, tokens, pools, page_table, lengths, active,
                 seeds, temps, top_ks, top_ps):
            self._note_trace(f"decode@{bucket}")
            return cfg.decode_step(
                params, tokens, pools, page_table, lengths, active,
                seeds, temps, top_ks, top_ps, kernels=kernels,
                with_stats=guard)

        return self._jit(impl, f"{cfg.program_family}decode_p{bucket}",
                         f"decode@{bucket}", (2,))

    def _build_prefill_fn(self, length_bucket, name="prefill",
                          cfg=None):
        cfg = cfg if cfg is not None else self.cfg
        attn_fn = None
        if self.ring_prefill and length_bucket >= self.ring_prefill:
            # NOTE: mxnet_tpu.parallel re-exports the ring_attention
            # FUNCTION under the module's name; import the module by
            # its full path
            from ..parallel.ring_attention import (ring_attention,
                                                   seq_mesh_for)

            mesh = seq_mesh_for(length_bucket)

            def attn_fn(q, k, v):
                return ring_attention(q, k, v, mesh=mesh, causal=True)

        kernels = self._kernels

        def impl(params, tokens, length, pools, page_ids, seed, temp,
                 top_k, top_p):
            self._note_trace(f"{name}@{length_bucket}")
            return cfg.prefill_step(
                params, tokens, length, pools, page_ids, seed, temp,
                top_k, top_p, kernels=kernels, attn_fn=attn_fn)

        return self._jit(impl, f"{name}_t{length_bucket}",
                         f"{name}@{length_bucket}", (3,))

    def _build_tail_fn(self, length_bucket, name="prefill_tail",
                       cfg=None):
        cfg = cfg if cfg is not None else self.cfg
        kernels = self._kernels

        def impl(params, tokens, start, length, pools, page_ids, seed,
                 temp, top_k, top_p):
            self._note_trace(f"{name}@{length_bucket}")
            return cfg.chunk_step(
                params, tokens, start, length, pools, page_ids, seed,
                temp, top_k, top_p, kernels=kernels)

        return self._jit(impl, f"{name}_t{length_bucket}",
                         f"{name}@{length_bucket}", (4,))

    def _build_chunk_fn(self, tokens_bucket, pages_bucket):
        """One chunk-prefill program: `tokens_bucket` prompt positions
        written and attended over a table of `pages_bucket` pages (the
        tail program's contract, `cfg.chunk_step`)."""
        return self._build_tail_fn(
            f"{tokens_bucket}_p{pages_bucket}",
            name=f"{self.cfg.program_family}prefill_chunk")

    def _build_propose_fn(self, bucket):
        cfg, attn, k = self.draft_cfg, self._attn, self.spec_k

        def impl(params, tokens, k_pages, v_pages, page_table,
                 lengths, active, seeds, temps, top_ks, top_ps):
            self._note_trace(f"draft@{bucket}")
            return _spec.draft_propose_forward(
                params, tokens, k_pages, v_pages, page_table, lengths,
                active, seeds, temps, top_ks, top_ps, cfg=cfg,
                attn=attn, k=k)

        return self._jit(impl, f"draft_p{bucket}", f"draft@{bucket}",
                         (2, 3))

    def _build_verify_fn(self, bucket):
        cfg, attn_multi, k = self.cfg, self._attn_multi, self.spec_k

        def impl(params, tokens, drafts, q_dists, k_pages, v_pages,
                 page_table, lengths, active, use_draft, seeds, temps,
                 top_ks, top_ps):
            self._note_trace(f"verify@{bucket}")
            return _spec.verify_forward(
                params, tokens, drafts, q_dists, k_pages, v_pages,
                page_table, lengths, active, use_draft, seeds, temps,
                top_ks, top_ps, cfg=cfg, attn_multi=attn_multi, k=k)

        return self._jit(impl, f"verify_p{bucket}", f"verify@{bucket}",
                         (4, 5))

    def _build_copy_fn(self):
        # the pool argument is a quant.KVPool pytree: ONE traced
        # program moves data AND scale plane together, so COW copies
        # can never split a page from its scales. K and V share the
        # pytree structure — still a single trace, like the bare-array
        # version this replaces.
        def impl(pool, src, dst):
            self._note_trace("copy_page")
            return _quant.KVPool(*(
                None if a is None else a.at[:, dst].set(a[:, src])
                for a in pool))

        return self._jit(impl, "copy_page", "copy_page", (0,))

    # --------------------------------------------- fixed-dtype packing
    @staticmethod
    def _samp_scalars(seed=0, temperature=0.0, top_k=0, top_p=1.0):
        """Sampling params as fixed-dtype scalars: one traced
        signature regardless of host value types."""
        return (np.uint32(int(seed) & 0xFFFFFFFF),
                np.float32(temperature), np.int32(top_k),
                np.float32(top_p))

    def _samp_arrays(self, seeds, temps, top_ks, top_ps, rows=None):
        """(rows,) sampling arrays, defaulting to greedy, fixed
        dtypes, padded up to `rows` (default step_rows — the merged
        step's width; the speculative pair passes max_batch)."""
        b = rows if rows is not None else self.step_rows

        def _fill(arr, dtype, fill):
            if arr is None:
                return np.full((b,), fill, dtype)
            arr = np.asarray(arr, dtype)
            if len(arr) < b:
                arr = np.concatenate(
                    [arr, np.full((b - len(arr),), fill, dtype)])
            return arr

        return (_fill(seeds, np.uint32, 0),
                _fill(temps, np.float32, 0.0),
                _fill(top_ks, np.int32, 0),
                _fill(top_ps, np.float32, 1.0))

    # ---------------------------------------------------------- warmup
    def _masked_step_args(self, bucket):
        """One decode program's arguments with every row masked (length
        0, inactive, all-scratch table): what warmup, the calibration
        harvest and `decode_program_text` dispatch or lower."""
        r = self.step_rows
        return (self._params, np.zeros((r,), np.int32), self._pools,
                np.zeros(self.table_shape(r, bucket), np.int32),
                np.zeros((r,), np.int32), np.zeros((r,), bool),
                *self._samp_arrays(None, None, None, None))

    def table_shape(self, *shape):
        """The shape of a page-table array of `shape` = (rows, bucket)
        or (bucket,): as it is for one page group, the groups' tables
        stacked in front for several."""
        return shape if len(self.groups) == 1 \
            else (len(self.groups),) + shape

    def warmup(self):
        """Pre-trace the full program grid: every prefill length
        bucket (full + tail when the prefix cache is on, for the
        draft too when speculation is on), every decode pages bucket
        (plus the draft/verify pair), and the page copy. All writes of
        the dry runs land in the scratch page (lengths 0, tables
        all-scratch), so the pool state is untouched except for
        scratch garbage — which is never read unmasked. Idempotent."""
        if self._warm:
            return self
        self._copy_fn = self._build_copy_fn()
        self.copy_page(SCRATCH_PAGE, SCRATCH_PAGE)
        sargs = self._samp_scalars()
        max_pages = pages_needed(self.max_context, self.page_size)
        for tb in self.chunk_buckets:
            # a chunk-prefilled configuration: this family alone
            for cb in self.context_buckets:
                self._chunk_fns[tb, cb] = self._build_chunk_fn(tb, cb)
                tok, self._pools = self._chunk_fns[tb, cb](
                    self._params, np.zeros((1, tb), np.int32),
                    jnp.int32(0), jnp.int32(0), self._pools,
                    np.zeros(self.table_shape(cb), np.int32), *sargs)
                tok.block_until_ready()
        for lb in () if self.chunk_buckets else self.prefill_buckets:
            tokens = np.zeros((1, lb), np.int32)
            page_ids = np.zeros((pages_needed(lb, self.page_size),),
                                np.int32)
            full_ids = np.zeros((max_pages,), np.int32)
            self._prefill_fns[lb] = self._build_prefill_fn(lb)
            tok, self._pools = self._prefill_fns[lb](
                self._params, tokens, jnp.int32(0), self._pools,
                page_ids, *sargs)
            tok.block_until_ready()
            if self.prefix_cache_enabled \
                    and not self.merged_step_enabled:
                # merged mode NEVER builds the per-length tail
                # programs: tail tokens ride the decode step below —
                # this is the warmup-grid shrink the merged step buys
                self._tail_fns[lb] = self._build_tail_fn(lb)
                tok, self._pools = self._tail_fns[lb](
                    self._params, tokens, jnp.int32(0), jnp.int32(0),
                    self._pools, full_ids, *sargs)
                tok.block_until_ready()
            if self.spec_enabled:
                self._draft_prefill_fns[lb] = self._build_prefill_fn(
                    lb, name="draft_prefill", cfg=self.draft_cfg)
                tok, (self._dk, self._dv) = self._draft_prefill_fns[lb](
                    self._draft_params, tokens, jnp.int32(0),
                    (self._dk, self._dv), page_ids, *sargs)
                tok.block_until_ready()
                if self.prefix_cache_enabled:
                    self._draft_tail_fns[lb] = self._build_tail_fn(
                        lb, name="draft_tail", cfg=self.draft_cfg)
                    tok, (self._dk, self._dv) = self._draft_tail_fns[lb](
                        self._draft_params, tokens, jnp.int32(0),
                        jnp.int32(0), (self._dk, self._dv), full_ids,
                        *sargs)
                    tok.block_until_ready()
        b = self.max_batch
        for bucket in self.page_buckets:
            self._decode_fns[bucket] = self._build_decode_fn(bucket)
            out = self._run_decode(self._decode_fns[bucket],
                                   *self._masked_step_args(bucket))
            self.next_tokens(out).block_until_ready()
            if self.spec_enabled:
                self._propose_fns[bucket] = self._build_propose_fn(
                    bucket)
                self._verify_fns[bucket] = self._build_verify_fn(
                    bucket)
                self.spec_step(
                    np.zeros((b,), np.int32), np.zeros((b, bucket),
                                                       np.int32),
                    np.zeros((b,), np.int32), np.zeros((b,), bool),
                    np.zeros((b,), bool))
        self._harvest_calibration()
        self._guard_pending = []  # warmup rows are all-masked noise
        self._warm = True
        return self

    def _harvest_calibration(self):
        """One TIMED warm decode step per bucket into the profiling
        CalibrationStore (programs are warm — real steady-state
        seconds, one extra masked step per bucket at warmup time; the
        grid stays cold-path only)."""
        import time as _time

        try:
            from .. import profiling as _profiling

            if not _profiling.profiling_enabled():
                return
            store = _profiling.calibration_store()
            platform = jax.default_backend()
            for bucket in self.page_buckets:
                t0 = _time.perf_counter()
                out = self._run_decode(self._decode_fns[bucket],
                                       *self._masked_step_args(bucket))
                out.block_until_ready()
                seconds = _time.perf_counter() - t0
                store.record(self._digest, platform,
                             f"decode_step[{bucket}]", seconds)
                if bucket == self.page_buckets[-1]:
                    store.record(self._digest, platform, "decode_step",
                                 seconds)
        except Exception as e:
            # calibration is advisory; warmup must never fail — but
            # don't lose the evidence either (serving.registry's
            # warn-once convention)
            import logging

            global _calibration_warned
            if not _calibration_warned:
                _calibration_warned = True
                logging.getLogger(__name__).warning(
                    "decode calibration harvest failed for engine %s: "
                    "%s — continuing without measured-cost records",
                    self._digest, e)

    # -------------------------------------------------------- hot path
    def prefill(self, token_ids, table, *, start=0, seed=0,
                temperature=0.0, top_k=0, top_p=1.0):
        """Fill `table`'s pages with the prompt's K/V; returns the
        first generated token (host int). `table` must already cover
        pages_needed(len(token_ids)); for a model of several page
        groups it is the list of the groups' tables, of which the
        first must cover the prompt and the others are covered, and a
        windowed one's pages released behind the window, chunk by
        chunk (`_launch_chunks`).

        `start > 0` is the prefix-cache hit path: positions < start
        already live in (shared) pages, so only the tail runs —
        through the tail program family, whose page table is padded to
        the largest bucket for a static shape. With a draft model
        loaded, the same prompt also prefills the draft pools (same
        pages, draft-shaped K/V). `launch_prefill` and `fetch_prefill`
        are the two halves: a scheduler with steps in flight takes
        their tokens out between them."""
        return self.fetch_prefill(self.launch_prefill(
            token_ids, table, start=start, seed=seed,
            temperature=temperature, top_k=top_k, top_p=top_p))

    def launch_prefill(self, token_ids, table, *, start=0, seed=0,
                       temperature=0.0, top_k=0, top_p=1.0):
        """The prefill's programs dispatched, nothing fetched: returns
        their outputs still on the device, for `fetch_prefill`."""
        n = len(token_ids)
        sargs = self._samp_scalars(seed, temperature, top_k, top_p)
        zargs = self._samp_scalars()  # draft prefill output is unused
        if self.chunk_buckets:
            return self._launch_chunks(token_ids, table, start, sargs)
        if start and self.merged_step_enabled:
            raise PageError(
                "tail prefill has no dedicated program in merged-step "
                "mode: the scheduler feeds tail tokens through step() "
                "rows (MXNET_DECODE_MERGED_STEP=0 restores the split "
                "tail-prefill grid)")
        if start:
            tail = token_ids[start:]
            lb = pick_bucket(len(tail), self.prefill_buckets)
            tokens = np.zeros((1, lb), np.int32)
            tokens[0, :len(tail)] = tail
            max_pages = pages_needed(self.max_context, self.page_size)
            page_ids = np.full((max_pages,), SCRATCH_PAGE, np.int32)
            page_ids[:len(table)] = table
            tok, self._pools = self._tail_fns[lb](
                self._params, tokens, jnp.int32(start), jnp.int32(n),
                self._pools, page_ids, *sargs)
            if self.spec_enabled:
                _, (self._dk, self._dv) = self._draft_tail_fns[lb](
                    self._draft_params, tokens, jnp.int32(start),
                    jnp.int32(n), (self._dk, self._dv), page_ids, *zargs)
        else:
            lb = pick_bucket(n, self.prefill_buckets)
            tokens = np.zeros((1, lb), np.int32)
            tokens[0, :n] = token_ids
            page_ids = np.full((pages_needed(lb, self.page_size),),
                               SCRATCH_PAGE, np.int32)
            page_ids[:len(table)] = table
            tok, self._pools = self._prefill_fns[lb](
                self._params, tokens, jnp.int32(n), self._pools,
                page_ids, *sargs)
            if self.spec_enabled:
                _, (self._dk, self._dv) = self._draft_prefill_fns[lb](
                    self._draft_params, tokens, jnp.int32(n),
                    (self._dk, self._dv), page_ids, *zargs)
        return tok

    def fetch_prefill(self, launched):
        """The first generated token of a launched prefill (host int):
        the sampled token must reach the host to stream/EOS-check —
        the one deliberate sync of the prefill path. A chunked
        prefill's outputs (the last chunk's first token, every chunk's
        counters) come back in this ONE fetch (`last_prefill`)."""
        if not self.chunk_buckets:
            return int(np.asarray(launched))
        host = np.stack(jax.device_get(launched))
        self.last_prefill = {"chunks": len(launched), **dict(zip(
            self.cfg.step_counters, self._sum_counters(host[:, 1:])))}
        return int(host[-1, 0])

    def _launch_chunks(self, token_ids, table, start, sargs):
        """Positions [start, n) of the prompt through the pages, in
        chunks of at most `cfg.prefill_chunk` tokens: each chunk one
        dispatch of the program of its (tokens, context pages) bucket,
        nothing fetched between them. Decode steps do not run between
        the chunks of one admission (the scheduler's turn is the whole
        prompt)."""
        n = len(token_ids)
        outs, pos = [], start
        tables = [table] if len(self.groups) == 1 else table
        side = list(zip(self.groups, self.allocators, tables))[1:]
        self.last_released = 0
        while pos < n:
            m = min(self.cfg.prefill_chunk, n - pos)
            tb = pick_bucket(m, self.chunk_buckets)
            cb = pick_bucket(pages_needed(pos + m, self.page_size),
                             self.context_buckets)
            tokens = np.zeros((1, tb), np.int32)
            tokens[0, :m] = token_ids[pos:pos + m]
            for g, alloc, tbl in side:
                # the chunk's pages, and none behind its first window
                cover(alloc, tbl, pos + m,
                      g.first_page(pos, self.page_size))
            page_ids = np.full((len(tables), cb), SCRATCH_PAGE, np.int32)
            for gi, tbl in enumerate(tables):
                page_ids[gi, :min(len(tbl), cb)] = tbl[:cb]
            out, self._pools = self._chunk_fns[tb, cb](
                self._params, tokens, jnp.int32(pos), jnp.int32(pos + m),
                self._pools, page_ids.reshape(self.table_shape(cb)),
                *sargs)
            outs.append(out)
            pos += m
            for g, alloc, tbl in side:
                # what the next query (the next chunk's first, or the
                # row's first decode step) no longer reads
                self.last_released += release_behind(
                    alloc, tbl, g.first_page(pos, self.page_size))
        return outs

    def prefill_pages(self, num_tokens):
        """Free pages each group needs before a prompt of `num_tokens`
        is prefilled from position 0, first group first: its whole
        table; a windowed group's most at once, a chunk's and the
        window's before it."""
        p = self.page_size
        chunk = self.cfg.prefill_chunk or num_tokens
        return [pages_needed(num_tokens, p) if not g.window
                else min(pages_needed(num_tokens, p),
                         pages_needed(chunk + g.window - 1, p) + 1)
                for g in self.groups]

    def _sum_counters(self, rows):
        """Counter rows (n, len(step_counters)) as one row: sums, and
        the largest of a `*_max` counter."""
        return [int(rows[:, i].max() if name.endswith("_max")
                    else rows[:, i].sum())
                for i, name in enumerate(self.cfg.step_counters)]

    def step(self, tokens, page_table, lengths, active, seeds=None,
             temps=None, top_ks=None, top_ps=None):
        """One continuous-decode step. Row arrays are the fixed
        (step_rows, ...) shapes — max_batch decode rows plus, in
        merged mode, tail_budget ragged tail-prefill rows;
        `page_table.shape[1]` must be a configured bucket. Narrower
        (e.g. legacy (max_batch,)) inputs are padded with masked rows
        so every dispatch replays the one warmed shape, and the
        return is sliced back to the caller's width. Per-row sampling
        params default to greedy. Returns next tokens as a host array
        (the stream/EOS sync — one fetch per step, by design).

        Two leaf spans partition the call: `engine.launch` (row
        padding, host-to-device transfers, the program call returning)
        and `engine.fetch` (the wait for the device and the copy
        back). `launch_step` and `fetch_step` are the two halves: a
        scheduler that keeps steps in flight launches several before
        it fetches the oldest."""
        out = self.launch_step(tokens, page_table, lengths, active,
                               seeds, temps, top_ks, top_ps)
        return self.fetch_step(out, len(tokens))

    def launch_step(self, tokens, page_table, lengths, active,
                    seeds=None, temps=None, top_ks=None, top_ps=None):
        """The step's first half: returns its output still on the
        device. `tokens` may be `next_tokens(out)` of the step launched
        before, so that nothing waits for the host between them."""
        bucket = page_table.shape[-1]
        r = self.step_rows
        with _trace.span("engine.launch"):
            if not isinstance(tokens, jax.Array):
                tokens = self._pad_rows(tokens, np.int32, 0)
            lengths = self._pad_rows(lengths, np.int32, 0)
            active = self._pad_rows(active, bool, False)
            if page_table.shape[-2] < r:
                page_table = np.concatenate(
                    [np.asarray(page_table, np.int32),
                     np.full(self.table_shape(
                         r - page_table.shape[-2], bucket),
                         SCRATCH_PAGE, np.int32)], axis=-2)
            sarr = self._samp_arrays(seeds, temps, top_ks, top_ps)
            return self._run_decode(
                self._decode_fns[bucket], self._params, tokens,
                self._pools, page_table, lengths, active, *sarr)

    def fetch_step(self, out, rows):
        """The step's second half: the wait for the device and the
        copy back of the rows' tokens and, after them, the step's
        counters (`last_step_counters`)."""
        with _trace.span("engine.fetch"):
            host = np.asarray(out)
        if self.cfg.step_counters:
            self.last_step_counters = dict(zip(
                self.cfg.step_counters,
                host[self.step_rows:].tolist()))
        return host[:rows]

    def next_tokens(self, out):
        """A launched step's tokens as the next step's `tokens`, on the
        device (its counters, where the block counts, cut off)."""
        if out.shape[0] == self.step_rows:
            return out
        return self._next_tokens_fn(out)

    def _pad_rows(self, arr, dtype, fill):
        arr = np.asarray(arr, dtype)
        if len(arr) < self.step_rows:
            arr = np.concatenate(
                [arr, np.full((self.step_rows - len(arr),), fill,
                              dtype)])
        return arr

    def spec_step(self, tokens, page_table, lengths, active,
                  use_draft, seeds=None, temps=None, top_ks=None,
                  top_ps=None):
        """One speculative step: draft proposes K tokens (one
        dispatch), target verifies K+1 positions (one dispatch); the
        drafts and their distributions stay on device between the two.
        Returns (tokens_out (B, K+1), n_emit (B,)) as host arrays in
        ONE fetch — row b emits tokens_out[b, :n_emit[b]]."""
        bucket = page_table.shape[1]
        with _trace.span("engine.launch"):
            sarr = self._samp_arrays(seeds, temps, top_ks, top_ps)
            use_draft = np.asarray(use_draft, bool)
            drafts, q_dists, self._dk, self._dv = self._propose_fns[
                bucket](self._draft_params, tokens, self._dk, self._dv,
                        page_table, lengths, active, *sarr)
            tokens_out, n_emit, *pools = self._verify_fns[
                bucket](self._params, tokens, drafts, q_dists, self._k,
                        self._v, page_table, lengths, active, use_draft,
                        *sarr)
            self._pools = tuple(pools)
        with _trace.span("engine.fetch"):
            host_toks, host_n = jax.device_get((tokens_out, n_emit))
        return np.asarray(host_toks), np.asarray(host_n)

    def copy_page(self, src, dst, group=0):
        """Device copy of one page of a page group (all its planes' pools
        — the draft pools track the target's COW decisions): the COW
        half of `BlockAllocator.make_writable`."""
        src = jnp.int32(src)
        dst = jnp.int32(dst)
        self._pools = tuple(
            self._copy_fn(p, src, dst) if gi == group else p
            for p, gi in zip(self._pools, self._plane_group))
        if self._draft_params is not None:
            self._dk = self._copy_fn(self._dk, src, dst)
            self._dv = self._copy_fn(self._dv, src, dst)

    # ----------------------------------------------------- test hooks
    def read_page(self, layer, page):
        """Host copy of one page of every plane ((K, V) for the dense
        block), dequantized to float32 — test/debug only (the hot
        paths never materialize this)."""
        return tuple(np.asarray(_quant.dequant_page(p, layer, page))
                     for p in self._pools)

    def read_page_raw(self, layer, page):
        """Host copy of one page's stored (K, V, k_scale, v_scale) —
        the bit-level view quantization tests compare (scale entries
        are None on non-int8 pools)."""
        k, v = self._k, self._v
        return (np.asarray(k.data[layer, page]),
                np.asarray(v.data[layer, page]),
                None if k.scale is None
                else np.asarray(k.scale[layer, page]),
                None if v.scale is None
                else np.asarray(v.scale[layer, page]))

    def decode_program_text(self, bucket):
        """Compiled (post-optimization) text of one warmed decode
        program, lowered over the warmup's all-masked rows — how
        chip_smoke.py sees whether the Pallas kernel
        (`tpu_custom_call`) is really inside the step."""
        return self._decode_fns[bucket].lower(
            *self._masked_step_args(bucket)).compile().as_text()

    def probe_logits(self, tokens, page_table, lengths, active):
        """Eager (un-jitted) logits of one decode step over the
        CURRENT pool state, discarding the step's K/V writes — the
        drift oracle bench/CI use to compare kv dtypes position by
        position under teacher forcing. Adds zero traces (nothing is
        jitted) and never mutates the pools."""
        logits, _picked = self.cfg.probe_step(
            self._params, jnp.asarray(tokens, jnp.int32), self._pools,
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active, bool),
            kernels=self._kernels)
        return np.asarray(logits, np.float32)

    def probe_selected(self, tokens, page_table, lengths, active):
        """`probe_logits` for a block that selects what it attends:
        (logits (B, V), selected positions (layers, B, k), -1 where a
        row had fewer in reach) of one decode step over the CURRENT
        pool state, nothing written. One jitted program per (rows,
        bucket), built at the first call — outside any warmed grid, so
        call it outside a measured window."""
        page_table = np.asarray(page_table, np.int32)
        key = page_table.shape
        if key not in self._probe_fns:
            cfg, kernels = self.cfg, self._kernels

            def impl(params, tokens, pools, page_table, lengths, active):
                return cfg.probe_step(params, tokens, pools, page_table,
                                      lengths, active, kernels=kernels)

            fam = cfg.program_family
            self._probe_fns[key] = self._jit(
                impl, f"{fam}probe_b{key[0]}_p{key[1]}",
                f"probe@{key[0]}x{key[1]}", ())
        logits, picked = self._probe_fns[key](
            self._params, np.asarray(tokens, np.int32), self._pools,
            page_table, np.asarray(lengths, np.int32),
            np.asarray(active, bool))
        if picked is None:
            raise PageError("this block attends every cached token: "
                            "nothing is selected")
        return np.asarray(logits, np.float32), np.asarray(picked)


def quant_parity_probe(params, cfg, prompt, max_new=16, *,
                       kv_dtype="int8", page_size=None, num_pages=None,
                       page_buckets=None, kernel=None):
    """Teacher-forced A/B of one greedy decode at float32 vs
    `kv_dtype`: the float32 arm's token stream is replayed through
    BOTH engines token by token, so every step compares the two
    precisions over IDENTICAL context (a free-running comparison
    would stop counting at the first divergence, understating
    agreement). The drift/agreement oracle behind ci/check_quant.py
    and tests/test_quant.py.

    Returns a dict: `top1_agreement` (fraction of positions where the
    quantized argmax matches float32's), `logit_drift_max` /
    `logit_drift_mean` (abs logit gap via `probe_logits`),
    `kv_pool_capacity_ratio` (measured bytes-per-token ratio),
    `retraces` (quantized arm's post-warmup traces — must be 0), and
    `tokens` (the float32 greedy stream)."""
    names = ("float32", kv_dtype)
    engines, tables, firsts = {}, {}, {}
    for name in names:
        engines[name] = DecodeEngine(
            params, cfg, max_batch=1, page_size=page_size,
            num_pages=num_pages, page_buckets=page_buckets,
            kernel=kernel, prefix_cache=False, merged_step=False,
            kv_dtype=name).warmup()
    ref, alt = engines["float32"], engines[kv_dtype]
    prompt = [int(t) for t in prompt]
    total = len(prompt) + int(max_new)
    if total > ref.max_context:
        raise PageError(
            f"probe needs {total} tokens > context capacity "
            f"{ref.max_context}")
    need = pages_needed(total, ref.page_size)
    bucket = pick_bucket(need, ref.page_buckets)
    p_need = pages_needed(len(prompt), ref.page_size)
    for name in names:
        tables[name] = engines[name].allocator.alloc(need)
        # prefill sees only the prompt-covering prefix of the table
        # (its program sizes page slots by the prompt length bucket);
        # decode steps use the full `need`-page table below
        firsts[name] = engines[name].prefill(
            prompt, tables[name][:p_need])
    floor = {name: engines[name].traces() for name in names}
    agree = 1 if firsts[kv_dtype] == firsts["float32"] else 0
    n_cmp = 1
    drift_max, drift_sum = 0.0, 0.0
    tok = firsts["float32"]
    tokens_out = [tok]
    for t in range(int(max_new) - 1):
        length = len(prompt) + t
        lg, out = {}, {}
        for name in names:
            tbl = np.full((1, bucket), SCRATCH_PAGE, np.int32)
            tbl[0, :need] = tables[name]
            lg[name] = engines[name].probe_logits(
                np.array([tok], np.int32), tbl,
                np.array([length], np.int32),
                np.array([True], bool))[0]
            out[name] = int(engines[name].step(
                [tok], tbl, [length], [True])[0])
        gap = np.abs(lg[kv_dtype] - lg["float32"])
        drift_max = max(drift_max, float(gap.max()))
        drift_sum += float(gap.mean())
        agree += 1 if out[kv_dtype] == out["float32"] else 0
        n_cmp += 1
        tok = out["float32"]
        tokens_out.append(tok)
    ref_bpt = ref.pool_stats()["kv_bytes_per_token"]
    alt_bpt = alt.pool_stats()["kv_bytes_per_token"]
    return {
        "kv_dtype": kv_dtype,
        "top1_agreement": round(agree / n_cmp, 4),
        "positions_compared": n_cmp,
        "logit_drift_max": round(drift_max, 6),
        "logit_drift_mean": round(drift_sum / max(1, n_cmp - 1), 6),
        "kv_pool_capacity_ratio": round(ref_bpt / alt_bpt, 4),
        "kv_bytes_per_token_float32": ref_bpt,
        "kv_bytes_per_token_quant": alt_bpt,
        "retraces": alt.traces() - floor[kv_dtype],
        "tokens": tokens_out,
    }
