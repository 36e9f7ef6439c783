"""mxnet_tpu.decoding — continuous-batching autoregressive serving
over a paged, ragged KV cache.

The serving tier (mxnet_tpu.serving) batches ONE forward per request;
autoregressive decoding needs hundreds of dependent steps per request,
and naive batching staircases every sequence to the longest one. This
package applies the Ragged Paged Attention recipe (PAPERS.md) instead:

  blocks     free-list page allocator + per-sequence page tables with
             refcounts (prefix sharing, copy-on-write fork)
  attention  page-table attention kernels: a gather-based lax kernel
             over the rows as stored (the query spread over the heads'
             lanes) and a Pallas kernel of the same arithmetic that
             reads each row's live pages in place, several a block
             with the next block's copies in flight: the default on a
             TPU (MXNET_DECODE_KERNEL=lax|pallas, unset: by the
             backend); sparse selection inside
             paged attention (an index score over every cached token,
             an exact top-k, attention over the selected rows only)
  model      the MODEL CONTRACT and its first instance: a
             configuration object (`DecoderConfig`) gives the engine
             the planes of its page pool (`planes`: name, width a
             token, scale groups), its step functions (`decode_step`,
             `prefill_step` or `chunk_step`, `probe_step`), the prefix
             of its programs' names and the counters a step returns
             beside its tokens; the dense block (learned positions,
             per-head K and V pages, ReLU MLP, tied head) is written
             as reference / prefill / decode-step forwards over one
             flat params dict
  sparse_latent  the second instance (`SparseLatentConfig`): a latent
             row and an index key a token, learned sparse attention,
             rotary positions with YaRN, routed experts that are told
             which they hold (`experts_held`), a shared expert, an
             untied head over the vocabulary slice held here; prompts
             go through the pages in chunks
  layers     what more than one block is made of: norm, gated
             feed-forward, rotary positions, the router over all
             experts and the held experts' grouped computation
  window_mixed  the third instance (`WindowMixedConfig`): window and
             full attention layers mixed, each kind with its own KV
             heads, planes and page group (the window group's pages
             behind a row's window are released while it decodes),
             grouped queries, partial rotary with a base a kind, a
             learned sink, routed experts without a shared one
  engine     DecodeEngine — owns the device page pool (one buffer per
             plane, one page table a page group) and a pre-traced
             fixed-shape program grid (zero steady-state retraces);
             dispatches on the configuration object alone
  scheduler  ContinuousScheduler + DecodedModel — per-step admission,
             eviction, priority preemption, streaming DecodeFuture
             (whose TokenStream owns/cancels the request); with
             `run_ahead` it keeps steps in flight so that the device
             does not wait for the host between them. Unset, the
             backend decides (`config.run_ahead`): `RUN_AHEAD` (1)
             on a TPU for the plain step, enough to hide the host's
             turn behind the device's step; 0, the turn that waits
             for each step, with a draft, the merged step or off the
             TPU. Each `decoding.step` span's `in_flight` counts the
             steps still launched as its tokens came out
  prefix     PrefixCache — radix index over cached prompt KV pages;
             admission maps shared prefixes via the fork path and
             prefills only the tail
  quant      precision-polymorphic page pools (KVPool pytree), one
             per `Plane` of width w: int8 pages with per-page scale
             planes, quantized at scatter and dequantized in-kernel
             (MXNET_DECODE_KV_DTYPE=float32|bf16|int8); whole-context
             and token-granular reads through the page table
  sampling   SamplingParams + the (seed, position, salt) counter
             streams: temperature/top-k/top-p inside the jitted step,
             bit-reproducible across preemption
  speculative draft-proposes-K / target-verifies-K+1 forwards over
             the same page tables (distribution-identical output,
             exact under greedy)
  stats      DecodeStats -> `decodingStats` view (profiler dumps,
             /metrics, /statusz)

    from mxnet_tpu import serving
    server = serving.ModelServer()
    dec = server.load_decoder("lm", params, cfg)        # warmed
    fut = server.submit_decode("lm", prompt_tokens)     # DecodeFuture
    for tok in fut.stream(): ...                        # per-step
    toks = server.generate("lm", prompt_tokens)         # sync

Knobs: MXNET_DECODE_* (docs/env_vars.md). Guide: docs/serving.md
("Continuous decoding").
"""
from . import attention, blocks, config, engine, layers, model, prefix, \
    quant, sampling, scheduler, sparse_latent, speculative, stats, \
    window_mixed
from .blocks import (SCRATCH_PAGE, BlockAllocator, PageError, PageGroup,
                     PagePoolExhausted, pages_needed)
from .attention import (get_kernel, get_multi_kernel,
                        paged_attention_lax, paged_attention_pallas)
from .engine import DecodeEngine, quant_parity_probe
from .quant import KVPool, Plane
from .model import DecoderConfig, init_decoder_params, reference_logits
from .sparse_latent import (SparseLatentConfig,
                            init_sparse_latent_params)
from .window_mixed import WindowMixedConfig, init_window_mixed_params
from .prefix import PrefixCache, page_digests
from .sampling import SamplingParams
from .scheduler import (ContinuousScheduler, DecodeFuture,
                        DecodedModel, RequestHandedOff, TokenStream)
from .stats import DecodeStats, decoding_stats, reset_decoding_stats

__all__ = [
    "BlockAllocator", "ContinuousScheduler", "DecodeEngine",
    "DecodeFuture", "DecodeStats", "DecodedModel", "DecoderConfig",
    "KVPool", "PageError", "PageGroup", "PagePoolExhausted", "Plane",
    "PrefixCache",
    "RequestHandedOff", "SCRATCH_PAGE", "SamplingParams",
    "SparseLatentConfig", "TokenStream", "WindowMixedConfig", "attention",
    "blocks", "config", "decoding_stats", "engine", "get_kernel",
    "get_multi_kernel", "init_decoder_params",
    "init_sparse_latent_params", "init_window_mixed_params", "layers",
    "model",
    "page_digests",
    "paged_attention_lax", "paged_attention_pallas", "pages_needed",
    "prefix", "quant", "quant_parity_probe", "reference_logits",
    "reset_decoding_stats", "sampling", "scheduler", "sparse_latent",
    "speculative", "stats", "window_mixed",
]
