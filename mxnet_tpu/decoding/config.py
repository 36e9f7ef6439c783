"""Env-knob resolution for the decode tier (registered in
mxnet_tpu.utils so `describe_env()`/docs/env_vars.md cover them).

Resolution order everywhere: explicit constructor argument > MXNET_*
env var > built-in default (the serving/config.py convention).
"""
from __future__ import annotations

from .. import utils
from ..serving.batcher import _parse_buckets


def page_size():
    return utils.getenv("MXNET_DECODE_PAGE_SIZE")


def num_pages():
    return utils.getenv("MXNET_DECODE_PAGES")


def max_batch():
    return utils.getenv("MXNET_DECODE_MAX_BATCH")


def page_buckets():
    raw = utils.getenv("MXNET_DECODE_PAGE_BUCKETS")
    return _parse_buckets(raw) if raw else None


def kernel():
    """The single-query paged attention. Unset, the backend decides
    (the fact `utils.pallas_interpret` reads): the in-place kernel on a
    TPU, the lax form elsewhere, where the kernel would be interpreted
    and every test of the tier would crawl. An explicit value wins."""
    named = str(utils.getenv("MXNET_DECODE_KERNEL"))
    if named:
        return named
    return "lax" if utils.pallas_interpret() else "pallas"


# Decode steps kept in flight beyond the one whose tokens the loop
# takes out (ContinuousScheduler._turn_ahead), where the default
# applies. One step in flight already hides the host's turn (pack,
# launch, emit: 6-7 ms for the 1.3B dense block at 48 rows on a v5e)
# behind the device's step of 6.5 ms. Each further one adds at most
# 1.5% of rate there (2, 4 or 8) and lengthens the gap between a
# caller's tokens around an admission, which takes everything launched
# out first: that mix's 95th-percentile gap was 47.6 ms at 1, 52.1 at
# 2, 62.0 at 4 and 79.6 at 8, against 49.4 with the step waited for.
RUN_AHEAD = 1


def run_ahead(engine):
    """Steps in flight when the caller names none. The backend decides
    (the fact `kernel()` reads): RUN_AHEAD on a TPU where the engine
    runs the plain step; 0, the turn that launches a step and waits for
    it, with a draft or the merged step (their steps are not the plain
    one) and off the TPU, where every test of the tier keeps the
    waited-for turn."""
    if utils.pallas_interpret() or engine.spec_enabled \
            or engine.merged_step_enabled:
        return 0
    return RUN_AHEAD


def merged_step():
    return bool(utils.getenv("MXNET_DECODE_MERGED_STEP"))


def kv_dtype():
    # KV-page storage precision: float32 | bf16 | int8 (fp8 reserved);
    # validated/normalized by decoding.quant.canonical at engine build
    return str(utils.getenv("MXNET_DECODE_KV_DTYPE") or "float32")


def ring_prefill():
    return utils.getenv("MXNET_DECODE_RING_PREFILL")


def max_tokens():
    return utils.getenv("MXNET_DECODE_MAX_TOKENS")


def queue_cap():
    return utils.getenv("MXNET_DECODE_QUEUE_CAP")


def prefix_cache():
    return bool(utils.getenv("MXNET_DECODE_PREFIX_CACHE"))


def spec_k():
    return utils.getenv("MXNET_DECODE_SPEC_K")


def spec_draft():
    return utils.getenv("MXNET_DECODE_SPEC_DRAFT")


def sampling_temperature():
    return utils.getenv("MXNET_DECODE_SAMPLING_TEMPERATURE")


def sampling_top_k():
    return utils.getenv("MXNET_DECODE_SAMPLING_TOP_K")


def sampling_top_p():
    return utils.getenv("MXNET_DECODE_SAMPLING_TOP_P")


def sampling_seed():
    return utils.getenv("MXNET_DECODE_SAMPLING_SEED")


def default_page_buckets(max_pages_per_seq):
    """Powers of two up to max_pages_per_seq (inclusive): each bucket
    is one compiled decode program, so the grid stays logarithmic."""
    out, b = [], 1
    while b < max_pages_per_seq:
        out.append(b)
        b *= 2
    out.append(max_pages_per_seq)
    return tuple(out)
