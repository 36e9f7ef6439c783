"""Sampling inside the jitted decode step: temperature / top-k /
top-p over per-sequence counter-based random streams.

The decode tier's reproducibility contract (ROADMAP item 1): a token
drawn for request R at sequence position P must be a PURE FUNCTION of
(R.seed, P) — never of batch composition, scheduling order, or how
many times the sequence was preempted and readmitted. Every draw here
derives its key as

    fold_in(fold_in(PRNGKey(seed), position), salt)

a counter-based construction (jax's threefry, the same Random123 /
Philox family the data pipeline's host-side `np.random.Philox`
sampler uses), so a readmitted sequence replays the identical stream:
re-prefill restores the cache, the position counter restores the
randomness. ci/check_decode.py gates the bit-identity.

Everything in this module is traced INTO the decode/prefill/verify
programs (shapes fixed, parameters passed as device arrays), so
sampled decoding adds zero host syncs and zero retraces: greedy vs
sampled rows differ only in the `temperature` array element (0 =
greedy argmax, the exact PR 8 behavior).

Filtering semantics (the standard ones):

  temperature  logits / max(t, eps); t <= 0 means greedy argmax
  top_k        keep the k highest logits (0 = off; ties at the k-th
               value are all kept)
  top_p        keep the smallest set of tokens whose probability mass
               reaches p, by descending probability (1.0 = off; the
               first token crossing p is included)

Sampling from the filtered distribution uses the Gumbel-max trick —
argmax(filtered_logits + gumbel) — which is exact categorical
sampling with one key and no cumsum/searchsorted numerics.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# stream salts: one independent substream per draw KIND at a position
SALT_TOKEN = 0      # the emitted token (plain sampled decode, bonus)
SALT_DRAFT = 1      # the draft model's proposal
SALT_ACCEPT = 2     # the speculative accept/reject uniform
SALT_RESAMPLE = 3   # the residual-distribution resample


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (host-side; the scheduler
    packs these into per-row device arrays). Defaults resolve through
    MXNET_DECODE_SAMPLING_* when constructed via `resolve()`."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    @staticmethod
    def resolve(sampling=None, seed=None):
        """Normalize a user-supplied SamplingParams | dict | None,
        filling unset fields from the MXNET_DECODE_SAMPLING_* env
        defaults (config.py getters)."""
        from . import config as _cfg

        if sampling is None:
            sp = SamplingParams(
                temperature=_cfg.sampling_temperature(),
                top_k=_cfg.sampling_top_k(),
                top_p=_cfg.sampling_top_p(),
                seed=_cfg.sampling_seed() if seed is None else int(seed))
            return sp
        if isinstance(sampling, dict):
            sampling = SamplingParams(**sampling)
        if seed is not None:
            sampling = SamplingParams(
                temperature=sampling.temperature, top_k=sampling.top_k,
                top_p=sampling.top_p, seed=int(seed))
        return sampling

    def validate(self, vocab):
        from ..serving.batcher import ServingError
        if self.temperature < 0:
            raise ServingError("temperature must be >= 0 (0 = greedy)")
        if not 0 <= self.top_k <= vocab:
            raise ServingError(f"top_k must be in [0, {vocab}]")
        if not 0.0 < self.top_p <= 1.0:
            raise ServingError("top_p must be in (0, 1]")
        return self


def stream_key(seed, position, salt):
    """The (seed, position, salt) -> PRNG key derivation (see module
    docstring). All arguments may be traced scalars."""
    key = jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32))
    key = jax.random.fold_in(key, jnp.asarray(position, jnp.int32))
    return jax.random.fold_in(key, jnp.asarray(salt, jnp.int32))


def filter_logits(scaled, top_k, top_p):
    """Apply top-k then top-p to already-temperature-scaled logits
    (V,), masking dropped entries to NEG_INF. `top_k`/`top_p` are
    traced scalars; 0 / 1.0 disable the respective filter."""
    v = scaled.shape[-1]
    desc = jnp.sort(scaled)[::-1]
    k = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    kth = desc[k - 1]
    keep = scaled >= kth
    probs = jax.nn.softmax(desc)
    below = (jnp.cumsum(probs) - probs) < top_p  # mass BEFORE token
    n_keep = jnp.maximum(jnp.sum(below), 1)
    pth = desc[n_keep - 1]
    keep = keep & (scaled >= pth)
    return jnp.where(keep, scaled, NEG_INF)


def sampling_dist(logits, temperature, top_k, top_p):
    """The request's effective token distribution (V,) — softmax of
    the filtered scaled logits; a one-hot argmax when temperature is 0
    (greedy is the zero-temperature limit, exactly). Feeds speculative
    accept/resample, which needs explicit p/q probabilities."""
    greedy = temperature <= 0.0
    t = jnp.where(greedy, 1.0, temperature)
    p = jax.nn.softmax(filter_logits(logits / t, top_k, top_p))
    onehot = jax.nn.one_hot(jnp.argmax(logits), logits.shape[-1],
                            dtype=p.dtype)
    return jnp.where(greedy, onehot, p)


def sample_token(logits, seed, position, temperature, top_k, top_p,
                 salt=SALT_TOKEN):
    """Draw one token id () int32 from `logits` (V,) under the
    request's sampling params, using the (seed, position, salt)
    stream. temperature <= 0 reproduces argmax bit-for-bit (no random
    bits consumed — greedy output is independent of the seed)."""
    greedy = temperature <= 0.0
    t = jnp.where(greedy, 1.0, temperature)
    filtered = filter_logits(logits / t, top_k, top_p)
    g = jax.random.gumbel(stream_key(seed, position, salt),
                          logits.shape)
    sampled = jnp.argmax(filtered + g)
    return jnp.where(greedy, jnp.argmax(logits),
                     sampled).astype(jnp.int32)


def sample_rows(logits, seeds, positions, temps, top_ks, top_ps):
    """Each row's token (B,) int32 from `logits` (B, V), drawn on its
    (seed, position) stream. The branch is chosen once for the batch,
    on the device: a batch with no row above temperature 0 takes the
    plain argmax (no sort, no Gumbel draw); any other takes every
    row's `sample_token`, whose greedy rows give that same argmax. The
    predicate stays outside the `vmap`: under it a `cond` turns into a
    `select` that runs both branches."""
    def sampled(lg):
        return jax.vmap(sample_token)(lg, seeds, positions, temps,
                                      top_ks, top_ps)

    def greedy(lg):
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temps > 0.0), sampled, greedy, logits)


def sample_from_dist(dist, seed, position, salt):
    """Draw from an explicit probability vector (V,) via Gumbel-max on
    log-probabilities (speculative residual resampling)."""
    g = jax.random.gumbel(stream_key(seed, position, salt), dist.shape)
    return jnp.argmax(jnp.log(jnp.maximum(dist, 1e-38)) +
                      g).astype(jnp.int32)


def accept_uniform(seed, position):
    """The accept/reject uniform for the token at `position`."""
    return jax.random.uniform(stream_key(seed, position, SALT_ACCEPT))
