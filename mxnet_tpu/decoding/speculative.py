"""Speculative decoding over the paged KV cache: a small draft model
proposes K tokens per step, the target model verifies all K+1
positions in ONE fixed-shape program over the same page tables.

Why it composes with the paged decode tier (ROADMAP item 1,
PAPERS.md): both models' K/V entries are pure functions of the token
prefix, so the draft keeps a PARALLEL pool of pages indexed by the
exact same page ids/tables the target uses — no second allocator, no
second scheduler. The allocator's refcount/COW decisions apply to
both pools (the engine copies draft pages alongside target pages on
COW breaks), and prefix-cache hits share draft K/V for free.

Rollback is by page-table truncation, never by copy: a step that
accepts n < K drafts leaves the rejected entries sitting in the pages
BEYOND the advanced length, where (a) every attention read masks them
out (per-query causal masks bound reads by position) and (b) the next
step's writes at positions [length', length'+K] overwrite every stale
entry before anything can unmask it — the write range of step t+1
always covers the stale range of step t because length' >= length+1.

The accept rule is the standard speculative-sampling one (accept
draft d_j with probability min(1, p_j(d_j)/q_j(d_j)); on the first
rejection, resample from normalize(max(p_j - q_j, 0))), which makes
the emitted stream distribution-identical to target-only decoding —
and EXACTLY equal under greedy, where p/q degenerate to one-hots and
the rule reduces to "accept while the draft matches the target
argmax". All randomness rides the (seed, position, salt) streams of
`sampling`, so speculative sampled output replays bit-identically
across preempt/readmit, like everything else in the tier.

A per-row `use_draft` flag lets requests opt out inside the same
fixed-shape program: opted-out rows force zero accepts and their
correction slot is a DIRECT sample from the target distribution on
the plain-decode (seed, position, TOKEN) stream — plain decode
semantics, one token per step, no separate program family.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import quant as _quant
from . import sampling as _sampling
from .blocks import SCRATCH_PAGE
from .model import _mlp, _qkv, _rms, decode_logits


def draft_propose_forward(params, last_tokens, k_pages, v_pages,
                          page_table, lengths, active, seeds, temps,
                          top_ks, top_ps, *, cfg, attn, k):
    """K statically-unrolled draft decode steps in one program.

    Feeds each sampled draft token back as the next step's input, so
    one dispatch proposes the whole K-token run. Returns (drafts
    (B, K), q_dists (B, K, V) — the draft's sampling distribution at
    each position, needed by the verify accept ratio — k_pages,
    v_pages). Draft tokens ride the SALT_DRAFT stream at the position
    they would be emitted (lengths+1+j).
    """
    tok = last_tokens
    drafts, q_dists = [], []
    for j in range(k):
        # one scope per unrolled draft step; the part names inside are
        # decode_logits' own (embed, l{i}/qkv ... logits)
        with jax.named_scope(f"draft{j}"):
            logits, k_pages, v_pages, _ = decode_logits(
                params, tok, k_pages, v_pages, page_table, lengths + j,
                active, cfg=cfg, attn=attn)
            with jax.named_scope("sample"):
                qd = jax.vmap(
                    lambda lg, tm, tk, tp: _sampling.sampling_dist(
                        lg, tm, tk, tp))(logits, temps, top_ks, top_ps)
                d = jax.vmap(
                    lambda lg, sd, p, tm, tk, tp:
                    _sampling.sample_token(
                        lg, sd, p, tm, tk, tp,
                        salt=_sampling.SALT_DRAFT))(
                    logits, seeds, lengths + 1 + j, temps, top_ks,
                    top_ps)
        drafts.append(d)
        q_dists.append(qd)
        tok = d
    return (jnp.stack(drafts, axis=1), jnp.stack(q_dists, axis=1),
            k_pages, v_pages)


def verify_forward(params, last_tokens, drafts, q_dists, k_pages,
                   v_pages, page_table, lengths, active, use_draft,
                   seeds, temps, top_ks, top_ps, *, cfg, attn_multi,
                   k):
    """The target's verify step: score positions lengths..lengths+K in
    one multi-query pass, accept/resample in-program.

    Writes the K+1 input tokens' K/V at positions lengths..lengths+K
    through the page table (the host guarantees those pages are
    exclusively owned — make_writable over the whole write range),
    attends each query j over context <= lengths+j, then runs the
    accept rule per row. Returns (tokens_out (B, K+1), n_emit (B,),
    k_pages, v_pages): row b emits tokens_out[b, :n_emit[b]], where
    slot n_acc holds the correction/bonus token and slots before it
    are the accepted drafts.
    """
    k_pages = _quant.as_pool(k_pages)
    v_pages = _quant.as_pool(v_pages)
    page_size = k_pages.page_size
    b = last_tokens.shape[0]
    bp = page_table.shape[1]
    s = k + 1
    rows = jnp.arange(b)
    tokens_in = jnp.concatenate(
        [last_tokens[:, None], drafts], axis=1)        # (B, K+1)
    pos = lengths[:, None] + jnp.arange(s)[None, :]    # (B, S) writes
    in_cap = pos < bp * page_size
    w_pages = jnp.where(
        active[:, None] & in_cap,
        page_table[rows[:, None],
                   jnp.clip(pos // page_size, 0, bp - 1)],
        SCRATCH_PAGE)
    slots = pos % page_size
    pos_safe = jnp.clip(pos, 0, cfg.max_len - 1)

    with jax.named_scope("embed"):
        x = params["embed"][tokens_in] + params["pos"][pos_safe]
    for i in range(cfg.n_layers):
        with jax.named_scope(f"l{i}"):
            with jax.named_scope("qkv"):
                h1 = _rms(x, params[f"l{i}.ln1"])
                q, kk, vv = _qkv(params, i, h1, cfg)
            with jax.named_scope("kv_write"):
                k_pages, _ = _quant.kv_scatter(k_pages, i, w_pages,
                                               slots, kk)
                v_pages, _ = _quant.kv_scatter(v_pages, i, w_pages,
                                               slots, vv)
            with jax.named_scope("attn"):
                o = attn_multi(q, k_pages.layer(i), v_pages.layer(i),
                               page_table, pos_safe)
            with jax.named_scope("out"):
                x = x + o.reshape(b, s, cfg.d_model) \
                    @ params[f"l{i}.wo"]
            with jax.named_scope("mlp"):
                x = x + _mlp(params, i, _rms(x, params[f"l{i}.ln2"]))
    with jax.named_scope("logits"):
        x = _rms(x, params["ln_f"])
        logits = x @ params["embed"].T                 # (B, S, V)

    # everything below chooses tokens: the accept rule and its
    # correction candidates
    with jax.named_scope("sample"):
        return _accept(logits, drafts, q_dists, lengths, active,
                       use_draft, seeds, temps, top_ks, top_ps, k) \
            + (k_pages, v_pages)


def _accept(logits, drafts, q_dists, lengths, active, use_draft, seeds,
            temps, top_ks, top_ps, k):
    """The speculative accept rule over the verify pass's logits
    (B, K+1, V): returns (tokens_out (B, K+1), n_emit (B,))."""
    b = drafts.shape[0]
    rows = jnp.arange(b)
    p_dists = jax.vmap(
        lambda lgs, tm, tk, tp: jax.vmap(
            lambda lg: _sampling.sampling_dist(lg, tm, tk, tp))(lgs))(
        logits, temps, top_ks, top_ps)                 # (B, S, V)

    # accept run: a_j = [all drafts before j accepted] & u_j < p/q
    acc = use_draft & active
    n_acc = jnp.zeros((b,), jnp.int32)
    for j in range(k):
        d_j = drafts[:, j]
        p_d = p_dists[rows, j, d_j]
        q_d = q_dists[rows, j, d_j]
        u_j = jax.vmap(_sampling.accept_uniform)(seeds,
                                                 lengths + 1 + j)
        a = acc & (u_j < p_d / jnp.maximum(q_d, 1e-9))
        n_acc = n_acc + a.astype(jnp.int32)
        acc = a

    # correction candidates, one per possible rejection slot (plus
    # the bonus slot K reached only on a clean sweep). Greedy rows
    # take the argmax directly: one-hot residuals make it exact, and
    # bypassing the Gumbel draw keeps greedy seed-independent.
    greedy = temps <= 0.0
    cols = []
    for j in range(k):
        pj, qj = p_dists[:, j], q_dists[:, j]
        resid = jnp.maximum(pj - qj, 0.0)
        rs = jnp.sum(resid, axis=-1, keepdims=True)
        resid_dist = jnp.where(rs > 1e-9,
                               resid / jnp.maximum(rs, 1e-9), pj)
        r = jax.vmap(
            lambda dd, sd, p: _sampling.sample_from_dist(
                dd, sd, p, _sampling.SALT_RESAMPLE))(
            resid_dist, seeds, lengths + 1 + j)
        t = jax.vmap(
            lambda dd, sd, p: _sampling.sample_from_dist(
                dd, sd, p, _sampling.SALT_TOKEN))(
            pj, seeds, lengths + 1 + j)
        gd = jnp.argmax(pj, axis=-1).astype(jnp.int32)
        r = jnp.where(greedy, gd, r)
        t = jnp.where(greedy, gd, t)
        cols.append(jnp.where(use_draft, r, t))
    pk = p_dists[:, k]
    bonus = jax.vmap(
        lambda dd, sd, p: _sampling.sample_from_dist(
            dd, sd, p, _sampling.SALT_TOKEN))(
        pk, seeds, lengths + 1 + k)
    bonus = jnp.where(greedy, jnp.argmax(pk, axis=-1).astype(jnp.int32),
                      bonus)
    cols.append(bonus)
    corr_all = jnp.stack(cols, axis=1)                 # (B, K+1)
    correction = corr_all[rows, n_acc]

    tokens_out = jnp.concatenate(
        [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    tokens_out = tokens_out.at[rows, n_acc].set(correction)
    n_emit = jnp.where(active, n_acc + 1, 0)
    return tokens_out, n_emit
