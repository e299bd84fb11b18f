"""In-jit token sampling: greedy / temperature / top-k / top-p, per-slot.

Sampling runs inside the jitted step so only the sampled token ids (a few
bytes) cross the device→host boundary per step — never the [slots, vocab]
logits. All parameters are per-slot vectors so one compiled function serves
any mix of requests.

A full descending sort of a 128k vocab is one of the slowest single ops on
TPU (sorts don't map to the MXU); instead we take the top ``CANDIDATES``
logits with ``lax.top_k`` (a partial sort) and sample within them. top-k is
clamped to the candidate budget and top-p is computed over the renormalized
candidate mass — exact whenever the requested cutoff lies inside the top
candidates, which at serving temperatures it essentially always does.

Encoding of "disabled": temperature <= 0 → greedy; top_k <= 0 → no top-k;
top_p >= 1 → no top-p.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# static candidate budget for top-k/top-p; raising it trades step time for
# exactness of very flat sampling distributions. The preprocessor clamps
# requested top_k to this bound (with a warning) so the API never silently
# serves a different distribution than validated.
CANDIDATES = 64


def apply_penalties(
    logits: jax.Array,  # [B, V] float32
    counts: jax.Array,  # [B, V] int — output-token occurrence counts
    frequency_penalty: jax.Array,  # [B]
    presence_penalty: jax.Array,  # [B]
) -> jax.Array:
    """OpenAI-semantics repetition penalties over *output* token counts.

    ``logit[t] -= freq * count[t] + presence * (count[t] > 0)`` — the counts
    buffer is maintained in-jit by the engine's step functions (one
    scatter-add per sampled token), so penalties cost two [B, V] elementwise
    ops and never leave the device. Reference: penalties flow through
    SamplingOptions (lib/llm/src/protocols/common.rs:52-644).
    """
    cf = counts.astype(jnp.float32)
    return (
        logits
        - frequency_penalty[:, None] * cf
        - presence_penalty[:, None] * (cf > 0.0)
    )


def update_counts(
    counts: jax.Array,  # [S, V] int32
    tokens: jax.Array,  # [B] int32 sampled this step
    active: jax.Array,  # [B] bool — lanes whose sample is real (not padding)
    rows: Optional[jax.Array] = None,  # [B] int32 — the count row of each lane
) -> jax.Array:
    """Scatter-add this step's sampled tokens into the count buffer. ``rows``
    names each lane's row where the lanes are not the buffer's rows in order
    (a chunk dispatch's packed rows); a row index past the buffer is dropped."""
    add = active.astype(counts.dtype)
    if rows is None:
        return counts.at[jnp.arange(counts.shape[0]), tokens].add(add)
    return counts.at[rows, tokens].add(add, mode="drop")


def sample_tokens(
    logits: jax.Array,  # [B, V] float32
    keys: jax.Array,  # [B] PRNG keys (per-slot, honors per-request seeds)
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32
    top_p: jax.Array,  # [B]
    *,
    greedy_only: bool = False,
) -> jax.Array:
    """Sample one token per row. Returns [B] int32.

    ``greedy_only`` (static) compiles just the argmax: when no lane in the
    batch has temperature > 0, the top-k partial sort, softmax/cumsum and
    categorical draw are dead weight — several ms per decode step at a 128k
    vocab, paid every step of every dispatch. The engine picks the variant
    per dispatch from the live lanes' sampling options."""
    b, v = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    if greedy_only:
        return greedy.astype(jnp.int32)

    c = min(CANDIDATES, v)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    top_logits, top_idx = jax.lax.top_k(scaled, c)  # [B, C], sorted desc

    ranks = jnp.arange(c)[None, :]
    k_eff = jnp.where(top_k > 0, jnp.minimum(top_k, c), c)[:, None]
    keep_k = ranks < k_eff

    probs_sorted = jax.nn.softmax(top_logits, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    # keep tokens until cumulative prob exceeds p (always keep the first)
    keep_p = (cum - probs_sorted) < jnp.clip(top_p, 0.0, 1.0)[:, None]

    keep = keep_k & keep_p
    masked = jnp.where(keep, top_logits, -jnp.inf)
    choice = jax.vmap(jax.random.categorical)(keys, masked)  # [B] in [0, C)
    sampled = jnp.take_along_axis(top_idx, choice[:, None], axis=1)[:, 0]

    return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)


def speculative_targets(
    logits_all: jax.Array,  # [B, K1, V] f32 — one row per fed position
    counts: jax.Array,  # [B, V] int32 penalty counts (dummy when unused)
    active: jax.Array,  # [B, K1] bool — position actually fed (not padding)
    step_key: jax.Array,  # dispatch-level PRNG key
    seeds: jax.Array,  # [B] per-request seeds
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    frequency_penalty: jax.Array,  # [B]
    presence_penalty: jax.Array,  # [B]
    *,
    with_pen: bool,
    with_sample: bool,
    with_lp: bool,
    n_top: int = 0,
) -> tuple:
    """Target tokens for a speculative-verify dispatch, position by position.

    The verify step feeds ``[last_token, draft_0, .., draft_{k-1}]`` through
    one forward pass; this samples the engine's OWN next token at every fed
    position — exactly the token the sequential sampler would have produced
    given the same prefix and the same per-position key. The engine then
    keeps the drafted prefix that MATCHES these targets plus the first
    non-matching target as the bonus token. That acceptance rule is the
    rejection-sampling scheme specialized to point-mass (deterministic)
    proposals: every emitted token was drawn from the model's conditional at
    its position, so the emitted stream follows the exact autoregressive
    distribution — and greedy (temperature 0) output is bitwise identical to
    non-speculative greedy decode.

    Penalties are sequentially exact along the chunk: the scan carries the
    count buffer, adding each position's target before scoring the next —
    identical to one-token-at-a-time decoding for every position up to and
    including the first draft mismatch (positions past it are discarded by
    the engine, and their garbage-fed logits never leave the device as
    emitted tokens). Because rejected positions DO pollute the returned
    count buffer, the engine subtracts exactly the non-emitted targets from
    each penalized row after every verify dispatch (``_counts_fix_fn`` —
    O(spec_k) per lane, never a full out_tokens rebuild).

    Returns ``(targets [B, K1], counts)`` plus, with ``with_lp``,
    ``(chosen_lp [B, K1], top_ids [B, K1, n_top], top_lps [B, K1, n_top])``
    inserted before ``counts`` — mirroring the decode scan's layout.
    """
    k1 = logits_all.shape[1]

    def body(carry, j):
        cnt = carry
        sel = logits_all[:, j]
        if with_sample:
            kk = jax.random.fold_in(step_key, j)
            keys = jax.vmap(lambda s: jax.random.fold_in(kk, s))(seeds)
        else:
            keys = None
        sampled_from = (
            apply_penalties(sel, cnt, frequency_penalty, presence_penalty)
            if with_pen else sel
        )
        nxt = sample_tokens(sampled_from, keys, temperature, top_k, top_p,
                            greedy_only=not with_sample)
        if with_pen:
            cnt = update_counts(cnt, nxt, active[:, j])
        if with_lp:
            lp, tids, tlps = token_logprobs(sel, nxt, n_top)
            return cnt, (nxt, lp, tids, tlps)
        return cnt, nxt

    counts, out = jax.lax.scan(body, counts, jnp.arange(k1))
    # scan stacks position-major [K1, B, ...] → slot-major
    if with_lp:
        nxt, lp, tids, tlps = out
        return (
            nxt.T, lp.T, tids.transpose(1, 0, 2), tlps.transpose(1, 0, 2),
            counts,
        )
    return out.T, counts


def token_logprobs(
    logits: jax.Array,  # [B, V] float32 (raw, temperature-unscaled)
    tokens: jax.Array,  # [B] int32 sampled tokens
    n_top: int,
) -> tuple:
    """Model log-probabilities for OpenAI ``logprobs`` reporting.

    Returns (chosen_lp [B], top_ids [B, n_top], top_lps [B, n_top]); raw
    model distribution, not the sampling-modified one. n_top == 0 returns
    empty [B, 0] alternatives.
    """
    b, v = logits.shape
    lse = jax.scipy.special.logsumexp(logits, axis=-1)  # [B]
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    chosen_lp = chosen - lse
    if n_top > 0:
        top_vals, top_ids = jax.lax.top_k(logits, n_top)
        top_lps = top_vals - lse[:, None]
    else:
        top_ids = jnp.zeros((b, 0), jnp.int32)
        top_lps = jnp.zeros((b, 0), jnp.float32)
    return chosen_lp, top_ids.astype(jnp.int32), top_lps
