"""Prompt-lookup speculative decoding (greedy): multi-token decode steps.

Small-batch decode on TPU is bound by the *sequential step chain*, not
bytes (this module attacks step COUNT).  The way through is fewer
sequential steps per generated token:
prompt-lookup decoding (PLD) drafts the next ``draft_len`` tokens by
matching the trailing n-gram of the context against its own history, then
verifies all of them in ONE cached forward.  Every committed token is an
argmax of model logits over exactly its committed prefix, so the output
is a greedy trajectory of the model (identical to ``generate_tokens``'s
greedy mode up to the usual multi-token-vs-single-token float
accumulation noise; bitwise-equal on CPU fp32 — see
tests/generation/test_speculative.py).

On repetitive continuations (summarization, code, retrieval-grounded
generation) acceptance is high and tokens/step approaches
``draft_len + 1``; on incompressible text acceptance drops and the loop
degrades gracefully toward one token per forward (plus the verify rows'
negligible extra FLOPs — decode is latency-bound, which is the point).

Extension beyond the reference (its generation loop is strictly one token
per pipelined ForwardStep, megatron/text_generation/generation.py:89-285).
This module is the ONE-SHOT path (fixed batch, dense cache, jitted loop)
and its drafter is strictly the linear prompt-lookup one.  The
continuous-batching serving engine carries TWO speculative paths over
paged blocks, both with per-slot acceptance policies: the same host
n-gram drafter verifying a linear window (docs/serving.md, "Speculative
decoding"), and a resident draft MODEL proposing candidate trees that
the target verifies in one forward — the path that still
speculates on traffic with nothing to look up (serving/engine.py
``_spec_step_tree``; docs/serving.md, "Tree speculation & resident
drafts").

Batched behavior (round 5): fully per-sample.  The KV cache carries a
[batch] vector of fill levels (ops/kv_quant.py:cache_update and the
decode attention masks accept it), so ragged prompts are supported
directly and each sample advances by ITS OWN acceptance count — no
batch-min lockstep, no uniform-prompt restriction.  Samples that hit EOS
or run out of window room freeze (their buffer and fill stop changing)
while the rest continue.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..models import model as model_lib

# shared with api.py's eligibility check so the two can't drift
DEFAULT_DRAFT_LEN = 5
DEFAULT_NGRAM = 3


def _greedy_ids(logits, vocab: int):
    """argmax over the REAL vocabulary — model logits cover the padded
    vocab (config.padded_vocab_size), and untrained pad columns must never
    win (sample_with_mode masks them the same way in the plain loop)."""
    return jnp.argmax(logits[..., :vocab], axis=-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class SpeculativeOutput:
    tokens: jax.Array   # [b, max_seq] int32 — prompts + generations
    lengths: jax.Array  # [b] int32 — total length incl. prompt
    steps: jax.Array    # scalar int32 — verify forwards run (speedup =
    #                     generated_tokens / steps vs one forward per token)


def _row_update(buf, rows, cur):
    """Per-sample dynamic_update_slice of ``rows`` [b, w] into ``buf``
    [b, T] at each sample's own column ``cur`` [b]."""
    return jax.vmap(
        lambda bi, ri, ci: jax.lax.dynamic_update_slice(bi, ri, (ci,))
    )(buf, rows, cur)


def _row_slice(buf, cur, w: int):
    """Per-sample dynamic_slice [b, w] of ``buf`` [b, T] at ``cur`` [b]."""
    return jax.vmap(
        lambda bi, ci: jax.lax.dynamic_slice(bi, (ci,), (w,)))(buf, cur)


def _ngram_draft(tokens, cur, t0, *, ngram: int, draft_len: int):
    """Per-sample draft via most-recent n-gram match.

    ``tokens`` [b, T] with content valid on [0, cur_i) per sample;
    ``cur`` [b]; ``t0`` [b] is the just-committed token logically at each
    sample's position ``cur_i``.  The lookup key is the last ``ngram``
    tokens ending at ``cur_i`` (inclusive); the draft is the
    ``draft_len`` tokens that followed the key's most recent earlier
    occurrence.  No match → repeat ``t0`` (verification then simply
    rejects, costing nothing extra)."""
    b, T = tokens.shape
    buf = _row_update(tokens, t0[:, None], cur)
    key = _row_slice(buf, cur + 1 - ngram, ngram)       # [b, ngram]
    # windows[j] = buf[:, j : j+ngram] for every j, via ngram static shifts
    n_win = T - ngram + 1
    match = jnp.ones((b, n_win), jnp.bool_)
    for o in range(ngram):
        match &= buf[:, o:o + n_win] == key[:, o:o + 1]
    # only occurrences ending before each sample's key position
    j_idx = jnp.arange(n_win)
    valid = (j_idx[None, :] + ngram - 1) < cur[:, None]
    score = jnp.where(match & valid, j_idx[None, :] + 1, 0)
    j_best = jnp.argmax(score, axis=1)          # [b] most recent match
    found = jnp.max(score, axis=1) > 0
    gather = (j_best[:, None] + ngram
              + jnp.arange(draft_len)[None, :])  # [b, draft_len]
    gather = jnp.clip(gather, 0, T - 1)
    draft = jnp.take_along_axis(buf, gather, axis=1)
    return jnp.where(found[:, None], draft,
                     jnp.broadcast_to(t0[:, None], (b, draft_len)))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_prompt_len", "eos_id", "draft_len",
                     "ngram", "use_eos_stop"),
)
def _pld_impl(cfg: ModelConfig, params, tokens, lengths, *,
              max_prompt_len: int, eos_id: int, draft_len: int,
              ngram: int, use_eos_stop: bool):
    b, max_seq = tokens.shape
    k = draft_len
    vocab = cfg.vocab_size
    rope = model_lib.rope_tables(cfg)
    # The cache is padded past max_seq: frozen samples (EOS'd or out of
    # room) still ride through the lockstep verify forward, and their
    # discarded window rows must land somewhere harmless — past-fill rows
    # are masked until overwritten, and the pad keeps even a window
    # starting at max_seq-1 in range.  The pad rounds up to a 128
    # multiple so the tail loop's single-token steps stay eligible for
    # the Pallas decode kernel (ops/attention.decode_kernel_eligible
    # requires max_len % 128 == 0).
    pad_len = -(-(max_seq + k + 1) // 128) * 128
    k_cache, v_cache = model_lib.init_kv_cache(cfg, b, pad_len)

    # One prefill over the longest prompt: right-pad rows beyond each
    # sample's own length hold garbage K/V, but the per-sample fill level
    # (= lengths) masks them, and committed tokens overwrite them in
    # order before the fill ever reaches them.
    logits, k_cache, v_cache = model_lib.forward_cached(
        cfg, params, tokens[:, :max_prompt_len], k_cache, v_cache,
        jnp.int32(0), rope=rope, empty_cache=True,
        logit_rows=lengths - 1)
    last_logits = logits[:, 0]

    cur = lengths                              # [b] per-sample fill
    done = jnp.zeros((b,), jnp.bool_)
    out_lengths = lengths
    steps = jnp.int32(0)

    def spec_cond(carry):
        cur, *_, done, _, _ = carry
        return jnp.any(~done & (cur + k + 1 <= max_seq))

    def spec_body(carry):
        (cur, tokens, k_cache, v_cache, last_logits, done, out_lengths,
         steps) = carry
        active = ~done & (cur + k + 1 <= max_seq)
        t0 = _greedy_ids(last_logits, vocab)
        draft = _ngram_draft(tokens, cur, t0, ngram=ngram, draft_len=k)
        window = jnp.concatenate([t0[:, None], draft], axis=1)  # [b, k+1]

        logits, k_cache, v_cache = model_lib.forward_cached(
            cfg, params, window, k_cache, v_cache, cur, rope=rope)
        greedy = _greedy_ids(logits, vocab)  # [b, k+1]

        # draft[:, i] is accepted iff it equals the model's greedy token
        # after the prefix ending at draft[:, i-1] — cumulative agreement,
        # advanced PER SAMPLE (frozen samples commit nothing).
        agree = jnp.cumprod(
            (draft == greedy[:, :k]).astype(jnp.int32), axis=1)
        m = jnp.sum(agree, axis=1)                        # [b]
        n_commit = jnp.where(active, m + 1, 0)

        # Commit [t0, d1..dm] at each sample's own position (positions
        # beyond cur+m are scratch the next iteration overwrites and
        # out_lengths never covers); frozen buffers stay bit-identical.
        old = _row_slice(tokens, jnp.minimum(cur, max_seq - (k + 1)),
                         k + 1)
        towrite = jnp.where(active[:, None], window, old)
        tokens = _row_update(tokens, towrite,
                             jnp.minimum(cur, max_seq - (k + 1)))

        if use_eos_stop:
            committed_mask = jnp.arange(k + 1)[None, :] < n_commit[:, None]
            is_eos = (window == eos_id) & committed_mask
            hit = jnp.any(is_eos, axis=1)
            first = jnp.argmax(is_eos, axis=1)
            just_done = active & hit
            out_lengths = jnp.where(
                just_done, cur + first + 1,
                jnp.where(active, cur + n_commit, out_lengths))
            done = done | just_done
        else:
            out_lengths = jnp.where(active, cur + n_commit, out_lengths)

        # next iteration's last_logits: the row after each sample's last
        # committed token (its argmax is the next t0)
        nl = jnp.take_along_axis(logits, m[:, None, None], axis=1)[:, 0]
        last_logits = jnp.where(active[:, None], nl, last_logits)
        return (cur + n_commit, tokens, k_cache, v_cache, last_logits,
                done, out_lengths, steps + 1)

    carry = (cur, tokens, k_cache, v_cache, last_logits, done,
             out_lengths, steps)
    carry = jax.lax.while_loop(spec_cond, spec_body, carry)
    (cur, tokens, k_cache, v_cache, last_logits, done, out_lengths,
     steps) = carry

    # Tail: fewer than draft_len+1 slots left for a sample — plain
    # greedy, one token per forward, still per-sample.
    def tail_cond(carry):
        cur, *_, done, _, _ = carry
        return jnp.any(~done & (cur < max_seq))

    def tail_body(carry):
        (cur, tokens, k_cache, v_cache, last_logits, done, out_lengths,
         steps) = carry
        active = ~done & (cur < max_seq)
        t0 = _greedy_ids(last_logits, vocab)
        safe = jnp.minimum(cur, max_seq - 1)
        old = _row_slice(tokens, safe, 1)
        tokens = _row_update(
            tokens, jnp.where(active[:, None], t0[:, None], old), safe)
        just_done = (active & (t0 == eos_id)) if use_eos_stop else (
            jnp.zeros_like(done))
        out_lengths = jnp.where(active, cur + 1, out_lengths)
        done = done | just_done
        logits, k_cache, v_cache = model_lib.forward_cached(
            cfg, params, t0[:, None], k_cache, v_cache, cur, rope=rope)
        last_logits = jnp.where(active[:, None], logits[:, 0],
                                last_logits)
        return (jnp.where(active, cur + 1, cur), tokens, k_cache,
                v_cache, last_logits, done, out_lengths, steps + 1)

    carry = jax.lax.while_loop(tail_cond, tail_body, carry)
    _, tokens, _, _, _, _, out_lengths, steps = carry
    return tokens, out_lengths, steps


def generate_tokens_pld(
    cfg: ModelConfig,
    params,
    tokens: jax.Array,   # [b, max_seq] right-padded prompts + room
    lengths: jax.Array,  # [b] prompt lengths (may be ragged)
    *,
    eos_id: int = 2,
    draft_len: int = DEFAULT_DRAFT_LEN,
    ngram: int = DEFAULT_NGRAM,
    use_eos_stop: bool = True,
) -> SpeculativeOutput:
    """Greedy generation with prompt-lookup speculative decoding.

    Prompts may be ragged: the KV cache tracks per-sample fill levels and
    acceptance advances per sample (see module docstring)."""
    lengths = jnp.asarray(lengths, jnp.int32)
    lo = int(jnp.min(lengths))
    if lo < ngram:
        raise ValueError(f"prompt length {lo} shorter than ngram {ngram}")
    if lo >= tokens.shape[1]:
        raise ValueError("no room to generate")
    toks, out_lengths, steps = _pld_impl(
        cfg, params, jnp.asarray(tokens, jnp.int32), lengths,
        max_prompt_len=int(jnp.max(lengths)),
        eos_id=eos_id, draft_len=draft_len, ngram=ngram,
        use_eos_stop=use_eos_stop)
    return SpeculativeOutput(tokens=toks, lengths=out_lengths, steps=steps)
