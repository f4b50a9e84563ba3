"""Continuous-batching serving engine: Orca-style iteration-level scheduling
over a slot-managed KV cache.

The REST server used to admit exactly one generation at a time behind a
global lock, so decode throughput never aggregated across concurrent
users.  This engine replaces that: a single scheduler thread owns a
long-lived batch KV cache (``slots.py``) and interleaves, at iteration
granularity,

1. **admission** — while a KV slot is free, the bounded queue
   (``queue.py``) has work, and the block pool can reserve the request's
   worst-case block count, prefill the next request's prompt into its own
   batch-1 cache (one jitted forward, prompt length padded up to
   ``prefill_bucket`` so compilations stay bounded) and publish it into
   freshly allocated pool blocks;
2. **one batched decode step** — a single jitted forward over ALL active
   slots with the per-sample fill vector ``forward_cached`` already
   supports (the ragged machinery built for prompt-lookup speculative
   decoding), plus per-slot sampling: greedy mask, temperature, top-k
   (dynamic rank mask), top-p, and a per-request RNG stream folded on the
   request's own generated-token counter — so a request samples the same
   trajectory regardless of which slot it lands in or who shares the
   batch;
3. **retirement** — requests leave the moment they hit EOS or their token
   budget (or are cancelled); the slot returns to the free list with no
   device work — every table entry just drops one ref count.

KV memory is **paged** (``slots.py`` / ``block_pool.py``): a slot owns a
block table over a fixed device-resident pool rather than a contiguous
``max_seq_len`` cache row, so HBM scales with actual fill and the pool —
not the slot count — bounds concurrency for mixed-length traffic.
Admission reserves a request's worst-case block count up front (evicting
unpinned prefix-cache blocks if the pool is tight, else parking the
request until a retirement frees blocks), so the lazy per-step block
allocation during decode can never fail.  Free slots still ride through
the decode step (fixed shapes keep ONE compiled executable); their
writes land in the pool's trash block, whose contents are never
unmasked.

The steady-state decode loop is **pipelined** (``EngineConfig.
pipeline_decode``, default on): step N's sampled tokens stay on the
device and feed step N+1's ``pending`` input directly — the host fetch
of step N's tokens (an async copy started at dispatch) overlaps step
N+1's execution, so the device never sits idle waiting for Python
bookkeeping.  The price is that retirement decisions lag one step: by
the time the host sees that a request hit EOS or its budget at step N,
step N+1 has already sampled one *speculative* token for that slot.
That token is masked — never committed to ``FinishedRequest.tokens``,
never streamed — so committed trajectories stay bitwise identical to
the one-shot path (the decode step is a pure function of per-slot
fill/counter/pending state the host tracks without syncing).  Join/
leave decisions still happen every iteration; they just act on the
previous step's tokens.

Admission can run **chunked** (``EngineConfig.prefill_chunk``): a long
prompt prefills at most ``prefill_chunk`` tokens per scheduler
iteration, interleaved between decode steps, so admission no longer
freezes every active stream's inter-token latency for the whole prompt
(the Sarathi-Serve argument).  The batched decode step reads each
slot's KV out of the pool through its block table where the paged
attention kernel runs (a TPU) and over a gathered dense view elsewhere —
models/model.py:forward_cached_paged decides from what it observes.

Admission also consults the **automatic prefix cache**
(``EngineConfig.prefix_cache_blocks``, prefix_cache.py): a request whose
prompt shares a block-aligned prefix with an earlier request's takes the
cached POOL BLOCKS into its own table by ref-count bump — zero K/V
copies — and prefills only the uncached suffix; retiring requests donate
their prefix blocks back the same way.  Because the shared blocks hold
exactly what a cold prefill would write, the cache is purely a prefill
shortcut — TTFT drops, trajectories don't move.

Greedy requests can opt the engine into **speculative decoding**
(``EngineConfig.spec_draft_len``): at schedule time the host proposes,
per slot, up to ``spec_draft_len`` draft tokens by prompt lookup — the
most recent earlier occurrence of the context's trailing n-gram, the
PLD idea of generation/speculative.py applied per slot over the paged
cache — and ONE batched verify forward scores every slot's
``[pending, draft...]`` window at its own fill positions
(models/model.py:forward_cached_paged_verify).  The longest draft
prefix matching greedy argmax commits in a single iteration; position 0
samples exactly like a plain step, so acceptance can only reproduce what
sequential decode would have emitted, bitwise, and non-greedy requests
ride the verify batch with an empty draft, their trajectories untouched.
Rejected drafts roll back by fill arithmetic alone: their K/V rows sit
past the slot's fill level, masked out of attention, and later steps
overwrite them in place — no block frees, no copies, COW and prefix
sharing untouched.  A per-slot acceptance EWMA adapts each slot's draft
budget down to zero on incompressible text (the batch then stays on the
untouched pipelined plain path, re-probing occasionally), so
speculation composes with the pipeline instead of fighting it: verify
steps are the one place the scheduler deliberately syncs, because the
next dispatch's fill depends on how many drafts landed.

Greedy requests reproduce the one-shot ``generation.generate_tokens``
trajectory token-for-token (tested bitwise on CPU fp32, the same
equivalence bar the PLD path meets), pipelined or not, speculative or
not.

**Multi-tenant LoRA** (``serving/adapters/``): requests may name an
``adapter_id`` and the engine serves them against one shared base model
plus a device-resident stacked LoRA arena.  Admission pins the adapter
in the arena (parking at the queue head when every arena slot is pinned,
the same FIFO backpressure as KV-pool pressure); every jitted step takes
the arena plus a per-row arena-slot vector and builds the one-hot rank
mask INSIDE the jit, so different adapters coexist per-row in one decode
batch with ONE compiled executable however many adapters rotate through.
Base requests ride with slot -1 (an exactly-zero delta).  Prefix-cache
blocks never cross tenants: adapter requests skip both match and offer,
since their K/V rows differ from the base model's.  ``swap_params``
replaces the base weights at an iteration boundary for zero-downtime
deploys (the router rolls it replica by replica).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import sanitizers
from ..config import KINDS, ModelConfig
from ..generation.sampling import NEG_INF
from ..kernels.flash_attention import tile_plan
from ..kernels.flash_decode import pool_walk, walk_counts
from ..kernels.mamba_step import heads_per_step
from ..models.diff_attention import flash_blocks
from ..models import model as model_lib
from ..obs import compile as obs_compile
from ..obs import profile as obs_profile
from ..obs.logging import EVENT_LOG
from ..obs.trace import GcWatch, TraceRecorder, device_annotation
from ..ops.lora import arena_sr, slot_mask
from ..resilience.chaos import chaos
from .adapters.registry import AdapterRegistry
from .block_pool import BlockPool, HostKVTier
from .metrics import ServingMetrics
from .prefix_cache import PrefixCache
from .queue import QueueFull, RequestQueue  # noqa: F401  (re-exported)
from .slots import SlotAllocator

#: The scheduler thread's iteration in phases: span name -> ``"own"``
#: (the host's own work) or ``"blocked"`` (the host waited for the
#: device).  The spans lie on the scheduler's track (``tid`` 0) inside an
#: ``engine_step`` or an ``admit`` and do not overlap; ``gc`` lies on a
#: track of its own (``obs/trace.py:GcWatch``).  What of an iteration no
#: phase covers is the loop's own overhead.  The one table of the
#: vocabulary: the benchmark's reader (``benchmarks/readers/
#: sched_phases.py``) imports it, ``docs/observability.md`` says which
#: path records which, a test holds it to the ``trace.add`` calls below.
SCHED_PHASES = {
    "step_inputs": "own",        # a decode step's host arrays
    "dispatch": "own",           # its jitted call(s), async copies started
    "fetch": "blocked",          # np.asarray of a dispatched step's tokens
    "commit": "own",             # tokens committed, callbacks, retirements
    "admit_setup": "own",        # slot, adapter, prefix match, reservation
    "prefill_dispatch": "own",   # padding, the prompt's copy, the call
    "slot_insert": "own",        # the scatter into the pool, state install
    "prefill_wait": "blocked",   # first token's dispatch and its fetch
    "admit_commit": "own",       # slot state, event log, first commit
    "gc": "own",                 # a Python collection of 1 ms or more
}


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Tuning knobs (documented in docs/serving.md)."""
    max_batch_size: int = 8       # KV slots = max concurrent requests
    max_seq_len: int = 1024       # per-slot cache width (prompt + generation)
    max_queue_size: int = 32      # bounded admission queue
    prefill_bucket: int = 1       # pad prompt lengths up to a multiple of
    #                               this before the prefill forward: larger
    #                               buckets bound the number of compiled
    #                               prefill shapes; 1 = exact lengths
    retry_after_s: float = 1.0    # backpressure hint surfaced on QueueFull
    idle_wait_s: float = 0.02     # max scheduler wait when idle / paused
    #                               (wakeups are condition-variable driven;
    #                               this only bounds the cancel/deadline
    #                               sweep latency while nothing else stirs)
    pipeline_decode: bool = True  # one-step decode pipeline: feed step N's
    #                               device-resident tokens straight into
    #                               step N+1 and overlap the host fetch with
    #                               device execution; retirement lags one
    #                               step with the speculative token masked.
    #                               False = classic dispatch->sync->commit.
    prefill_chunk: Optional[int] = None  # run admission prefill at most
    #                               this many prompt tokens per scheduler
    #                               iteration, interleaved between decode
    #                               steps (Sarathi-style); supersedes
    #                               prefill_bucket when set.  None = whole-
    #                               prompt prefill in one forward.
    default_deadline_s: Optional[float] = None  # per-request wall-clock
    #                               budget (submit -> finish) applied when a
    #                               request doesn't set its own; None = no
    #                               deadline.  Expired requests finish with
    #                               reason "timeout" instead of occupying a
    #                               slot / queue position forever.
    prefix_cache_blocks: int = 256  # automatic prefix caching
    #                               (serving/prefix_cache.py): HBM budget in
    #                               blocks of prefill_chunk (chunked mode)
    #                               or prefill_bucket tokens each.  Shared
    #                               block-aligned prompt prefixes skip
    #                               re-prefill on admission; retiring
    #                               requests donate theirs back.  Bitwise
    #                               neutral to sampled trajectories.
    #                               0 disables the cache.
    trace: bool = True            # per-request span tracing (obs/trace.py):
    #                               queued / prefix_match / prefill_chunk[i]
    #                               / decode / retire spans per request plus
    #                               per-iteration engine_step spans, kept in
    #                               a bounded ring and exported as Chrome
    #                               trace JSON (GET /trace).  Off = every
    #                               record path returns before locking.
    trace_capacity: int = 8192    # span ring size (oldest spans drop)
    kv_block_size: int = 0        # paged KV cache block size in tokens
    #                               (block_pool.py).  0 = follow the
    #                               admission granularity (prefill_chunk,
    #                               else prefill_bucket), capped at
    #                               max_seq_len, so prefix-cache blocks ==
    #                               pool blocks and sharing stays zero-copy.
    kv_pool_blocks: int = 0       # total pool blocks incl. the reserved
    #                               trash block.  0 = auto-size so every
    #                               slot can grow to max_seq_len plus the
    #                               prefix-cache budget (capacity-neutral
    #                               vs the old fixed-stride cache); set it
    #                               lower to trade worst-case headroom for
    #                               more concurrent mixed-length requests
    #                               at the same HBM.
    spec_draft_len: int = 0       # speculative decoding: max draft tokens
    #                               per slot per verify step, proposed by
    #                               a host-side n-gram matcher over the
    #                               request's own context (prompt lookup)
    #                               and checked in ONE batched multi-token
    #                               verify forward.  Greedy requests only;
    #                               accepted tokens are bitwise the ones
    #                               plain decode would have produced, and
    #                               a per-slot acceptance EWMA backs the
    #                               draft budget off to zero on text that
    #                               doesn't repeat.  0 = off (default: the
    #                               verify executable costs W model
    #                               passes' FLOPs per step, which only
    #                               pays off on repetitive traffic).
    spec_ngram: int = 3           # trailing n-gram length the drafter
    #                               matches on (longer = fewer, better
    #                               drafts)
    spec_reprobe_interval: int = 16  # how many zero-draft iterations a
    #                               slot whose acceptance EWMA collapsed
    #                               the draft budget to zero waits before
    #                               probing again with a single token —
    #                               so a repetitive (or draftable)
    #                               stretch later in the generation can
    #                               re-engage speculation
    sanitize: bool = False        # runtime sanitizers (analysis/
    #                               sanitizers.py): per-iteration block-
    #                               pool ledger checks, a leak report at
    #                               shutdown/drain, and lock-order
    #                               tracking across the engine's locks.
    #                               Also enabled by MEGATRON_SANITIZE=1.
    #                               Costs one host pass over the slot
    #                               tables per iteration — tests/debug
    #                               only, default off.
    adapter_cache_slots: int = 0  # multi-tenant LoRA (serving/adapters/):
    #                               device-resident arena slots the
    #                               engine's AdapterRegistry may hold at
    #                               once.  Any number of adapters can be
    #                               registered host-side; residency is
    #                               LRU with ref pinning (an adapter is
    #                               pinned while any KV slot serves it,
    #                               unpinned residents evict on demand).
    #                               When every arena slot is pinned,
    #                               admission parks the request at the
    #                               queue head — the same FIFO
    #                               backpressure as KV-pool pressure.
    #                               0 = no adapter serving (the registry,
    #                               if any, sizes itself).  Must match
    #                               the registry's n_slots when both are
    #                               set.
    host_kv_blocks: int = 0       # tiered KV (block_pool.py:HostKVTier):
    #                               host-RAM KV blocks backing the device
    #                               pool.  Enables prefix-cache spill
    #                               (evicted trie leaves demote to host
    #                               and re-promote on hit), priority
    #                               preemption (low-priority decodes swap
    #                               out bitwise and resume later), and
    #                               oversubscribed admission (admit
    #                               beyond worst-case HBM reservations
    #                               against host capacity, bounded by
    #                               measured swap bandwidth, instead of
    #                               parking at the queue head).  0 = off.
    #                               Size it so host_kv_blocks * block
    #                               bytes fits comfortably in RAM; see
    #                               docs/serving.md "Tiered KV".
    role: str = "mixed"           # disaggregated prefill/decode
    #                               (docs/serving.md): "prefill" runs a
    #                               request's prefill + first token, then
    #                               ships its KV blocks to a decode-role
    #                               replica via the router's ship handler
    #                               (falling back to decoding locally when
    #                               no handler / no destination);
    #                               "decode" engines receive shipments and
    #                               run decode; "mixed" (default) does
    #                               both and never initiates a ship.


@dataclasses.dataclass
class FinishedRequest:
    tokens: List[int]             # prompt + generated (EOS included)
    prompt_len: int
    finish_reason: str            # "eos" | "length" | "cancelled" |
    #                               "timeout" | "error" | "quarantined"
    #                               (router: crash-correlated across >= 2
    #                               replica incarnations, not resubmitted)
    logprobs: Optional[List[float]] = None  # [len-1] incl. prompt positions


@dataclasses.dataclass
class KVShipment:
    """A request's KV blocks + scheduling state in flight between engines.

    Produced by ``ServingEngine.extract_request`` on the source scheduler
    thread, consumed by ``install_shipment`` on the destination's.  The
    dense leaves are table-ordered (``BlockPool.export_blocks``) and stay
    in the pool's own dtypes — int8 ``{"q", "scale"}`` ships quantized.
    ``meta["req"]`` is the live ``_Request`` itself (token lists, RNG
    seed + fold counter, stream callback, done event), so the client's
    stream continues bitwise across the move: the per-request RNG folds
    on the request's own counter, never on slot or batch identity.
    The source pool's ``shipments`` ledger holds one ref per block until
    the owner of the shipment calls ``end_ship`` (router.py)."""
    ship_id: str
    request_id: str
    k_dense: object
    v_dense: object
    bids: List[int]               # source block ids, table order
    n_live: int                   # = len(bids)
    nbytes: int                   # dense payload size (ship_bytes metric)
    meta: dict                    # fill/count/pending/spec state + req


# process-global so ship ids stay unique across every engine in a cluster
_SHIP_IDS = iter(range(1, 1 << 62))


class _Request:
    """Internal request record; the public face is ``RequestHandle``."""

    _ids = iter(range(1, 1 << 62))

    def __init__(self, prompt: Sequence[int], max_new_tokens: int, *,
                 eos_id: int = 2, temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 0.0, seed: Optional[int] = None,
                 use_eos_stop: bool = True, return_logprobs: bool = False,
                 on_token: Optional[Callable[[int], None]] = None,
                 deadline_s: Optional[float] = None,
                 adapter_id: Optional[str] = None,
                 spec_force: bool = False,
                 priority: int = 0):
        self.id = next(self._ids)
        self.rid = f"req-{self.id}"  # correlation id: every log line and
        #                              trace span of this request carries it
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = int(eos_id)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.greedy = top_k == 0 and top_p == 0.0
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        self.seed = int(seed) & 0xFFFFFFFF
        self.use_eos_stop = bool(use_eos_stop)
        self.return_logprobs = bool(return_logprobs)
        self.on_token = on_token
        # multi-tenant LoRA: which registered adapter decorates the base
        # model for this request; None = the base model alone
        self.adapter_id = adapter_id
        # warm-probe knob: propose a draft even without an n-gram match
        # (verify is lossless — a wrong draft is simply rejected), so a
        # rebuilt engine can compile the verify executable outside the
        # serving window instead of on the first organically repetitive
        # request mid-serve
        self.spec_force = bool(spec_force)
        # QoS class (tiered KV): higher wins at the queue, and a pool-
        # exhausted admission may suspend a STRICTLY lower-priority
        # decode to the host tier instead of parking.  Default 0.
        self.priority = int(priority)

        self.generated: List[int] = []
        self.logprobs: List[float] = []
        self.cancel_flag = threading.Event()
        self.done_event = threading.Event()
        self.result: Optional[FinishedRequest] = None
        self.submit_time = time.perf_counter()
        self.first_token_time: Optional[float] = None
        # when its admission first parked on pool blocks (None: never)
        self.parked_at: Optional[float] = None
        # Absolute wall-clock deadline (perf_counter domain); None = never.
        self.deadline: Optional[float] = (
            None if deadline_s is None
            else self.submit_time + float(deadline_s))


class RequestHandle:
    """Client-side view of a submitted request."""

    def __init__(self, req: _Request, engine: "ServingEngine"):
        self._req = req
        self._engine = engine

    @property
    def request_id(self) -> int:
        return self._req.id

    @property
    def rid(self) -> str:
        """String correlation id shared by log lines and trace spans."""
        return self._req.rid

    def done(self) -> bool:
        return self._req.done_event.is_set()

    def cancel(self) -> None:
        """Ask the scheduler to drop the request at the next iteration
        boundary (or immediately if it is still queued)."""
        self._engine._cancel(self._req)

    def result(self, timeout: Optional[float] = None) -> FinishedRequest:
        if not self._req.done_event.wait(timeout):
            raise TimeoutError(
                f"request {self._req.id} not finished within {timeout}s")
        assert self._req.result is not None
        if self._req.result.finish_reason == "error":
            raise RuntimeError(
                "serving engine scheduler failed: "
                f"{self._engine._scheduler_error!r}")
        return self._req.result


# ---------------------------------------------------------------------------
# Jitted steps
# ---------------------------------------------------------------------------


def _sample_slots(logits, seeds, counters, greedy, temps, top_ks, top_ps,
                  vocab: int):
    """Per-slot mixed-mode sampling over ``[S, V]`` logits.

    Unlike ``sampling.sample_with_mode`` (static mode / static top_k for
    the whole batch), every slot here carries its own knobs as traced
    vectors, so one compiled decode step serves any mix of requests:
    - greedy slots take the padded-vocab-masked argmax (identical to the
      one-shot loop's greedy mode);
    - top-k is a dynamic rank mask (rank-of-logit >= k_i -> -inf), the
      vectorized equivalent of ``lax.top_k`` thresholding;
    - top-p reuses the nucleus filter's traced-threshold core with a
      per-slot p (p<=0 -> keep everything);
    - randomness is a per-REQUEST stream: key(seed_i) folded on the
      request's own generated-token counter, so a request's trajectory is
      independent of slot placement and batch composition.

    What only a sampling slot needs (``_draw_slots``: the ordering of the
    vocabulary, the nucleus, the keys, the draw) sits under a ``cond`` on
    the ``greedy`` vector the step is handed: the device reads the
    predicate, so it is still ONE executable for any mix, and a batch
    whose every slot is greedy pays for the argmax and the log-prob alone.
    """
    with jax.named_scope("sample"):
        pad = jnp.arange(logits.shape[-1]) >= vocab
        logits = jnp.where(pad[None, :], NEG_INF, logits)
        greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = jax.lax.cond(
            jnp.any(~greedy),
            lambda: jnp.where(
                greedy, greedy_tok,
                _draw_slots(logits, seeds, counters, temps, top_ks, top_ps)),
            lambda: greedy_tok)
        lp = jax.nn.log_softmax(logits, axis=-1)
        tok_lp = jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]
    return tok, tok_lp


def _draw_slots(logits, seeds, counters, temps, top_ks, top_ps):
    """One categorical draw a row of pad-masked ``[S, V]`` logits under
    each row's temperature, top-k and top-p.  ONE stable sort orders the
    vocabulary: it gives the descending values top-p accumulates over and
    the order whose ``k_i``-th entry closes top-k — a row keeps what is
    larger than its ``k_i``-th value and, of the ties at that value, the
    indices up to the ``k_i``-th entry's own, which is what a stable rank
    ``< k_i`` keeps."""
    S, V = logits.shape
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    idx = jax.lax.broadcasted_iota(jnp.int32, (S, V), 1)
    neg_desc, order = jax.lax.sort((-scaled, idx), dimension=1,
                                   is_stable=True, num_keys=1)
    desc = -neg_desc
    # dynamic per-slot top-k: position 0 = largest
    k = top_ks[:, None]
    kth = jnp.clip(k - 1, 0, V - 1)
    kth_val = jnp.take_along_axis(desc, kth, axis=-1)
    kth_idx = jnp.take_along_axis(order, kth, axis=-1)
    kmask = (k > 0) & ((scaled < kth_val)
                       | ((scaled == kth_val) & (idx > kth_idx)))
    scaled = jnp.where(kmask, NEG_INF, scaled)
    # per-slot top-p (inline nucleus filter with a [S, 1] threshold) over
    # the descending values of what top-k kept
    p_eff = jnp.where(top_ps > 0.0, top_ps, 1.0)[:, None]
    sorted_logits = jnp.where((k > 0) & (idx >= k), NEG_INF, desc)
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    remove_sorted = (cum - sorted_probs) > p_eff
    kept = jnp.where(remove_sorted, jnp.inf, sorted_logits)
    threshold = jnp.min(kept, axis=-1, keepdims=True)
    scaled = jnp.where(scaled < threshold, NEG_INF, scaled)

    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.key(s), c))(seeds,
                                                               counters)
    sampled = jax.vmap(
        lambda row, key: jax.random.categorical(key, row))(scaled, keys)
    return sampled.astype(jnp.int32)


def _lora_operand(arenas, slots, rank: int):
    """Arena + per-row arena-slot vector -> the ``(arenas, mask)`` pair
    the model layer consumes.  The one-hot rank mask is built INSIDE the
    jitted step from the tiny ``[S]`` int32 slot vector, so the host
    never materializes per-request factor tensors (tpulint R8) and the
    step stays one compiled executable as adapters churn — slot -1 rows
    (base-model requests, free slots) get an all-zero mask and therefore
    an exactly-zero delta."""
    if rank == 0 or arenas is None:
        return None
    n_slots = arena_sr(arenas) // rank
    return arenas, slot_mask(slots, n_slots, rank)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_seq_len", "want_logprobs",
                                    "lora_rank"))
def _prefill_impl(cfg: ModelConfig, params, tokens, length,
                  lora_arenas=None, lora_slots=None, *,
                  max_seq_len: int, want_logprobs: bool,
                  lora_rank: int = 0):
    """Prefill one request (batch 1, possibly bucket-padded prompt) into a
    fresh batch-1 cache.  Rows past ``length`` hold pad-token K/V, but the
    slot's fill level masks them and committed tokens overwrite them in
    order before the fill ever reaches them (the PLD ragged-prefill
    argument, generation/speculative.py)."""
    rope = model_lib.rope_tables(cfg)
    lora = _lora_operand(lora_arenas, lora_slots, lora_rank)
    k, v = model_lib.init_kv_cache(cfg, 1, max_seq_len)
    rows = None if want_logprobs else length - 1
    rec = None
    if cfg.layer_pattern:
        # a hybrid stack: the recurrent state this prompt ends with, at
        # its TRUE last position (the bucket's padded tail advances
        # nothing), comes back beside the K/V for ``slots.insert``
        valid = jnp.arange(tokens.shape[1])[None, :] < length[:, None]
        logits, k, v, rec = model_lib.forward_cached_hybrid(
            cfg, params, tokens, k, v, jnp.int32(0),
            model_lib.init_rec_state(cfg, 1), valid=valid,
            empty_cache=True, logit_rows=rows)
    else:
        logits, k, v = model_lib.forward_cached(
            cfg, params, tokens, k, v, jnp.int32(0), rope=rope,
            empty_cache=True, logit_rows=rows, lora=lora)
    if want_logprobs:
        lp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(
            lp[:, :-1], tokens[:, 1:, None], axis=-1)[..., 0]  # [1, L-1]
        last = jnp.take_along_axis(
            logits, (length - 1)[:, None, None], axis=1)[:, 0]
        return last, picked, k, v, rec
    return logits[:, 0], None, k, v, rec


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prompt_logprobs_impl(cfg: ModelConfig, params, tokens):
    """The log-probability of every token of a (bucket-padded) prompt
    given the tokens before it, ``[1, s - 1]``: every row through every
    layer, nothing kept, the head a block of rows at a time (a whole
    bucket's float32 logits are gigabytes at a 200k vocabulary).  The
    second pass of a stack whose prefill cuts its rows
    (``cfg.row_cut_layer``)."""
    x, _aux = model_lib.forward_hidden(cfg, params, tokens)
    s = tokens.shape[1]
    block = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if s % b == 0)
    targets = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])

    def picked(rows):
        xs, ts = rows
        lp = jax.nn.log_softmax(model_lib.unembed(cfg, params, xs), axis=-1)
        return jnp.take_along_axis(lp, ts[:, None], axis=-1)[:, 0]

    out = jax.lax.map(picked, (x[0].reshape(s // block, block, -1),
                               targets.reshape(s // block, block)))
    return out.reshape(1, s)[:, :-1]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _first_token_impl(cfg: ModelConfig, last_logits, seeds, counters,
                      greedy, temps, top_ks, top_ps):
    return _sample_slots(last_logits, seeds, counters, greedy, temps,
                         top_ks, top_ps, cfg.vocab_size)


def _decode_impl(cfg: ModelConfig, params, k_pool, v_pool, tables, pending,
                 fills, seeds, counters, greedy, temps, top_ks, top_ps,
                 lora_arenas=None, lora_slots=None, rec=None, live=None, *,
                 allow_paged: bool = True, lora_rank: int = 0):
    """One batched decode step over every slot: feed each slot's pending
    token at its own fill position, scatter its K/V row into the pool
    block its table names, sample the next token per slot.  Free slots
    ride along (fixed shapes = one compiled executable); their reads and
    writes target the trash block and are masked.  Only the integer
    ``tables``/``fills`` change between steps — the pool shape is static,
    so this stays ONE compiled executable.

    A hybrid stack also carries ``rec`` (``SlotAllocator.rec``) through
    the step as it carries the pools: the slots ``live`` marks advance
    their recurrent state by the fed token, the others keep theirs."""
    rope = model_lib.rope_tables(cfg)
    if cfg.layer_pattern:
        logits, k_pool, v_pool, rec = model_lib.forward_paged_hybrid(
            cfg, params, pending[:, None], k_pool, v_pool, tables, fills,
            rec, live)
    else:
        logits, k_pool, v_pool = model_lib.forward_cached_paged(
            cfg, params, pending[:, None], k_pool, v_pool, tables, fills,
            rope=rope, allow_paged=allow_paged,
            lora=_lora_operand(lora_arenas, lora_slots, lora_rank))
    tok, tok_lp = _sample_slots(logits[:, 0], seeds, counters, greedy,
                                temps, top_ks, top_ps, cfg.vocab_size)
    return tok, tok_lp, k_pool, v_pool, rec


_decode_donated = functools.partial(
    jax.jit, static_argnames=("cfg", "allow_paged", "lora_rank"),
    donate_argnums=(2, 3), donate_argnames=("rec",))(_decode_impl)
_decode_plain = functools.partial(
    jax.jit, static_argnames=("cfg", "allow_paged", "lora_rank"))(
        _decode_impl)


def _verify_impl(cfg: ModelConfig, params, k_pool, v_pool, tables, window,
                 fills, bids, offs, seeds, counters, greedy, temps, top_ks,
                 top_ps, lora_arenas=None, lora_slots=None, *,
                 lora_rank: int = 0):
    """One speculative verify step over every slot: feed each slot's
    ``[pending, draft...]`` window at its own fill positions and score
    ALL window positions in one forward
    (models/model.py:forward_cached_paged_verify).  Position 0 samples
    exactly like ``_decode_impl`` — same ``_sample_slots``, same RNG
    fold — so a slot riding with an empty draft (non-greedy request, no
    n-gram match) takes a bitwise-unchanged plain step.  Positions >= 1
    only ever commit under greedy acceptance, so their pad-masked argmax
    is the whole sampling story.  Returns ``([S, W] tokens, [S, W]
    logprobs, pools)``; the host ignores columns past each slot's
    accepted prefix."""
    rope = model_lib.rope_tables(cfg)
    logits, k_pool, v_pool = model_lib.forward_cached_paged_verify(
        cfg, params, window, k_pool, v_pool, tables, fills, bids, offs,
        rope=rope, lora=_lora_operand(lora_arenas, lora_slots, lora_rank))
    tok0, tok0_lp = _sample_slots(logits[:, 0], seeds, counters, greedy,
                                  temps, top_ks, top_ps, cfg.vocab_size)
    V = logits.shape[-1]
    pad = jnp.arange(V) >= cfg.vocab_size
    masked = jnp.where(pad[None, None, :], NEG_INF, logits)
    g_tok = jnp.argmax(masked, axis=-1).astype(jnp.int32)       # [S, W]
    lp = jax.nn.log_softmax(masked, axis=-1)
    g_lp = jnp.take_along_axis(lp, g_tok[..., None], axis=-1)[..., 0]
    g_tok = g_tok.at[:, 0].set(tok0)
    g_lp = g_lp.at[:, 0].set(tok0_lp)
    return g_tok, g_lp, k_pool, v_pool


_verify_donated = functools.partial(
    jax.jit, static_argnames=("cfg", "lora_rank"),
    donate_argnums=(2, 3))(_verify_impl)
_verify_plain = functools.partial(
    jax.jit, static_argnames=("cfg", "lora_rank"))(_verify_impl)


def _verify_tree_impl(cfg: ModelConfig, params, k_pool, v_pool, tables,
                      window, depths, anc, fills, bids, offs, seeds,
                      counters, greedy, temps, top_ks, top_ps,
                      lora_arenas=None, lora_slots=None, *,
                      lora_rank: int = 0):
    """Tree-verify twin of ``_verify_impl``: the window columns are the
    nodes of a per-slot candidate tree (``depths``/``anc``, see
    forward_cached_paged_verify) instead of a linear run, so one forward
    scores every root-to-leaf branch the resident draft model proposed.
    Node 0 is the root (the pending token at the slot's fill position)
    and samples exactly like a plain step — same ``_sample_slots``, same
    RNG fold — so rider slots with a root-only tree take a
    bitwise-unchanged step.  Deeper nodes only ever commit under greedy
    acceptance along a root path, so pad-masked argmax is their whole
    sampling story.  K/V rows land node-indexed at ``(bids, offs)``;
    the host compacts the accepted path afterwards."""
    rope = model_lib.rope_tables(cfg)
    logits, k_pool, v_pool = model_lib.forward_cached_paged_verify(
        cfg, params, window, k_pool, v_pool, tables, fills, bids, offs,
        rope=rope, tree=(depths, anc),
        lora=_lora_operand(lora_arenas, lora_slots, lora_rank))
    tok0, tok0_lp = _sample_slots(logits[:, 0], seeds, counters, greedy,
                                  temps, top_ks, top_ps, cfg.vocab_size)
    V = logits.shape[-1]
    pad = jnp.arange(V) >= cfg.vocab_size
    masked = jnp.where(pad[None, None, :], NEG_INF, logits)
    g_tok = jnp.argmax(masked, axis=-1).astype(jnp.int32)       # [S, W]
    lp = jax.nn.log_softmax(masked, axis=-1)
    g_lp = jnp.take_along_axis(lp, g_tok[..., None], axis=-1)[..., 0]
    g_tok = g_tok.at[:, 0].set(tok0)
    g_lp = g_lp.at[:, 0].set(tok0_lp)
    return g_tok, g_lp, k_pool, v_pool


_verify_tree_donated = functools.partial(
    jax.jit, static_argnames=("cfg", "lora_rank"),
    donate_argnums=(2, 3))(_verify_tree_impl)
_verify_tree_plain = functools.partial(
    jax.jit, static_argnames=("cfg", "lora_rank"))(_verify_tree_impl)


# number of candidate branches the resident draft model surfaces per
# window position: branch 0 extends the main chain, branch 1 is the
# depth-1 hedge leaf (the tree planner never fans wider, so a static 2
# keeps the draft-step executable's output shape fixed)
_DRAFT_TOPK = 2


def _draft_step_impl(cfg: ModelConfig, params, k_pool, v_pool, tables,
                     window, fills, bids, offs):
    """One resident-draft forward over the draft model's shadow pool:
    a chain verify of up to W tokens per slot at the slot's own draft
    positions, returning the top-``_DRAFT_TOPK`` candidate tokens per
    position instead of full logits (the tree planner only needs the
    ranked heads, and [S, W, 2] int32 keeps the host transfer tiny).
    Serves both draft phases with ONE executable: the absorb pass
    (committed tokens at real block destinations, advancing the draft
    fill) and chain expansions (speculative tokens routed to the trash
    block, draft fill untouched).  Draft numerics never touch committed
    trajectories — candidates only steer which tokens the TARGET
    verifies — so there is no bitwise bar here, just fixed shapes."""
    rope = model_lib.rope_tables(cfg)
    logits, k_pool, v_pool = model_lib.forward_cached_paged_verify(
        cfg, params, window, k_pool, v_pool, tables, fills, bids, offs,
        rope=rope)
    V = logits.shape[-1]
    pad = jnp.arange(V) >= cfg.vocab_size
    masked = jnp.where(pad[None, None, :], NEG_INF, logits)
    _, cand = jax.lax.top_k(masked, _DRAFT_TOPK)
    return cand.astype(jnp.int32), k_pool, v_pool


_draft_step_donated = functools.partial(
    jax.jit, static_argnames=("cfg",),
    donate_argnums=(2, 3))(_draft_step_impl)
_draft_step_plain = functools.partial(
    jax.jit, static_argnames=("cfg",))(_draft_step_impl)


@functools.partial(jax.jit, static_argnames=("cfg", "max_seq_len"))
def _draft_prefill_impl(cfg: ModelConfig, params, tokens, *,
                        max_seq_len: int):
    """Dense draft-model prefill of one request's context (batch 1,
    always padded to the full slot width so this stays ONE compiled
    shape per engine).  Rows past the real context hold pad-token K/V
    that the draft fill level masks and absorb steps overwrite in
    order — the same ragged-prefill argument as the target's bucketed
    prefill, minus the bucketing."""
    rope = model_lib.rope_tables(cfg)
    k, v = model_lib.init_kv_cache(cfg, 1, max_seq_len)
    _, k, v = model_lib.forward_cached(
        cfg, params, tokens, k, v, jnp.int32(0), rope=rope,
        empty_cache=True, last_logit_only=True)
    return k, v


def _draft_install_impl(k_pool, v_pool, k_small, v_small, bids):
    """Publish a dense draft prefill into the draft shadow pool at the
    slot's (target-governed) block ids; trash entries skip."""
    return (model_lib.cache_scatter_blocks(k_pool, k_small, bids),
            model_lib.cache_scatter_blocks(v_pool, v_small, bids))


_draft_install_donated = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_draft_install_impl)
_draft_install_plain = jax.jit(_draft_install_impl)


def _move_rows_impl(k_pool, v_pool, src_bids, src_offs, dst_bids,
                    dst_offs):
    """Compact a verify step's accepted tree paths: move the accepted
    node-indexed K/V rows down to their depth positions in both pools
    (models/model.py:cache_move_rows — functional gather-then-scatter,
    so overlapping moves behave simultaneously).  Fixed [S·W] operand
    arrays; no-op entries route trash -> trash."""
    return (model_lib.cache_move_rows(k_pool, src_bids, src_offs,
                                      dst_bids, dst_offs),
            model_lib.cache_move_rows(v_pool, src_bids, src_offs,
                                      dst_bids, dst_offs))


_move_rows_donated = functools.partial(
    jax.jit, donate_argnums=(0, 1))(_move_rows_impl)
_move_rows_plain = jax.jit(_move_rows_impl)


# speculative decoding policy: weight of the newest per-slot acceptance
# observation in the EWMA that scales the draft budget (the re-probe
# interval for collapsed slots is EngineConfig.spec_reprobe_interval)
_SPEC_EWMA_ALPHA = 0.3


def _ngram_draft_host(ctx: Sequence[int], ngram: int,
                      draft_len: int) -> List[int]:
    """Host-side prompt-lookup draft — the numpy mirror of the jitted
    ``generation/speculative.py:_ngram_draft``: find the most recent
    *earlier* occurrence of the context's trailing ``ngram`` tokens and
    propose the tokens that followed it.  Draft quality only moves
    throughput — any draft verifies exactly — so unlike the fixed-arity
    device version this returns a variable-length (possibly empty) list
    instead of clip-padding a miss."""
    n = len(ctx)
    if draft_len < 1 or n < ngram + 1:
        return []
    a = np.asarray(ctx, np.int64)
    tail = a[-ngram:]
    # windows over a[:-1] so the trailing n-gram can't match itself
    wins = np.lib.stride_tricks.sliding_window_view(a[:-1], ngram)
    hits = np.flatnonzero((wins == tail).all(axis=1))
    if hits.size == 0:
        return []
    j = int(hits[-1])
    return [int(t) for t in a[j + ngram:j + ngram + draft_len]]


@jax.jit
def _gather_lease_impl(k_pool, v_pool, table):
    """Materialize a prefix lease's shared blocks as a batch-1 dense
    admission cache (leaves ``[L, 1, kv, width(, d)]``) in one fixed-arity
    gather — the suffix prefill attends the shared rows through this view;
    rows past the match are trash garbage no causal position ever sees."""
    return (model_lib.cache_gather_blocks(k_pool, table),
            model_lib.cache_gather_blocks(v_pool, table))


@jax.jit
def _merge_pending(tok, mask, vals):
    """Override the device-resident pending-token vector (last step's
    sampled tokens, still on device in pipelined mode) with host-known
    values for freshly (re)admitted slots."""
    return jnp.where(mask, vals, tok)


def _prefill_chunk_impl(cfg: ModelConfig, params, tokens, off, logit_row,
                        k_small, v_small, lora_arenas=None,
                        lora_slots=None, *, max_seq_len: int, first: bool,
                        last: bool, lora_rank: int = 0):
    """One bounded chunk of a chunked prefill (batch 1, fixed chunk width).

    ``off`` is the chunk's start position; the batch-1 cache is created on
    the first chunk and threaded through subsequent calls.  Only the chunk
    containing the prompt's final real token (``last``) needs its logits
    (at ``logit_row``, an in-chunk row index); earlier chunks compute one
    ignored logit row so each (first, last) arm stays a single compiled
    shape regardless of prompt length."""
    rope = model_lib.rope_tables(cfg)
    if first:
        k_small, v_small = model_lib.init_kv_cache(cfg, 1, max_seq_len)
    logits, k_small, v_small = model_lib.forward_cached(
        cfg, params, tokens, k_small, v_small, off, rope=rope,
        empty_cache=first,
        lora=_lora_operand(lora_arenas, lora_slots, lora_rank),
        **(dict(logit_rows=logit_row) if last
           else dict(last_logit_only=True)))
    return logits[:, 0], k_small, v_small


_prefill_chunk_donated = functools.partial(
    jax.jit, static_argnames=("cfg", "max_seq_len", "first", "last",
                              "lora_rank"),
    donate_argnums=(5, 6))(_prefill_chunk_impl)
_prefill_chunk_plain = functools.partial(
    jax.jit, static_argnames=("cfg", "max_seq_len", "first", "last",
                              "lora_rank"))(
        _prefill_chunk_impl)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class _SlotState:
    """Host-side per-slot bookkeeping (device state lives in SlotAllocator).

    ``fill`` and ``count`` advance at DISPATCH time, not commit time: the
    decode step is a pure function of (pending, fill, counter), so the
    host can keep dispatching pipelined steps without waiting to see the
    sampled tokens.  ``pending`` is the host's copy of the slot's last
    sampled token; in pipelined steady state the authoritative value rides
    on device in ``_Inflight.tok`` and ``fresh`` marks the slots (new
    admissions, post-pause survivors) whose host value must override it.
    """

    def __init__(self, req: _Request, fill: int, pending: int):
        self.req = req
        self.fill = fill          # cache rows written once every dispatched
        #                           step lands (prompt + dispatched decodes)
        self.count = 1            # tokens sampled so far incl. in-flight =
        #                           RNG fold counter of the NEXT sample
        self.pending = pending    # host-known last sampled token
        self.fresh = True         # pending must override the device vector
        self.lease = None         # PrefixLease pinning this request's
        #                           cached prefix blocks until retirement
        self.spec_ewma = 1.0      # EWMA of this slot's draft acceptance
        #                           fraction, scaling the next verify
        #                           step's draft budget (1.0 at admission
        #                           = optimistic engagement)
        self.spec_stall = 0       # consecutive iterations this slot
        #                           carried no draft — drives the
        #                           periodic re-probe once the budget
        #                           collapses to zero
        self.adapter_slot = -1    # LoRA arena slot serving this request
        #                           (-1 = base model; the per-row mask
        #                           the jitted steps build from it zeroes
        #                           the delta exactly).  The registry pin
        #                           under this slot is held until
        #                           retirement / extraction.
        self.draft_fill = 0       # rows of this slot's context absorbed
        #                           into the resident draft model's
        #                           shadow KV pool (<= fill + 1; 0 when
        #                           no draft model is resident)


class _Suspended:
    """A decode preempted to the host tier (tiered KV).

    Carries exactly the state ``install_shipment`` carries for a
    migration — the live ``_Request`` plus fill / RNG-fold count /
    pending token / speculation EWMA — so a resume rebuilds the slot
    bitwise: the per-request RNG folds on (seed, count), never on slot
    or batch identity, and the KV rows round-trip the host arena
    verbatim (int8 ``{q, scale}`` included)."""

    __slots__ = ("req", "hids", "n_live", "meta", "t_suspend")

    def __init__(self, req, hids, n_live, meta, t_suspend):
        self.req = req
        self.hids = hids          # host-tier block ids, table order
        self.n_live = n_live
        self.meta = meta          # fill/count/pending/spec state
        self.t_suspend = t_suspend


class _Inflight:
    """A dispatched-but-unprocessed decode step (pipelined mode).

    ``slots`` snapshots slot -> _SlotState at dispatch; a state object is
    unique per admission, so an identity check at processing time masks
    every speculative token sampled for a slot that retired (EOS, budget,
    cancel, deadline) while the step was in flight.

    On a pp>1 mesh the step is microbatch-interleaved
    (``ServingEngine._decode_groups``): ``tok``/``tok_lp`` are then LISTS
    of per-group device arrays over contiguous slot ranges
    ``[g*gs, (g+1)*gs)`` instead of one [S] array — the groups' dispatches
    chain through the KV pool, so while group g's tokens stream back the
    later groups keep the other pipeline stages busy (bubble fill)."""

    __slots__ = ("tok", "tok_lp", "slots", "t_dispatch", "sampling",
                 "positions", "walk", "iter")

    def __init__(self, tok, tok_lp, slots, t_dispatch, sampling,
                 positions=0, iter=0, walk=None):
        self.positions = positions  # cached positions its slots held
        self.walk = walk or {}    # a call of its paged walk, in grid steps
        self.iter = iter          # the scheduler iteration that dispatched it
        self.tok = tok            # [S] device array (or per-group list)
        self.tok_lp = tok_lp      # [S] logprobs, same layout as ``tok``
        self.slots = slots
        self.t_dispatch = t_dispatch
        self.sampling = sampling  # a slot of the step was not greedy


class _PrefillState:
    """A chunked prefill in progress: the request holds a KV slot but is
    not yet decoding; its batch-1 cache grows one chunk per scheduler
    iteration."""

    def __init__(self, req: _Request, slot: int, padded: int):
        self.req = req
        self.slot = slot
        self.padded = padded      # total prompt rows to prefill (chunk-
        #                           padded; the tail rows hold pad-token
        #                           K/V masked by the slot's fill level)
        self.done = 0             # prompt rows prefilled so far (a prefix
        #                           hit pre-advances this past the cached
        #                           blocks already spliced into k_small)
        self.k_small = None       # batch-1 cache, created on chunk 0
        self.v_small = None
        self.lease = None         # PrefixLease behind a pre-advanced done
        self.adapter_slot = -1    # pinned LoRA arena slot (-1 = base)


def _refuse_for_hybrid(cfg: ModelConfig, config: EngineConfig, mesh,
                       draft_cfg, adapters) -> None:
    """What the engine cannot do for a hybrid stack (``cfg.layer_pattern``:
    a recurrent mixer's state a slot beside its K/V blocks, whatever the
    mixer: a delta rule's or a state-space layer's), refused at
    construction so that none of it is served wrongly.  Each of these
    paths moves, shares or rolls back K/V blocks alone."""
    refused = [
        (config.prefix_cache_blocks,
         "prefix_cache_blocks > 0: a prefix hit would need a snapshot of "
         "the recurrent state at the shared boundary, and a hit on K/V "
         "alone would start the recurrent mixers from zero mid-prompt"),
        (config.spec_draft_len or draft_cfg is not None,
         "speculation (spec_draft_len, a draft model): a rejected draft "
         "rolls back by fill arithmetic, and the recurrent state has "
         "already been advanced over it"),
        (cfg.kv_cache_quant != "none",
         "kv_cache_quant=int8: the hybrid decode route reads a bf16 pool"),
        (mesh is not None and mesh.size > 1,
         "a tp/pp serving mesh: serving_param_specs has no layout for "
         "the period-stacked layers, the experts or the recurrent state"),
        (adapters is not None or config.adapter_cache_slots,
         "adapters: no LoRA epilogue on a recurrent mixer's or the "
         "experts' matmuls"),
        (config.prefill_chunk,
         "prefill_chunk: the recurrent state is not carried from chunk "
         "to chunk"),
        (config.host_kv_blocks,
         "host_kv_blocks: preemption to the host tier moves K/V blocks "
         "and would leave the recurrent state behind"),
        (config.role != "mixed",
         f"role={config.role!r}: a shipment between replicas carries K/V "
         "blocks and no recurrent state"),
    ]
    for hit, why in refused:
        if hit:
            raise ValueError(
                f"a hybrid stack (layer_pattern {cfg.layer_pattern}) is "
                f"not served with {why}")


def _refuse_for_latent(cfg: ModelConfig, config: EngineConfig, mesh,
                       draft_cfg, adapters) -> None:
    """What the engine cannot do for a latent-attention stack
    (``cfg.kv_lora_rank``: a pool of one kind of row, ``c | k_pe``, which
    a prompt's prefill writes from the expanded form and a decode step
    reads in the absorbed one), refused at construction so that none of
    it is served wrongly."""
    refused = [
        (cfg.kv_cache_quant != "none",
         "kv_cache_quant=int8: the pool holds latent rows in the "
         "weights' precision; there is no 8-bit row"),
        (config.spec_draft_len or draft_cfg is not None,
         "speculation (spec_draft_len, a draft model): the verify paths "
         "walk per-head K/V rows of a dense view, which this pool has "
         "not"),
        (config.prefill_chunk,
         "prefill_chunk: a chunk after the first would have to expand "
         "the cached latent rows of the chunks before it; the prefill "
         "runs the expanded form on an empty cache only"),
        (config.prefix_cache_blocks,
         "prefix_cache_blocks > 0: a prefix hit starts a prefill whose "
         "first rows lie in the pool, the same expansion of cached "
         "latent rows that chunked prefill would need"),
        (config.host_kv_blocks,
         "host_kv_blocks: the host tier's arenas and swaps are laid out "
         "for a pool of K and V leaves"),
        (config.role != "mixed",
         f"role={config.role!r}: a shipment between replicas carries "
         "K/V blocks"),
        (adapters is not None or config.adapter_cache_slots,
         "adapters: no LoRA epilogue on the latent projections or the "
         "experts' matmuls"),
        (mesh is not None and mesh.size > 1,
         "a tp/pp serving mesh: a latent row has no head axis to split "
         "over tp, and serving_param_specs has no layout for the latent "
         "projections, the leading dense layer or the experts"),
    ]
    for hit, why in refused:
        if hit:
            raise ValueError(
                f"a latent-attention stack (kv_lora_rank "
                f"{cfg.kv_lora_rank}) is not served with {why}")


class ServingEngine:
    """Continuous-batching engine over a fixed set of KV slots.

    ``submit`` / ``submit_many`` are thread-safe and non-blocking (they
    raise ``QueueFull`` under backpressure); all device work happens on
    the single scheduler thread.
    """

    def __init__(self, cfg: ModelConfig, params,
                 engine_config: Optional[EngineConfig] = None,
                 metrics: Optional[ServingMetrics] = None,
                 mesh=None, draft_cfg: Optional[ModelConfig] = None,
                 draft_params=None,
                 adapters: Optional[AdapterRegistry] = None):
        self.cfg = cfg
        self.params = params
        # Resident draft model (speculative decoding beyond prompt
        # lookup): a small model sharing the target's vocabulary whose
        # on-device forwards propose candidate TREES for the tree-verify
        # kernel.  It keeps a shadow paged KV pool aligned to the
        # target's block tables (same bids, its own head geometry) so
        # drafting needs no second ledger.
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        if draft_cfg is not None:
            assert draft_params is not None, \
                "draft_cfg requires draft_params"
            assert draft_cfg.vocab_size == cfg.vocab_size, (
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size}: draft tokens must be verifiable")
        self._draft_kv = None     # (k_pool, v_pool) shadow pool, start()
        # Serving submesh (serving/cluster/): params arrive pre-sharded
        # (models/sharding.py:shard_for_serving layout), the paged pool
        # is placed at start() with heads over tp and the stacked layer
        # axis over pp (stage-local KV slices), and the scheduler thread
        # runs its dispatches inside ``use_mesh(mesh)`` so sharding
        # constraints and the shard-aware kernel dispatch resolve.  None
        # = the unchanged single-chip engine.
        self.mesh = mesh
        self.config = engine_config or EngineConfig()
        if cfg.kv_lora_rank:
            _refuse_for_latent(cfg, self.config, mesh, draft_cfg, adapters)
        elif cfg.layer_pattern:
            _refuse_for_hybrid(cfg, self.config, mesh, draft_cfg, adapters)
        assert self.config.max_seq_len <= cfg.max_position_embeddings, (
            f"max_seq_len {self.config.max_seq_len} exceeds the model's "
            f"max_position_embeddings {cfg.max_position_embeddings}")
        # Multi-tenant LoRA (serving/adapters/): the registry owns the
        # device arena; the engine pins adapters at admission and threads
        # the arena + a per-row slot vector through every jitted step.
        self.adapters = adapters
        if self.config.adapter_cache_slots and adapters is None:
            raise ValueError(
                "EngineConfig.adapter_cache_slots is set but no "
                "AdapterRegistry was passed to the engine")
        if (adapters is not None and self.config.adapter_cache_slots
                and adapters.n_slots != self.config.adapter_cache_slots):
            raise ValueError(
                f"AdapterRegistry has {adapters.n_slots} arena slots but "
                f"EngineConfig.adapter_cache_slots="
                f"{self.config.adapter_cache_slots}")
        self._lora_rank = 0 if adapters is None else adapters.rank
        # sanitizer resolution comes first so every lock/condition the
        # engine (and its queue) creates below is order-tracked
        self._sanitize = bool(self.config.sanitize) or sanitizers.env_enabled()
        if self._sanitize:
            sanitizers.enable_lock_tracking()
        self._sanitizer: Optional[sanitizers.LedgerSanitizer] = None
        self.sanitizer_report: List[dict] = []  # leaks found at shutdown
        self.metrics = metrics or ServingMetrics(self.config.max_batch_size)
        if adapters is not None and adapters._metrics is None:
            adapters._metrics = self.metrics
        self.metrics.set_gauges(num_slots=self.config.max_batch_size)
        self.trace = TraceRecorder(capacity=self.config.trace_capacity,
                                   enabled=self.config.trace,
                                   phases=SCHED_PHASES)
        # a profile session keeps this recorder: its readers join the
        # spans (prompt lengths, live slots) with the device's operations
        obs_profile.while_profiling(self.trace)
        # on gc.callbacks from start() to shutdown(), where tracing is on
        self._gc_watch = GcWatch(self.trace) if self.config.trace else None
        # a compile inside a span of this recorder is exported with it
        obs_compile.install()
        self.queue = RequestQueue(self.config.max_queue_size,
                                  self.config.retry_after_s)
        self.slots: Optional[SlotAllocator] = None  # allocated on start
        self.prefix_cache: Optional[PrefixCache] = None  # built on start
        self._active: dict[int, _SlotState] = {}    # slot -> state
        self._decode = (_decode_plain if jax.default_backend() == "cpu"
                        else _decode_donated)
        self._verify = (_verify_plain if jax.default_backend() == "cpu"
                        else _verify_donated)
        self._verify_tree = (
            _verify_tree_plain if jax.default_backend() == "cpu"
            else _verify_tree_donated)
        self._draft_step = (
            _draft_step_plain if jax.default_backend() == "cpu"
            else _draft_step_donated)
        self._draft_install = (
            _draft_install_plain if jax.default_backend() == "cpu"
            else _draft_install_donated)
        self._move_rows = (
            _move_rows_plain if jax.default_backend() == "cpu"
            else _move_rows_donated)
        self._prefill_chunk_fn = (
            _prefill_chunk_plain if jax.default_backend() == "cpu"
            else _prefill_chunk_donated)
        self._thread: Optional[threading.Thread] = None
        # per-iteration scheduler heartbeat (perf_counter).  A live thread
        # wedged inside a device dispatch stops refreshing it — the
        # cluster supervisor's watchdog compares its age against
        # hang_timeout_s, which thread-liveness probes cannot see.
        self.heartbeat: float = time.perf_counter()
        # cluster rebuild recipe (cfg/params/devices/...) attached by the
        # sharded.py builders; ReplicaSupervisor uses it to rebuild this
        # replica on its original submesh after a crash.  None for engines
        # built outside a cluster.
        self.rebuild_spec: Optional[dict] = None
        # tiered KV (block_pool.py:HostKVTier): built at start() when
        # host_kv_blocks > 0.  ``_suspended`` maps req.id -> _Suspended
        # for decodes preempted to the host tier, in suspension order.
        self.host_tier = None
        self._suspended: dict[int, _Suspended] = {}
        self._admitting: Optional[_Request] = None  # popped, not yet slotted
        self._held: Optional[_Request] = None  # popped but parked: the pool
        #                               could not reserve its worst-case
        #                               block count; retried (FIFO order
        #                               preserved) as retirements free blocks
        self._prefilling: Optional[_PrefillState] = None  # chunked prefill
        self._inflight: Optional[_Inflight] = None  # dispatched decode step
        # decode microbatch groups (resolved at start()): pp on a pp>1
        # mesh when the slot batch divides evenly, else 1.  Each
        # scheduler iteration then splits the batch into this many
        # interleaved dispatches so the pipeline stages overlap distinct
        # microbatches instead of idling pp-1/pp of the mesh per step.
        self._decode_groups = 1
        self._scheduler_error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._paused = threading.Event()
        self._draining = threading.Event()
        self._started = threading.Event()
        self._lock = sanitizers.make_lock("engine.lifecycle")
        #                              guards start/shutdown
        self._wake = sanitizers.make_condition("engine.wake")
        #                              paused-loop wakeups
        self._drain_cond = sanitizers.make_condition("engine.drain")
        #                              drain() wakeups
        assert self.config.role in ("mixed", "prefill", "decode"), \
            f"unknown engine role {self.config.role!r}"
        # control ops: closures other threads (the router) need the
        # scheduler thread to run between iterations — shipment installs,
        # extractions for migration.  Drained at the top of every loop
        # iteration, including while paused/draining.
        self._control: List = []
        self._control_lock = sanitizers.make_lock("engine.control")
        # router-installed callback a prefill-role engine hands finished
        # prefills to: handler(KVShipment) ships the blocks to a decode
        # replica (serving/cluster/router.py:_dispatch_shipment)
        self._ship_handler: Optional[Callable] = None
        # device/host overlap accounting (metrics.observe_step_breakdown)
        self._last_dispatch_t: Optional[float] = None
        self._last_ready_t: Optional[float] = None
        # scheduler iteration, carried by the admit / engine_step / prefill
        # / decode spans so a token's gap can be put down to the iteration
        # (and the admission) that caused it
        self._iter = 0
        # how a dropless stack's experts run, on its prefill and step
        # spans (kernels/grouped_matmul.py; no such field elsewhere)
        self._experts_arg = ({"experts": "grouped"} if cfg.moe_dropless
                             else {})
        # a hybrid stack's kinds of slot state, on its prefill and decode
        # spans ("linear", "mamba", "linear+mamba"; no such field for a
        # one-kind stack), and whether a state-space layer is among them
        kinds = [keeps for keeps in model_lib.REC_STATE_KINDS
                 if cfg.layers_keeping(keeps)]
        self._state_arg = {"state_kinds": "+".join(kinds)} if kinds else {}
        self._counts_ssm = cfg.mamba_layers + cfg.mamba1_layers > 0
        # layers that walk a cache another layer wrote (the kinds that
        # read "kv": ``config.KINDS``), beside the one that wrote it: a
        # step's walks are counted by kind, its decode spans carry the
        # cached positions it attended
        self._kv_readers = (
            {"full": cfg.kv_layers, **{
                kind: cfg.layer_kinds.count(kind)
                for kind, k in KINDS.items() if k.reads == "kv"}}
            if cfg.cross_layers else
            {"full": cfg.kv_layers} if cfg.window_layers else {})
        # rows a slot's "window" rings hold at most (0: no such layers):
        # a step's decode spans carry the live ones (``ring_rows``)
        self._ring_window = cfg.sliding_window if cfg.window_layers else 0
        # (KV heads, heads a copy) of the paged walk over its pool, read
        # off the pool at the first step that counts by it (_walk_arg)
        self._walk_grid = None
        # how a step's recurrent mixers ran, on its decode spans: each
        # between its two projections as one kernel, which advances the
        # layer's states and tails where they lie stacked
        # (kernels/gdn_step.py; kernels/mamba_step.py, so many heads of a
        # slot a grid step; no such fields for a stack without them)
        self._step_arg = dict(
            self._state_arg,
            **({"gdn_step": "mixer"} if cfg.linear_layers else {}),
            **({"ssm_step": "mixer", "ssm_tile": heads_per_step(
                cfg.mamba_num_heads, cfg.mamba_n_groups)}
               if cfg.mamba_layers else {}))
        # on a prompt's prefill spans: how its Gated DeltaNet layers ran
        # (between their projections as one kernel, kernels/gdn_scan.py;
        # no such field for a stack without them), and the bytes of slot
        # state its install wrote beside the K/V (``slots.insert``)
        self._prefill_arg = dict(self._state_arg, **(
            {"gdn": "fused"} if cfg.linear_layers else {}))
        if kinds:
            self._prefill_arg["state_installed_bytes"] = sum(
                a.size * a.dtype.itemsize for a in jax.tree.leaves(
                    jax.eval_shape(lambda: model_lib.rec_states(
                        model_lib.init_rec_state(cfg, 1)))))
        # which form of a latent-attention layer ran (models/mla.py), on
        # the prefill and decode spans; a decode span then also carries
        # the cached positions its step attended, over all live slots
        self._latent = bool(cfg.kv_lora_rank)
        if self._latent:
            self._prefill_arg["attn"] = "mla_expanded"
            self._step_arg["attn"] = "mla_absorbed"
        # a stack that cuts a prefill to one row at the boundary between
        # its decoders (cfg.row_cut_layer): the rows its later layers ran
        # on its prefill spans (1, or the bucket's where a request's
        # prompt log-probs took a second pass over every row)
        self._row_cut = cfg.row_cut_layer is not None
        # a bucket's ``flash_tiles`` on its whole-prompt prefill spans, by
        # padded length (``_flash_tiles_arg``)
        self._flash_tiles: Dict[int, dict] = {}
        self._admit_count = 0        # this iteration's admissions
        self._admit_tokens = 0       # and their prompt tokens
        # the decode step decides for itself whether it reads KV
        # through the block tables inside the paged attention kernel
        # (models/model.py:forward_cached_paged) — except while
        # speculating: the verify step walks the gather route's
        # arithmetic, and decode must round as it does.  _paged_decode is
        # the same predicate asked at start(), to label the steps.
        self._allow_paged = self.config.spec_draft_len == 0
        self._paged_decode = False
        # draft model actually engaged: resident params AND speculation on
        self._draft_enabled = (self.draft_cfg is not None
                               and self.config.spec_draft_len > 0)
        # weight precision route (ops/quant.py:precision_route) labelling
        # the paged/fallback counters per precision — resolved at start()
        self._precision_route = "fp32"

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ServingEngine":
        with self._lock:
            if self._thread is None:
                cfg_e = self.config
                # block size follows the admission granularity by default
                # so prefix-cache blocks == pool blocks (zero-copy sharing)
                # and hit suffixes reuse the cold path's compiled shapes
                bk = int(cfg_e.kv_block_size
                         or cfg_e.prefill_chunk
                         or max(1, cfg_e.prefill_bucket))
                bk = max(1, min(bk, cfg_e.max_seq_len))
                table_blocks = -(-cfg_e.max_seq_len // bk)
                n_blocks = int(cfg_e.kv_pool_blocks) or (
                    1 + cfg_e.max_batch_size * table_blocks
                    + (cfg_e.prefix_cache_blocks or 0))
                pool = BlockPool(
                    self.cfg, n_blocks, bk,
                    on_cow=lambda: self.metrics.inc("cow_copies_total"),
                    mesh=self.mesh)
                if self.mesh is not None:
                    from ..parallel import mesh as mesh_lib
                    pp = mesh_lib.pipeline_parallel_size(self.mesh)
                    # microbatch-interleaved decode: split the slot batch
                    # into pp groups whose dispatches chain through the
                    # KV pool, overlapping across the layer-sharded
                    # stages.  Per-group shapes are identical ([S/pp]),
                    # so all groups share ONE executable — zero extra
                    # compiles — and tokens stay bitwise equal to the
                    # single-dispatch path (per-row math, disjoint-row
                    # pool scatters, RNG folded on (seed, count) only).
                    if pp > 1 and cfg_e.max_batch_size % pp == 0:
                        self._decode_groups = pp
                self.slots = SlotAllocator(self.cfg,
                                           cfg_e.max_batch_size,
                                           cfg_e.max_seq_len, pool)
                if cfg_e.host_kv_blocks:
                    self.host_tier = HostKVTier(
                        pool, cfg_e.host_kv_blocks,
                        arity=self.slots.table_blocks,
                        metrics=self.metrics)
                if cfg_e.prefix_cache_blocks:
                    self.prefix_cache = PrefixCache(
                        self.cfg, pool=pool,
                        max_blocks=cfg_e.prefix_cache_blocks,
                        max_seq_len=cfg_e.max_seq_len,
                        metrics=self.metrics,
                        host_tier=self.host_tier)
                from ..ops.quant import precision_route
                self._precision_route = precision_route(self.params)
                self._paged_decode = (
                    self._allow_paged
                    and model_lib.paged_decode_eligible(
                        self.cfg, pool.k_pool, mesh=self.mesh))
                if self._draft_enabled:
                    # shadow paged pool for the draft model: SAME block
                    # count and block size as the target pool so the
                    # target's block tables index both — no second
                    # ledger, no separate alloc/free, and trash (block
                    # 0) masks identically.  Only the head geometry
                    # differs (draft_cfg's kv heads / head dim).
                    if self.mesh is not None:
                        from ..models import sharding as shard_lib
                        dk, dv = shard_lib.init_sharded_kv_pool(
                            self.draft_cfg, n_blocks, bk, self.mesh)
                    else:
                        dk, dv = model_lib.init_kv_pool(
                            self.draft_cfg, n_blocks, bk)
                    self._draft_kv = (dk, dv)
                self._update_pool_gauges()
                self.metrics.set_gauges(kv_pool_bytes={
                    "latent" if self._latent else "kv": sum(
                        int(a.nbytes) for a in jax.tree.leaves(
                            (pool.k_pool, pool.v_pool)))})
                if self.slots.rec is not None:
                    self.metrics.set_gauges(
                        rec_state_bytes=self.slots.rec_state_bytes,
                        rec_state_slots=cfg_e.max_batch_size)
                    self.metrics.expert_load = self.expert_load
                    self.metrics.expert_layers = self.cfg.moe_layer_ids
                    self.metrics.expert_rows = self.expert_rows
                if self._sanitize:
                    self._sanitizer = sanitizers.LedgerSanitizer()
                if self._gc_watch is not None:
                    self._gc_watch.install()
                self._thread = threading.Thread(
                    target=self._loop, name="serving-engine", daemon=True)
                self._thread.start()
                self._started.set()
        return self

    def shutdown(self, timeout: float = 10.0) -> None:
        with self._lock:
            if self._thread is None:
                return
            self._stop.set()
            self.queue.notify()
            with self._wake:
                self._wake.notify_all()
            self._thread.join(timeout)
            self._thread = None
            if self._gc_watch is not None:
                self._gc_watch.remove()
            with self._drain_cond:
                self._drain_cond.notify_all()
            if self._sanitizer is not None:
                self.sanitizer_report = self._sanitizer.leak_report(self)
                for leak in self.sanitizer_report:
                    EVENT_LOG.emit("sanitizer", "kv_block_leak", **leak)

    def expert_load(self):
        """→ (counts [layers, router outputs] of how often each expert was
        chosen since the engine started, the first held expert, the held
        experts), fetched from the device now.  From any thread: the
        decode step donates the tree the counts ride in, so while the
        scheduler runs it is the scheduler that reads them, between two
        iterations; this is for ``/metrics`` and the benchmark, not for a
        step."""
        counts = self._fetch_counter("load")
        return counts, self.cfg.moe_expert_offset, self.cfg.num_experts

    def expert_rows(self) -> dict:
        """→ the (token, choice) rows the experts' kernel was handed since
        the engine started, by what it did with them (``multiplied``: a
        held expert's; ``skipped``: a sort key and nothing else), over
        all layers.  Fetched as ``expert_load`` is."""
        rows = model_lib.rows_total(self._fetch_counter("rows")).sum(axis=0)
        return {"multiplied": int(rows[0]), "skipped": int(rows[1])}

    def _fetch_counter(self, name: str):
        def fetch():
            # tpulint: allow[host-sync] asked for, outside any step
            return np.asarray(self.slots.rec[name])
        try:
            return self.call_in_scheduler(fetch)
        except RuntimeError:        # not running: nothing donates it
            return fetch()

    def pause(self) -> None:
        """Stop admitting and decoding (requests keep queueing) — used for
        drains and by tests that need deterministic queue pressure."""
        self._paused.set()

    def resume(self) -> None:
        self._paused.clear()
        with self._wake:           # wake the paused scheduler immediately
            self._wake.notify_all()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain: stop admitting new requests (submissions are
        rejected with ``QueueFull``), let everything in flight finish, and
        return True once the engine is idle (False on timeout).

        Used by the HTTP server's SIGTERM handler so a rolling restart
        never drops partially-generated responses."""
        self._draining.set()
        self.queue.notify()
        if self._thread is None:  # never started: trivially drained
            return True
        deadline = (None if timeout is None
                    else time.perf_counter() + float(timeout))
        with self._drain_cond:
            while True:
                idle = self._is_idle()
                if idle or self._stop.is_set():
                    if idle and self._sanitizer is not None:
                        self.sanitizer_report = (
                            self._sanitizer.leak_report(self))
                    return idle
                remaining = (None if deadline is None
                             else deadline - time.perf_counter())
                if remaining is not None and remaining <= 0:
                    return False
                # woken by _finish / the scheduler going idle / shutdown,
                # not polled
                self._drain_cond.wait(remaining)

    def _is_idle(self) -> bool:
        return (not self._active and self._admitting is None
                and self._prefilling is None and self._inflight is None
                and self._held is None and not self._suspended
                and len(self.queue) == 0)

    def _notify_drain(self) -> None:
        with self._drain_cond:
            self._drain_cond.notify_all()

    # -- submission (any thread) ------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               eos_id: int = 2, temperature: float = 1.0, top_k: int = 0,
               top_p: float = 0.0, seed: Optional[int] = None,
               use_eos_stop: bool = True, return_logprobs: bool = False,
               on_token: Optional[Callable[[int], None]] = None,
               deadline_s: Optional[float] = None,
               adapter_id: Optional[str] = None,
               priority: int = 0) -> RequestHandle:
        return self.submit_many([dict(
            prompt=prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            use_eos_stop=use_eos_stop, return_logprobs=return_logprobs,
            on_token=on_token, deadline_s=deadline_s,
            adapter_id=adapter_id, priority=priority)])[0]

    def submit_many(self, specs: Sequence[dict]) -> List[RequestHandle]:
        """Validate + enqueue a batch of requests all-or-nothing.

        Raises ``ValueError`` for a request that can never fit (admission
        control: the per-slot sequence budget) and ``QueueFull`` under
        backpressure."""
        self.start()
        if self._draining.is_set():
            self.metrics.inc("rejected_draining", by=len(specs))
            raise QueueFull(
                "engine is draining (shutting down); not accepting requests",
                retry_after_s=self.config.retry_after_s)
        reqs = []
        for spec in specs:
            spec = dict(spec)
            if spec.get("deadline_s") is None:
                spec["deadline_s"] = self.config.default_deadline_s
            req = _Request(**spec)
            if len(req.prompt) < 1:
                self.metrics.inc("rejected_invalid")
                raise ValueError("empty prompt")
            if req.max_new_tokens < 1:
                self.metrics.inc("rejected_invalid")
                raise ValueError("max_new_tokens must be >= 1")
            if len(req.prompt) + req.max_new_tokens > self.config.max_seq_len:
                self.metrics.inc("rejected_invalid")
                raise ValueError(
                    f"prompt ({len(req.prompt)} tokens) + max_new_tokens "
                    f"({req.max_new_tokens}) exceeds the per-slot sequence "
                    f"budget ({self.config.max_seq_len})")
            if req.adapter_id is not None:
                if self.adapters is None:
                    self.metrics.inc("rejected_invalid")
                    raise ValueError(
                        f"request names adapter {req.adapter_id!r} but "
                        "the engine has no adapter registry")
                if not self.adapters.known(req.adapter_id):
                    self.metrics.inc("rejected_invalid")
                    raise ValueError(
                        f"unknown adapter {req.adapter_id!r} (register "
                        "it before submitting)")
            pool = self.slots.pool
            need = -(-(len(req.prompt) + req.max_new_tokens)
                     // pool.block_size)
            if need > pool.usable_blocks:
                self.metrics.inc("rejected_invalid")
                raise ValueError(
                    f"request needs {need} KV blocks but the pool only has "
                    f"{pool.usable_blocks} (kv_pool_blocks too small for "
                    f"this sequence budget)")
            reqs.append(req)
        try:
            self.queue.put_many(reqs)
        except QueueFull:
            self.metrics.inc("rejected_queue_full", by=len(reqs))
            raise
        self.metrics.inc("submitted", by=len(reqs))
        self.metrics.set_gauges(queue_depth=len(self.queue))
        for req in reqs:
            EVENT_LOG.emit("engine", "submitted", request_id=req.rid,
                           prompt_len=len(req.prompt),
                           max_new_tokens=req.max_new_tokens,
                           queue_depth=len(self.queue))
        return [RequestHandle(r, self) for r in reqs]

    def _cancel(self, req: _Request) -> None:
        req.cancel_flag.set()
        if self.queue.remove(req):  # still queued: finish it right here
            self._finish(req, "cancelled")
            self.metrics.set_gauges(queue_depth=len(self.queue))

    # -- control ops (cross-thread -> scheduler thread) --------------------

    def set_ship_handler(self, handler: Optional[Callable]) -> None:
        """Install the router's shipment dispatcher.  A prefill-role
        engine calls it (on the scheduler thread) with each finished
        prefill's :class:`KVShipment`; the handler owns the shipment's
        lifecycle — install on a decode replica, or reinstall here on
        failure — and must call ``pool.end_ship`` when done."""
        self._ship_handler = handler

    def call_in_scheduler(self, fn: Callable, timeout: float = 30.0):
        """Run ``fn()`` on the scheduler thread and return its result.

        All slot/pool/table state is owned by the scheduler thread; the
        router uses this to install shipments and extract requests
        without adding locks to the hot path.  Called *from* the
        scheduler thread it runs inline (so a prefill engine's ship
        handler can reinstall locally on failure without deadlocking).
        Exceptions propagate to the caller — they never touch the
        scheduler's own crash handler."""
        if threading.current_thread() is self._thread:
            return fn()
        if self._thread is None or not self._thread.is_alive():
            raise RuntimeError("engine scheduler is not running")
        box = {"done": threading.Event(), "result": None, "error": None}
        with self._control_lock:
            self._control.append((fn, box))
        self.queue.notify()          # wake the idle wait
        with self._wake:             # wake the paused wait
            self._wake.notify_all()
        if not box["done"].wait(timeout):
            raise TimeoutError(f"scheduler control op not run in {timeout}s")
        if box["error"] is not None:
            raise box["error"]
        return box["result"]

    def _run_control_ops(self) -> None:
        with self._control_lock:
            ops, self._control = self._control, []
        for fn, box in ops:
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — belongs to caller
                box["error"] = e
            finally:
                box["done"].set()

    # -- scheduler loop (engine thread only) -------------------------------

    def _loop(self) -> None:
        if self.mesh is not None:
            # the scheduler thread owns all device dispatch; entering the
            # submesh here covers every jitted step (mesh contexts are
            # thread-local, so concurrent replicas don't interleave)
            from ..parallel import mesh as mesh_lib

            with mesh_lib.use_mesh(self.mesh):
                return self._loop_body()
        return self._loop_body()

    def _loop_body(self) -> None:
        try:
            while not self._stop.is_set():
                self.heartbeat = time.perf_counter()
                chaos().point("serve-step")
                # Control ops (shipment installs / migration extractions)
                # and cancellations/deadline expiry run even while paused:
                # a paused engine must not hold expired requests — or the
                # router's in-flight shipments — hostage.
                self._run_control_ops()
                self._drain_cancellations()
                self._expire_deadlines()
                if self._paused.is_set():
                    self._flush_inflight()
                    self._last_dispatch_t = self._last_ready_t = None
                    with self._wake:  # resume()/shutdown wake this; the
                        # timeout only bounds the cancel/deadline sweep
                        if self._paused.is_set() and not self._stop.is_set():
                            self._wake.wait(self.config.idle_wait_s)
                    continue
                self._iter += 1
                self._admit_traced()
                if self._active:
                    self._step()
                elif self._inflight is not None:
                    # every slot retired while the step was in flight: its
                    # tokens are all speculative — discard without syncing
                    self._flush_inflight()
                elif self._prefilling is None:
                    if (self.host_tier is not None
                            and self.host_tier.in_flight):
                        # nothing to decode: drain the swap backlog now
                        self.host_tier.pump()
                        continue  # re-check admission (resume/oversubscribe)
                    # idle: queue.notify (submit / drain / shutdown) wakes
                    # this immediately; no sleep-polling
                    self._last_dispatch_t = self._last_ready_t = None
                    self._notify_drain()
                    self.queue.wait_for_work(self.config.idle_wait_s)
                if self._sanitizer is not None:
                    # ledger audit once per iteration; a LedgerError
                    # lands in the handler below — loud, fails everything
                    self._sanitizer.check_engine(self)
        except Exception as e:  # noqa: BLE001 — a dead scheduler must not
            # leave submitters blocked on result() forever: fail every
            # in-flight and queued request loudly, then stop.
            import logging

            logging.getLogger(__name__).exception(
                "serving engine scheduler died: %s", e)
            self._scheduler_error = e
            self._inflight = None
            if self._admitting is not None:  # popped but not yet slotted
                self._finish(self._admitting, "error")
                self._admitting = None
            if self._prefilling is not None:  # mid chunked prefill
                self._finish(self._prefilling.req, "error")
                self._prefilling = None
            if self._held is not None:  # parked on pool pressure
                self._finish(self._held, "error")
                self._held = None
            for slot in list(self._active):
                st = self._active.pop(slot)
                self._finish(st.req, "error")
            for key in list(self._suspended):  # preempted to host tier
                sus = self._suspended.pop(key)
                if self.host_tier is not None:
                    self.host_tier.free(sus.hids)
                self._finish(sus.req, "error")
            while True:
                req = self.queue.pop()
                if req is None:
                    break
                self._finish(req, "error")
            with self._control_lock:  # pending control ops: fail callers
                ops, self._control = self._control, []
            for _, box in ops:
                box["error"] = RuntimeError(
                    f"serving engine scheduler died: {e!r}")
                box["done"].set()
            self._stop.set()
            self._notify_drain()
        except BaseException as e:  # noqa: BLE001 — a hard crash
            # (chaos SimulatedCrash &c.) tears through cleanup the way
            # SIGKILL would: record it so probes/crash-correlation see the
            # cause, then die WITHOUT failing requests — they stay
            # unfinished exactly like after a real kill, and the router's
            # probe thread fails them over (or quarantines them).
            self._scheduler_error = e
            self._stop.set()

    def _drain_cancellations(self) -> None:
        for slot in [s for s, st in self._active.items()
                     if st.req.cancel_flag.is_set()]:
            self._retire(slot, "cancelled")
        if (self._prefilling is not None
                and self._prefilling.req.cancel_flag.is_set()):
            self._abort_prefill("cancelled")
        if self._held is not None and self._held.cancel_flag.is_set():
            req, self._held = self._held, None
            self._finish(req, "cancelled")
        for key in [k for k, s in self._suspended.items()
                    if s.req.cancel_flag.is_set()]:
            self._discard_suspended(key, "cancelled")

    def _abort_prefill(self, reason: str) -> None:
        ps, self._prefilling = self._prefilling, None
        if self.prefix_cache is not None:
            # unpin without offering: the slot holds a partial prefill
            self.prefix_cache.release(ps.lease)
        self._release_adapter(ps.req)
        self.slots.release(ps.slot)
        self._finish(ps.req, reason)
        self.metrics.set_gauges(slots_active=self.slots.active_slots)

    def _expire_deadlines(self) -> None:
        """Retire every request past its wall-clock deadline — active slots
        finish with whatever tokens they produced so far, queued requests
        expire without ever occupying a slot."""
        now = time.perf_counter()

        def expired(req: _Request) -> bool:
            return req.deadline is not None and now >= req.deadline

        for slot in [s for s, st in self._active.items()
                     if expired(st.req)]:
            self._retire(slot, "timeout")
        if self._prefilling is not None and expired(self._prefilling.req):
            self._abort_prefill("timeout")
        if self._held is not None and expired(self._held):
            req, self._held = self._held, None
            self._finish(req, "timeout")
        for key in [k for k, s in self._suspended.items()
                    if expired(s.req)]:
            self._discard_suspended(key, "timeout")
        for req in self.queue.remove_if(expired):
            self._finish(req, "timeout")
        self.metrics.set_gauges(queue_depth=len(self.queue))

    def _note_dequeued(self, req: _Request) -> None:
        """Close the request's ``queued`` span (submit -> scheduler pop)."""
        self.trace.add("queued", req.submit_time, time.perf_counter(),
                       request_id=req.rid, tid=req.id,
                       args={"prompt_len": len(req.prompt)})

    def _try_reserve(self, need: int,
                     req: Optional[_Request] = None) -> bool:
        """Reserve ``need`` pool blocks for an admission.

        Escalation order under pool pressure: (1) squeeze the prefix
        cache's unpinned blocks (which *spill to the host tier* instead
        of dropping when one is configured); (2) tiered-KV oversubscribed
        admission — suspend STRICTLY lower-priority active decodes to the
        host tier, bounded by host capacity and measured swap bandwidth,
        so the admitted set can exceed worst-case HBM reservations.
        Queue-head parking is the caller's last resort, not the first
        response to exhaustion."""
        pool = self.slots.pool
        if pool.reserve(need):
            return True
        if self.prefix_cache is not None:
            short = need - (pool.free_blocks - pool.reserved_blocks)
            if short > 0:
                self.prefix_cache.evict_blocks(short)
                self.metrics.set_gauges(
                    prefix_blocks=self.prefix_cache.blocks)
            if pool.reserve(need):
                return True
        if req is not None and self.host_tier is not None:
            while not pool.can_reserve(need):
                if not self.host_tier.swap_ok():
                    break  # swap backlog past the bandwidth bound
                victim = self._pick_preemption_victim(req.priority)
                if victim is None:
                    break
                before = len(self._active)
                if (not self._preempt_slot(victim)
                        and len(self._active) == before):
                    break  # no progress (demote fault / tier full)
            if pool.reserve(need):
                return True
        return False

    def _pick_preemption_victim(self, priority: int) -> Optional[int]:
        """The active decode to suspend for an admission of ``priority``:
        lowest priority STRICTLY below it, oldest submit within a class,
        and its live blocks must fit in the host tier's free space."""
        best_key, best_slot = None, None
        for slot, st in self._active.items():
            if st.req.priority >= priority:
                continue
            if not self.host_tier.can_store(
                    len(self.slots.live_bids(slot))):
                continue
            key = (st.req.priority, st.req.submit_time)
            if best_key is None or key < best_key:
                best_key, best_slot = key, slot
        return best_slot

    def _acquire_adapter(self, req: _Request) -> Optional[int]:
        """Pin the request's adapter in the device arena.  Returns the
        arena slot (-1 for base-model requests) or ``None`` when every
        arena slot is pinned by other active requests — the caller parks
        the request at the queue head, the same FIFO backpressure shape
        as KV-pool pressure."""
        if req.adapter_id is None:
            return -1
        return self.adapters.acquire(req.adapter_id)

    def _release_adapter(self, req: _Request) -> None:
        if req.adapter_id is not None and self.adapters is not None:
            self.adapters.release(req.adapter_id)

    def _lora_args(self, aslots) -> dict:
        """Keyword operands threading the adapter arena + per-row arena-
        slot vector into a jitted step.  Empty for base-only engines, so
        their call signatures (and compiled executables) are untouched;
        with a registry the operand SHAPES never change — only arena
        contents and the tiny int vector — so steps stay one executable
        as adapters churn."""
        if self.adapters is None:
            return {}
        return dict(lora_arenas=self.adapters.arenas,
                    lora_slots=jnp.asarray(np.asarray(aslots, np.int32)),
                    lora_rank=self._lora_rank)

    def _next_admission(self) -> Optional[_Request]:
        """The next request to admit: the parked one first (FIFO order is
        preserved under pool pressure), else a fresh queue pop."""
        if self._held is not None:
            req, self._held = self._held, None
            return req
        req = self.queue.pop()
        if req is not None:
            # keyed on the resolved seed, which — unlike the rid — is
            # stable across failover resubmits: a poison request armed
            # here crashes every incarnation that admits it
            chaos().point(f"serve-admit:{req.seed}")
            self._note_dequeued(req)
            self.metrics.set_gauges(queue_depth=len(self.queue))
        return req

    def _admit_traced(self) -> None:
        """``_admit`` with an ``admit`` span on the scheduler's track when
        it admitted anything: what a neighbour's token waited for."""
        self._admit_count = self._admit_tokens = 0
        t0 = time.perf_counter()
        self._admit()
        if self._admit_count:
            self.trace.add("admit", t0, time.perf_counter(), tid=0,
                           args={"admitted": self._admit_count,
                                 "prompt_tokens": self._admit_tokens,
                                 "iter": self._iter})

    def _admit(self) -> None:
        assert self.slots is not None
        if self.host_tier is not None:
            self._maybe_resume()
        if self.config.prefill_chunk:
            self._admit_chunked()
            return
        while self.slots.free_slots:
            req = self._next_admission()
            if req is None:
                break
            if req.cancel_flag.is_set():
                self._finish(req, "cancelled")
                continue
            # between pop and slot the request is in neither the queue nor
            # _active; remember it so a prefill crash still fails it loudly
            self._admitting = req
            admitted = self._prefill_into_slot(req)
            self._admitting = None
            if not admitted:  # parked in _held: pool pressure, stop here
                break
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots,
                                queue_depth=len(self.queue))

    def _admit_chunked(self) -> None:
        """Chunked admission: at most ONE prefill chunk per scheduler
        iteration, so active streams get a decode step between chunks
        instead of stalling for a whole long prompt."""
        if self._prefilling is None and self.slots.free_slots:
            req = self._next_admission()
            while req is not None and req.cancel_flag.is_set():
                self._finish(req, "cancelled")
                req = self._next_admission()
            if req is not None:
                if req.return_logprobs:
                    # prompt logprobs need every prompt logit in one pass;
                    # rare admin path — take the whole-prompt prefill
                    self._admitting = req
                    self._prefill_into_slot(req)
                    self._admitting = None
                else:
                    self._begin_chunked_prefill(req)
        if self._prefilling is not None:
            self._advance_prefill()
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots,
                                queue_depth=len(self.queue))

    def _begin_chunked_prefill(self, req: _Request) -> None:
        chunk = max(1, int(self.config.prefill_chunk))
        plen = len(req.prompt)
        padded = min(-(-plen // chunk) * chunk, self.config.max_seq_len)
        slot = self.slots.alloc()
        assert slot is not None
        aslot = self._acquire_adapter(req)
        if aslot is None:
            # arena fully pinned: park at the queue head (FIFO under
            # adapter-cache pressure, same shape as pool pressure)
            self.slots.release(slot)
            self._held = req
            return
        lease = None
        # adapter K/V never crosses tenants: no prefix match, no offer
        if self.prefix_cache is not None and req.adapter_id is None:
            t_pm = time.perf_counter()
            lease = self.prefix_cache.match_and_acquire(req.prompt)
            self.trace.add(
                "prefix_match", t_pm, time.perf_counter(),
                request_id=req.rid, tid=req.id,
                args={"hit": lease is not None,
                      "matched_tokens": lease.tokens if lease else 0})
        bk = self.slots.pool.block_size
        n_shared = len(lease.bids) if lease is not None else 0
        need = -(-(plen + req.max_new_tokens) // bk) - n_shared
        if not self._try_reserve(need, req):
            # pool pressure: park the request (FIFO head) and retry once
            # retirements free blocks; nothing was allocated yet
            if self.prefix_cache is not None:
                self.prefix_cache.release(lease)
            self._release_adapter(req)
            self.slots.release(slot)
            self._held = req
            return
        self.slots.set_reservation(slot, need)
        ps = _PrefillState(req, slot, padded)
        ps.lease = lease
        ps.adapter_slot = aslot
        if lease is not None:
            # prefix hit: gather the shared blocks into the batch-1
            # working cache (their pool blocks themselves are shared by
            # ref bump at insert — no K/V copies into the pool) and start
            # the chunk cursor past them; only the suffix chunks run
            ps.done = lease.tokens
            ps.k_small, ps.v_small = self._gather_lease(lease)
        self._prefilling = ps

    def _advance_prefill(self) -> None:
        ps = self._prefilling
        req = ps.req
        chunk = max(1, int(self.config.prefill_chunk))
        t = self.metrics.timers("serving-prefill", 2)
        t.start()
        off = ps.done
        c = min(chunk, ps.padded - off)
        tokens = np.zeros((1, c), np.int32)
        seg = req.prompt[off:off + c]  # shorter than c at the padded tail
        tokens[0, :len(seg)] = seg
        last = off + c >= ps.padded
        # chunk 0 creates the cache inside the jit; later chunks thread
        # (and on TPU donate) it
        fn = (_prefill_chunk_plain if ps.k_small is None
              else self._prefill_chunk_fn)
        with self.trace.span(f"prefill_chunk[{off // chunk}]",
                             request_id=req.rid, tid=req.id, annotate=True,
                             args={"off": off, "tokens": c}):
            logits, ps.k_small, ps.v_small = fn(
                self.cfg, self.params, jnp.asarray(tokens), jnp.int32(off),
                jnp.asarray([len(req.prompt) - 1 - off], jnp.int32),
                ps.k_small, ps.v_small,
                max_seq_len=self.slots.width,
                first=(off == 0), last=last,
                **self._lora_args([ps.adapter_slot]))
        ps.done = off + c
        self.metrics.inc("prefill_chunks")
        if not last:
            t.stop()
            return
        # final chunk: its logit_row is the prompt's last real token (the
        # chunk-padded tail rows, like bucket padding, hold pad-token K/V
        # masked by the slot's fill level)
        self._prefilling = None
        self.slots.insert(ps.slot, ps.k_small, ps.v_small,
                          len(req.prompt),
                          ps.lease.bids if ps.lease is not None else ())
        tok, tok_lp = _first_token_impl(
            self.cfg, logits,
            jnp.asarray([req.seed], jnp.uint32),
            jnp.asarray([0], jnp.int32),
            jnp.asarray([req.greedy]),
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.top_p], jnp.float32))
        first_tok = int(np.asarray(tok)[0])
        t.stop()
        self._admit_count += 1
        self._admit_tokens += len(req.prompt)
        self.metrics.inc("admitted")
        self.metrics.inc("prefills")
        EVENT_LOG.emit("engine", "admitted", request_id=req.rid,
                       slot=ps.slot, prompt_len=len(req.prompt),
                       cached_tokens=ps.lease.tokens if ps.lease else 0,
                       chunked=True)
        st = _SlotState(req, fill=len(req.prompt), pending=first_tok)
        st.lease = ps.lease
        st.adapter_slot = ps.adapter_slot
        self._active[ps.slot] = st
        if self._draft_enabled and self.config.role != "prefill":
            self._draft_prefill(ps.slot, st)
        self._commit_token(ps.slot, first_tok, float(np.asarray(tok_lp)[0]))
        self._maybe_handoff(ps.slot)

    def _gather_lease(self, lease):
        """One fixed-arity gather of a lease's shared blocks into a fresh
        batch-1 working cache (trash-padded past the match)."""
        table = np.zeros((1, self.slots.table_blocks), np.int32)
        table[0, :len(lease.bids)] = lease.bids
        return _gather_lease_impl(self.slots.k_pool, self.slots.v_pool,
                                  jnp.asarray(table))

    def _prefill_into_slot(self, req: _Request) -> bool:
        """Whole-prompt admission.  Returns False (request parked in
        ``_held``, nothing allocated) when the pool cannot reserve the
        request's worst-case block count."""
        t_in = time.perf_counter()
        slot = self.slots.alloc()
        assert slot is not None
        aslot = self._acquire_adapter(req)
        if aslot is None:
            # every arena slot is pinned by an active request: park at
            # the queue head and retry as retirements drop pins (nothing
            # allocated yet — acquire pinned nothing on None)
            self.slots.release(slot)
            self._held = req
            return False
        plen = len(req.prompt)
        bucket = max(1, self.config.prefill_bucket)
        # prompt-logprob requests need every prompt logit in one pass, so
        # they always take the cold whole-prompt prefill.  Adapter
        # requests skip the prefix cache entirely (match AND offer):
        # their K/V rows carry the adapter's wk/wv deltas, so sharing
        # them with base-model (or other-adapter) requests would be
        # numerically wrong in both directions.
        lease = rec_small = None
        flash_arg = {}      # a whole-prompt prefill's alone
        if (self.prefix_cache is not None and not req.return_logprobs
                and req.adapter_id is None):
            t_pm = time.perf_counter()
            lease = self.prefix_cache.match_and_acquire(req.prompt)
            self.trace.add(
                "prefix_match", t_pm, time.perf_counter(),
                request_id=req.rid, tid=req.id,
                args={"hit": lease is not None,
                      "matched_tokens": lease.tokens if lease else 0})
        bk = self.slots.pool.block_size
        n_shared = len(lease.bids) if lease is not None else 0
        need = -(-(plen + req.max_new_tokens) // bk) - n_shared
        if not self._try_reserve(need, req):
            if self.prefix_cache is not None:
                self.prefix_cache.release(lease)
            self._release_adapter(req)
            self.slots.release(slot)
            self._held = req
            if req.parked_at is None:
                # a free slot (and whatever fixed state goes with it: a
                # hybrid stack's rings and states wait in place) and too
                # few free blocks: counted once a request, timed until
                # its admission
                req.parked_at = t_in
                self.metrics.inc("admissions_parked")
            return False
        parked_arg = {}
        if req.parked_at is not None:
            parked_s = t_in - req.parked_at
            self.metrics.inc("admission_parked_seconds_total", parked_s)
            parked_arg = {"parked_ms": round(1e3 * parked_s, 3)}
        self.slots.set_reservation(slot, need)
        t = self.metrics.timers("serving-prefill", 2)
        t.start()
        t_pf = time.perf_counter()
        if lease is not None:
            # prefix hit: gather the shared blocks into a fresh batch-1
            # working cache and prefill only the uncached suffix.  The
            # shared rows are the ones a cold prefill would have written,
            # so the logits at the prompt's last token — and every sampled
            # token after — are bitwise identical (prefix_cache.py); the
            # pool blocks themselves are shared by ref bump at insert —
            # a hit copies zero K/V
            matched = lease.tokens
            k_small, v_small = self._gather_lease(lease)
            suffix = plen - matched
            padded = min(-(-suffix // bucket) * bucket,
                         self.config.max_seq_len - matched)
            tokens = np.zeros((1, padded), np.int32)
            tokens[0, :suffix] = req.prompt[matched:]
            with device_annotation("prefill"):
                last_logits, k_small, v_small = self._prefill_chunk_fn(
                    self.cfg, self.params, jnp.asarray(tokens),
                    jnp.int32(matched),
                    jnp.asarray([suffix - 1], jnp.int32), k_small, v_small,
                    max_seq_len=self.slots.width, first=False,
                    last=True, **self._lora_args([aslot]))
        else:
            padded = -(-plen // bucket) * bucket
            padded = min(padded, self.config.max_seq_len)
            flash_arg = self._flash_tiles_arg(padded)
            tokens = np.zeros((1, padded), np.int32)
            tokens[0, :plen] = req.prompt
            with device_annotation("prefill"):
                # (a stack with a row cut: the caches and the first token
                # come from the program every prefill runs, a request's
                # prompt log-probs from a second pass that keeps nothing)
                (last_logits, picked, k_small, v_small,
                 rec_small) = _prefill_impl(
                    self.cfg, self.params, jnp.asarray(tokens),
                    jnp.asarray([plen], jnp.int32),
                    max_seq_len=self.slots.width,
                    want_logprobs=(req.return_logprobs
                                   and not self._row_cut),
                    **self._lora_args([aslot]))
                if req.return_logprobs and self._row_cut:
                    picked = _prompt_logprobs_impl(
                        self.cfg, self.params, jnp.asarray(tokens))
            if self._row_cut:
                flash_arg = dict(flash_arg, cross_rows=(
                    padded if req.return_logprobs else 1))
            if req.return_logprobs:
                req.logprobs.extend(
                    np.asarray(picked)[0, :plen - 1].tolist())
        t_ins = time.perf_counter()
        self.slots.insert(slot, k_small, v_small, plen,
                          lease.bids if lease is not None else (),
                          rec_small=rec_small)

        # first generated token: same per-request sampling rule as decode
        t_ft = time.perf_counter()
        tok, tok_lp = _first_token_impl(
            self.cfg, last_logits,
            jnp.asarray([req.seed], jnp.uint32),
            jnp.asarray([0], jnp.int32),
            jnp.asarray([req.greedy]),
            jnp.asarray([req.temperature], jnp.float32),
            jnp.asarray([req.top_k], jnp.int32),
            jnp.asarray([req.top_p], jnp.float32))
        first = int(np.asarray(tok)[0])
        t.stop()
        t_tok = time.perf_counter()
        if self.trace.enabled:
            # the admission's phases so far (SCHED_PHASES), ahead of the
            # spans that enclose them: a reader that names an idle gap
            # breaks a tie by the order of the ring
            rid = req.rid
            self.trace.add("admit_setup", t_in, t_pf, request_id=rid,
                           args={"iter": self._iter})
            self.trace.add("prefill_dispatch", t_pf, t_ins, request_id=rid,
                           args={"padded": padded})
            self.trace.add("slot_insert", t_ins, t_ft, request_id=rid)
            self.trace.add("prefill_wait", t_ft, t_tok, request_id=rid)
        self.trace.add("prefill", t_pf, t_tok,
                       request_id=req.rid, tid=req.id,
                       args={"prompt_len": plen, "padded": padded,
                             "cached_tokens": lease.tokens if lease else 0,
                             "iter": self._iter, **self._experts_arg,
                             **self._prefill_arg, **flash_arg,
                             **parked_arg})
        if self._counts_ssm:
            self.metrics.add_ssm_positions("prefill", plen)
        self._admit_count += 1
        self._admit_tokens += plen
        self.metrics.inc("admitted")
        self.metrics.inc("prefills")
        EVENT_LOG.emit("engine", "admitted", request_id=req.rid, slot=slot,
                       prompt_len=plen,
                       cached_tokens=lease.tokens if lease else 0,
                       chunked=False)

        st = _SlotState(req, fill=plen, pending=first)
        st.lease = lease
        st.adapter_slot = aslot
        self._active[slot] = st
        if self._draft_enabled and self.config.role != "prefill":
            # prefill-role engines hand the slot off immediately; the
            # decode replica re-prefills the draft on install instead
            self._draft_prefill(slot, st)
        self._commit_token(slot, first, float(np.asarray(tok_lp)[0]))
        # the first token's own cost: sample, host fetch (which waits for
        # the prefill), slot state, commit
        self.trace.add("first_token", t_ft, time.perf_counter(),
                       request_id=req.rid, tid=req.id)
        self._maybe_handoff(slot)
        self.trace.add("admit_commit", t_tok, time.perf_counter(),
                       request_id=req.rid)
        return True

    # tpulint: hot-path
    def _step(self) -> None:
        """One scheduler iteration of the decode fast path: dispatch step
        N+1, then process step N's tokens (which the device computed — and
        whose host copy streamed — while we were doing this bookkeeping).

        Non-pipelined mode runs the same code with the processing moved
        after the dispatch of the SAME step, i.e. the classic
        dispatch -> sync -> commit loop.

        With speculative decoding enabled, an iteration where some slot
        can carry a draft takes the verify path instead: the pipeline is
        flushed (drafts must match against fully committed context, and
        the next fill depends on how many land), one multi-token verify
        forward runs, and up to draft_len+1 tokens commit per slot."""
        if self.config.spec_draft_len > 0 and self._plan_spec():
            self._flush_inflight()
            if self._draft_enabled:
                plans = self._plan_tree_budgets()
                if plans:
                    self._spec_step_tree(plans)
                    return
            else:
                drafts = self._build_drafts()
                if drafts:
                    self._spec_step(drafts)
                    return
        it0 = time.perf_counter()
        t = self.metrics.timers("serving-decode", 2)
        t.start()
        chaos().maybe_hang("serve-dispatch")
        inflight = self._dispatch_decode()
        prev, self._inflight = self._inflight, inflight
        if self.host_tier is not None and self.host_tier.in_flight:
            # host phase of the pipelined step: finalize at most one
            # queued demote while the device chews on the dispatch — the
            # D2H copy was issued async at begin_demote, so this is
            # (usually) just landing already-arrived bytes in the arena
            self.host_tier.pump(max_swaps=1)
        wait_s = 0.0
        if prev is not None:
            wait_s += self._process_step_results(prev)
        if not self.config.pipeline_decode:
            cur, self._inflight = self._inflight, None
            wait_s += self._process_step_results(cur)
        t.stop()
        # scheduler/Python overhead this iteration = wall time minus the
        # portion actually blocked on the device
        host_s = max(0.0, (time.perf_counter() - it0) - wait_s)
        self.metrics.observe_step_breakdown(host_s=host_s)
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        self.trace.add(
            "engine_step", it0, time.perf_counter(), tid=0,
            args={"iter": self._iter, "batch": len(inflight.slots),
                  "route": self._decode_route,
                  "sampling": inflight.sampling,
                  "pipelined": self.config.pipeline_decode,
                  **self._experts_arg})

    def _flash_tiles_arg(self, padded: int) -> dict:
        """What ``flash_fwd`` does for a whole-prompt prefill of ``padded``
        rows, ``"live/masked/padded_rows"``: the tiles it computes, how
        many of them the diagonal or the ragged end crosses and the rows
        that are padding (kernels/flash_attention.py:tile_plan), reckoned
        once a bucket and kept beside it.  No such field where another
        attention runs (another ``attention_impl``; a prompt of one row,
        which goes the decode way)."""
        arg = self._flash_tiles.get(padded)
        if arg is None:
            arg = {}
            if self.cfg.attention_impl == "flash" and padded > 1:
                # (a stack of runs: its "window" layers' band, the only
                # flash_fwd of a prefill cut to one row)
                w = self.cfg.sliding_window if self.cfg.window_layers else 0
                plan = tile_plan(padded, padded,
                                 *flash_blocks(self.cfg, w), True, w)
                arg = {"flash_tiles":
                       f"{plan.live}/{plan.masked}/{plan.padded_rows}"}
            self._flash_tiles[padded] = arg
        return arg

    @property
    def _decode_route(self) -> str:
        """The plain decode step's route, as the ``engine_step`` span and
        the ``<route>_steps`` counters name it."""
        return "paged" if self._paged_decode else "fallback"

    def _spec_budget(self, st: _SlotState) -> int:
        """Draft-token budget from the slot's acceptance EWMA; a slot
        the policy collapsed to zero re-probes with one token every
        ``EngineConfig.spec_reprobe_interval`` iterations so a
        repetitive stretch later in the generation can re-engage
        speculation."""
        k = int(round(st.spec_ewma * self.config.spec_draft_len))
        if k < 1:
            return (1 if st.spec_stall >= self.config.spec_reprobe_interval
                    else 0)
        return k

    def _plan_spec(self) -> bool:
        """Per-iteration speculative gate, run BEFORE breaking the
        decode pipeline: stall bookkeeping plus a stale-context n-gram
        probe, so the engine only pays a pipeline flush when some slot
        can plausibly carry a draft.  The host context is missing at
        most the one in-flight token; the authoritative drafts are
        rebuilt after the flush (``_build_drafts``)."""
        if not self._active:
            return False
        W = self.config.spec_draft_len + 1
        if any(st.fill + W > self.slots.width
               for st in self._active.values()):
            # a slot is within W rows of its table width: every rider's
            # verify forward writes (masked, later overwritten) rows at
            # fill..fill+W-1, so the whole batch takes plain steps for
            # this tail stretch — at most W iterations per request
            return False
        want = False
        for st in self._active.values():
            if not st.req.greedy or st.count > st.req.max_new_tokens - 2:
                continue
            if not st.req.spec_force and self._spec_budget(st) < 1:
                st.spec_stall += 1
                continue
            if self._draft_enabled or st.req.spec_force:
                # a resident draft model always has something to propose
                # (no n-gram match required), so a budgeted greedy slot
                # is enough to pay for the flush; a spec_force warm
                # probe likewise always drafts (``_build_drafts``)
                want = True
            elif _ngram_draft_host(st.req.prompt + st.req.generated,
                                   self.config.spec_ngram, 1):
                want = True
            else:
                st.spec_stall += 1
        return want

    def _build_drafts(self) -> dict:
        """slot -> draft tokens for this verify step.  Authoritative:
        the pipeline is flushed, so every context is fully committed and
        the remaining-token budgets are exact."""
        drafts = {}
        for slot, st in self._active.items():
            if not st.req.greedy:
                continue
            rem = st.req.max_new_tokens - len(st.req.generated)
            budget = (self.config.spec_draft_len if st.req.spec_force
                      else self._spec_budget(st))
            k_cap = min(self.config.spec_draft_len, budget, rem - 1)
            if k_cap < 1:
                continue
            d = _ngram_draft_host(st.req.prompt + st.req.generated,
                                  self.config.spec_ngram, k_cap)
            if not d and st.req.spec_force:
                # no organic match — repeat the last committed token.
                # The draft is almost surely rejected, but verify commits
                # the correct base token anyway (speculation is
                # lossless), and the verify executable gets compiled,
                # which is the whole point of the probe.
                ctx = st.req.prompt + st.req.generated
                d = [int(ctx[-1])] * k_cap
            if d:
                drafts[slot] = d
                st.spec_stall = 0
        return drafts

    def _plan_tree_budgets(self) -> dict:
        """slot -> draft-token budget for this tree-verify step
        (resident-draft twin of ``_build_drafts``).  Authoritative: the
        pipeline is flushed, so the remaining-token budgets are exact.
        The budget counts DRAFT tokens (tree nodes minus the root); the
        tree planner decides how to spend it between the main chain and
        the depth-1 hedge."""
        plans = {}
        for slot, st in self._active.items():
            if not st.req.greedy:
                continue
            rem = st.req.max_new_tokens - len(st.req.generated)
            k_cap = min(self.config.spec_draft_len, self._spec_budget(st),
                        rem - 1)
            if k_cap < 1:
                continue
            plans[slot] = k_cap
            st.spec_stall = 0
        return plans

    def _draft_prefill(self, slot: int, st: _SlotState) -> None:
        """Absorb a slot's committed context into the resident draft
        model's shadow pool in one dense prefill (padded to the slot
        width: ONE compiled shape per engine), published at the slot's
        target-governed block table.  Runs at admission and after a
        migration install; the pending token and later commits are
        absorbed incrementally by ``_spec_step_tree``.

        Blocks shared through the prefix cache get their draft rows
        rewritten with identical values (same tokens, same deterministic
        draft forward), so concurrent leaseholders are unaffected.
        After a target-side COW the new block's older draft rows are
        stale pad-K/V — harmless: draft output only steers which tokens
        the TARGET verifies, never what commits."""
        ctx = list(st.req.prompt) + list(st.req.generated)
        n = min(st.fill, len(ctx))
        toks = np.zeros((1, self.slots.width), np.int32)
        toks[0, :n] = ctx[:n]
        with device_annotation("draft_prefill"):
            k_small, v_small = _draft_prefill_impl(
                self.draft_cfg, self.draft_params, jnp.asarray(toks),
                max_seq_len=self.slots.width)
            dk, dv = self._draft_kv
            bids = jnp.asarray(self.slots.tables[slot])
            # tpulint: allow[lock-discipline] scheduler-thread-owned;
            # the start() write under the lock precedes thread launch
            self._draft_kv = self._draft_install(dk, dv, k_small, v_small,
                                                 bids)
        st.draft_fill = n

    def _draft_absorb(self, plans: dict, tables) -> dict:
        """Catch each planned slot's draft cache up to ``fill + 1`` rows
        (context plus the pending token) in W-token chunks, and return
        slot -> [top1, top2] candidate continuations of the pending
        token from the final chunk's last real position.

        In speculative steady state every slot is exactly ``acc + 1 <=
        W`` rows behind (the tokens the last verify committed), so this
        is ONE draft forward; slots that took plain steps for a stretch
        (budget collapse, spec tail gate) need more chunks, all through
        the same executable.  Chunk rows land at their real positions in
        the shadow pool — the target's block tables cover them, the
        ledger never hears about it."""
        S = self.config.max_batch_size
        W = self.config.spec_draft_len + 1
        bk = self.slots.pool.block_size
        dk, dv = self._draft_kv
        heads = {}
        while True:
            window = np.zeros((S, W), np.int32)
            fills_d = np.zeros((S,), np.int32)
            bids_d = np.zeros((S * W,), np.int32)  # default: trash
            offs_d = np.zeros((S * W,), np.int32)
            finishing = []
            pending_work = False
            for slot, st in self._active.items():
                if slot not in plans:
                    continue
                seq = list(st.req.prompt) + list(st.req.generated)
                lo = st.draft_fill
                hi = min(st.fill + 1, lo + W)
                fills_d[slot] = lo
                if hi <= lo:
                    continue
                n = hi - lo
                window[slot, :n] = seq[lo:hi]
                for j in range(n):
                    pos = lo + j
                    bids_d[slot * W + j] = \
                        self.slots.tables[slot][pos // bk]
                    offs_d[slot * W + j] = pos % bk
                st.draft_fill = hi
                if hi == st.fill + 1:
                    finishing.append((slot, n))
                else:
                    pending_work = True
            if not finishing and not pending_work:
                break
            with device_annotation("draft_absorb"):
                cand, dk, dv = self._draft_step(
                    self.draft_cfg, self.draft_params, dk, dv, tables,
                    jnp.asarray(window), jnp.asarray(fills_d),
                    jnp.asarray(bids_d), jnp.asarray(offs_d))
            if finishing:
                # tpulint: allow[host-sync] draft candidates feed the
                # host-side tree packer; nothing to overlap
                cand = np.asarray(cand)
                for slot, n in finishing:
                    heads[slot] = cand[slot, n - 1].tolist()
        # tpulint: allow[lock-discipline] scheduler-thread-owned;
        # the start() write under the lock precedes thread launch
        self._draft_kv = (dk, dv)
        return heads

    def _draft_expand(self, chains: dict, tables) -> None:
        """Grow each planned slot's main chain to its budgeted length by
        repeated draft forwards over the chain-so-far at ``fill + 1``
        with ALL-trash landing rows: the verify window's in-window
        splice makes depth >= 2 attention exact without a single shadow-
        pool write, so rejected chains leave nothing to roll back.
        ``chains``: slot -> (token list, target length), mutated in
        place."""
        S = self.config.max_batch_size
        W = self.config.spec_draft_len + 1
        dk, dv = self._draft_kv
        trash = jnp.zeros((S * W,), jnp.int32)
        for depth in range(1, W - 1):
            window = np.zeros((S, W), np.int32)
            fills_d = np.zeros((S,), np.int32)
            growing = []
            for slot, (chain, want) in chains.items():
                if len(chain) != depth or len(chain) >= want:
                    continue
                st = self._active[slot]
                window[slot, :depth] = chain
                fills_d[slot] = st.fill + 1
                growing.append(slot)
            if not growing:
                break
            with device_annotation("draft_expand"):
                cand, dk, dv = self._draft_step(
                    self.draft_cfg, self.draft_params, dk, dv, tables,
                    jnp.asarray(window), jnp.asarray(fills_d), trash, trash)
            # tpulint: allow[host-sync] chain growth is host-driven
            cand = np.asarray(cand)
            for slot in growing:
                chains[slot][0].append(int(cand[slot, depth - 1, 0]))
        # tpulint: allow[lock-discipline] scheduler-thread-owned;
        # the start() write under the lock precedes thread launch
        self._draft_kv = (dk, dv)

    # tpulint: hot-path
    def _spec_step(self, drafts: dict) -> None:
        """One speculative verify iteration (pipeline already flushed):
        feed every slot's ``[pending, draft...]`` window through the
        verify forward, accept the longest draft prefix matching what
        greedy decode would have produced, commit accepted+1 tokens, and
        roll the rest back by simply not advancing ``fill`` past them —
        rejected rows sit beyond the fill level, masked out of
        attention, and later steps overwrite them in place.  No block
        churn: the row targeting went through the same
        ``append_block_id`` path as plain decode, COW included."""
        assert self._inflight is None
        it0 = time.perf_counter()
        t = self.metrics.timers("serving-decode", 2)
        t.start()
        S = self.config.max_batch_size
        W = self.config.spec_draft_len + 1
        window = np.zeros((S, W), np.int32)
        fills = np.zeros((S,), np.int32)
        seeds = np.zeros((S,), np.uint32)
        counters = np.zeros((S,), np.int32)
        greedy = np.ones((S,), bool)
        temps = np.ones((S,), np.float32)
        top_ks = np.zeros((S,), np.int32)
        top_ps = np.zeros((S,), np.float32)
        aslots = np.full((S,), -1, np.int32)
        bids = np.zeros((S * W,), np.int32)  # default: the trash block
        offs = np.zeros((S * W,), np.int32)
        bk = self.slots.pool.block_size
        for slot, st in self._active.items():
            d = drafts.get(slot, ())
            window[slot, 0] = st.pending
            window[slot, 1:1 + len(d)] = d
            fills[slot] = st.fill
            seeds[slot] = st.req.seed
            counters[slot] = st.count
            greedy[slot] = st.req.greedy
            temps[slot] = st.req.temperature
            top_ks[slot] = st.req.top_k
            top_ps[slot] = st.req.top_p
            aslots[slot] = st.adapter_slot
            st.fresh = False
            # every window row that may commit needs its destination
            # block resolved (lazily allocated / COWed) BEFORE the
            # tables snapshot, exactly like the plain path's single row;
            # rows past the draft stay routed to the trash block
            for j in range(len(d) + 1):
                pos = st.fill + j
                self.slots.append_block_id(slot, pos)
                bids[slot * W + j] = self.slots.tables[slot][pos // bk]
                offs[slot * W + j] = pos % bk
        tables = jnp.asarray(self.slots.tables)

        t0 = time.perf_counter()
        if self._last_dispatch_t is not None:
            wall = t0 - self._last_dispatch_t
            if wall > 0 and self._last_ready_t is not None:
                gap = min(wall, t0 - self._last_ready_t)
                self.metrics.observe_step_breakdown(gap_frac=gap / wall)
        self._last_dispatch_t = t0
        # what the device's predicate in _sample_slots will read
        sampling = not greedy.all()
        self.metrics.inc_step(
            "fallback", self._precision_route, sampling)
        with device_annotation("verify"):
            g_tok, g_lp, k_pool, v_pool = self._verify(
                self.cfg, self.params, self.slots.k_pool,
                self.slots.v_pool, tables, jnp.asarray(window),
                jnp.asarray(fills), jnp.asarray(bids), jnp.asarray(offs),
                jnp.asarray(seeds), jnp.asarray(counters),
                jnp.asarray(greedy), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps),
                **self._lora_args(aslots))
        self.slots.set_pools(k_pool, v_pool)
        # tpulint: allow[host-sync] verify steps are synchronous by
        # design: the next dispatch's fill vector depends on how many
        # drafts were accepted, so there is nothing to overlap
        g_tok = np.asarray(g_tok)
        g_lp = np.asarray(g_lp)  # tpulint: allow[host-sync] same fetch
        t_ready = time.perf_counter()
        self._last_ready_t = t_ready
        device_s = t_ready - t0
        if self.trace.enabled:
            # a verify step is synchronous and takes no reading between
            # its call and its fetch: ``fetch`` holds the call's dispatch
            self.trace.add("step_inputs", it0, t0,
                           args={"iter": self._iter,
                                 "live": len(self._active)})
            self.trace.add("fetch", t0, t_ready,
                           args={"iter": self._iter,
                                 "dispatched": self._iter})

        total_committed = 0
        proposed = 0
        accepted_total = 0
        per_slot_committed = []
        slot_ewmas = {}
        for slot, st in list(self._active.items()):
            d = drafts.get(slot, ())
            k_i = len(d)
            acc = 0
            # tpulint: allow[host-sync] numpy row, fetched above
            while acc < k_i and int(g_tok[slot, acc]) == d[acc]:
                acc += 1
            proposed += k_i
            accepted_total += acc
            if k_i:
                st.spec_ewma = ((1.0 - _SPEC_EWMA_ALPHA) * st.spec_ewma
                                + _SPEC_EWMA_ALPHA * acc / k_i)
                slot_ewmas[slot] = st.spec_ewma
            # dispatch-time semantics, span-sized: rows for the pending
            # token and the accepted drafts landed; the bonus token's
            # row is the NEXT step's write
            st.fill += acc + 1
            st.count += acc + 1
            st.fresh = True
            committed_here = 0
            for j in range(acc + 1):
                if self._active.get(slot) is not st:
                    break  # EOS / budget retired the slot mid-window
                # tpulint: allow[host-sync] numpy row, fetched above
                st.pending = int(g_tok[slot, j])
                committed_here += 1
                # tpulint: allow[host-sync] numpy row, fetched above
                self._commit_token(slot, st.pending, float(g_lp[slot, j]))
            total_committed += committed_here
            if k_i:
                per_slot_committed.append(committed_here)
            if self.trace.enabled:
                self.trace.add("decode", t0, t_ready,
                               request_id=st.req.rid, tid=st.req.id,
                               args={"slot": slot, "iter": self._iter,
                                     "spec": True,
                                     "proposed": k_i, "accepted": acc,
                                     "committed": committed_here})
        t.stop()
        self.metrics.observe_spec_step(proposed, accepted_total,
                                       per_slot_committed, source="ngram",
                                       slot_ewmas=slot_ewmas)
        self.metrics.observe_decode_iteration(total_committed, device_s)
        self.metrics.observe_step_breakdown(device_s=device_s)
        t_end = time.perf_counter()
        host_s = max(0.0, (t_end - it0) - (t_ready - t0))
        self.metrics.observe_step_breakdown(host_s=host_s)
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        if self.trace.enabled:
            self.trace.add("commit", t_ready, t_end,
                           args={"iter": self._iter,
                                 "committed": total_committed})
        self.trace.add(
            "engine_step", it0, time.perf_counter(), tid=0,
            args={"iter": self._iter, "batch": len(drafts),
                  "route": "spec_fallback",
                  "sampling": sampling,
                  "pipelined": False, "proposed": proposed,
                  "accepted": accepted_total})

    # tpulint: hot-path
    def _spec_step_tree(self, plans: dict) -> None:
        """One resident-draft tree-verify iteration (pipeline already
        flushed).  Each planned slot spends its ``k_i``-token budget on
        a candidate tree rooted at the pending token: a main chain from
        the draft model's repeated top-1, plus — when the budget affords
        it (``k_i >= 3``) — a depth-1 HEDGE leaf from the draft's
        second choice, which rescues one token on exactly the steps
        where chain speculation dies at the first position.  The target
        scores every node in ONE tree-verify forward (each node attends
        only its root path), and the commit is the longest root path
        whose tokens match the target's argmax, plus the bonus token
        from its deepest node — bitwise what plain decode would have
        produced.

        Rollback stays zero-churn: node K/V rows land NODE-indexed at
        ``fill + node``, rejected rows sit beyond the advanced fill
        (masked, overwritten in place later), and only a hedge
        acceptance needs a row move to re-pack the surviving path
        depth-contiguously — dispatched BEFORE commits so a retirement
        can never free the blocks under a pending move.  Riders (non-
        greedy slots, collapsed budgets) take the root-only path with
        unchanged seed/counter streams, exactly like ``_spec_step``."""
        assert self._inflight is None
        it0 = time.perf_counter()
        t = self.metrics.timers("serving-decode", 2)
        t.start()
        S = self.config.max_batch_size
        W = self.config.spec_draft_len + 1
        bk = self.slots.pool.block_size
        # block targeting before anything touches the device: a slot's
        # nodes land node-indexed at rows fill..fill+k_i, and the draft
        # absorb writes the pending token's shadow row at fill, so every
        # one of those blocks must exist (lazily allocated / COWed)
        # before the single tables snapshot both models share
        for slot, st in self._active.items():
            for j in range(plans.get(slot, 0) + 1):
                self.slots.append_block_id(slot, st.fill + j)
        tables = jnp.asarray(self.slots.tables)

        # draft phase: absorb committed tokens into the shadow pool,
        # fork the tree heads, grow the main chains
        heads = self._draft_absorb(plans, tables)
        chains = {}
        hedges = {}
        for slot, k_i in plans.items():
            top = heads[slot]    # host ints (tolist in _draft_absorb)
            if k_i >= 3:
                chains[slot] = ([top[0]], k_i - 1)
                hedges[slot] = top[1]
            else:
                chains[slot] = ([top[0]], k_i)
        self._draft_expand(chains, tables)

        # pack the fixed-shape tree operands (host-side, numpy)
        window = np.zeros((S, W), np.int32)
        depths = np.zeros((S, W), np.int32)
        anc = np.zeros((S, W, W), np.int32)
        fills = np.zeros((S,), np.int32)
        seeds = np.zeros((S,), np.uint32)
        counters = np.zeros((S,), np.int32)
        greedy = np.ones((S,), bool)
        temps = np.ones((S,), np.float32)
        top_ks = np.zeros((S,), np.int32)
        top_ps = np.zeros((S,), np.float32)
        aslots = np.full((S,), -1, np.int32)
        bids = np.zeros((S * W,), np.int32)  # default: the trash block
        offs = np.zeros((S * W,), np.int32)
        n_real = {}
        for slot, st in self._active.items():
            window[slot, 0] = st.pending
            fills[slot] = st.fill
            seeds[slot] = st.req.seed
            counters[slot] = st.count
            greedy[slot] = st.req.greedy
            temps[slot] = st.req.temperature
            top_ks[slot] = st.req.top_k
            top_ps[slot] = st.req.top_p
            # the (base) draft model proposed this tree, but acceptance
            # is judged under the REQUESTER's adapter: the target verify
            # applies the slot's arena columns, so committed tokens are
            # bitwise what adapter-decorated plain decode would emit
            aslots[slot] = st.adapter_slot
            st.fresh = False
            # node list in BFS order (depths non-decreasing, parents
            # before children, deepest node last — the kernel's per-row
            # iteration bound reads the LAST column's position)
            node_dep = [0]
            parent = [0]
            chain_nodes = [0]     # chain node index at each depth
            hedge = hedges.get(slot)
            chain = chains[slot][0] if slot in chains else []
            for t_, tok in enumerate(chain):
                node_dep.append(t_ + 1)
                parent.append(chain_nodes[t_])
                chain_nodes.append(len(node_dep) - 1)
                window[slot, len(node_dep) - 1] = tok
                if t_ == 0 and hedge is not None:
                    node_dep.append(1)
                    parent.append(0)
                    window[slot, len(node_dep) - 1] = hedge
            n = len(node_dep)
            n_real[slot] = n
            for j in range(1, n):
                p = parent[j]
                for dd in range(node_dep[j] - 1, -1, -1):
                    anc[slot, j, dd] = p
                    p = parent[p]
            depths[slot, :n] = node_dep
            # trailing pad nodes: depth pinned to the slot's max real
            # depth (keeps BFS order and the deepest-last clamp valid),
            # ancestor row borrowed from the deepest real node so every
            # gather index stays in range; outputs ignored, rows trashed
            depths[slot, n:] = node_dep[-1]
            anc[slot, n:, :] = anc[slot, n - 1, :]
            for j in range(n):
                pos = st.fill + j
                bids[slot * W + j] = self.slots.tables[slot][pos // bk]
                offs[slot * W + j] = pos % bk

        t0 = time.perf_counter()
        if self._last_dispatch_t is not None:
            wall = t0 - self._last_dispatch_t
            if wall > 0 and self._last_ready_t is not None:
                gap = min(wall, t0 - self._last_ready_t)
                self.metrics.observe_step_breakdown(gap_frac=gap / wall)
        self._last_dispatch_t = t0
        # what the device's predicate in _sample_slots will read
        sampling = not greedy.all()
        self.metrics.inc_step(
            "fallback", self._precision_route, sampling)
        with device_annotation("verify_tree"):
            g_tok, g_lp, k_pool, v_pool = self._verify_tree(
                self.cfg, self.params, self.slots.k_pool,
                self.slots.v_pool, tables, jnp.asarray(window),
                jnp.asarray(depths), jnp.asarray(anc),
                jnp.asarray(fills), jnp.asarray(bids), jnp.asarray(offs),
                jnp.asarray(seeds), jnp.asarray(counters),
                jnp.asarray(greedy), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps),
                **self._lora_args(aslots))
        # tpulint: allow[host-sync] verify steps are synchronous by
        # design: the accepted path decides the next fill vector AND
        # whether rows must move, so there is nothing to overlap
        g_tok = np.asarray(g_tok)
        g_lp = np.asarray(g_lp)  # tpulint: allow[host-sync] same fetch
        t_ready = time.perf_counter()
        self._last_ready_t = t_ready
        device_s = t_ready - t0
        if self.trace.enabled:
            # as on the n-gram verify step; no ``step_inputs``: what lies
            # before ``t0`` holds the draft model's own calls and fetches
            self.trace.add("fetch", t0, t_ready,
                           args={"iter": self._iter,
                                 "dispatched": self._iter})

        # accept walk (host): longest root path matching target argmax
        paths = {}
        src_b = np.zeros((S * W,), np.int32)   # default trash -> trash
        src_o = np.zeros((S * W,), np.int32)
        dst_b = np.zeros((S * W,), np.int32)
        dst_o = np.zeros((S * W,), np.int32)
        any_moves = False
        for slot, st in self._active.items():
            cur, acc, path = 0, 0, [0]
            while True:
                # tpulint: allow[host-sync] numpy row, fetched above
                tgt = int(g_tok[slot, cur])
                nxt = -1
                for c in range(1, n_real.get(slot, 1)):
                    if (depths[slot, c] == acc + 1
                            and anc[slot, c, acc] == cur
                            and window[slot, c] == tgt):
                        nxt = c
                        break
                if nxt < 0:
                    break
                cur = nxt
                path.append(nxt)
                acc += 1
            paths[slot] = path
            # depth-contiguous re-pack of the accepted path: only a node
            # whose index differs from its depth (the hedge leaf) moved
            for t_ in range(1, acc + 1):
                p_t = path[t_]
                if p_t == t_:
                    continue
                any_moves = True
                src = st.fill + p_t
                dst = st.fill + t_
                src_b[slot * W + t_] = self.slots.tables[slot][src // bk]
                src_o[slot * W + t_] = src % bk
                dst_b[slot * W + t_] = self.slots.tables[slot][dst // bk]
                dst_o[slot * W + t_] = dst % bk
        if any_moves:
            with device_annotation("spec_compact"):
                k_pool, v_pool = self._move_rows(
                    k_pool, v_pool, jnp.asarray(src_b),
                    jnp.asarray(src_o), jnp.asarray(dst_b),
                    jnp.asarray(dst_o))
        self.slots.set_pools(k_pool, v_pool)

        total_committed = 0
        proposed = 0
        accepted_total = 0
        per_slot_committed = []
        slot_ewmas = {}
        for slot, st in list(self._active.items()):
            path = paths[slot]
            acc = len(path) - 1
            k_i = plans.get(slot, 0)
            proposed += k_i
            accepted_total += acc
            if k_i:
                chain_len = chains[slot][1]
                st.spec_ewma = ((1.0 - _SPEC_EWMA_ALPHA) * st.spec_ewma
                                + _SPEC_EWMA_ALPHA * acc / chain_len)
                slot_ewmas[slot] = st.spec_ewma
            # dispatch-time semantics, span-sized: rows for the pending
            # token and the accepted path landed (and were re-packed);
            # the bonus token's row is the NEXT step's write
            st.fill += acc + 1
            st.count += acc + 1
            st.fresh = True
            committed_here = 0
            for t_ in range(acc + 1):
                if self._active.get(slot) is not st:
                    break  # EOS / budget retired the slot mid-path
                # tpulint: allow[host-sync] numpy row, fetched above
                st.pending = int(g_tok[slot, path[t_]])
                committed_here += 1
                # tpulint: allow[host-sync] numpy row, fetched above
                lp = float(g_lp[slot, path[t_]])
                self._commit_token(slot, st.pending, lp)
            total_committed += committed_here
            if k_i:
                per_slot_committed.append(committed_here)
            if self.trace.enabled:
                self.trace.add("decode", t0, t_ready,
                               request_id=st.req.rid, tid=st.req.id,
                               args={"slot": slot, "iter": self._iter,
                                     "spec": True,
                                     "tree": True, "proposed": k_i,
                                     "accepted": acc,
                                     "committed": committed_here})
        t.stop()
        self.metrics.observe_spec_step(proposed, accepted_total,
                                       per_slot_committed,
                                       source="model",
                                       slot_ewmas=slot_ewmas)
        self.metrics.observe_decode_iteration(total_committed, device_s)
        self.metrics.observe_step_breakdown(device_s=device_s)
        t_end = time.perf_counter()
        host_s = max(0.0, (t_end - it0) - (t_ready - t0))
        self.metrics.observe_step_breakdown(host_s=host_s)
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        if self.trace.enabled:
            self.trace.add("commit", t_ready, t_end,
                           args={"iter": self._iter,
                                 "committed": total_committed})
        self.trace.add(
            "engine_step", it0, time.perf_counter(), tid=0,
            args={"iter": self._iter, "batch": len(plans),
                  "route": "spec_fallback",
                  "sampling": sampling,
                  "pipelined": False, "tree": True, "proposed": proposed,
                  "accepted": accepted_total})

    # tpulint: hot-path
    def _dispatch_decode(self) -> _Inflight:
        assert self.slots is not None
        t_in = time.perf_counter()
        S = self.config.max_batch_size
        overrides = np.zeros((S,), np.int32)
        override_mask = np.zeros((S,), bool)
        fills = np.zeros((S,), np.int32)
        seeds = np.zeros((S,), np.uint32)
        counters = np.zeros((S,), np.int32)
        greedy = np.ones((S,), bool)
        temps = np.ones((S,), np.float32)
        top_ks = np.zeros((S,), np.int32)
        top_ps = np.zeros((S,), np.float32)
        aslots = np.full((S,), -1, np.int32)  # -1 rows: zero LoRA delta
        live = np.zeros((S,), bool)   # hybrid: whose state the step moves
        for slot, st in self._active.items():
            live[slot] = True
            fills[slot] = st.fill
            seeds[slot] = st.req.seed
            counters[slot] = st.count
            greedy[slot] = st.req.greedy
            temps[slot] = st.req.temperature
            top_ks[slot] = st.req.top_k
            top_ps[slot] = st.req.top_p
            aslots[slot] = st.adapter_slot
            overrides[slot] = st.pending
            if st.fresh:
                override_mask[slot] = True
                st.fresh = False
            # lazy paged growth: make sure the block receiving this step's
            # K/V row exists before the tables snapshot (reservation-backed,
            # so this cannot fail mid-flight)
            self.slots.append_block_id(slot, st.fill)

        t0 = time.perf_counter()
        if self._last_dispatch_t is not None:
            wall = t0 - self._last_dispatch_t
            if wall > 0:
                # time the device sat idle between steps: zero when a step
                # was still in flight, else the gap since its results
                # arrived (= host bookkeeping on the critical path)
                gap = (0.0 if self._inflight is not None
                       or self._last_ready_t is None
                       else min(wall, t0 - self._last_ready_t))
                self.metrics.observe_step_breakdown(gap_frac=gap / wall)
        self._last_dispatch_t = t0

        # what the device's predicate in _sample_slots will read (on a pp
        # mesh each group's own: a step counts once if any group samples)
        sampling = not greedy.all()
        self.metrics.inc_step(self._decode_route, self._precision_route,
                              sampling)
        # Microbatch-interleaved dispatch: the slot batch is split into
        # G contiguous groups (G = pp on a pp>1 mesh, else 1) whose
        # decode calls chain through the donated KV pool — group g+1's
        # dispatch depends on group g's pool output, so under async
        # dispatch the stages of the layer-sharded pipeline overlap
        # distinct groups instead of idling.  G identical [S/G] shapes
        # share one executable, and per-row math + disjoint-row pool
        # scatters keep the tokens bitwise equal to a single full-batch
        # dispatch.  G == 1 degenerates to exactly the old behavior
        # (one [S] dispatch, _Inflight.tok a plain array).
        G = self._decode_groups
        gs = S // G
        k_pool, v_pool = self.slots.k_pool, self.slots.v_pool
        rec = self.slots.rec
        state = ({} if rec is None       # (a hybrid stack has no groups)
                 else dict(rec=rec, live=jnp.asarray(live)))
        toks, tok_lps = [], []
        with device_annotation("decode"):
            for g in range(G):
                sl = slice(g * gs, (g + 1) * gs)
                prev_tok = None
                if self._inflight is not None:
                    prev_tok = (self._inflight.tok[g] if G > 1
                                else self._inflight.tok)
                if prev_tok is None:
                    # no device-resident tokens: every active slot's
                    # pending value is host-known (fresh admission,
                    # post-pause/post-sync commit)
                    pending = jnp.asarray(overrides[sl])
                elif override_mask[sl].any():
                    pending = _merge_pending(prev_tok,
                                             jnp.asarray(override_mask[sl]),
                                             jnp.asarray(overrides[sl]))
                else:
                    pending = prev_tok  # pure device->device handoff
                tok, tok_lp, k_pool, v_pool, rec = self._decode(
                    self.cfg, self.params, k_pool, v_pool,
                    jnp.asarray(self.slots.tables[sl]),
                    pending, jnp.asarray(fills[sl]),
                    jnp.asarray(seeds[sl]), jnp.asarray(counters[sl]),
                    jnp.asarray(greedy[sl]), jnp.asarray(temps[sl]),
                    jnp.asarray(top_ks[sl]), jnp.asarray(top_ps[sl]),
                    allow_paged=self._allow_paged,
                    **self._lora_args(aslots[sl]), **state)
                toks.append(tok)
                tok_lps.append(tok_lp)
        self.slots.set_pools(k_pool, v_pool, rec)
        if self._counts_ssm:
            self.metrics.add_ssm_positions("decode", len(self._active))
        try:  # start the host copies now so they overlap the next dispatch
            for tok, tok_lp in zip(toks, tok_lps):
                tok.copy_to_host_async()
                tok_lp.copy_to_host_async()
        except AttributeError:  # backend without async transfers
            pass
        snapshot = dict(self._active)
        for st in snapshot.values():
            st.fill += 1   # the fed token's K/V row lands this step
            st.count += 1  # one more token sampled (possibly speculative)
        # tpulint: allow[host-sync] fills is host numpy (built above)
        positions = int(fills.sum())
        if self._kv_readers:
            self.metrics.add_kv_walks(positions, self._kv_readers)
        walk = (self._walk_arg(fills) if self.trace.enabled
                and (self._latent or self._kv_readers) else None)
        if self.trace.enabled:
            self.trace.add("step_inputs", t_in, t0,
                           args={"iter": self._iter, "live": len(snapshot)})
            self.trace.add("dispatch", t0, time.perf_counter(),
                           args={"iter": self._iter})
        if G == 1:
            toks, tok_lps = toks[0], tok_lps[0]
        return _Inflight(toks, tok_lps, snapshot, t0, sampling, positions,
                         self._iter, walk)

    def _walk_arg(self, fills: np.ndarray) -> dict:
        """``walk_steps`` / ``walk_prefetched`` of a step's decode spans,
        beside ``live_positions``: ONE call of the step's paged walk over
        ``fills`` in grid steps with a live row, and how many of them
        found their first copies already in flight
        (kernels/flash_decode.py:walk_counts; a stack that carries them
        is served without a mesh, so the call's grid is the pool's).  The
        latent rows' walk (kernels/mla_decode.py) is a grid step a slot,
        each starting its own first copy."""
        if self._latent:
            return {"walk_steps": int(np.count_nonzero(fills)),
                    "walk_prefetched": 0}
        ring = ({"ring_rows": int(np.minimum(fills, self._ring_window).sum())}
                if self._ring_window else {})
        if self._walk_grid is None:
            self._walk_grid = pool_walk(
                jax.tree.leaves(self.slots.k_pool)[0],
                jax.tree.leaves(self.slots.v_pool)[0],
                self.slots.tables.shape[1])[:2]
        steps, prefetched = walk_counts(fills, *self._walk_grid)
        return {"walk_steps": steps, "walk_prefetched": prefetched, **ring}

    # tpulint: hot-path
    def _process_step_results(self, step: _Inflight) -> float:
        """Sync a dispatched step's tokens to the host and commit them.
        Returns the wall time spent blocked on the device."""
        t_fetch = time.perf_counter()
        # tpulint: allow[host-sync] THE deliberate scheduling point: the
        # one place per iteration the host waits for sampled tokens (the
        # copy was started async at dispatch, so pipelined mode overlaps
        # it with the next step's execution).  Microbatch-interleaved
        # steps carry per-group lists over contiguous slot ranges, so
        # concatenation restores the slot-indexed [S] vector.
        if isinstance(step.tok, list):
            # tpulint: allow[host-sync] the deliberate fetch, group form
            tok = np.concatenate([np.asarray(t) for t in step.tok])
            # tpulint: allow[host-sync] same fetch: arrives with tok
            tok_lp = np.concatenate([np.asarray(t) for t in step.tok_lp])
        else:
            # tpulint: allow[host-sync] the deliberate fetch (see above)
            tok = np.asarray(step.tok)
            tok_lp = np.asarray(step.tok_lp)  # tpulint: allow[host-sync] same fetch: arrives with tok, no extra sync
        t_ready = time.perf_counter()
        self._last_ready_t = t_ready
        device_s = t_ready - step.t_dispatch
        # ahead of this step's ``decode`` spans, which cover the same wait
        # (dispatch -> tokens on the host) and more: an idle gap the host
        # sat out here is named ``fetch`` by a reader that breaks a tie by
        # the order of the ring
        if self.trace.enabled:
            self.trace.add("fetch", t_fetch, t_ready,
                           args={"iter": self._iter,
                                 "dispatched": step.iter})
        committed = 0
        step_arg = self._step_arg
        if (self._latent or self._kv_readers) and self.trace.enabled:
            step_arg = dict(step_arg, live_positions=step.positions,
                            **step.walk)
        for slot, st in step.slots.items():
            if self._active.get(slot) is not st:
                # the slot retired (EOS/budget/cancel/deadline) or was
                # re-admitted after this step dispatched: its sampled
                # token is speculative — masked, never committed/streamed
                continue
            committed += 1
            # tpulint: allow[host-sync] tok is already host numpy (the
            # fetch above); int() here is a free scalar conversion
            st.pending = int(tok[slot])
            # with no newer step in flight the device token vector is
            # gone; the next dispatch must feed this host value
            st.fresh = self._inflight is None
            if self.trace.enabled:
                self.trace.add("decode", step.t_dispatch, t_ready,
                               request_id=st.req.rid, tid=st.req.id,
                               args={"slot": slot, "iter": self._iter,
                                     "token_index": len(st.req.generated),
                                     "live": len(step.slots),
                                     **step_arg})
            # tpulint: allow[host-sync] tok_lp is host numpy; no device
            # round-trip
            self._commit_token(slot, st.pending, float(tok_lp[slot]))
        self.metrics.observe_decode_iteration(committed, device_s)
        self.metrics.observe_step_breakdown(device_s=device_s)
        if self.trace.enabled:
            self.trace.add("commit", t_ready, time.perf_counter(),
                           args={"iter": self._iter, "committed": committed})
        return t_ready - t_fetch

    # tpulint: hot-path
    def _flush_inflight(self) -> None:
        """Drain the in-flight step (pause/idle paths).  If every slot it
        covered has retired, all its tokens are speculative: drop the step
        without even syncing it."""
        prev, self._inflight = self._inflight, None
        if prev is None:
            return
        if any(self._active.get(s) is st for s, st in prev.slots.items()):
            self._process_step_results(prev)

    def _commit_token(self, slot: int, token: int, logprob: float) -> None:
        """Append a sampled token to the slot's request, stream it, and
        retire the slot on EOS / budget."""
        st = self._active[slot]
        req = st.req
        req.generated.append(token)
        if req.return_logprobs:
            req.logprobs.append(logprob)
        if req.first_token_time is None:
            req.first_token_time = time.perf_counter()
            ttft = req.first_token_time - req.submit_time
            self.metrics.observe_ttft(ttft)
            EVENT_LOG.emit("engine", "first_token", request_id=req.rid,
                           ttft_s=round(ttft, 6))
        if req.on_token is not None:
            try:
                req.on_token(token)
            except Exception:  # noqa: BLE001 — a client callback must not
                pass           # take the scheduler down
        if req.use_eos_stop and token == req.eos_id:
            self._retire(slot, "eos")
        elif len(req.generated) >= req.max_new_tokens:
            self._retire(slot, "length")

    def _retire(self, slot: int, reason: str) -> None:
        st = self._active.pop(slot)
        self.trace.instant("retire", request_id=st.req.rid, tid=st.req.id,
                           args={"slot": slot, "reason": reason})
        if self.prefix_cache is not None:
            # donate the slot's block-aligned prompt prefix back (a pure
            # ref-count adoption of blocks the slot already owns) before
            # the slot releases them, then unpin the admission lease (so
            # the request's own prefix blocks were protected throughout).
            # Adapter requests never offer: their K/V rows carry the
            # adapter's deltas and must not seed base-model prefills.
            if st.req.adapter_id is None:
                self.prefix_cache.offer(st.req.prompt,
                                        self.slots.tables[slot])
            self.prefix_cache.release(st.lease)
            self.metrics.set_gauges(
                prefix_blocks=self.prefix_cache.blocks)
        self._release_adapter(st.req)
        self.slots.release(slot)
        self._finish(st.req, reason)
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots)

    def _update_pool_gauges(self) -> None:
        s = self.slots.pool.stats()
        self.metrics.set_gauges(blocks_free=s["blocks_free"],
                                blocks_used=s["blocks_used"],
                                kv_cache_util=s["kv_cache_util"])
        if self.host_tier is not None:
            self.metrics.set_gauges(
                host_blocks_used=self.host_tier.host_used,
                host_blocks_free=self.host_tier.host_free)

    def kv_snapshot(self) -> dict:
        """Debug view of the paged KV state (GET /kv,
        tools/dump_kv_pool.py): pool stats, per-slot block tables + fills,
        ref counts, fragmentation (live tokens / allocated tokens slack),
        and — when a host tier is configured — host arena occupancy plus
        per-request swapped-out block counts, so the snapshot reports ALL
        resident KV, not just the HBM share.  On a pp>1 mesh a
        ``stages`` section breaks the pool down per pipeline stage: each
        stage's layer range, device ids, and its stage-local ledger view
        (the block ledger is host-global and block ids are identical on
        every stage, so a healthy engine shows the SAME free/used counts
        on all stages — an imbalance means a stage's pool diverged).
        Best-effort under concurrent scheduling — served from any thread
        without locking, like /metrics and /trace."""
        if self.slots is None:
            return {"pool": None, "slots": {}}
        fills = {s: st.fill for s, st in dict(self._active).items()}
        snap = self.slots.snapshot(fills)
        if self.mesh is not None:
            from ..parallel import mesh as mesh_lib
            pp = mesh_lib.pipeline_parallel_size(self.mesh)
            if pp > 1 and self.cfg.num_layers % pp == 0:
                pool_stats = snap.get("pool") or {}
                axis = list(self.mesh.axis_names).index(
                    mesh_lib.PIPELINE_AXIS)
                devs = np.asarray(self.mesh.devices)
                snap["stages"] = [
                    {"stage": s,
                     "layers": [lo, hi],
                     "devices": sorted(
                         d.id for d in devs.take(s, axis=axis).ravel()),
                     "blocks_free": pool_stats.get("blocks_free"),
                     "blocks_used": pool_stats.get("blocks_used"),
                     "fragmentation": snap.get("fragmentation")}
                    for s, (lo, hi) in enumerate(
                        mesh_lib.stage_layer_ranges(self.cfg.num_layers,
                                                    pp))]
        if self.host_tier is not None:
            snap["host_tier"] = self.host_tier.stats()
            snap["host_tier"]["suspended"] = {
                sus.req.rid: {"blocks": sus.n_live,
                              "priority": sus.req.priority,
                              "generated": len(sus.req.generated)}
                for sus in list(self._suspended.values())}
        return snap

    def _finish(self, req: _Request, reason: str) -> None:
        req.result = FinishedRequest(
            tokens=req.prompt + req.generated,
            prompt_len=len(req.prompt),
            finish_reason=reason,
            logprobs=list(req.logprobs) if req.return_logprobs else None)
        if reason == "cancelled":
            self.metrics.inc("cancelled")
        elif reason == "timeout":
            self.metrics.inc("timeouts")
        elif reason != "error":
            self.metrics.inc("completed")
            self.metrics.observe_e2e(time.perf_counter() - req.submit_time)
        # availability SLO: timeouts and scheduler errors are the server's
        # fault; eos/length/cancelled finishes are successful service
        self.metrics.observe_finish(reason not in ("timeout", "error"))
        EVENT_LOG.emit("engine", "finished", request_id=req.rid,
                       reason=reason, generated=len(req.generated),
                       e2e_s=round(time.perf_counter() - req.submit_time, 6))
        req.done_event.set()
        self._notify_drain()

    # -- KV-block shipping (disaggregated prefill/decode, migration) -------

    def _maybe_handoff(self, slot: int) -> None:
        """Prefill-role post-admission hook: hand the freshly prefilled
        request to the router's ship handler.  Runs on the scheduler
        thread right after the first token committed (so TTFT is paid on
        the compute-tuned prefill engine).  No handler, a one-token
        request that already retired, or a handler failure all leave the
        request decoding locally — shipping is an optimization, never a
        correctness dependency."""
        if self.config.role != "prefill" or self._ship_handler is None:
            return
        if self._active.get(slot) is None:  # retired on its first token
            return
        try:
            ship = self._extract_slot(slot)
        except OSError as e:  # export I/O failed BEFORE any ledger
            # mutation (_extract_slot exports first): the slot is intact,
            # the request simply keeps decoding here
            import logging

            logging.getLogger(__name__).warning(
                "KV export failed; decoding slot %d locally: %r", slot, e)
            self.metrics.inc("ship_failures_total")
            EVENT_LOG.emit("engine", "ship_export_failed", slot=slot,
                           error=repr(e))
            return
        try:
            self._ship_handler(ship)
        except Exception:  # noqa: BLE001 — last resort: decode locally
            import logging

            logging.getLogger(__name__).exception(
                "ship handler failed; decoding %s locally", ship.request_id)
            self.metrics.inc("ship_failures_total")
            self.install_shipment(ship)
            self.slots.pool.end_ship(ship.ship_id)

    def extract_request(self, req: _Request) -> Optional[KVShipment]:
        """Pull an actively decoding request out of this engine (live
        migration).  Scheduler thread only — route through
        ``call_in_scheduler`` from anywhere else.  Returns None when the
        request is not in an extractable state (queued, mid-prefill,
        parked, or already finished)."""
        self._flush_inflight()  # may retire the slot (EOS/budget/cancel)
        for slot, st in self._active.items():
            if st.req is req:
                return self._extract_slot(slot)
        return None

    def _extract_slot(self, slot: int) -> KVShipment:
        """Export a slot's KV blocks + scheduling state into a shipment.

        The handoff is ledger-atomic: ``begin_ship`` increfs every block
        *before* the slot's table refs drop, so counts never touch zero
        mid-transfer and the LedgerSanitizer sees the shipment as the
        owner until ``end_ship``.  The admission lease is released
        without a prefix-cache ``offer`` — the request is moving, not
        retiring — so shared prefix blocks stay pinned only by the cache
        itself (the shipment carries a verbatim copy of their rows)."""
        if self.slots.rec is not None:
            raise RuntimeError(
                "a hybrid stack's slot cannot be shipped: the shipment "
                "carries K/V blocks and no recurrent state")
        self._flush_inflight()
        st = self._active[slot]
        req = st.req
        pool = self.slots.pool
        row = self.slots.tables[slot]
        bids: List[int] = []
        for b in row:  # non-TRASH entries form a prefix of the row
            if int(b) == BlockPool.TRASH:
                break
            bids.append(int(b))
        # export BEFORE any ledger mutation: an export I/O failure
        # (chaos "ship-export") propagates with the slot untouched, so
        # the caller can simply keep decoding here
        k_dense, v_dense = pool.export_blocks(bids, self.slots.table_blocks)
        self._active.pop(slot)
        nbytes = sum(int(x.nbytes)
                     for x in jax.tree.leaves((k_dense, v_dense)))
        ship_id = f"ship-{next(_SHIP_IDS)}"
        pool.begin_ship(ship_id, req.rid, bids, nbytes)
        if self.prefix_cache is not None:
            self.prefix_cache.release(st.lease)
        # the destination re-pins the adapter at install (raising — so
        # the router reinstalls here — when it can't); dropping our pin
        # AFTER export is safe: eviction only reuses arena columns, the
        # host-side factors stay registered
        self._release_adapter(req)
        self.slots.release(slot)
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        self.metrics.inc("ships_out_total")
        return KVShipment(
            ship_id=ship_id, request_id=req.rid,
            k_dense=k_dense, v_dense=v_dense,
            bids=bids, n_live=len(bids), nbytes=nbytes,
            meta={"req": req, "fill": st.fill, "count": st.count,
                  "pending": st.pending, "spec_ewma": st.spec_ewma,
                  "spec_stall": st.spec_stall,
                  "draft_fill": st.draft_fill,
                  "adapter_id": req.adapter_id})

    def install_shipment(self, ship: KVShipment) -> int:
        """Adopt a shipment into a free slot of this engine.  Scheduler
        thread only (``call_in_scheduler``).  Raises when no slot or no
        block reservation is available — the caller (router) reinstalls
        on the source, which cannot fail: the source just freed the
        capacity and the shipment's refs still pin the original blocks.

        The decode trajectory continues bitwise: block contents moved
        verbatim, and the sampling RNG folds on the request's own
        (seed, counter) — both in ``ship.meta`` — never on slot index,
        batch composition, or which engine runs the step."""
        if self.slots.rec is not None:
            raise RuntimeError(
                "a hybrid stack cannot adopt a shipment: it carries K/V "
                "blocks and no recurrent state")
        req: _Request = ship.meta["req"]
        pool = self.slots.pool
        if req.adapter_id is not None:
            # chaos site BEFORE any allocation: an injected adapter-
            # install failure propagates with this engine's ledger
            # untouched, same contract as a real registry refusal below
            chaos().io_attempt("adapter-install")
        slot = self.slots.alloc()
        if slot is None:
            raise RuntimeError("no free slot for shipment install")
        # adapter requests need their adapter registered AND pinnable
        # here; any failure raises so the router reinstalls at the
        # source, whose registry still holds the factors
        aslot = -1
        if req.adapter_id is not None:
            if self.adapters is None or not self.adapters.known(
                    req.adapter_id):
                self.slots.release(slot)
                raise RuntimeError(
                    f"shipment {ship.ship_id} needs adapter "
                    f"{req.adapter_id!r}, not registered on this engine")
            got = self.adapters.acquire(req.adapter_id)
            if got is None:
                self.slots.release(slot)
                raise RuntimeError(
                    f"adapter arena fully pinned; cannot install "
                    f"shipment {ship.ship_id}")
            aslot = got
        bk = pool.block_size
        total = -(-(len(req.prompt) + req.max_new_tokens) // bk)
        need = ship.n_live + max(0, total - ship.n_live)
        if not self._try_reserve(need):
            self._release_adapter(req)
            self.slots.release(slot)
            raise RuntimeError(
                f"pool cannot reserve {need} blocks for shipment install")
        self.slots.set_reservation(slot, need)
        table = np.full(self.slots.table_blocks, BlockPool.TRASH, np.int32)
        for i in range(ship.n_live):
            table[i] = pool.alloc_reserved()
            # tpulint: allow[lock-discipline] scheduler thread only (via
            # call_in_scheduler) — same single-writer discipline as every
            # other slot-table mutation; _lock only guards start/shutdown
            self.slots.reserved[slot] -= 1
        # tpulint: allow[lock-discipline] scheduler thread only, as above
        self.slots.tables[slot] = table
        # pad columns of the dense payload carry the source's trash
        # garbage; scattering them into our trash block is a no-op
        try:
            pool.import_blocks(ship.k_dense, ship.v_dense, table)
        except Exception:
            # import I/O failed (chaos "ship-import" on the device_put
            # path): unwind — release drops the freshly alloc'd blocks
            # and the unused reservation, leaving this ledger balanced;
            # the shipment's own refs still pin the source blocks, so
            # the router's reinstall-at-source fallback stays safe
            self._release_adapter(req)
            self.slots.release(slot)
            self._update_pool_gauges()
            raise
        st = _SlotState(req, fill=ship.meta["fill"],
                        pending=ship.meta["pending"])
        st.count = ship.meta["count"]
        st.spec_ewma = ship.meta["spec_ewma"]
        st.spec_stall = ship.meta["spec_stall"]
        st.adapter_slot = aslot  # may differ from the source's arena slot
        st.fresh = True  # next dispatch feeds the host-known pending token
        self._active[slot] = st
        if self._draft_enabled and self.config.role != "prefill":
            # the draft shadow pool does not travel with the shipment
            # (draft rows are derived state, cheap to rebuild with a
            # tiny model); re-prefill the context so this replica can
            # keep speculating.  The source's draft_fill in ship.meta is
            # informational — the dense prefill always rebuilds from 0.
            self._draft_prefill(slot, st)
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        self.metrics.inc("ships_in_total")
        with self._wake:  # a paused/idle loop should start decoding it
            self._wake.notify_all()
        self.queue.notify()
        return slot

    # -- tiered KV: decode preemption to the host tier ---------------------

    def _preempt_slot(self, slot: int) -> bool:
        """Suspend an active decode to the host tier.

        Mirrors ``_extract_slot`` with the host arena as the
        destination: the fixed-arity export (inside
        ``HostKVTier.begin_demote``) runs FIRST, so a ``host-swap-out``
        chaos fault returns False with the slot — and the device copy —
        fully intact.  On success the staged dense leaves own the bytes,
        the slot's device blocks free immediately, and the scheduling
        state (fill, RNG fold count, pending token, speculation EWMA)
        moves into ``_suspended`` for a bitwise resume."""
        self._flush_inflight()  # may retire the victim (EOS/budget)
        st = self._active.get(slot)
        if st is None:
            return False
        req = st.req
        bids = self.slots.live_bids(slot)
        if not bids or not self.host_tier.can_store(len(bids)):
            return False
        t0 = time.perf_counter()
        try:
            hids = self.host_tier.begin_demote(bids, owner=req.rid)
        except OSError as e:  # armed chaos / real I/O failure BEFORE any
            # state mutated: the request simply keeps decoding here
            EVENT_LOG.emit("engine", "swap_out_failed", request_id=req.rid,
                           slot=slot, error=repr(e))
            return False
        self._active.pop(slot)
        if self.prefix_cache is not None:
            # unpin without offering: the request is suspended, not
            # retiring (its blocks are leaving the device anyway)
            self.prefix_cache.release(st.lease)
        self._release_adapter(req)
        self.slots.release(slot)
        self._suspended[req.id] = _Suspended(
            req, hids, len(bids),
            meta={"fill": st.fill, "count": st.count,
                  "pending": st.pending, "spec_ewma": st.spec_ewma,
                  "spec_stall": st.spec_stall,
                  "draft_fill": st.draft_fill},
            t_suspend=t0)
        nbytes = self.host_tier.block_nbytes * len(bids)
        self.metrics.inc("preemptions_total")
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        EVENT_LOG.emit("engine", "swapped", request_id=req.rid,
                       direction="out", blocks=len(bids), bytes=nbytes)
        EVENT_LOG.emit("engine", "preempted", request_id=req.rid,
                       slot=slot, priority=req.priority,
                       blocks=len(bids), generated=len(req.generated))
        self.trace.add("preempt", t0, time.perf_counter(),
                       request_id=req.rid, tid=req.id,
                       args={"slot": slot, "blocks": len(bids),
                             "priority": req.priority})
        return True

    def _maybe_resume(self) -> None:
        """Admission-side hook: bring suspended decodes back on device
        when a slot and a full reservation are available — highest
        priority first, FIFO within a class, never leapfrogging a
        strictly higher-priority parked admission."""
        if not self._suspended:
            return
        pool = self.slots.pool
        bk = pool.block_size
        for sus in sorted(self._suspended.values(),
                          key=lambda s: (-s.req.priority, s.t_suspend)):
            req = sus.req
            if not self.slots.free_slots:
                break
            if (self._held is not None
                    and self._held.priority > req.priority):
                break
            total = -(-(len(req.prompt) + req.max_new_tokens) // bk)
            if not pool.can_reserve(max(total, sus.n_live)):
                continue  # a smaller suspended request may still fit
            try:
                self._resume_suspended(sus)
            except OSError:
                # host-swap-in fault (chaos) or adapter pressure: the
                # host copy stays resident, re-fetched next iteration
                break

    def _discard_suspended(self, key: int, reason: str) -> None:
        sus = self._suspended.pop(key)
        self.host_tier.free(sus.hids)
        self._finish(sus.req, reason)
        self._update_pool_gauges()

    def _resume_suspended(self, sus: _Suspended) -> int:
        """Swap a suspended decode back in and rebuild its slot state.

        Bitwise: block contents round-trip the host arena verbatim and
        the sampling RNG folds on the request's own (seed, count) — the
        resumed trajectory is the one an uninterrupted run produces.
        Raises ``OSError`` with the host copy intact (and this ledger
        balanced) when the swap-in faults or the adapter arena is
        pinned shut."""
        req = sus.req
        pool = self.slots.pool
        t0 = time.perf_counter()
        slot = self.slots.alloc()
        assert slot is not None
        aslot = self._acquire_adapter(req)
        if aslot is None:
            self.slots.release(slot)
            raise OSError("adapter arena fully pinned; resume deferred")
        bk = pool.block_size
        total = -(-(len(req.prompt) + req.max_new_tokens) // bk)
        need = max(total, sus.n_live)
        if not pool.reserve(need):
            self._release_adapter(req)
            self.slots.release(slot)
            raise OSError("pool cannot reserve for resume")
        self.slots.set_reservation(slot, need)
        table = np.full(self.slots.table_blocks, BlockPool.TRASH, np.int32)
        for i in range(sus.n_live):
            table[i] = pool.alloc_reserved()
            # tpulint: allow[lock-discipline] scheduler thread only —
            # same single-writer discipline as install_shipment
            self.slots.reserved[slot] -= 1
        # tpulint: allow[lock-discipline] scheduler thread only, as above
        self.slots.tables[slot] = table
        try:
            self.host_tier.promote(sus.hids, table[:sus.n_live])
        except OSError:
            # swap-in fault: unwind — release drops the fresh blocks and
            # the unused reservation; the host copy stays resident for a
            # later re-fetch
            self.slots.release(slot)
            self._release_adapter(req)
            self._update_pool_gauges()
            raise
        self.host_tier.free(sus.hids)
        del self._suspended[req.id]
        st = _SlotState(req, fill=sus.meta["fill"],
                        pending=sus.meta["pending"])
        st.count = sus.meta["count"]
        st.spec_ewma = sus.meta["spec_ewma"]
        st.spec_stall = sus.meta["spec_stall"]
        st.adapter_slot = aslot
        st.fresh = True  # next dispatch feeds the host-known pending token
        self._active[slot] = st
        if self._draft_enabled and self.config.role != "prefill":
            # the draft shadow pool does not survive suspension (derived
            # state, cheap to rebuild) — re-prefill the context
            self._draft_prefill(slot, st)
        dt = time.perf_counter() - t0
        suspended_s = t0 - sus.t_suspend
        nbytes = self.host_tier.block_nbytes * sus.n_live
        self.metrics.inc("resumes_total")
        self.metrics.observe_resume(dt)
        self._update_pool_gauges()
        self.metrics.set_gauges(slots_active=self.slots.active_slots)
        EVENT_LOG.emit("engine", "swapped", request_id=req.rid,
                       direction="in", blocks=sus.n_live, bytes=nbytes)
        EVENT_LOG.emit("engine", "resumed", request_id=req.rid, slot=slot,
                       priority=req.priority,
                       suspended_s=round(suspended_s, 6),
                       resume_s=round(dt, 6))
        self.trace.add("resume", t0, time.perf_counter(),
                       request_id=req.rid, tid=req.id,
                       args={"slot": slot, "blocks": sus.n_live,
                             "suspended_s": round(suspended_s, 6)})
        return slot

    # -- live weight swap (zero-downtime deploys) --------------------------

    def swap_params(self, new_params):
        """Replace the base model weights at an iteration boundary and
        return the old tree (double-buffered: the caller decides when to
        drop it, so a rolling deploy can fall back instantly).

        Runs on the scheduler thread between iterations via
        ``call_in_scheduler``: the in-flight pipelined step — dispatched
        against the OLD weights — is processed normally first, so no
        sampled token is lost, duplicated, or recomputed; every later
        step runs the new weights.  The tree must match the resident
        params' structure/shapes/dtypes exactly, so every compiled
        executable carries over with zero recompiles.  Adapter arenas
        are untouched: LoRA factors compose with whichever base is
        resident.  In-flight requests simply continue — mid-generation
        tokens after the fence come from the new weights, which is the
        semantics a weight deploy wants; callers needing whole-request
        consistency drain or migrate first (router.rolling_swap).
        Callable from any thread; before ``start()`` it swaps inline."""
        try:
            same = jax.tree.all(jax.tree.map(
                lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                self.params, new_params))
        except ValueError:
            same = False
        if not same:
            raise ValueError(
                "swap_params needs a tree matching the resident params' "
                "structure/shapes/dtypes (same executables, zero "
                "recompiles); retrain/export with the serving layout")

        def _swap():
            self._flush_inflight()
            old, self.params = self.params, new_params
            from ..ops.quant import precision_route
            # tpulint: allow[lock-discipline] scheduler thread only (via
            # call_in_scheduler when the loop is live) — single-writer,
            # same discipline as every other step-loop mutation
            self._precision_route = precision_route(self.params)
            self.metrics.inc("param_swaps")
            EVENT_LOG.emit("engine", "param_swap",
                           active_slots=len(self._active))
            return old

        if self._thread is None or not self._thread.is_alive():
            return _swap()
        return self.call_in_scheduler(_swap)
