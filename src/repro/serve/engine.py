"""Serving engine: paged admission with shared-prefix reuse, chunked +
ragged admission prefill for EVERY architecture, and multi-step
*scanned* decode with slot-based continuous batching, plus the A^3
approximate decode path.

The engine holds a fixed number of request *slots*. Every engine tick
runs the admission state machine::

    admit -----------> chunked prefill ------> blocked decode
    (trie walk +       (suffix only;           (T x [in-graph resort
     paged gather)      + in-graph handoff)        -> step -> sample])

* **Admit — trie walk + paged gather.** Queued requests claim free
  slots. With the paged prefix cache enabled (``cache_pages > 0``), a
  submit first walks the radix trie over the prompt's ``page_size``-
  token pages (:mod:`repro.serve.prefix_cache`); every matched page is
  gathered into the slot's per-segment cache with ONE jitted copy
  dispatch — attention ring rows from pool pages, recurrent carries
  from the matched node's boundary snapshot (the chunked-prefill carry
  *is* the snapshot), and the A^3 sorted columns + ``sorted_upto``
  watermark restored at the boundary, so reuse triggers no re-sort.
  The slot's prompt cursor starts at the matched length and only the
  unmatched *suffix* chunk-prefills (always >= 1 token: a full hit is
  capped one page short, so the final chunk still produces the
  next-token logits and re-folds the A^3 sort exactly like a cold
  admission). ``stats["prefix_hits"]`` / ``stats["prefix_tokens_reused"]``
  count the reuse; ``prefill_tokens`` counts only suffix tokens, so a
  cold run's ``prefill_tokens`` equals a warm run's ``prefill_tokens +
  prefix_tokens_reused`` on the same workload. On a miss (or with the
  cache disabled) admission is unchanged: no cache work at admit time —
  the slot's first chunk dispatch resets its mixer state in-graph.
  Admitted prompts are *recorded* as they prefill: chunks clamp to page
  boundaries, each boundary copies one page pool-ward and snapshots the
  recurrent carry into a new trie node (refcounted; LRU-evicted under
  the ``cache_pages`` budget), and divergent requests copy-on-write by
  recording sibling pages — pool pages are never mutated.
* **Chunked ragged prefill — one dispatch per tick, every arch.** All
  PREFILLING slots advance by at most ``prefill_chunk`` prompt tokens
  in a *single* jitted ``prefill_chunk`` dispatch that computes only
  the prefilling lanes: a packed ``[b, chunk]`` token block, ``b`` the
  next power of two of the prefilling lanes (at most ``slots``), with a
  ``[b]`` ``lane_slot`` vector naming each lane's slot and per-lane
  start positions and lengths. The program gathers those slots' cache
  state, computes ``b`` lanes, and scatters the new state back; padding
  lanes take other slots at length 0 and pass their rows through
  untouched. At ``b == slots`` the lanes are the slots in order and the
  program is the unpacked ``[slots, chunk]`` one. Every width is
  compiled at the first prefill tick of each chunk length (one no-op
  dispatch each), so changing widths never compiles mid-stream. The
  per-segment mixer-state
  interface (``repro.models.mixer``) carries mid-prompt state for
  recurrent segments across chunk boundaries, so hybrid RG-LRU / xLSTM
  stacks admit through the same bounded-tick path as attention-only
  ones — there is no whole-prompt fallback. Long prompts therefore
  never stall decoding slots for more than one chunk, and multiple
  queued prompts prefill together. ``stats["prefill_dispatches"]``
  counts these dispatches; it is at most ``stats["ticks"]`` by
  construction. ``prefill_chunk=None`` uses a default chunk of
  ``min(max_len, 512)`` — same dispatch, bounded working set; short
  prompts still admit in a single dispatch. With
  ``prefill_chunk_min`` set, the effective chunk *adapts*: ticks where
  >= 1 slot is actively decoding shrink it to the floor (bounding the
  stall those decoders see), while a cold queue drains at the full
  chunk (``stats["adaptive_shrink_ticks"]`` counts shrunk prefill
  ticks). Chunking — fixed or adaptive — never changes outputs.
* **Device-resident prefill -> decode handoff.** The prefill dispatch
  samples each finishing lane's first token in-graph and returns it as
  a device array; the same tick's decode block consumes it directly
  (``jnp.where`` over the token lane vector) and the host learns it
  from the *decode* harvest — prefill ticks do not block. Only when a
  prompt finishes with no decode dispatch to ride (budget exhausted by
  its first token, or the prompt already at ``max_len``) does the
  engine read the first-token array directly; ``stats["handoff_syncs"]``
  counts those rare reads.
* **Blocked decode — T steps per dispatch, fully device-resident.**
  ``decoder.decode_block`` runs ``decode_block`` = T decode steps under
  one jitted ``lax.scan``: each step samples its successor token from
  its own on-device logits (greedy argmax; temperature hook behind
  ``ServeConfig``), re-sorts due lanes' A^3 key columns in-graph, and
  appends to an on-device ``[slots, T]`` token ring. The host syncs
  *once per block* to harvest the ring (prepended with the block's
  input tokens, which carries any prefill-handoff first tokens along
  for free) and run the finish/admit state machine. Lanes that exhaust
  their budget or hit ``max_len`` mid-block ride along at ``pos = -1``
  with dropped ring writes and bit-identical (masked) recurrent state.
  ``stats["decode_steps"]`` counts executed scan iterations
  (``decode_block x decode_dispatches``);
  ``stats["decode_steps_advanced"]`` counts the subset that advanced
  at least one lane — the gap is partial-block padding, and dispatch
  efficiency obeys the falsifiable bound ``decode_dispatches <=
  ceil(decode_steps_advanced / T) + prefill_dispatches`` (a partial
  block means every active lane finished, which can only follow a
  prefill dispatch that flipped its cohort). ``stats["host_syncs"]``
  counts blocking device reads — one ring harvest per decode dispatch
  plus the rare direct handoff reads, so ``host_syncs <=
  decode_dispatches + handoff_syncs``.
* **Pipelined tick loop — device-resident carry + deferred harvest.**
  ``decode_block`` also returns each lane's *last* scan token as a
  device array (the cross-block token carry): the next block's input
  token vector is that carry, so back-to-back decode dispatches chain
  entirely on device with no host readback in between. With
  ``pipeline_depth = d > 0`` the ring harvest itself is *deferred* —
  each dispatch's ``[slots, 1+T]`` harvest array is queued, and BEFORE
  each tick's dispatch the loop force-lands only the over-``d`` oldest
  rings (dispatched ``d+1`` ticks ago, so the device has normally long
  finished them) plus any newer rings that already completed. Up to
  ``d`` blocks therefore stay in flight behind the device at all
  times: the pipe stays primed, the device never drains dry waiting on
  host bookkeeping, and the blocking host reads mostly find their data
  ready (``host_sync_stalls`` counts the ones that did not). Host
  bookkeeping
  acts on the one-tick-delayed view: slot ``pos``/``budget`` advance
  optimistically at dispatch time (the advance is deterministic in the
  control words), while finish/poison/A^3-resort accounting runs at
  harvest, guarded by per-row ``uid`` checks and a per-slot ``pending``
  count so stale rows from released slots are dropped and a slot is
  only FINISHED once its rings have all landed. ``pipeline_depth = 0``
  harvests synchronously and is bit-identical to the historical
  engine. Timeline at ``d = 1`` (H(n) = deferred harvest of block n,
  issued before that tick's dispatch; block n is always fully behind
  the device by the time its forced read issues)::

      tick:      1          2          3          4          5
      device:  [block 1]  [block 2]  [block 3]  [block 4]  [block 5]
      host:     dispatch   dispatch   H(1)       H(2)       H(3)
                                      dispatch   dispatch   dispatch

  Checkpoints drain all pending harvests first, so snapshots stay
  host-consistent and ``pending`` never serializes. On hosts where
  XLA compute timeshares the tick loop's cores (single-core CI) the
  overlap cannot move wall clock; the
  ``virtual_device_latency_s`` constructor knob emulates an
  accelerator's completion latency per decode block (a GIL-releasing
  readiness floor on each queued harvest) so benches and tests can
  observe the pipeline hiding device time that a synchronous loop
  serializes on. Token streams are never affected by the knob.
* **Packed control-block uploads.** All per-tick host->device control
  scalars (prefill start/len/sort/sample columns; decode pos/budget/
  sample ids/handoff mask) ride ONE packed int32 ``[slots, CTRL_COLS]``
  array per tick; both the prefill and decode jits slice their columns
  in-graph (a packed prefill gathers its lanes' rows by ``lane_slot``),
  so a tick issues a single small upload plus the token block (and a
  packed prefill's ``[b]`` lane vector) instead of ~9 scattered
  transfers.
  ``stats["host_sync_stalls"]`` counts harvests that actually blocked
  on an unfinished device computation (``is_ready()`` false at drain
  time).
* **Tick phases on the profiler's clock.** Every tick is one
  ``serve.tick`` span (a ``StepTraceAnnotation`` numbered by the tick,
  with its prefill and decode lane counts) holding the phases
  ``serve.admit``, ``serve.plan``, ``serve.dispatch.prefill``,
  ``serve.prefill.book``, ``serve.dispatch.decode``,
  ``serve.harvest.wait``, ``serve.harvest.apply`` and ``serve.finish``
  (:class:`repro.serve.telemetry.phase`). Under ``jax.profiler`` they
  share the timebase of the device's programs (``jit_prefill_chunk``,
  ``jit_decode_block``, ...), so device idle time can be charged to
  the host phase that held it; with telemetry on they also land in
  the trace ring. ``stats["prefill_lanes_computed"]`` sums the width
  ``b`` of every prefill dispatch (the ``width`` of its span) and
  ``stats["prefill_positions"]`` the ``b x chunk`` positions it
  computes, beside the real ``prefill_tokens``.
* **Cache donation.** Both the prefill-chunk and decode-block jits
  donate the cache argument, so ring buffers and recurrent states
  update in place instead of being copied each tick.
* **In-graph A^3 re-sort — zero host watermark reads.** The
  ``sorted_upto`` watermark check lives inside the decode dispatch
  (``decoder.resort_sorted_keys``): per segment, a ``lax.cond`` folds a
  due lane's fresh tail into its sorted key columns when
  ``pos - sorted_upto >= resort_every``. The host mirrors the watermark
  arithmetic (it is deterministic in ``pos``) to keep the
  ``stats["resorts"]`` counter without any device read.

A^3 state at serve time: the paper's "comprehension-time" preprocessing
maps to prefill — the prompt's keys are column-sorted per slot and
reused across all decode steps (amortization argument of SSIV-C). With
chunked prefill the sort stays once-per-prompt: the dispatch of a
prompt's *final* chunk folds the completed ring into the per-column
sorted matrices and advances the ``sorted_upto`` watermark (a
``lax.cond`` skips the sort on every other tick — nothing reads a
PREFILLING slot's sort). Tokens generated after prefill form the
*fresh tail*, always treated as candidates (exact attention) until an
in-graph re-sort folds them in.

``make_serve_step`` / ``make_decode_block_step`` /
``make_prefill_chunk_step`` build the jitted dispatches used by both
the engine and the multi-pod dry-run (they are what the ``decode_*`` /
chunked-prefill shapes lower).

Request lifecycle
-----------------

Every submitted request moves through the state machine below; the
terminal states are exactly {FINISHED, REJECTED, CANCELLED, EXPIRED,
FAILED} and a request reaches exactly one of them::

    submit() ──────────────> REJECTED   (queue full w/ reject-new,
       │                                 or engine draining)
       v
    QUEUED ────────────────> REJECTED   (shed by evict-oldest-queued)
       │        ├──────────> CANCELLED  (cancel(uid) / drain())
       │        └──────────> EXPIRED    (deadline_ticks elapsed)
       v  admit (slot free; prefix-cache gather may chaos-FAIL)
    PREFILLING ────────────> CANCELLED | EXPIRED | FAILED
       v  prompt exhausted (first token sampled in-graph)
    DECODING ──────────────> CANCELLED | EXPIRED
       │        └──────────> FAILED     (non-finite logits: the lane
       │                                 emits the POISON sentinel on
       v                                 the harvested ring)
    FINISHED    (budget exhausted or max_len reached)

    ── durability (orthogonal to the per-request lifecycle) ──────────
    any state ──checkpoint()──> <directory>     (atomic rename commit;
       │                                         every QUEUED /
       │                                         PREFILLING / DECODING
       │                                         request snapshots
       │                                         mid-flight)
       X  crash (EngineCrash / process death: partial tick discarded)
       │
    ServeEngine.restore() ──> same states as at checkpoint() — ticking
    on yields token-for-token the uninterrupted run's outputs for
    every in-flight request (greedy argmax and the (seed, uid, pos)-
    keyed sampler are both replay-deterministic; the device cache,
    prefix trie, pool pages, and L2 blobs round-trip bit-exactly)

Releasing a slot from ANY in-flight state reclaims it the same tick
(cancel/expire/poison never strand a lane) and drops the request's
prefix-cache recording pin, so trie refcounts return to baseline — no
leaked pages. The stats counters obey the conservation identity
checked by the lifecycle tests::

    submitted == finished + rejected + cancelled + expired + failed
                 + in_flight            (in_flight = queued + on-slot)

Overload policy: ``max_queue == 0`` keeps the historical unbounded
deque; ``max_queue > 0`` bounds it, and ``shed_policy`` picks the
victim — ``reject-new`` sheds the arriving request, ``evict-oldest-
queued`` sheds the head of the queue (freshest-first service under
overload). ``drain()`` enters graceful shutdown: queued work is
cancelled, in-flight work finishes, new submits are rejected.

Telemetry span lifecycle
------------------------

With ``telemetry=True`` every request leaves a timeline in the ring-
buffered trace (:mod:`repro.serve.telemetry`; Chrome-trace export).
Spans and instants per request, in lifecycle order — tracks (``tid``)
are the queue lane or the slot index the request occupies::

    track "queue":  submit ▸──────[ queued ]──────▸ admit
                      │   (queue-sojourn histogram, by terminal)
    track slot i:         admit ▸ [prefill c0][prefill c1]...[prefill cN]
                                  (one span per chunk dispatch, shared
                                   ragged-dispatch wall time)
                          ▸ first_token        (TTFT histogram keyed by
                                                terminal state; sampled
                                                in-graph, stamped when
                                                the harvest lands)
                          ▸ [decode_block][decode_block]...
                                  (span = dispatch -> harvest: a
                                   deferred-harvest stall is a visible
                                   gap; TPOT histogram at terminal)
                          ▸ terminal {finished|cancelled|expired|failed}
    track "engine": the tick phases (serve.tick and the serve.* spans
                    nested in it) / host_sync_stall / checkpoint /
                    restore / chaos_{delay,corrupt,spill,abort,
                    gather_fail} / prefix-cache + L2 events
                    (hit/evict/demote/promote)

Metrics land in the registry (``serve_ttft_ns{terminal=...}``,
``serve_tpot_ns``, ``serve_queue_sojourn_ns{...}``, A^3
``serve_a3_captured_mass`` / ``serve_a3_candidates`` probe histograms
sampled every ``telemetry_every`` decode dispatches), with the legacy
``stats`` dict exported as ``serve_*`` counters through a zero-cost
compatibility view. Telemetry off is bit-identical to the
untelemetered engine; telemetry on adds **zero host syncs** — probes
ride the deferred ring drain the host already performs.

Chaos injection: constructed with a ``serve.chaos.ChaosInjector`` the
engine consults the injector at tick phase boundaries (delay / abort),
before decode dispatches (corrupt one decoding lane's mixer state so
its logits go non-finite), and inside warm prefix-cache admissions
(fail the page gather). Faults are quarantined per request; the chaos
conformance tests assert every un-injected request's token stream is
bit-identical to a chaos-free run and that ``host_syncs`` does not
grow (poison detection rides the existing per-block ring harvest).
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import shutil
import time
import zlib
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional, \
    Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import A3Config, A3Mode, ModelConfig, ServeConfig
from repro.models import decoder
from repro.serve.chaos import ChaosError, ChaosInjector, EngineCrash, \
    corrupt_cache_lane
from repro.serve.page_store import CheckpointError, IntegrityError, \
    deserialize_tree, serialize_tree
from repro.serve.prefix_cache import PrefixCache
from repro.serve.telemetry import Telemetry, phase


def make_serve_step(
    cfg: ModelConfig,
    a3: A3Config = A3Config(),
    *,
    use_kernel: bool = False,
) -> Callable:
    """Returns decode_step(params, cache, token [B], pos scalar or [B])
    -> (logits [B, Vp], new_cache); jitted, it lowers to the module
    ``jit_decode_step``."""

    def decode_step(params, cache, token, pos):
        return decoder.decode_step(params, cfg, cache, token, pos, a3=a3,
                                   use_kernel=use_kernel)

    return decode_step


# Packed control-word layout: the per-tick scatter of small host int
# vectors (prefill pos/length/sort/sample columns, decode pos/budget/
# uid/handoff columns) collapses into ONE [slots, CTRL_COLS] int32
# upload shared by the prefill and decode dispatches — each jit slices
# the columns it needs in-graph, so a steady-state decode tick uploads
# exactly one small array (the token vector rides the device-resident
# carry and never leaves the device at all).
CTRL_P_POS = 0        # prefill: per-lane chunk start position
CTRL_P_LEN = 1        # prefill: per-lane chunk length (0 = ride-along)
CTRL_P_SORT = 2       # prefill: 1 = final chunk (fold the A^3 sort)
CTRL_P_SPOS = 3       # prefill: sampling position for the handoff draw
CTRL_P_SIDS = 4       # prefill: sampling uid for the handoff draw
CTRL_D_POS = 5        # decode: per-lane next position (-1 = ride-along)
CTRL_D_STEPS = 6      # decode: per-lane steps_left budget for the block
CTRL_D_IDS = 7        # decode: per-request sampling uid
CTRL_D_HMASK = 8      # decode: 1 = take the handoff first-token lane
CTRL_COLS = 9


def make_decode_block_step(
    cfg: ModelConfig,
    a3: A3Config = A3Config(),
    *,
    steps: int = 1,
    use_kernel: bool = False,
    resort_every: int = 0,
    temperature: float = 0.0,
    probe: bool = False,
) -> Callable:
    """Returns the blocked-decode dispatch: decode_block(params, cache,
    token [B], first_tok [B], ctrl [B, CTRL_COLS][, rng]) ->
    (harvest [B, 1+steps], carry [B], new_cache). ``steps`` decode
    iterations run device-resident under one ``lax.scan`` — in-graph
    sampling feeds each step's token from the previous step's logits,
    and ``resort_every > 0`` folds due lanes' A^3 fresh tails into the
    sorted key columns in-graph (no host watermark read).

    All small per-lane scalars (pos / steps_left / sample uid / the
    handoff mask) arrive packed in the ``ctrl`` int32 block and are
    sliced in-graph (``CTRL_D_*`` columns), so one upload feeds the
    whole dispatch. The prefill->decode handoff select also happens
    in-graph: lanes with ``ctrl[:, CTRL_D_HMASK]`` set take their input
    token from ``first_tok`` (the prefill dispatch's device-resident
    output). The returned ``harvest`` prepends the effective input
    token column to the ring — it is the ONE array a host ever reads
    back, and the read is deferrable: ``carry`` is the scan's final
    per-lane token, feeding the next block's ``token`` argument
    directly so chained blocks never wait on a harvest. The ``rng``
    argument exists only when ``temperature > 0`` (greedy dispatches
    keep the production signature the dry-run lowers).

    ``probe=True`` builds the A^3 telemetry variant: the dispatch
    returns ``(harvest, probe [B, 3], carry, new_cache)`` where the
    probe accumulates in-graph (samples, candidate-count sum,
    captured-score-mass-ratio sum) per lane over the block's advanced
    steps — harvested alongside the ring at the same deferred read, so
    sampling it adds zero host syncs. The token path runs identical
    ops (see :func:`repro.models.decoder.decode_block`).

    The returned function is named ``decode_block`` (``probe=True``:
    ``decode_block_probe``), so its jitted module is
    ``jit_decode_block`` / ``jit_decode_block_probe`` in a profile."""

    def _run(params, cache, token, first_tok, ctrl, rng=None):
        token = jnp.where(ctrl[:, CTRL_D_HMASK] > 0, first_tok, token)
        out = decoder.decode_block(
            params, cfg, cache, token, ctrl[:, CTRL_D_POS],
            ctrl[:, CTRL_D_STEPS], steps=steps, a3=a3,
            use_kernel=use_kernel, resort_every=resort_every,
            temperature=temperature, rng=rng,
            sample_ids=ctrl[:, CTRL_D_IDS], probe=probe)
        if probe:
            ring, carry, cache, pr = out
            harvest = jnp.concatenate([token[:, None], ring], axis=1)
            return harvest, pr, carry, cache
        ring, carry, cache = out
        harvest = jnp.concatenate([token[:, None], ring], axis=1)
        return harvest, carry, cache

    if temperature > 0.0:
        def step(params, cache, token, first_tok, ctrl, rng):
            return _run(params, cache, token, first_tok, ctrl, rng)
    else:
        def step(params, cache, token, first_tok, ctrl):
            return _run(params, cache, token, first_tok, ctrl)

    return _named(step, "decode_block_probe" if probe else "decode_block")


def make_prefill_chunk_step(cfg: ModelConfig, *, a3: bool = False,
                            update_sort: bool = True,
                            temperature: float = 0.0) -> Callable:
    """Returns prefill_chunk(params, cache, tokens [B, C],
    ctrl [slots, CTRL_COLS][, rng], lane_slot=None) -> (first_tok
    [slots], new_cache) — the ragged chunked-prefill
    dispatch with the device-resident prefill->decode handoff: each
    lane's next-token draw from its last valid position's logits
    happens in-graph, so finishing lanes hand their first generated
    token straight to the same tick's decode block without a blocking
    read (non-finishing lanes' entries are meaningless and ignored).
    The per-lane scalars ride the shared packed ``ctrl`` upload
    (``CTRL_P_*`` columns, one row per slot): chunk start ``pos``, chunk
    ``length``, ``sort_lanes`` marking lanes on their final chunk (A^3:
    fold the completed prompt into the column sort), and the handoff
    draw's sampling position / uid. Without ``lane_slot`` the token
    block has one lane per slot (``B`` = slots). With a ``[B]``
    ``lane_slot`` of distinct slots the block is packed: lane ``b``
    prefills slot ``lane_slot[b]``, the program gathers those slots'
    ``ctrl`` rows and cache state, computes ``B`` lanes only, and
    scatters the new state and the sampled tokens back by slot
    (:func:`repro.models.decoder.prefill_chunk`). ``update_sort=False``
    builds the cheaper
    specialization that treats the sorted-key leaves as read-only
    (dispatched on ticks where no lane finishes its prompt). The
    ``rng`` argument exists only when ``temperature > 0`` (greedy
    dispatches keep the production signature). Its jitted module is
    ``jit_prefill_chunk`` (``update_sort=False``:
    ``jit_prefill_chunk_nosort``) at every width."""

    def _run(params, cache, tokens, ctrl, lane_slot, rng=None):
        pc = ctrl if lane_slot is None else ctrl[lane_slot]
        logits, cache = decoder.prefill_chunk(
            params, cfg, cache, tokens, pc[:, CTRL_P_POS],
            pc[:, CTRL_P_LEN], a3=a3, sort_lanes=pc[:, CTRL_P_SORT] > 0,
            update_sort=update_sort, lane_slot=lane_slot)
        if rng is None:
            tok = decoder.sample_logits(logits)
        else:
            tok = decoder.sample_logits(logits, temperature=temperature,
                                        rng=rng, pos=pc[:, CTRL_P_SPOS],
                                        ids=pc[:, CTRL_P_SIDS])
        # poison quarantine rides the handoff: a finishing lane whose
        # prompt logits are non-finite hands POISON to the decode block
        # (or the direct read) instead of a garbage token — healthy
        # lanes take the identical select, bit-for-bit
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        tok = jnp.where(finite, tok, decoder.POISON)
        if lane_slot is not None:
            tok = jnp.zeros((ctrl.shape[0],), tok.dtype).at[lane_slot].set(
                tok, unique_indices=True)
        return tok, cache

    if temperature > 0.0:
        def step(params, cache, tokens, ctrl, rng, lane_slot=None):
            return _run(params, cache, tokens, ctrl, lane_slot, rng)
    else:
        def step(params, cache, tokens, ctrl, lane_slot=None):
            return _run(params, cache, tokens, ctrl, lane_slot)

    return _named(step, "prefill_chunk" if update_sort
                  else "prefill_chunk_nosort")


def _prefill_width(lanes: int, slots: int) -> int:
    """The packed prefill width for ``lanes`` prefilling lanes: the
    next power of two, at most ``slots``."""
    return min(1 << (lanes - 1).bit_length(), slots)


def _named(fn: Callable, name: str) -> Callable:
    """Name a step program: ``jax.jit`` names its module ``jit_<name>``
    after the function, which is how a profile tells the programs
    apart."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class Request(NamedTuple):
    uid: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int
    deadline: Optional[int] = None   # absolute tick, None = no deadline


# slot phases (doubling as the in-flight request statuses)
IDLE = "idle"
PREFILLING = "prefilling"
DECODING = "decoding"

# request lifecycle statuses (see the module docstring's state diagram)
QUEUED = "queued"
FINISHED = "finished"
REJECTED = "rejected"
CANCELLED = "cancelled"
EXPIRED = "expired"
FAILED = "failed"

# terminal status -> stats counter (the conservation identity's terms)
_TERMINAL = {FINISHED: "finished", REJECTED: "rejected",
             CANCELLED: "cancelled", EXPIRED: "expired", FAILED: "failed"}

SHED_POLICIES = ("reject-new", "evict-oldest-queued")

# admission chunk when ServeConfig.prefill_chunk is None: bounds the
# chunk dispatch's per-layer score/scan working set independent of
# max_len (prompts <= 512 still admit in a single dispatch)
_DEFAULT_ADMIT_CHUNK = 512


@dataclasses.dataclass
class SlotState:
    uid: int = -1
    pos: int = 0                  # next position to write
    generated: List[int] = dataclasses.field(default_factory=list)
    budget: int = 0
    phase: str = IDLE
    prompt: Optional[np.ndarray] = None
    cursor: int = 0               # prompt tokens prefilled so far
    # host-side mirror of the in-graph A^3 ``sorted_upto`` watermark
    # (deterministic in pos; keeps stats["resorts"] without device reads)
    sorted_upto: int = 0
    # prefix-cache recording anchor: the trie node whose boundary the
    # cursor last crossed (ref-pinned against eviction while the slot
    # prefills); None = not recording (cache disabled / budget exhausted)
    rec_node: Any = None
    # absolute tick by which the request must finish (None = never):
    # enforced at tick boundaries by the engine's expiry sweep
    deadline: Optional[int] = None
    # number of in-flight (unharvested) ring blocks referencing this
    # lane: ``pos``/``budget`` advance optimistically at dispatch, but
    # the lane may not FINISH until every referencing harvest has
    # landed (its tokens live only on the device until then)
    pending: int = 0

    @property
    def active(self) -> bool:
        """Occupied (prefilling or decoding)."""
        return self.phase != IDLE

    @property
    def decoding(self) -> bool:
        return self.phase == DECODING


@dataclasses.dataclass
class _PendingHarvest:
    """One dispatched decode block whose ring is still device-side.

    ``full`` is the dispatch's harvest output ``[slots, 1+T]`` (input
    token column + ring). The host bookkeeping needed to land it is
    frozen at dispatch time: ``handoff`` lanes take their first token
    from column 0, ``lanes`` carry (slot, uid, steps-this-block,
    position-before-block) for the generated/extend + A^3 watermark
    mirror, and ``refs`` maps every referenced slot to the uid it held
    at dispatch — a lane released (cancel / expire / poison) while its
    harvest was in flight fails the uid guard and its rows are
    dropped, never misattributed to a successor request."""
    full: Any
    handoff: List[Tuple[int, int]]
    lanes: List[Tuple[int, int, int, int]]
    refs: Dict[int, int]
    # virtual-device emulation: earliest monotonic time this block is
    # allowed to be read (0.0 = no emulation, real readiness governs)
    ready_at: float = 0.0
    # telemetry: the A^3 quality-probe array ([slots, 3], present only
    # on sampled dispatches — it rides the same drain as ``full``, so
    # reading it adds no host sync event) and the dispatch timestamp
    # (the telemetry ``Tracer`` clock, ns) anchoring the block's span
    probe: Any = None
    t_dispatch: int = 0


class ServeEngine:
    """Slot-based batched serving on one device. There is no multi-chip
    serving path: ``launch.serve`` builds no mesh, and ``launch.dryrun``
    only compiles the decoder's step functions for simulated meshes."""

    def __init__(self, params: Any, cfg: ModelConfig, *, slots: int = 4,
                 max_len: int = 2048, a3: A3Config = A3Config(),
                 resort_every: int = 64,
                 prefill_chunk: Optional[int] = None,
                 prefill_chunk_min: Optional[int] = None,
                 decode_block: int = 1, use_kernel: bool = False,
                 temperature: float = 0.0, sample_seed: int = 0,
                 page_size: int = 64, cache_pages: int = 0,
                 max_queue: int = 0, shed_policy: str = "reject-new",
                 deadline_ticks: Optional[int] = None,
                 kv_quant: str = "none", l2_bytes: int = 0,
                 pipeline_depth: int = 0,
                 virtual_device_latency_s: float = 0.0,
                 telemetry: bool = False, telemetry_every: int = 8,
                 trace_events: int = 4096, retain_results: int = 0,
                 chaos: Optional[ChaosInjector] = None):
        if cfg.frontend:
            # the engine admits token prompts; frontend archs (audio /
            # vision) need precomputed embeddings the submit() API cannot
            # carry — raise instead of silently serving garbage tokens
            raise ValueError(
                f"{cfg.name}: frontend archs serve from precomputed "
                f"embeddings; the token-prompt ServeEngine does not "
                f"support them")
        self.params, self.cfg, self.a3 = params, cfg, a3
        self.max_len = max_len
        self._use_a3 = a3.mode != A3Mode.OFF
        # clamp to >= 1: the in-graph dispatch treats resort_every <= 0
        # as "resort disabled", while the historical host-side meaning
        # of 0 was "resort whenever any fresh tail exists" — which is
        # what 1 expresses (0 would only add no-op sorts at pos == upto)
        self.resort_every = max(1, int(resort_every))
        # every arch admits through the chunked path (the mixer-state
        # interface carries recurrent mid-prompt state across chunks);
        # None = a default admission chunk of min(max_len, 512) — the
        # chunk dispatch materializes O(C x (ring + C)) attention
        # scores and O(C) recurrent-scan intermediates per layer, so an
        # uncapped max_len-sized chunk would blow peak memory at large
        # max_len for no latency benefit
        if prefill_chunk is not None and int(prefill_chunk) <= 0:
            raise ValueError(f"prefill_chunk must be positive, got "
                             f"{prefill_chunk} (use None for the "
                             f"default)")
        self.prefill_chunk = prefill_chunk
        self._chunk = (int(prefill_chunk) if prefill_chunk is not None
                       else min(int(max_len), _DEFAULT_ADMIT_CHUNK))
        # adaptive admission chunking: shrink to the floor on ticks
        # where >= 1 slot is decoding (bound the stall decoders see),
        # drain a cold queue at the full chunk
        if prefill_chunk_min is not None:
            if int(prefill_chunk_min) <= 0:
                raise ValueError(f"prefill_chunk_min must be positive, "
                                 f"got {prefill_chunk_min} (use None to "
                                 f"disable the adaptive policy)")
            if int(prefill_chunk_min) > self._chunk:
                raise ValueError(f"prefill_chunk_min "
                                 f"({prefill_chunk_min}) must not exceed "
                                 f"the effective prefill chunk "
                                 f"({self._chunk})")
        self._chunk_min = (int(prefill_chunk_min)
                           if prefill_chunk_min is not None else None)
        if int(page_size) < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if int(cache_pages) < 0:
            raise ValueError(f"cache_pages must be >= 0, got "
                             f"{cache_pages} (0 disables the prefix "
                             f"cache)")
        self.page_size = int(page_size)
        self.cache_pages = int(cache_pages)
        if kv_quant not in ("none", "int8"):
            raise ValueError(f"kv_quant must be 'none' or 'int8', got "
                             f"{kv_quant!r}")
        self.kv_quant = kv_quant
        if int(l2_bytes) < 0:
            raise ValueError(f"l2_bytes must be >= 0, got {l2_bytes} "
                             f"(0 disables the host-RAM L2 tier)")
        self.l2_bytes = int(l2_bytes)
        # bounded admission + load shedding (max_queue == 0 keeps the
        # historical unbounded deque)
        if int(max_queue) < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue} "
                             f"(0 = unbounded queue)")
        if shed_policy not in SHED_POLICIES:
            raise ValueError(f"shed_policy must be one of "
                             f"{SHED_POLICIES}, got {shed_policy!r}")
        if deadline_ticks is not None and int(deadline_ticks) < 1:
            raise ValueError(f"deadline_ticks must be >= 1, got "
                             f"{deadline_ticks} (use None for no "
                             f"deadline)")
        self.max_queue = int(max_queue)
        self.shed_policy = shed_policy
        self.deadline_ticks = (int(deadline_ticks)
                               if deadline_ticks is not None else None)
        self._chaos = chaos
        self._draining = False
        if int(pipeline_depth) < 0:
            raise ValueError(f"pipeline_depth must be >= 0, got "
                             f"{pipeline_depth} (0 = synchronous "
                             f"harvest)")
        self.pipeline_depth = int(pipeline_depth)
        # virtual-device emulation: each decode block's ring becomes
        # readable no earlier than dispatch + this latency, modelling
        # an accelerator whose completion the host must wait out. On a
        # host where XLA compute timeshares the same cores as the tick
        # loop (single-core CI), this is the only way to observe the
        # host/device overlap the pipelined drain buys: the wait is a
        # GIL-releasing sleep, so the synchronous engine serializes on
        # it while a primed pipeline hides it behind tick work.
        # Token streams are unaffected — only readiness timing shifts.
        if float(virtual_device_latency_s) < 0.0:
            raise ValueError(f"virtual_device_latency_s must be >= 0, "
                             f"got {virtual_device_latency_s}")
        self.virtual_device_latency_s = float(virtual_device_latency_s)
        # telemetry plane: metrics registry + request tracing + A^3
        # quality probes. OFF is the default and keeps every hot path
        # byte-identical to the untelemetered engine (each hook sits
        # behind one ``self._tm is not None`` check); ON adds host-side
        # bookkeeping only — probe arrays ride the existing deferred
        # ring drain, so ``stats["host_syncs"]`` is pinned either way.
        if int(telemetry_every) < 1:
            raise ValueError(f"telemetry_every must be >= 1, got "
                             f"{telemetry_every}")
        if int(trace_events) < 1:
            raise ValueError(f"trace_events must be >= 1, got "
                             f"{trace_events}")
        if int(retain_results) < 0:
            raise ValueError(f"retain_results must be >= 0, got "
                             f"{retain_results} (0 = unbounded "
                             f"retention)")
        self.telemetry = bool(telemetry)
        self.telemetry_every = int(telemetry_every)
        self.trace_events = int(trace_events)
        self.retain_results = int(retain_results)
        self._tm: Optional[Telemetry] = None
        if self.telemetry:
            self._tm = Telemetry(trace_events=self.trace_events,
                                 telemetry_every=self.telemetry_every)
        self.decode_block = max(1, int(decode_block))
        self.use_kernel = use_kernel
        # temperature > 0 is THE sampling switch: 0 pins greedy argmax
        self.temperature = max(0.0, temperature)
        # the seed is the whole sampling state: the key is never
        # mutated (draws fold (uid, pos) per request), so a restored
        # engine reconstructs identical sampling from this int alone
        self.sample_seed = int(sample_seed)
        self._sample_rng = (jax.random.PRNGKey(self.sample_seed)
                            if self.temperature > 0.0 else None)
        self.slots = [SlotState() for _ in range(slots)]
        self.cache = decoder.init_cache(cfg, slots, max_len,
                                        a3=self._use_a3)
        # host-side mirror input for stats["resorts"]: number of
        # global-attention segments carrying sorted-key state (dict-key
        # inspection only — no device read).
        self._n_a3_segs = sum(1 for sc in self.cache.values()
                              if isinstance(sc, dict) and "sk_vals" in sc)
        # donate the cache argument: ring buffers update in place (no
        # full-cache copy per tick; the jit aliases input to output).
        self._decode_block = jax.jit(
            make_decode_block_step(
                cfg, a3, steps=self.decode_block, use_kernel=use_kernel,
                resort_every=self.resort_every if self._use_a3 else 0,
                temperature=self.temperature),
            donate_argnums=(1,))
        # A^3 quality-probe variant: identical token/cache ops plus the
        # in-graph (candidate count, captured-score-mass) accumulator.
        # Built only when telemetry is on AND sorted-key state exists;
        # dispatched every ``telemetry_every``-th decode block.
        self._decode_block_probe = None
        if self._tm is not None and self._use_a3 and self._n_a3_segs > 0:
            self._decode_block_probe = jax.jit(
                make_decode_block_step(
                    cfg, a3, steps=self.decode_block,
                    use_kernel=use_kernel,
                    resort_every=self.resort_every,
                    temperature=self.temperature, probe=True),
                donate_argnums=(1,))
        self._prefill = jax.jit(
            make_prefill_chunk_step(cfg, a3=self._use_a3,
                                    temperature=self.temperature),
            donate_argnums=(1,))
        self._prefill_nosort = None
        if self._use_a3:
            # ticks where no lane finishes its prompt skip the sort
            # AND the per-layer sorted-key passthrough copy
            self._prefill_nosort = jax.jit(
                make_prefill_chunk_step(cfg, a3=True, update_sort=False,
                                        temperature=self.temperature),
                donate_argnums=(1,))
        # chunk lengths whose every packed width is compiled
        self._prefill_warm: set = set()
        # device-resident prefill->decode handoff: slots that finished
        # their prompt this tick, whose first sampled token lives only
        # in ``_first_tok`` (the prefill dispatch output) until the next
        # decode harvest (or a direct read if no decode block runs)
        self._handoff: set = set()
        self._first_tok = None
        # pipelined harvest state: dispatched-but-unharvested decode
        # blocks (at most pipeline_depth stay in flight across ticks;
        # depth 0 drains every block the tick that dispatched it —
        # the synchronous engine, bit-identical), plus the device-
        # resident cross-block token carry: the previous block's final
        # per-lane token, consumed as the next block's input without
        # ever rebuilding the lane vector from host state
        self._pending: Deque[_PendingHarvest] = collections.deque()
        self._token_carry = None
        self._carry_ok = np.zeros((slots,), bool)
        # cached constant device buffers (built once, reused every
        # tick): the zero first-token vector fed to decode dispatches
        # on ticks with no prefill handoff (constant shape/value — no
        # per-tick upload)
        self._zero_tok = jnp.zeros((slots,), jnp.int32)
        self._queue: Deque[Request] = collections.deque()
        self._done: Dict[int, List[int]] = {}
        # request lifecycle: uid -> status (QUEUED / PREFILLING /
        # DECODING / one of the _TERMINAL states)
        self._status: Dict[int, str] = {}
        self._uid = 0
        self.stats = {"prefill_tokens": 0, "decode_steps": 0,
                      "decode_steps_advanced": 0,
                      "decode_dispatches": 0, "decode_blocks": 0,
                      "prefill_dispatches": 0, "host_syncs": 0,
                      "handoff_syncs": 0, "ticks": 0, "resorts": 0,
                      "prefix_hits": 0, "prefix_tokens_reused": 0,
                      "gather_dispatches": 0, "pages_recorded": 0,
                      "pages_evicted": 0, "adaptive_shrink_ticks": 0,
                      # lifecycle counters: conservation identity
                      # submitted == finished + rejected + cancelled
                      #              + expired + failed + in_flight
                      "submitted": 0, "finished": 0, "rejected": 0,
                      "cancelled": 0, "expired": 0, "failed": 0,
                      # robustness bookkeeping
                      "chaos_aborted_ticks": 0, "max_ticks_exhausted": 0,
                      "chaos_delayed_ticks": 0,
                      # durable-state bookkeeping (host-RAM L2 tier +
                      # engine checkpoint/restore)
                      "l2_spills": 0, "l2_hits": 0, "l2_evictions": 0,
                      "l2_integrity_drops": 0, "checkpoints": 0,
                      "restores": 0,
                      # harvest reads that actually blocked on an
                      # unfinished device block
                      "host_sync_stalls": 0,
                      # lanes and token positions the packed prefill
                      # dispatches computed (width, width x chunk
                      # each), real or not
                      "prefill_lanes_computed": 0,
                      "prefill_positions": 0}
        if self._tm is not None:
            # compatibility view: the legacy stats dict is exported by
            # the registry at exposition time (read by reference — the
            # dict stays a plain dict, so checkpointing and the
            # PrefixCache shared-stats contract are untouched)
            self._tm.registry.attach_stats("serve_", self.stats)
        # bounded retention of terminal bookkeeping (uid -> status /
        # result): FIFO order of terminal transition; 0 = historical
        # unbounded maps
        self._terminal_order: Deque[int] = collections.deque()
        # paged prefix cache: shared-prefix reuse across all mixer kinds
        # (cache_pages == 0 disables it — admission is byte-identical to
        # the cache-less engine, and no pool memory is allocated)
        self._pc: Optional[PrefixCache] = None
        if self.cache_pages > 0:
            self._pc = PrefixCache(cfg, max_len=max_len,
                                   page_size=self.page_size,
                                   cache_pages=self.cache_pages,
                                   a3=self._use_a3,
                                   kv_quant=self.kv_quant,
                                   l2_bytes=self.l2_bytes,
                                   stats=self.stats)
            self._pc.tm = self._tm
            if self._pc.l2 is not None and chaos is not None:
                # restore_corrupt site: flip a blob byte right before
                # its verified L2 restore (checksum must catch it)
                self._pc.l2_fault_hook = (
                    lambda key: self._chaos.l2_restore_corrupt(
                        self.stats["ticks"], key))

    @classmethod
    def from_config(cls, params: Any, cfg: ModelConfig, serve: ServeConfig,
                    a3: A3Config = A3Config(),
                    chaos: Optional[ChaosInjector] = None) -> "ServeEngine":
        return cls(params, cfg, slots=serve.slots, max_len=serve.max_len,
                   a3=a3, resort_every=serve.resort_every,
                   prefill_chunk=serve.prefill_chunk,
                   prefill_chunk_min=serve.prefill_chunk_min,
                   decode_block=serve.decode_block,
                   use_kernel=serve.use_kernel,
                   temperature=serve.temperature,
                   sample_seed=serve.sample_seed,
                   page_size=serve.page_size,
                   cache_pages=serve.cache_pages,
                   max_queue=serve.max_queue,
                   shed_policy=serve.shed_policy,
                   deadline_ticks=serve.deadline_ticks,
                   kv_quant=serve.kv_quant,
                   l2_bytes=serve.l2_bytes,
                   pipeline_depth=serve.pipeline_depth,
                   telemetry=serve.telemetry,
                   telemetry_every=serve.telemetry_every,
                   trace_events=serve.trace_events,
                   retain_results=serve.retain_results,
                   chaos=chaos)

    # -- public API ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               deadline_ticks: Optional[int] = None) -> int:
        """Submit a prompt; returns the request uid.

        Invalid *inputs* raise (TypeError / ValueError) without
        consuming a uid; overload *shedding* does not raise — the uid
        comes back with ``status(uid) == "rejected"`` so callers can
        distinguish "you sent garbage" from "the server is full".

        Validation: the prompt must be a non-empty 1-D integer array
        with token ids in ``[0, vocab_size)`` and length <= ``max_len``
        (a prompt of length *exactly* ``max_len`` is admitted and
        finishes with just its prefill-sampled token — there is no
        room to decode past it; longer prompts are an error, not a
        silent truncation). ``max_new_tokens`` must be >= 1.
        ``deadline_ticks`` (default: the engine-wide setting) expires
        the request if it has not FINISHED within that many ticks of
        submission."""
        arr = np.asarray(prompt)
        if arr.ndim != 1:
            raise ValueError(f"prompt must be 1-D, got shape {arr.shape}")
        if arr.size == 0:
            # neither admission path supports empty prompts (chunked
            # would fold a reused slot's stale ring into the A^3 sort;
            # whole-prompt prefill has no last position to unembed)
            raise ValueError("empty prompt")
        if not np.issubdtype(arr.dtype, np.integer):
            raise TypeError(f"prompt must be an integer token array, "
                            f"got dtype {arr.dtype}")
        if arr.size > self.max_len:
            raise ValueError(
                f"prompt length {arr.size} exceeds max_len "
                f"{self.max_len}: the slot cache cannot hold it "
                f"(submit a shorter prompt or raise max_len)")
        if (arr < 0).any() or (arr >= self.cfg.vocab_size).any():
            raise ValueError(
                f"prompt token ids must lie in [0, "
                f"{self.cfg.vocab_size}); got range "
                f"[{int(arr.min())}, {int(arr.max())}]")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if deadline_ticks is None:
            deadline_ticks = self.deadline_ticks
        deadline = None
        if deadline_ticks is not None:
            if int(deadline_ticks) < 1:
                raise ValueError(f"deadline_ticks must be >= 1, got "
                                 f"{deadline_ticks}")
            deadline = self.stats["ticks"] + int(deadline_ticks)
        uid = self._uid
        self._uid += 1
        self.stats["submitted"] += 1
        if self._tm is not None:
            self._tm.on_submit(uid)
        if self._draining:
            self._terminal(uid, REJECTED)
            return uid
        if self.max_queue and len(self._queue) >= self.max_queue:
            if self.shed_policy == "evict-oldest-queued":
                victim = self._queue.popleft()
                self._terminal(victim.uid, REJECTED)
            else:                      # reject-new
                self._terminal(uid, REJECTED)
                return uid
        self._status[uid] = QUEUED
        self._queue.append(
            Request(uid, arr.astype(np.int32), max_new_tokens, deadline))
        return uid

    def result(self, uid: int) -> Optional[List[int]]:
        """Generated tokens for a FINISHED request, else None (still in
        flight, or terminated rejected/cancelled/expired/failed).

        With bounded retention (``retain_results > 0``) a fetched
        result is popped — the first read returns the tokens and
        releases the engine's copy (later reads return None), so a
        long-running engine's result map holds only unread results,
        and at most ``retain_results`` of those."""
        if self.retain_results > 0:
            return self._done.pop(uid, None)
        return self._done.get(uid)

    def status(self, uid: int) -> str:
        """Lifecycle status of a submitted uid (see module docstring)."""
        try:
            return self._status[uid]
        except KeyError:
            raise KeyError(f"unknown request uid {uid}") from None

    def cancel(self, uid: int) -> bool:
        """Cancel a request in any non-terminal state. Queued requests
        leave the queue; on-slot requests are reclaimed immediately —
        mid-prefill or mid-decode — and their prefix-cache recording
        pin is dropped (refcounts return to baseline). Returns True if
        the request was cancelled, False if already terminal (or
        unknown)."""
        st = self._status.get(uid)
        if st == QUEUED:
            self._queue = collections.deque(
                r for r in self._queue if r.uid != uid)
            self._terminal(uid, CANCELLED)
            return True
        if st in (PREFILLING, DECODING):
            for si, s in enumerate(self.slots):
                if s.active and s.uid == uid:
                    self._release_slot(si, CANCELLED)
                    return True
        return False

    def drain(self):
        """Graceful shutdown: cancel all queued work, keep ticking
        in-flight slots to completion, reject every new submit.
        Idempotent; ``run_to_completion`` after ``drain`` finishes the
        slots and returns."""
        self._draining = True
        while self._queue:
            req = self._queue.popleft()
            self._terminal(req.uid, CANCELLED)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def tm(self) -> Optional[Telemetry]:
        """The telemetry bundle (None unless ``telemetry=True``)."""
        return self._tm

    @property
    def in_flight(self) -> int:
        """Requests not yet terminal: queued plus on-slot."""
        return len(self._queue) + sum(1 for s in self.slots if s.active)

    def step(self):
        """One engine tick: expire -> admit -> plan + pack -> chunked
        prefill -> blocked decode (the A^3 re-sort runs *inside* the
        decode dispatch) -> deferred harvest. Both dispatch phases are
        *planned* first against the post-admission slot table, their
        per-lane scalars packed into one ``[slots, CTRL_COLS]`` int32
        upload, and the prefill + decode dispatches issued
        back-to-back before any host sync; the ring harvest at the
        tail lands every block older than ``pipeline_depth``. With a
        chaos injector attached the injector is consulted at each
        phase boundary and may abort the tick with
        :class:`~repro.serve.chaos.ChaosError` — every phase leaves the
        engine consistent, so the next tick simply resumes (the
        caller counts the abort; ``run_to_completion`` does)."""
        self.stats["ticks"] += 1
        tick = self.stats["ticks"]
        tm = self._tm
        with phase("serve.tick", tm, step_num=tick) as span:
            ch = self._chaos
            if ch is not None:
                ch.phase(tick, "tick_start")
                if ch.consume_delay():
                    # virtual stall: the whole tick does no work (the
                    # wall-clock-free replacement for the old time.sleep
                    # delay — deterministic, and deadlines still elapse)
                    self.stats["chaos_delayed_ticks"] += 1
                    if tm is not None:
                        tm.event("chaos_delay", tick=tick)
                    return
                spill = ch.pick_spill(tick)
                if spill and self._pc is not None:
                    if tm is not None:
                        tm.event("chaos_spill", tick=tick, pages=spill)
                    self._pc.spill(spill)
            with phase("serve.admit", tm):
                self._expire_tick()
                self._admit()
            if ch is not None:
                ch.phase(tick, "pre_prefill")
            if any(s.phase == PREFILLING for s in self.slots):
                # an aborted tick (injected mid-tick raise) can leave
                # handoff first tokens unharvested; resolve them with a
                # direct read BEFORE the prefill dispatch overwrites
                # ``_first_tok`` (and before planning reads slot state)
                self._flush_stale_handoff()
            # plan both dispatch phases, pack their control words into
            # ONE transfer (the decode plan simulates the prefill plan's
            # slot transitions, so it needs no sync in between)
            with phase("serve.plan", tm):
                ctrl = np.zeros((len(self.slots), CTRL_COLS), np.int32)
                ctrl[:, CTRL_D_POS] = -1
                plan_p = self._plan_prefill(ctrl)
                plan_d = self._plan_decode(plan_p, ctrl)
                ctrl_dev = (jnp.asarray(ctrl) if plan_p is not None
                            or plan_d is not None else None)
            span.set(prefill_lanes=len(plan_p["pre"]) if plan_p else 0,
                     decode_lanes=len(plan_d["active"]) if plan_d else 0)
            self._prefill_tick(plan_p, ctrl_dev)
            if ch is not None:
                ch.phase(tick, "pre_advance")
            self._corrupt_tick()
            self._advance(plan_d, ctrl_dev)

    def run_to_completion(self, max_ticks: int = 10_000):
        """Tick until no work remains. Injected tick aborts
        (:class:`ChaosError`) are absorbed and counted in
        ``stats["chaos_aborted_ticks"]``. Hitting ``max_ticks`` with
        work still pending raises RuntimeError (and bumps
        ``stats["max_ticks_exhausted"]``) instead of returning
        silently with requests stranded in flight."""
        ticks = 0
        while self.in_flight and ticks < max_ticks:
            try:
                self.step()
            except EngineCrash:
                # injected process death: NOT absorbed — the caller's
                # recovery path is restore() from the last checkpoint
                raise
            except ChaosError:
                self.stats["chaos_aborted_ticks"] += 1
                if self._tm is not None:
                    self._tm.event("chaos_abort",
                                   tick=self.stats["ticks"])
            ticks += 1
        if self.in_flight:
            self.stats["max_ticks_exhausted"] += 1
            queued = [r.uid for r in self._queue]
            on_slot = [s.uid for s in self.slots if s.active]
            raise RuntimeError(
                f"run_to_completion exhausted max_ticks={max_ticks} "
                f"with {self.in_flight} requests still in flight "
                f"(queued uids {queued}, on-slot uids {on_slot}) — "
                f"raise max_ticks or investigate a stalled lane")

    # -- crash-consistent checkpoint / restore --------------------------------
    def _ckpt_kwargs(self) -> Dict[str, Any]:
        """The JSON-serializable constructor kwargs a restore rebuilds
        the engine from (params / cfg / a3 / chaos come from the
        caller and are validated against the saved echo)."""
        return {"slots": len(self.slots), "max_len": self.max_len,
                "resort_every": self.resort_every,
                "prefill_chunk": self.prefill_chunk,
                "prefill_chunk_min": self._chunk_min,
                "decode_block": self.decode_block,
                "use_kernel": bool(self.use_kernel),
                "temperature": self.temperature,
                "sample_seed": self.sample_seed,
                "page_size": self.page_size,
                "cache_pages": self.cache_pages,
                "max_queue": self.max_queue,
                "shed_policy": self.shed_policy,
                "deadline_ticks": self.deadline_ticks,
                "kv_quant": self.kv_quant,
                "l2_bytes": self.l2_bytes,
                "pipeline_depth": self.pipeline_depth,
                "virtual_device_latency_s":
                    self.virtual_device_latency_s,
                "telemetry": self.telemetry,
                "telemetry_every": self.telemetry_every,
                "trace_events": self.trace_events,
                "retain_results": self.retain_results}

    def checkpoint(self, path: str) -> None:
        """Snapshot the complete serving state to directory ``path``
        with an atomic rename commit: slots (mid-prefill cursors,
        generated tokens, budgets), queue, per-request status map and
        results, sampling state (the seed — the key is never mutated),
        stats, the device cache, and the prefix trie + pool + L2 blob
        store. A crash at ANY point leaves either the previous complete
        checkpoint or the new one — never a torn mix: everything is
        written into ``path + ".tmp"`` first and a single
        ``os.rename`` is the commit point (an interrupted commit
        leaves ``path + ".old"``, which :meth:`restore` falls back
        to). ``state.json`` carries a crc32 and the array payload is a
        self-checksummed :func:`~repro.serve.page_store.serialize_tree`
        blob, so a torn or bit-rotted checkpoint fails restore loudly
        (:class:`~repro.serve.page_store.CheckpointError`) instead of
        resuming with silently wrong state."""
        # land every in-flight ring harvest and resolve any pending
        # device-resident handoff tokens first: the snapshot must be
        # host-consistent at a tick boundary (a crash between a
        # dispatch and its deferred harvest loses only post-checkpoint
        # work — the restored engine re-decodes those tokens
        # bit-identically)
        t_ck = time.monotonic_ns()
        self._drain_harvests()
        self._flush_stale_handoff()
        self._finish_done_slots()
        slots_meta = []
        for s in self.slots:
            rec = None
            if s.rec_node is not None and self._pc is not None:
                rec = [int(x) for x in self._pc._path_of(s.rec_node)]
            slots_meta.append({
                "uid": s.uid, "pos": s.pos,
                "generated": [int(x) for x in s.generated],
                "budget": s.budget, "phase": s.phase,
                "prompt": (None if s.prompt is None
                           else [int(x) for x in s.prompt]),
                "cursor": s.cursor, "sorted_upto": s.sorted_upto,
                "rec": rec, "has_rec": s.rec_node is not None,
                "deadline": s.deadline})
        state: Dict[str, Any] = {
            "version": 1, "cfg_name": self.cfg.name,
            "a3_mode": self.a3.mode.value,
            "engine": self._ckpt_kwargs(),
            "uid": self._uid, "draining": self._draining,
            "stats": dict(self.stats),
            "status": {str(k): v for k, v in self._status.items()},
            "done": {str(k): [int(t) for t in v]
                     for k, v in self._done.items()},
            "queue": [{"uid": r.uid,
                       "prompt": [int(x) for x in r.prompt],
                       "max_new": r.max_new_tokens,
                       "deadline": r.deadline} for r in self._queue],
            "slots": slots_meta}
        if self._tm is not None:
            # histogram/counter state round-trips so a restored
            # engine's latency distributions continue instead of
            # resetting (optional key: older checkpoints lack it)
            state["telemetry"] = self._tm.dump_state()
        arrays: Dict[str, Any] = {"cache": self.cache}
        l2_blobs: List[bytes] = []
        if self._pc is not None:
            pc_meta, pc_arrays = self._pc.dump_state()
            state["pc"] = pc_meta
            arrays["pc"] = pc_arrays
            if self._pc.l2 is not None:
                index, off = [], 0
                for key, blob in self._pc.l2.raw_items():
                    index.append({"key": list(key), "off": off,
                                  "len": len(blob)})
                    l2_blobs.append(blob)
                    off += len(blob)
                state["l2_index"] = index
        tmp = path + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        payload = json.dumps(state, sort_keys=True).encode()
        with open(os.path.join(tmp, "state.json"), "wb") as f:
            f.write(b"%d\n" % zlib.crc32(payload) + payload)
        with open(os.path.join(tmp, "arrays.bin"), "wb") as f:
            f.write(serialize_tree(arrays))
        with open(os.path.join(tmp, "l2.bin"), "wb") as f:
            f.write(b"".join(l2_blobs))
        # atomic commit: the rename below is the durability point
        old = path + ".old"
        shutil.rmtree(old, ignore_errors=True)
        if os.path.isdir(path):
            os.rename(path, old)
        os.rename(tmp, path)
        shutil.rmtree(old, ignore_errors=True)
        self.stats["checkpoints"] += 1
        if self._tm is not None:
            now = time.monotonic_ns()
            self._tm.span("checkpoint", ts_ns=t_ck, dur_ns=now - t_ck,
                          path=path)

    @classmethod
    def restore(cls, path: str, params: Any, cfg: ModelConfig,
                a3: A3Config = A3Config(),
                chaos: Optional[ChaosInjector] = None) -> "ServeEngine":
        """Rebuild an engine from a :meth:`checkpoint` directory and
        resume exactly where it left off: ticking the restored engine
        yields token-for-token the outputs the uninterrupted run would
        have produced, for every queued / prefilling / decoding
        request (see the module docstring's durability diagram). The
        caller supplies what a checkpoint cannot durably own — params,
        the model config, the A^3 config, and optionally a fresh chaos
        injector — and the saved echo (cfg name, A^3 mode) is
        validated against them. Raises
        :class:`~repro.serve.page_store.CheckpointError` on any
        verification failure."""
        if not os.path.isdir(path) and os.path.isdir(path + ".old"):
            # a crash between the commit renames leaves only .old:
            # the previous complete checkpoint is still durable
            path = path + ".old"
        try:
            with open(os.path.join(path, "state.json"), "rb") as f:
                raw = f.read()
            crc_s, payload = raw.split(b"\n", 1)
            if zlib.crc32(payload) != int(crc_s):
                raise CheckpointError(
                    f"{path}: state.json checksum mismatch")
            state = json.loads(payload.decode())
            with open(os.path.join(path, "arrays.bin"), "rb") as f:
                arrays = deserialize_tree(f.read())
            with open(os.path.join(path, "l2.bin"), "rb") as f:
                l2_raw = f.read()
        except CheckpointError:
            raise
        except (OSError, ValueError, IntegrityError) as e:
            raise CheckpointError(
                f"unreadable checkpoint {path}: {e}") from None
        if state.get("version") != 1:
            raise CheckpointError(
                f"unsupported checkpoint version "
                f"{state.get('version')!r}")
        if state["cfg_name"] != cfg.name:
            raise CheckpointError(
                f"checkpoint was taken with model "
                f"{state['cfg_name']!r}; restoring with {cfg.name!r}")
        if state["a3_mode"] != a3.mode.value:
            raise CheckpointError(
                f"checkpoint A^3 mode {state['a3_mode']!r} does not "
                f"match {a3.mode.value!r}")
        eng = cls(params, cfg, a3=a3, chaos=chaos, **state["engine"])
        # stats is SHARED with the prefix cache: update in place; keys
        # this engine no longer keeps (an older checkpoint's) drop
        eng.stats.update({k: int(v) for k, v in state["stats"].items()
                          if k in eng.stats})
        eng._uid = int(state["uid"])
        eng._draining = bool(state["draining"])
        eng._status = {int(k): v for k, v in state["status"].items()}
        eng._done = {int(k): [int(t) for t in v]
                     for k, v in state["done"].items()}
        eng._queue = collections.deque(
            Request(int(q["uid"]), np.asarray(q["prompt"], np.int32),
                    int(q["max_new"]),
                    None if q["deadline"] is None else int(q["deadline"]))
            for q in state["queue"])
        eng.cache = jax.tree_util.tree_map(jnp.asarray, arrays["cache"])
        if eng._pc is not None and "pc" in state:
            eng._pc.load_state(state["pc"], arrays.get("pc", {}))
            if eng._pc.l2 is not None:
                for entry in state.get("l2_index", []):
                    off, n = int(entry["off"]), int(entry["len"])
                    eng._pc.l2.put_raw(
                        tuple(int(x) for x in entry["key"]),
                        l2_raw[off:off + n])
        for si, sm in enumerate(state["slots"]):
            s = SlotState(
                uid=int(sm["uid"]), pos=int(sm["pos"]),
                generated=[int(x) for x in sm["generated"]],
                budget=int(sm["budget"]), phase=sm["phase"],
                prompt=(None if sm["prompt"] is None
                        else np.asarray(sm["prompt"], np.int32)),
                cursor=int(sm["cursor"]),
                sorted_upto=int(sm["sorted_upto"]),
                deadline=(None if sm["deadline"] is None
                          else int(sm["deadline"])))
            if sm["has_rec"] and eng._pc is not None:
                # re-derive the recording-anchor pin from the node's
                # token path (refs are not serialized — they restore
                # exactly from the slots that hold them)
                node: Any = eng._pc.root
                toks = [int(x) for x in sm["rec"]]
                ps = eng.page_size
                for b in range(0, len(toks), ps):
                    node = node.children.get(tuple(toks[b:b + ps]))
                    if node is None:
                        break
                if node is not None:
                    s.rec_node = node
                    eng._pc.ref(node)
            eng.slots[si] = s
        eng.stats["restores"] += 1
        if eng._tm is not None:
            if "telemetry" in state:
                eng._tm.load_state(state["telemetry"])
            eng._tm.event("restore", tick=int(eng.stats["ticks"]))
        return eng

    # -- internals ------------------------------------------------------------
    def _terminal(self, uid: int, status: str):
        """Move a request to a terminal status exactly once and bump
        the matching conservation counter. With ``retain_results > 0``
        the oldest terminal entries beyond the bound are dropped from
        the status/result maps (the conservation counters above are
        the durable record; the maps are a serving-window view)."""
        self._status[uid] = status
        self.stats[_TERMINAL[status]] += 1
        if self._tm is not None:
            self._tm.on_terminal(uid, status)
        if self.retain_results > 0:
            self._terminal_order.append(uid)
            while len(self._terminal_order) > self.retain_results:
                old = self._terminal_order.popleft()
                self._status.pop(old, None)
                self._done.pop(old, None)

    def _release_slot(self, si: int, status: str):
        """Reclaim a slot from ANY in-flight phase (cancel / expire /
        poison-fail): drop the prefix-cache recording pin so trie
        refcounts return to baseline, forget any pending device-
        resident handoff token, and free the lane — the slot admits new
        work on the next tick. No device cleanup is needed: a fresh
        admission resets the lane's mixer state in-graph at pos == 0."""
        s = self.slots[si]
        if s.rec_node is not None and self._pc is not None:
            self._pc.unref(s.rec_node)
        self._handoff.discard(si)
        self._carry_ok[si] = False
        self._terminal(s.uid, status)
        self.slots[si] = SlotState()

    def _expire_tick(self):
        """Enforce per-request deadlines at the tick boundary: a
        request submitted at tick T with deadline_ticks d expires at
        the start of tick T + d + 1 if not yet FINISHED — it gets d
        full ticks of service, queued or on-slot alike."""
        now = self.stats["ticks"]
        if self._queue and any(r.deadline is not None
                               for r in self._queue):
            kept: Deque[Request] = collections.deque()
            for req in self._queue:
                if req.deadline is not None and now > req.deadline:
                    self._terminal(req.uid, EXPIRED)
                else:
                    kept.append(req)
            self._queue = kept
        for si, s in enumerate(self.slots):
            if s.active and s.deadline is not None and now > s.deadline:
                self._release_slot(si, EXPIRED)

    def _corrupt_tick(self):
        """Chaos site: overwrite one decoding lane's mixer state with
        NaN (victim picked deterministically by the injector). The
        lane's next logits go non-finite and the decode dispatch emits
        POISON on the harvested ring — detection costs no extra sync."""
        if self._chaos is None:
            return
        decoding = {s.uid: si for si, s in enumerate(self.slots)
                    if s.decoding}
        if not decoding:
            return
        victim = self._chaos.pick_corrupt_victim(
            self.stats["ticks"], sorted(decoding))
        if victim is None:
            return
        if self._tm is not None:
            self._tm.event("chaos_corrupt", uid=victim,
                           track=decoding[victim])
        self.cache = corrupt_cache_lane(self.cache, decoding[victim])

    def _admit(self):
        # Phase 1 — assignment: queued requests claim free slots. The
        # warm path walks the prefix trie (extending through the L2
        # tier: demoted pages promote back with verified restores) —
        # the cursor starts past the matched prefix and only the
        # suffix chunk-prefills. Cold path (miss / cache disabled): no
        # host-side cache work at admit; the slot's first chunk
        # dispatch resets its mixer state in-graph (pos == 0), so
        # chunked prefill reproduces the whole-prompt cache state.
        assigned: List[Tuple[int, Request, int, Any]] = []
        for si, slot in enumerate(self.slots):
            if slot.active:
                continue
            while self._queue:
                req = self._queue.popleft()
                t, node = 0, None
                if self._pc is not None:
                    t, node = self._pc.lookup(req.prompt)
                    if t > 0 and self._chaos is not None:
                        try:
                            self._chaos.gather_fail(self.stats["ticks"],
                                                    req.uid, t)
                        except ChaosError:
                            # injected page-gather failure BEFORE the
                            # copy dispatch: the device cache is
                            # untouched and no trie ref was taken —
                            # fail the request, keep the slot free for
                            # the next one
                            if self._tm is not None:
                                self._tm.event("chaos_gather_fail",
                                               uid=req.uid)
                            self._terminal(req.uid, FAILED)
                            continue
                    # pin the matched chain NOW: a later assignment's
                    # L2 promotion could otherwise evict it between
                    # this lookup and the batched gather below
                    self._pc.ref(node)       # recording anchor pin
                assigned.append((si, req, t, node))
                break
        # Phase 2 — one stacked gather dispatch warm-admits EVERY
        # matched slot (ring rows from pool pages, recurrent carries
        # from boundary snapshots, A^3 sorted state + watermark
        # restored — no re-sort): a flash crowd of N same-prefix hits
        # costs one gather_dispatches increment, not N.
        warm = [(si, t, node) for si, req, t, node in assigned if t > 0]
        if warm:
            self.cache = self._pc.gather_into(self.cache, warm)
        for si, req, t, node in assigned:
            self.slots[si] = SlotState(uid=req.uid, pos=t,
                                       generated=[],
                                       budget=req.max_new_tokens,
                                       phase=PREFILLING,
                                       prompt=req.prompt, cursor=t,
                                       sorted_upto=t, rec_node=node,
                                       deadline=req.deadline)
            self._status[req.uid] = PREFILLING
            if self._tm is not None:
                self._tm.on_admit(req.uid, si, reused_tokens=t)

    def _plan_prefill(self, ctrl: np.ndarray) -> Optional[Dict[str, Any]]:
        """Plan this tick's chunked-prefill dispatch against the
        post-admission slot table WITHOUT touching any state: compute
        each PREFILLING lane's chunk ``take`` (page-boundary clamping
        included), pack the lanes into the ``[b, chunk]`` token block
        and its ``lane_slot`` vector (None at full width), and write the
        ``CTRL_P_*`` columns of the shared packed control block (rows
        stay keyed by slot). Returns None when no lane prefills. The
        decode plan consumes the result to simulate the prefill's
        slot transitions, so both dispatches issue back-to-back off
        one upload with no sync between them."""
        pre = [si for si, s in enumerate(self.slots)
               if s.phase == PREFILLING]
        if not pre:
            return None
        n, c = len(self.slots), self._chunk
        # only the prefilling lanes are computed, packed to a
        # power-of-two width; padding lanes take other slots at length
        # 0 (their rows pass through). At full width the lanes are the
        # slots in order: the unpacked program, with no lane_slot.
        b = _prefill_width(len(pre), n)
        lanes = sorted(pre + [si for si in range(n)
                              if si not in pre][:b - len(pre)])
        # adaptive chunking: decoders active -> shrink the admission
        # stall to the floor; cold queue -> drain at the full chunk
        if self._chunk_min is not None \
                and any(s.decoding for s in self.slots):
            c = self._chunk_min
            self.stats["adaptive_shrink_ticks"] += 1
        ps = self.page_size
        tokens = np.zeros((b, c), np.int32)
        row = {si: j for j, si in enumerate(lanes)}
        sort_any = False
        takes = {}
        for si in pre:
            s = self.slots[si]
            take = min(c, len(s.prompt) - s.cursor)
            if s.rec_node is not None:
                # Recorded prompts bound EVERY chunk by record_span and
                # land EVERY boundary-crossing chunk exactly on its last
                # page boundary (an unaligned tail < page_size follows
                # in the next dispatch, crossing nothing). Page capture
                # reads the rings once at chunk end, so together these
                # guarantee each recorded page's unmasked positions are
                # still ring-resident at capture — a wider or unaligned
                # chunk would record rows the chunk itself had already
                # overwritten in a sliding ring, stale pages a later
                # dedupe could upgrade into a match terminal. The
                # post-chunk mixer carry at the END boundary IS the trie
                # node's snapshot, so no replay dispatch is ever needed.
                take = min(take, self._pc.record_span)
                if s.cursor % ps:
                    # unaligned start (adaptive floor / sub-page
                    # chunks): realign at the FIRST boundary — crossing
                    # several boundaries from an unaligned start can
                    # outrun a sliding ring's capture residency even
                    # within record_span
                    take = min(take, ps - s.cursor % ps)
                else:
                    aligned = ((s.cursor + take) // ps) * ps
                    if aligned > s.cursor:
                        take = aligned - s.cursor
            tokens[row[si], :take] = s.prompt[s.cursor:s.cursor + take]
            ctrl[si, CTRL_P_POS] = s.cursor
            ctrl[si, CTRL_P_LEN] = take
            takes[si] = take
            # A^3 sort amortization: fold into the column sort only on
            # the prompt's final chunk (one sort per admitted prompt).
            if s.cursor + take >= len(s.prompt):
                ctrl[si, CTRL_P_SORT] = 1
                sort_any = True
            # sampling key for the in-graph first-token draw, keyed at
            # the producing position len(prompt)-1 (== cursor+take-1 on
            # the final chunk; meaningless and unused for other lanes)
            ctrl[si, CTRL_P_SPOS] = s.cursor + take - 1
            ctrl[si, CTRL_P_SIDS] = s.uid
        return {"pre": pre, "takes": takes, "tokens": jnp.asarray(tokens),
                "lane_slot": (jnp.asarray(np.array(lanes, np.int32))
                              if b < n else None),
                "sort_any": sort_any}

    def _prefill_tick(self, plan: Optional[Dict[str, Any]],
                      ctrl_dev) -> None:
        """Advance every PREFILLING slot by one prompt chunk in a single
        ragged dispatch of the packed ``[b, chunk]`` block (planned by
        :meth:`_plan_prefill`); finishing lanes' first tokens are
        sampled in-graph and stay on device for the decode handoff."""
        if plan is None:
            return
        pre, takes = plan["pre"], plan["takes"]
        tokens = plan["tokens"]
        width, c = tokens.shape
        if c not in self._prefill_warm:
            self._warm_prefill(c)
        fn = self._prefill
        if self._prefill_nosort is not None and not plan["sort_any"]:
            fn = self._prefill_nosort
        tm = self._tm
        with phase("serve.dispatch.prefill", tm, lanes=len(pre),
                   width=width, tokens=sum(takes.values()),
                   positions=tokens.size) as disp:
            first_tok, self.cache = self._dispatch_prefill(
                fn, tokens, ctrl_dev, plan["lane_slot"])
        self.stats["prefill_dispatches"] += 1
        self.stats["prefill_lanes_computed"] += width
        self.stats["prefill_positions"] += tokens.size
        with phase("serve.prefill.book", tm):
            self._book_prefill(plan, first_tok, disp)

    def _dispatch_prefill(self, fn, tokens, ctrl, lane_slot):
        args = (self.params, self.cache, tokens, ctrl)
        if self._sample_rng is not None:
            args += (self._sample_rng,)
        if lane_slot is None:
            return fn(*args)
        return fn(*args, lane_slot=lane_slot)

    def _warm_prefill(self, c: int) -> None:
        """Compile the prefill program of every packed width (and both
        A^3 sort variants) for chunk length ``c`` before the first real
        dispatch at that length: each dispatches once with every
        length 0, which passes the cache through untouched. Ticks that
        pack 1, 2, 4, ... lanes then never compile mid-stream."""
        n = len(self.slots)
        # device arrays, as the real dispatches pass: numpy arguments
        # fill another dispatch-cache entry, and the first real
        # dispatch of each width would trace again
        ctrl = jnp.zeros((n, CTRL_COLS), jnp.int32)
        fns = [f for f in (self._prefill, self._prefill_nosort)
               if f is not None]
        for b in sorted({_prefill_width(k, n) for k in range(1, n + 1)}):
            lane_slot = jnp.arange(b, dtype=jnp.int32) if b < n else None
            for fn in fns:
                _, self.cache = self._dispatch_prefill(
                    fn, jnp.zeros((b, c), jnp.int32), ctrl, lane_slot)
        self._prefill_warm.add(c)

    def _book_prefill(self, plan: Dict[str, Any], first_tok,
                      disp: phase) -> None:
        """Host bookkeeping after a prefill dispatch: per-lane cursors,
        prefix-cache page records, and the prefill -> decode handoff."""
        pre, takes = plan["pre"], plan["takes"]
        ps = self.page_size
        if self._tm is not None:
            # one ragged dispatch serves every prefilling lane; each
            # lane gets a span of the shared dispatch wall time
            for si in pre:
                s = self.slots[si]
                self._tm.on_prefill_chunk(s.uid, si, ts_ns=disp.t0_ns,
                                          dur_ns=disp.dur_ns, pos=s.cursor,
                                          chunk=takes[si])
        for si in pre:
            s = self.slots[si]
            s.cursor += takes[si]
            s.pos = s.cursor
            self.stats["prefill_tokens"] += takes[si]
            if s.rec_node is not None and s.cursor > s.rec_node.end:
                # record every page boundary the chunk crossed: each
                # copies one page pool-ward (deduped against concurrent
                # recorders); only the chunk-END boundary carries the
                # recurrent snapshot (the slot's carry is at end-state
                # only there). A None return means the page budget is
                # exhausted with nothing evictable — stop recording,
                # keep the prefix recorded so far
                prev = s.cursor - takes[si]
                for b in range((prev // ps + 1) * ps, s.cursor + 1, ps):
                    child = self._pc.record_boundary(
                        self.cache, si, s.prompt, b, s.rec_node,
                        carry=(b == s.cursor))
                    self._pc.unref(s.rec_node)
                    self._pc.ref(child)
                    s.rec_node = child
                    if child is None:
                        break
            if s.cursor >= len(s.prompt):
                # device-resident handoff: the first token exists only
                # in ``first_tok`` until the decode harvest resolves it
                s.phase = DECODING
                self._status[s.uid] = DECODING
                s.generated = []
                s.budget -= 1
                s.sorted_upto = len(s.prompt)  # final chunk folded the sort
                self._handoff.add(si)
                if s.rec_node is not None:
                    # leaf capture of the A^3 sorted columns (the final
                    # chunk just folded the full-ring sort), then drop
                    # the recording pin
                    self._pc.record_final(self.cache, si, s.rec_node,
                                          len(s.prompt))
                    self._pc.unref(s.rec_node)
                    s.rec_node = None
        if self._handoff:
            self._first_tok = first_tok

    def _flush_stale_handoff(self):
        """Resolve leftover device-resident handoff tokens with one
        direct read. Only an injected mid-tick abort between the
        prefill dispatch and the decode harvest leaves any — in normal
        operation the same tick's ``_advance`` always consumes the
        handoff set, so this never fires (and never costs a sync).
        Pending ring harvests land first so ``generated`` is current
        before the finish check runs."""
        if not self._handoff:
            return
        self._drain_harvests()
        self._read_handoff(self._handoff)
        self._handoff = set()
        self._first_tok = None
        self._finish_done_slots()

    def _read_handoff(self, handoff: set) -> None:
        """Land the first tokens of ``handoff`` lanes with one direct
        read of the prefill dispatch's device-resident output."""
        with phase("serve.harvest.wait", self._tm, forced=1):
            first = np.asarray(self._first_tok)
        self.stats["host_syncs"] += 1
        self.stats["handoff_syncs"] += 1
        for si in sorted(handoff):
            s = self.slots[si]
            if not s.decoding:
                continue               # released while the token was stale
            tok = int(first[si])
            if tok == decoder.POISON:
                # non-finite prompt logits: quarantine
                self._release_slot(si, FAILED)
            else:
                s.generated.append(tok)
                if self._tm is not None:
                    self._tm.on_first_token(s.uid)
            # the lane's token never entered a decode block, so the
            # device carry has no valid entry for it: the next block
            # rebuilds its input from ``generated`` (cold path)
            self._carry_ok[si] = False

    def _plan_decode(self, plan_p: Optional[Dict[str, Any]],
                     ctrl: np.ndarray) -> Optional[Dict[str, Any]]:
        """Plan this tick's decode block against the slot table AS IT
        WILL BE after the planned prefill dispatch lands: lanes on
        their final prompt chunk join the handoff set with
        ``pos = len(prompt)`` and one budget unit spent on the in-graph
        first token. The simulation is exact (the prefill bookkeeping
        applies the same ``takes``), which is what lets both dispatches
        issue off one packed upload with no sync between them. Writes
        the ``CTRL_D_*`` columns; returns None when no lane can
        advance (the caller then handles any direct handoff reads)."""
        handoff = set(self._handoff)
        state: Dict[int, Tuple[int, int]] = {}
        for si, s in enumerate(self.slots):
            if s.decoding:
                state[si] = (s.pos, s.budget)
            elif plan_p is not None and si in plan_p["takes"]:
                if s.cursor + plan_p["takes"][si] >= len(s.prompt):
                    # finishes its prompt this tick: decodes from
                    # pos = len(prompt) with the first token's budget
                    # unit already spent (sampled in-graph)
                    state[si] = (len(s.prompt), s.budget - 1)
                    handoff.add(si)
        active = [si for si in sorted(state)
                  if state[si][1] > 0 and state[si][0] < self.max_len - 1]
        # the handoff mask covers ALL handoff lanes — ride-along ones
        # included, so their first token reaches the host via the
        # harvest's input column even when they cannot advance
        for si in handoff:
            ctrl[si, CTRL_D_HMASK] = 1
        if not active:
            return None
        n = len(self.slots)
        steps_left = np.zeros((n,), np.int32)
        pos0 = {}
        for si in active:
            p, b = state[si]
            steps_left[si] = min(b, self.max_len - 1 - p)
            pos0[si] = p
            ctrl[si, CTRL_D_POS] = p
            ctrl[si, CTRL_D_STEPS] = steps_left[si]
            ctrl[si, CTRL_D_IDS] = self.slots[si].uid
        return {"active": active, "steps_left": steps_left, "pos0": pos0}

    def _advance(self, plan: Optional[Dict[str, Any]], ctrl_dev) -> None:
        handoff = self._handoff
        self._handoff = set()
        if plan is None:
            # nothing can advance: land anything still in flight, then
            # resolve handoff lanes with a direct read (rare — every
            # handoff lane finished with its prefill token, from
            # budget == 1 or a max_len-length prompt)
            self._drain_harvests()
            if handoff:
                self._read_handoff(handoff)
            self._finish_done_slots()
            return
        # blocked ragged decode: every advanceable slot moves up to
        # ``decode_block`` tokens in ONE jitted dispatch — sampling,
        # token feedback, the handoff select, and the A^3 re-sort all
        # happen in-graph off the packed ctrl upload. Idle/prefilling
        # slots ride along at pos=-1 (dropped ring writes, masked
        # recurrent state); lanes that exhaust their budget or hit
        # max_len mid-block are masked off in-graph via ``steps_left``.
        n, t = len(self.slots), self.decode_block
        active, steps_left = plan["active"], plan["steps_left"]
        # pipelined drain point (depth >= 1): land the over-depth
        # OLDEST rings BEFORE this tick's dispatch, keeping up to
        # ``depth`` blocks in flight behind the device. Draining only
        # the excess is what keeps the pipe primed — the popped ring
        # was dispatched depth+1 ticks ago and is (almost always)
        # already computed, while the newer rings stay queued so the
        # device never goes idle waiting on host bookkeeping. Depth 0
        # instead drains synchronously after the dispatch below.
        if self.pipeline_depth > 0:
            self._drain_harvests(keep=self.pipeline_depth)
        # input tokens: the previous block's device-resident carry, by
        # construction the last emitted token of every lane that has
        # ever decoded (handoff lanes take ``first_tok`` in-graph
        # instead). Cold path — engine start, restore, or a lane whose
        # carry a direct read invalidated — rebuilds the vector from
        # host ``generated`` state, landing pending harvests first so
        # that state is current.
        if self._token_carry is None or \
                any(not self._carry_ok[si] for si in active
                    if si not in handoff):
            self._drain_harvests()
            tokens = np.zeros((n,), np.int32)
            for si in active:
                s = self.slots[si]
                if s.decoding and s.generated:
                    tokens[si] = s.generated[-1]
            token_dev = jnp.asarray(tokens)
        else:
            token_dev = self._token_carry
        first = self._first_tok if handoff else self._zero_tok
        args = (self.params, self.cache, token_dev, first, ctrl_dev)
        # A^3 telemetry sampling: every telemetry_every-th decode
        # dispatch routes through the probe jit — identical token ops
        # plus the in-graph quality accumulator, harvested on the same
        # deferred drain (zero extra syncs, bit-identical streams)
        probe_out = None
        fn = self._decode_block
        if self._decode_block_probe is not None and \
                self.stats["decode_dispatches"] % self.telemetry_every == 0:
            fn = self._decode_block_probe
        with phase("serve.dispatch.decode", self._tm, lanes=len(active),
                   steps=t) as disp:
            if self._sample_rng is not None:
                out = fn(*args, self._sample_rng)
            else:
                out = fn(*args)
        if fn is self._decode_block:
            full, carry, self.cache = out
        else:
            full, probe_out, carry, self.cache = out
        # decode_steps counts executed scan iterations (T per dispatch);
        # decode_steps_advanced counts sequential steps that advanced at
        # least one lane (the deepest lane's progress) — iterations past
        # it only push masked ride-along lanes
        self.stats["decode_steps"] += t
        self.stats["decode_steps_advanced"] += int(min(t, steps_left.max()))
        self.stats["decode_dispatches"] += 1
        self.stats["decode_blocks"] += 1
        # the carry is valid for every lane the block touched: active
        # lanes end on their last emitted token, handoff lanes pass
        # their first token through, every other previously-valid lane
        # passes its carry through unchanged
        self._token_carry = carry
        for si in active:
            self._carry_ok[si] = True
        for si in handoff:
            self._carry_ok[si] = True
        # enqueue the harvest with its bookkeeping frozen at dispatch
        # time, then advance pos/budget optimistically (``steps_left``
        # is deterministic in them — the device executes exactly this
        # schedule; only a poison release can cut a lane short, and
        # the uid guard drops that lane's stale entries). Depth 0
        # lands this block immediately (synchronous engine); depth d
        # leaves it in flight for the pre-dispatch drain above, so
        # finish/poison/deadline bookkeeping acts on the harvested
        # (delayed) view while the device runs ahead.
        entry = _PendingHarvest(
            full=full,
            handoff=[(si, self.slots[si].uid) for si in sorted(handoff)
                     if self.slots[si].decoding],
            lanes=[(si, self.slots[si].uid,
                    int(min(t, steps_left[si])), plan["pos0"][si])
                   for si in active if self.slots[si].decoding],
            refs={},
            ready_at=(time.monotonic() + self.virtual_device_latency_s
                      if self.virtual_device_latency_s > 0.0 else 0.0),
            probe=probe_out, t_dispatch=disp.t0_ns)
        for si, uid in entry.handoff:
            entry.refs[si] = uid
        for si, uid, nb, _pos0 in entry.lanes:
            entry.refs[si] = uid
            s = self.slots[si]
            s.pos += nb
            s.budget -= nb
        for si in entry.refs:
            self.slots[si].pending += 1
        self._pending.append(entry)
        if self.pipeline_depth == 0:
            self._drain_harvests()
        self._finish_done_slots()

    def _drain_harvests(self, keep: int = 0):
        """Land queued ring harvests oldest-first at ONE
        synchronization point, leaving up to ``keep`` of the newest in
        flight. The forced pops are the over-``keep`` excess — blocks
        dispatched long enough ago that the device has normally
        finished them — and any further blocks that already completed
        ride along for free, so a drain batches as wide as the device
        allows without ever waiting out work it just queued.
        ``host_syncs`` grows once per drain event, not once per block;
        ``host_sync_stalls`` counts drains where a forced block had
        not finished computing when the read issued (a depth-0 drain
        always stalls: it reads the block it just dispatched; a primed
        pipeline's pre-dispatch drain mostly finds the data ready)."""
        if len(self._pending) <= keep:
            return
        now = time.monotonic()
        entries = [self._pending.popleft()
                   for _ in range(len(self._pending) - keep)]
        forced = len(entries)
        if any(not _block_done(e.full) or e.ready_at > now
               for e in entries):
            self.stats["host_sync_stalls"] += 1
            if self._tm is not None:
                # the stall shows on the timeline as the gap between
                # this instant and the stalled blocks' span ends
                self._tm.event("host_sync_stall",
                               forced_blocks=len(entries),
                               in_flight=len(self._pending))
        # opportunistic sweep: newer blocks that have already landed
        # on-device cost nothing to read now and widen the gap to the
        # next forced drain
        while self._pending and _block_done(self._pending[0].full) \
                and self._pending[0].ready_at <= now:
            entries.append(self._pending.popleft())
        self.stats["host_syncs"] += 1
        rows = []
        with phase("serve.harvest.wait", self._tm, forced=forced):
            for e in entries:
                # virtual-device emulation: a block is unreadable before
                # its emulated completion; the sleep releases the GIL,
                # so real XLA compute (and nothing else, on the
                # synchronous path) proceeds underneath it
                wait = e.ready_at - time.monotonic()
                if wait > 0.0:
                    time.sleep(wait)
                rows.append(np.asarray(e.full))
        with phase("serve.harvest.apply", self._tm):
            for e, h in zip(entries, rows):
                self._apply_harvest(e, h)

    def _apply_harvest(self, e: _PendingHarvest, h: np.ndarray):
        """Run one block's deferred host bookkeeping against its
        harvested rows: handoff first tokens off column 0, generated
        extends + the A^3 watermark mirror off the ring columns, and
        poison quarantine for lanes whose rows carry the sentinel.
        Every row is uid-guarded — a lane released while the harvest
        was in flight contributes nothing to its slot's successor."""
        tm = self._tm
        if tm is not None:
            now = tm.tracer.now_ns()
            tm.on_decode_block(
                [(si, uid) for si, uid, _nb, _p0 in e.lanes],
                ts_ns=e.t_dispatch or now,
                dur_ns=now - e.t_dispatch if e.t_dispatch else 0,
                steps=max((nb for _si, _u, nb, _p0 in e.lanes),
                          default=0),
                deferred=self.pipeline_depth > 0)
            if e.probe is not None:
                # the probe array computed in the same dispatch as the
                # ring: np.asarray here is part of the same drain
                # event, so ``host_syncs`` does not grow
                tm.on_a3_probe(np.asarray(e.probe))
        for si, uid in e.handoff:
            s = self.slots[si]
            if s.uid != uid or not s.decoding:
                continue               # released while the block flew
            tok = int(h[si, 0])
            if tok == decoder.POISON:
                # non-finite prompt logits poisoned the handoff token:
                # quarantine off the harvest the block already paid for
                self._release_slot(si, FAILED)
            else:
                s.generated.append(tok)
                if tm is not None:
                    tm.on_first_token(s.uid)
        for si, uid, nb, pos0 in e.lanes:
            s = self.slots[si]
            if s.uid != uid or not s.decoding:
                continue               # failed via its handoff token,
                                       # or released while the block flew
            row = h[si, 1:1 + nb]
            if (row == decoder.POISON).any():
                # the lane's logits went non-finite mid-block (POISON
                # rode the existing harvest — no extra sync): FAIL the
                # request and reclaim the slot; every other lane's
                # tokens and cache state are bit-identical (the poison
                # select is lane-local, and a poisoned carry re-poisons
                # any block the lane rode before this harvest landed)
                self._release_slot(si, FAILED)
                continue
            s.generated.extend(int(tok) for tok in row)
            if tm is not None and nb > 0:
                tm.on_decode_steps(s.uid, nb)
            if self._use_a3:
                # mirror the in-graph watermark (checked before each
                # step's ring write, exactly as resort_sorted_keys
                # does) from the position the lane held at dispatch
                for p in range(pos0, pos0 + nb):
                    if p - s.sorted_upto >= self.resort_every:
                        s.sorted_upto = p
                        self.stats["resorts"] += self._n_a3_segs
        for si, uid in e.refs.items():
            s = self.slots[si]
            if s.uid == uid:
                s.pending = max(0, s.pending - 1)

    def _finish_done_slots(self):
        with phase("serve.finish", self._tm):
            for si, s in enumerate(self.slots):
                if s.decoding and s.pending == 0 \
                        and (s.budget <= 0 or s.pos >= self.max_len - 1):
                    self._finish(si)

    def _finish(self, si: int):
        slot = self.slots[si]
        self._done[slot.uid] = slot.generated
        self._terminal(slot.uid, FINISHED)
        self._carry_ok[si] = False
        self.slots[si] = SlotState()


def _block_done(arr) -> bool:
    """True when a dispatched block's output has finished computing
    (so reading it back will not stall the host). Conservative: a
    runtime without ``is_ready`` reports False (counts as a stall)."""
    try:
        return bool(arr.is_ready())
    except AttributeError:             # pragma: no cover - runtime-dependent
        return False
