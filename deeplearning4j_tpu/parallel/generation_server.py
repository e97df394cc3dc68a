"""GenerationServer: continuous-batching decode serving.

``ParallelInference`` coalesces STATELESS forwards; a causal decoder is
the stateful analogue — every decode tick streams the full parameter
set from HBM regardless of how many rows ride along (how far a tick
sits from that params-bandwidth ideal is not measured on today's
code), so aggregate tokens/s scales almost free with batch until
memory binds.  This module multiplexes many concurrent
``submit()`` callers onto ONE jitted decode tick over a fixed pool of
``n_slots`` slots — Orca-style continuous batching: requests join and
leave mid-flight instead of waiting for the whole batch.

KV memory is PAGED (PR 7): instead of each slot owning a contiguous
``[max_len]`` stripe (which pinned a whole stripe per request however
short, and re-prefilled identical system prompts per request), K/V
live in a global pool of ``kv_blocks`` fixed-size blocks
([n_layers, 1 + kv_blocks, h, block_size, dh]; block 0 is the
never-read scratch sink for masked-inactive writes) and every slot
carries a device-resident ``[max_blocks]`` int32 **block table**
beside its pos/remaining/EOS state.  A request pins
``ceil((t0 + n_new) / block_size)`` blocks, so BLOCKS — not slots —
are the scarce resource admission queues on.  Attention reads through
the table via ``kernels.paged_attention`` (Pallas kernel on TPU, a
``jnp.take``-gather reference path elsewhere — the reference mirrors
the stripe math exactly, which is what keeps greedy byte parity with
offline ``generate()`` through the paged rewrite).

Shared-prefix reuse rides on the block pool: admission chain-hashes
the prompt's full blocks, looks them up in a host-side ref-counted
prefix cache (under ``_lock``), maps hits into the new slot's block
table COPY-FREE, and prefill runs only on the uncached suffix
(``_prefill_rows_chunked`` — the cached prefix's compute is the work
the cache saves, the dominant serving win when many requests share
one system prompt).  At retire a block whose refcount drains stays
resident as an EVICTABLE cache entry (LRU-evicted only when admission
runs short of free blocks), so the next same-prefix request still
hits.

Design:

* the decode tick is ONE static-shape XLA program: per-slot
  position / remaining-budget / EOS-id / block-table / sampling params
  live in device-side state, sampling masks inactive slots, and cache
  writes land at (block, offset) targets routed through each slot's
  table (``TransformerGenerator._step_paged``: every run's ``step()``
  with the paged pool as its ``attend``);
* the scheduler fuses up to ``tick_batch`` ticks into ONE device-side
  ``lax.scan`` (``_decode_scan``): sampled tokens stage in a [B, K]
  device buffer and the host polls ONCE per scan instead of once per
  token — per-token dispatch overhead and the device->host sync drop
  by ~K.  The scan length adapts: K=1 whenever admission is pending
  (TTFT does not regress behind a long scan) and the largest
  power-of-two <= the longest live budget otherwise (trailing ticks
  drain exactly; retired/EOS slots inside a scan tick masked at pos 0,
  preserving the poisoned-slot invariant below);
* between ticks the host scheduler admits queued requests into free
  slots — ON A MISS prefill runs the existing batched causal forward
  (``_prefill_rows``: every run's ``sequence()`` scanned over its
  stacked params) with the
  prompt padded to a power-of-two bucket rounded to the block size
  (bounds prefill recompiles at log2(L) variants; padded rows are
  never attended before being overwritten by decode writes); ON A
  PREFIX HIT the cached blocks are gathered as the key prefix and
  only the suffix prefills (``_prefill_rows_chunked``; the prefix
  gather is EXACT-length — padding inside the key axis would change
  XLA's reduction grouping and break byte parity, so hit-path
  compiles key on (suffix bucket, matched blocks)).  Either way the
  resulting K/V rows scatter into the slot's fresh blocks;
* finished slots (budget exhausted or EOS sampled) retire back to
  their callers and free up for the next queued request.

Self-healing (resilience layer): the scheduler's in-flight state
(active slots, wait line, free list) lives on the INSTANCE under a
lock, and the scheduler thread holds an epoch token — so a watchdog
thread can declare a tick stuck (``tick_timeout_s`` exceeded) or the
scheduler dead, bump the epoch (the old thread, if it ever wakes, sees
the stale token and exits without touching anything), and start a
fresh scheduler — admission resumes instead of the server dying with
its callers blocked forever.  Recovery is SURGICAL and
BLOCK-GRANULAR (KV salvage): the finiteness screen runs per pool
BLOCK, a slot is implicated only when one of ITS OWN blocks (or its
held logits) is poisoned, and the rebuild zeroes exactly the dropped
blocks — kept slots' blocks, their device state, AND finite
prefix-cache blocks carry over, so unaffected in-flight requests
complete without resubmission, byte-identical to offline
``generate()``, and the prefix cache stays warm across a recovery —
only the implicated slot(s) (a raising admission's slot, a poisoned
block, or an unrecoverable donated pool) fail with a typed
``RetryableServerError``; queued requests just wait the recovery out
(``kv_slots_{salvaged,dropped}_total`` and the block-granular
``kv_blocks_{salvaged,dropped}_total``).
Requests carry optional deadlines (queue wait counts), handles can be
``cancel()``-ed to release their queue entry/slot budget, blocking
``submit()`` optionally retries retryable failures with jittered
exponential backoff, and ``shutdown(drain=True)`` finishes in-flight
work before exiting.  ``server_healthy`` /
``serve_watchdog_restarts_total`` expose the recovery loop to scrapes.

Greedy decode through the server is byte-identical to offline
``TransformerGenerator.generate()`` per request — the tick runs the
same stacked-params layer scan, at every scan length.  Sampling is
PER REQUEST (``submit(..., sampling={"temperature": .., "top_k": ..,
"top_p": .., "seed": ..})``; the constructor's ``temperature``/
``top_k``/``top_p`` are the defaults): temperature, top-k and top-p
ride as [B] vectors in device state, vectorized inside the scanned
step, so greedy and sampled requests share one program.  Each slot's PRNG
stream splits exactly once per tick it is active, so sampled outputs
are reproducible per seed and INVARIANT to scan batching — but do not
replay the offline scan's key schedule.

Cancelled / deadline-expired active slots are killed device-side (a
tiny jitted ``remaining``-zeroing op) so they stop burning ticks
instead of decoding out their budget as zombies.

SPECULATIVE multi-token decode (``speculative={...}``, PR 11): a
cheap draft model runs K tokens ahead per slot through its own block
table (``dtable`` — ordinary pool blocks holding the first
``draft_layers`` layers of the pool leaves, claimed at admission in
the same block economy), and the target model verifies the whole
K+1-token chunk in ONE batched pass (``_verify_rows_paged``: the
runs' ``step()`` over flat rows + ``kernels.paged_verify_attention``)
— the agreeing prefix commits, the
first disagreement falls back to the target's own argmax, so greedy
output stays BYTE-IDENTICAL to non-speculative decode at every
acceptance pattern (the verification runs flat-row matmuls and
per-row-unrolled attention precisely so its logits and cache writes
are bitwise equal to sequential ticks).  Up to ``rounds`` such rounds
fuse into one dispatch, staged in the same [B, R*W] buffer /
``emitted``-counter machinery the multi-tick scan uses.  SAMPLED
slots speculate too (ISSUE 20): proposals are drawn from the draft's
per-slot-filtered distribution and accepted by Leviathan rejection
resampling (``u < p_target/p_draft``), a genuine rejection holding
the normalized residual ``max(0, p - q)`` as the slot's next-anchor
distribution — the committed stream is EXACTLY target-distributed,
and greedy rows in the same mixed pool keep the byte-identical greedy
rule.  With ``adaptive: True`` an :class:`AcceptanceController` tunes
each slot's draft depth within ``[1, k_max]`` from per-(tenant,
prefix) acceptance EWMAs (TSDB-seeded via :meth:`attach_history`),
dispatched through a per-slot ``kcap`` operand so depth changes never
recompile.  ``generation_server_spec_{proposed,accepted}_total``, the
acceptance-rate + adaptive-K gauges and the per-tenant acceptance
series watch the draft's quality in production.

TIERED KV cache (``host_tier_blocks``, PR 14): HBM is the binding
serving constraint, and an LRU-evicted prefix block used to die —
capping the effective prefix cache at pool size.  With a host tier
armed, eviction SPILLS the block's raw bytes to a capacity-bounded
host-RAM LRU (``kv_tiering.HostKVTier``, keyed by the same chain
hashes), and an admission whose chain walk runs past the device map
into the tier restores the spilled blocks with ONE batched H2D inside
the admit dispatch, then prefills only the still-uncached suffix —
byte-identical to a device-resident hit, at a block copy instead of a
re-prefill.  The same store carries DISAGGREGATED prefill/decode
handoffs: ``prefill_async`` runs admission+prefill and retires without
a decode tick (the registered prefix blocks are the product),
``export_prefix`` serializes them (hash + raw token bytes + K/V
bytes), and ``import_blocks`` lands them in the target replica's tier,
where the handed-off request's admission restores them exactly like a
tier hit and re-registers them device-resident for copy-free reuse.

MESH-SHARDED tick (``devices=``, ISSUE 17): an explicit device slice
turns every dispatch into ONE GSPMD program over a ``("data", "tp")``
mesh (``parallel.mesh.serving_mesh`` + ``TpShardCtx``) — attention
heads and qkv/mlp/vocab OUTPUT columns shard along ``tp``, per-slot
state and block tables along ``data`` — so one replica serves params
N× too big for a single chip's HBM.  Byte parity is by construction,
not by tolerance: no contracting dimension is ever sharded, and the
decode/verify/prefill bodies gather to full replication immediately
before every feature-axis reduction (``TpShardCtx.rep``), so
cross-chip traffic is exact data movement and tp=2 greedy output is
bitwise tp=1 output.  ``tp > 1`` routes paged attention through the
reference path (``pallas_call`` is opaque to GSPMD; a ``shard_map``'d
local-head kernel is a ROADMAP remainder).  ``devices=None`` (the
default) never builds a shard ctx — the single-device program is the
exact pre-mesh jaxpr.
"""
from __future__ import annotations

import contextlib
import itertools
import logging
import queue
import threading
import time
from collections import OrderedDict, namedtuple
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.analysis import sanitize as _sanitize
from deeplearning4j_tpu.telemetry import gc_watch as _gc_watch
from deeplearning4j_tpu.telemetry.tracing import _profiler_annotation

#: the per-host flight recorder (ISSUE 15): admissions, retires,
#: allocator spill/fetch and watchdog transitions land in the
#: black-box ring a postmortem bundle freezes
_FLIGHT = telemetry.get_flight_recorder()
from deeplearning4j_tpu.kernels import (paged_head_rows, paged_pool_rows,
                                        paged_pool_shape, paged_route,
                                        paged_walk_blocks, paged_walk_extent)
from deeplearning4j_tpu.models.generation import (TransformerGenerator,
                                                  _cast_floating,
                                                  _filter_logits_rows,
                                                  _filtered_logprobs_rows)
from deeplearning4j_tpu.parallel import speculative as _speculative
from deeplearning4j_tpu.parallel.kv_tiering import HostKVTier
from deeplearning4j_tpu.parallel.mesh import TpShardCtx, serving_mesh
from deeplearning4j_tpu.parallel.inference import _bucket
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.resilience.errors import (CancelledError,
                                                  DeadlineExceededError,
                                                  RetryableServerError)
from deeplearning4j_tpu.resilience.retry import retry_call

log = logging.getLogger("deeplearning4j_tpu")

# Serving-decode telemetry (the serve-side counterpart of the
# parallel.inference series): slot occupancy answers "is the decode
# pool saturated", queue depth is the backpressure a load balancer
# watches, TTFT is the caller-visible SLO.
_ADMITTED = telemetry.counter(
    "generation_server_admitted_total",
    "requests admitted into a decode slot (prefill done)")
_RETIRED = telemetry.counter(
    "generation_server_retired_total",
    "requests retired back to their caller (budget or EOS)")
_TICKS = telemetry.counter(
    "generation_server_ticks_total",
    "device decode ticks executed (a K-tick scan counts K)")
_SCANS = telemetry.counter(
    "generation_server_scan_ticks_total",
    "fused decode scans dispatched, by scan length k (k=1 is the "
    "admission-pending fallback)", labelnames=("k",))
_HOST_SYNCS = telemetry.counter(
    "generation_server_host_syncs_total",
    "device->host polls by the scheduler (one per decode scan — the "
    "dispatch-overhead denominator; syncs/token ~ 1/k steady-state)")
# what a dispatch costs the host beyond the call itself: every program
# the scheduler dispatches takes its host operands as ONE packed array
# and hands back what the host needs as ONE packed array, both moved by
# GenerationServer._to_device / _from_device and counted there
_HOST_TRANSFERS = telemetry.counter(
    "generation_server_host_transfers_total",
    "arrays the scheduler's thread moved between host and device, by "
    "the kind of dispatch they belong to and the direction (one d2h a "
    "decode scan: its packed result; one h2d an admission: its packed "
    "operands, a host-tier restore's K/V payloads beside them)",
    labelnames=("site", "dir"))
_MOVED = {(site, way): _HOST_TRANSFERS.labels(site=site, dir=way)
          for site in ("scan", "admit", "kill") for way in ("h2d", "d2h")}
_DISPATCHES = telemetry.counter(
    "generation_server_dispatches_total",
    "programs the scheduler dispatched, by kind (scan: a decode scan "
    "or a speculative round; admit: a miss or hit admission; kill: "
    "the budget-zeroing of cancelled / expired slots)",
    labelnames=("program",))
_DISPATCHED = {p: _DISPATCHES.labels(program=p)
               for p in ("scan", "admit", "kill")}
# useful share of the decode pool: tokens / slot-ticks is the share of
# slot-ticks in which a slot emitted (a slot whose request ended
# mid-scan rides the scan out idle).  A speculative round can commit
# several tokens, so there the ratio may pass 1.
_TOKENS_EMITTED = telemetry.counter(
    "generation_server_tokens_emitted_total",
    "new tokens the decode dispatches emitted (summed over slots at "
    "each scan's host poll)")
_SLOT_TICKS = telemetry.counter(
    "generation_server_slot_ticks_total",
    "active slots at dispatch x ticks of the scan (speculative "
    "dispatches: x rounds, as generation_server_ticks_total counts)")
# how much of the decode read's walk over the block tables is context:
# the kernel walks a slot's table in whole chunks of blocks
# (kernels.paged_walk_blocks), the reference routes gather all of it
_PAGED_BLOCKS = telemetry.counter(
    "generation_server_paged_blocks_total",
    "block-table entries the decode scans' reads covered, by kind "
    "(live: at or before the slot's position; dead: the rest of the "
    "walk's last chunk, or of the table on the reference routes), "
    "summed over each active slot's live ticks at the scan's host poll",
    labelnames=("kind",))
_PAGED_LIVE = _PAGED_BLOCKS.labels(kind="live")
_PAGED_DEAD = _PAGED_BLOCKS.labels(kind="dead")
# ... and how much of what those blocks hold is numbers: a kernel-route
# pool's rows are whole 128-lane rows (kernels.paged_pool_shape), as
# many heads side by side as fit and zeros in what is left
_PAGED_LANE_BYTES = telemetry.counter(
    "generation_server_paged_lane_bytes_total",
    "bytes of the blocks the decode scans' reads covered (the entries "
    "generation_server_paged_blocks_total counts, every layer's K and "
    "V), by kind (kv: in lanes that hold a head's numbers; pad: in "
    "lanes that pad a pool row to the lane width)",
    labelnames=("kind",))
_PAGED_LANES_KV = _PAGED_LANE_BYTES.labels(kind="kv")
_PAGED_LANES_PAD = _PAGED_LANE_BYTES.labels(kind="pad")
# the scheduler thread's own time between decode scans — while it
# runs the device has nothing queued.  NOT the dispatch -> poll wait
# (serve/tick) and NOT the blocked-on-an-empty-queue wait (serve/idle).
_SCHED_HOST = telemetry.counter(
    "generation_server_sched_host_seconds_total",
    "scheduler-thread seconds with no decode scan in flight, by phase "
    "(the durations of the serve/admit and serve/retire spans)",
    labelnames=("phase",))
_SCHED_HOST_PHASE = {p: _SCHED_HOST.labels(phase=p)
                     for p in ("admit", "retire")}
#: a poll that lands tokens more than this many seconds (less the
#: scheduler's idle time) after the previous landing is a stall
STALL_S = 1.0
_STALLS = telemetry.counter(
    "generation_server_stalls_total",
    "polls that landed tokens more than STALL_S seconds (1 s) after "
    "the previous landing, not counting the scheduler's idle time")
_STALL_SECONDS = telemetry.counter(
    "generation_server_stall_seconds_total",
    "seconds of the stalls' gaps by phase: idle, admit, launch, poll "
    "and retire partition the scheduler thread's time; gc is Python's "
    "collections in the gap, on any thread, and overlaps the phase it "
    "fell in", labelnames=("phase",))
# a prompt is prefilled at its admit bucket's length: a padded position
# costs a matmul row in every layer and, in a recurrent layer, a step
# of the time scan
_PREFILL_TOKENS = telemetry.counter(
    "generation_server_prefill_tokens_total",
    "prompt positions the admit programs were dispatched over, by kind "
    "(real: the prompt's or suffix's own; pad: the bucket's tail)",
    labelnames=("kind",))
_PREFILL_REAL = _PREFILL_TOKENS.labels(kind="real")
_PREFILL_PAD = _PREFILL_TOKENS.labels(kind="pad")
# ... and how its attention reaches the keys, in the runs that choose
# (``AttentionBlockRun.prefill_route``): the flash kernel over the
# causal triangle's and the band's blocks, or every key in jax.numpy
_PREFILL_ATTENTION = telemetry.counter(
    "generation_server_prefill_attention_total",
    "prompt positions x attention layers the admit programs were "
    "dispatched over, by route (kernel: kernels.flash_prefill; dense: "
    "every key scored, a query block at a time)", labelnames=("route",))
_PREFILL_ROUTE = {r: _PREFILL_ATTENTION.labels(route=r)
                  for r in ("kernel", "dense")}
# the routed feed-forwards' work: the tally every routed layer keeps on
# the device (``rec["routed"]``) rides the decode scan's one read to the
# host, with what the admissions since the last scan added to it
_EXPERT_ROWS = telemetry.counter(
    "generation_server_expert_rows_total",
    "token-expert pairs the routers made over decode ticks and "
    "prefills, by where the expert is held (held: here, a row of "
    "kernels.expert_ffn; absent: on another chip of the deployment, "
    "left out)", labelnames=("kind",))
_EXPERT_HELD = _EXPERT_ROWS.labels(kind="held")
_EXPERT_ABSENT = _EXPERT_ROWS.labels(kind="absent")
_EXPERT_CALLS = telemetry.counter(
    "generation_server_expert_calls_total",
    "routed layers run (a decode tick's, an admission's) x experts "
    "held: the experts' turns, each of which rows could have filled")
_EXPERT_REACHED = telemetry.counter(
    "generation_server_expert_reached_total",
    "held experts that got at least one row, summed over the routed "
    "layers run (a decode tick's, an admission's): the experts whose "
    "weights a call streamed; over expert_calls_total it is the share "
    "of the experts' turns that rows filled")
_EXPERT_LOAD = telemetry.histogram(
    "generation_server_expert_load_ratio",
    "one sample a decode dispatch: the rows of the fullest held expert "
    "/ the mean held expert's, over the dispatch and the admissions "
    "before it (1 = even)",
    buckets=(1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 8.0, 16.0))
_WINDOW_BYTES = telemetry.gauge(
    "generation_server_window_cache_bytes",
    "device bytes of the window layers' per-slot rings (K and V, all "
    "slots and layers); 0 for a net without window attention")
_POOL_BYTES = telemetry.gauge(
    "generation_server_kv_pool_bytes",
    "device bytes of a kind of attention layer's K and V pools (full: "
    "the paged, allocated pool; window: the per-slot rings)",
    labelnames=("kind",))
_REC_BYTES = telemetry.gauge(
    "generation_server_recurrent_state_bytes",
    "device bytes of the per-slot recurrent state (state-space layers' "
    "h and convolution window, all slots); 0 for a K/V-only net")
_SLOTS_BUSY = telemetry.gauge(
    "generation_server_slots_busy", "slots decoding at the last tick")
_QDEPTH = telemetry.gauge(
    "generation_server_queue_depth",
    "submitted requests waiting for a free slot")
_OCC = telemetry.histogram(
    "generation_server_slot_occupancy",
    "active slots / n_slots per tick (params-stream amortization)",
    buckets=telemetry.RATIO_BUCKETS)
_TTFT = telemetry.histogram(
    "generation_server_ttft_seconds",
    "submit -> first generated token per request (queue wait + "
    "prefill + first tick)")
# Self-healing series: a load balancer drains on server_healthy == 0;
# watchdog restarts at any steady rate are an incident, not noise.
_HEALTHY = telemetry.gauge(
    "server_healthy",
    "1 while the decode scheduler is alive and admitting; 0 during "
    "watchdog recovery and after shutdown (one child per server "
    "instance — a process can run several)", labelnames=("server",))
_SERVER_SEQ = itertools.count()
_WATCHDOG_RESTARTS = telemetry.counter(
    "serve_watchdog_restarts_total",
    "scheduler restarts forced by the watchdog (stuck tick or dead "
    "scheduler thread)")
_TICK_FAILURES = telemetry.counter(
    "generation_server_tick_failures_total",
    "decode/prefill dispatch failures absorbed by the inline "
    "rebuild path")
_DEADLINE_EXCEEDED = telemetry.counter(
    "generation_server_deadline_exceeded_total",
    "requests failed because their deadline elapsed (queue + decode)")
_CANCELLED = telemetry.counter(
    "generation_server_cancelled_total",
    "requests released via handle.cancel() before completion")
# Surgical-recovery series: a recovery that salvages N-1 of N slots is
# routine self-healing; growth in dropped slots is lost caller work.
_KV_SALVAGED = telemetry.counter(
    "kv_slots_salvaged_total",
    "in-flight slots whose KV rows + device state survived a pool "
    "recovery (the requests completed without resubmission)")
_KV_DROPPED = telemetry.counter(
    "kv_slots_dropped_total",
    "in-flight slots failed by a pool recovery (implicated in the "
    "failure, non-finite state, or unrecoverable donated buffers)")
# Paged-pool series: the block economy.  allocated/freed track the
# allocator's churn (freed counts refcount-drains — a drained block
# may stay resident as an evictable prefix-cache entry), shared counts
# copy-free prefix-block mappings (each one is a block of prefill
# compute AND a block of HBM the cache saved), and the free gauge is
# the admission headroom (free list + evictable cache entries).
_KV_BLK_ALLOC = telemetry.counter(
    "kv_blocks_allocated_total",
    "fresh KV blocks claimed from the pool at admission")
_KV_BLK_FREED = telemetry.counter(
    "kv_blocks_freed_total",
    "KV blocks whose refcount drained at retire/cancel/recovery "
    "(cached blocks stay resident as evictable entries)")
_KV_BLK_SHARED = telemetry.counter(
    "kv_blocks_shared_total",
    "prefix-cache blocks mapped copy-free into an admitted slot's "
    "block table (prefill skipped for these tokens)")
_POOL_FREE = telemetry.gauge(
    "kv_pool_blocks_free",
    "FREE-LIST KV blocks (unclaimed, holding no cache entry).  "
    "ISSUE 14 split: evictable refcount-0 cache entries are counted "
    "separately in kv_pool_blocks_evictable — summing them here hid "
    "imminent spill pressure (a pool can be 100% cache-resident with "
    "a zero free list and still admit, but every admission then "
    "evicts/spills)")
_POOL_EVICTABLE = telemetry.gauge(
    "kv_pool_blocks_evictable",
    "refcount-0 prefix-cache blocks resident in the device pool "
    "(reclaimable by admission; with a host tier configured an "
    "eviction spills the block instead of dropping it).  Admission "
    "headroom = kv_pool_blocks_free + this")
# Tiered-KV series (ISSUE 14): the HBM→host spill economy.  spills
# count device evictions whose bytes landed host-side, fetches count
# blocks restored device-side by an admission (one batched H2D per
# admission), hits count admissions that restored >= 1 tier block —
# fetch TTFT vs full re-prefill TTFT is the tier's headline.
_TIER_SPILLS = telemetry.counter(
    "kv_tier_spills_total",
    "evicted device prefix-cache blocks spilled to the host-RAM tier "
    "(bytes preserved; the next same-prefix admission pays one H2D "
    "copy instead of a re-prefill)")
_TIER_FETCHES = telemetry.counter(
    "kv_tier_fetches_total",
    "KV blocks restored from the host tier into device pool blocks "
    "by an admission (batched: one H2D per admission regardless of "
    "block count)")
_TIER_HITS = telemetry.counter(
    "kv_tier_hits_total",
    "admissions whose chain-hash walk missed the device prefix map "
    "but restored >= 1 spilled block from the host tier")
# Disaggregated-serving handoff series (ISSUE 14): a prefill replica's
# finished prefix blocks shipped into a decode replica through the
# block-table abstraction (export_prefix -> import_blocks).
_HANDOFF_BLOCKS = telemetry.counter(
    "kv_handoff_blocks_total",
    "prefix KV blocks imported from another replica's export "
    "(disaggregated prefill->decode handoff)")
_HANDOFF_BYTES = telemetry.counter(
    "kv_handoff_bytes_total",
    "raw K/V bytes imported through prefix handoffs")
_PREFIX_HITS = telemetry.counter(
    "prefix_cache_hits_total",
    "admissions that mapped >= 1 cached prefix block (prefill ran "
    "only on the uncached suffix)")
_PREFIX_MISSES = telemetry.counter(
    "prefix_cache_misses_total",
    "admissions with no cached prefix block (full-prompt prefill)")
# Block-granular salvage series (the slot-granular pair above stays
# for request-level accounting): salvaged = blocks carried over a pool
# recovery (kept slots' + finite cached), dropped = previously-used
# blocks zeroed by the rebuild.
_KV_BLK_SALVAGED = telemetry.counter(
    "kv_blocks_salvaged_total",
    "KV blocks carried over a pool recovery (kept slots' blocks + "
    "finite prefix-cache blocks)")
_KV_BLK_DROPPED = telemetry.counter(
    "kv_blocks_dropped_total",
    "previously-used KV blocks zeroed by a pool recovery (implicated "
    "slots' private blocks + poisoned cache entries)")
# Speculative-decode series: proposed counts every draft token offered
# for verification, accepted the ones the target's own argmax agreed
# with — their ratio is THE health number of a speculative deployment
# (rate ~1 means the draft models the target well and every verify
# commits ~K+1 tokens; rate ~0 means the expensive verification is
# buying ~1 token per round and the draft is pure overhead).
_SPEC_PROPOSED = telemetry.counter(
    "generation_server_spec_proposed_total",
    "draft tokens proposed for target verification (K per active "
    "slot per speculative round)")
_SPEC_ACCEPTED = telemetry.counter(
    "generation_server_spec_accepted_total",
    "draft proposals the batched target verification accepted "
    "(committed byte-identical to non-speculative greedy decode)")
_SPEC_ACCEPT_RATE = telemetry.gauge(
    "generation_server_spec_acceptance_rate",
    "cumulative accepted/proposed draft-token ratio of the most "
    "recently dispatching speculative server")
_SPEC_ADAPTIVE_K = telemetry.gauge(
    "generation_server_spec_adaptive_k",
    "draft depth K of the most recent speculative dispatch — the "
    "acceptance controller's pick (max over live slots) clamped by "
    "the degrade ladder's shrink_draft_k cap; a fixed-K server "
    "reports its configured k")
_TENANT_SPEC_ACCEPT = telemetry.gauge(
    "generation_server_tenant_spec_acceptance_rate",
    "cumulative per-tenant accepted/proposed draft-token ratio (the "
    "acceptance controller's raw signal: a tenant whose prompts the "
    "draft models poorly converges to a shallower adaptive K than "
    "its neighbors)", labelnames=("tenant",))
# Mesh-sharded serving (ISSUE 17): the tp degree of the most recently
# constructed server — 1 means single-device; N means params + KV
# heads spread over an N-chip slice (the per-replica split lives in
# fleet_replica_devices{replica=} on the router side).
_TP_DEGREE = telemetry.gauge(
    "generation_server_tp_degree",
    "tensor-parallel degree of the most recently constructed server "
    "(chips one replica's params/KV-head shards span; 1 = unsharded)")
# Replica-side half of the request-phase family (the fleet router owns
# the admission/placement/total phases): the SAME spans that build a
# request's trace tree observe these series, so TTFT decomposes into
# replica queue wait + prefill + decode on every scrape.
_PHASE = telemetry.histogram(
    "fleet_request_phase_seconds",
    "per-request phase wall times (the trace spans' durations)",
    labelnames=("phase",))

#: prefill device-time sampling rate (ISSUE 13): the admit dispatch
#: is async and the scheduler never waits for it, so a sampled
#: admission's clock runs from its dispatch to the NEXT scan's poll —
#: the first host sync that shows the device past it (the decode tick
#: samples every dispatch: that site host-syncs anyway)
_PROFILE_PREFILL_EVERY = 4


class _SchedPhases:
    """The scheduler thread's phases, a partition of its time from the
    loop's first admission to its exit: one is open at a time, each a
    span ``serve/<phase>`` in the tracer (where one's end and the next
    one's start are one instant) and in a running profile (on the
    device lines' clock).

    ========  =====================================================
    idle      blocked on the empty queue
    admit     ingest, planning, the admit dispatches, the scan's plan
    launch    from ``serve/tick``'s opening to the scan's dispatch and
              its result's copy to the host started
    poll      the one read of the scan's result (``_from_device``)
    retire    everything after the poll, up to the next phase
    ========  =====================================================

    :meth:`switch` closes the open phase and opens the next, so the
    many ``return`` and ``continue`` edges of the loop need no scope of
    their own; :meth:`tick` is the ``serve/tick`` span around exactly
    ``launch`` and ``poll``.  ``seconds`` keeps each phase's running
    total (for :meth:`landed`), and the phases during which the device
    has nothing queued -- ``admit``, ``retire`` -- add theirs to
    ``generation_server_sched_host_seconds_total``.  Each switch also
    brings the ``gc_watch`` counters up to date."""

    PHASES = ("idle", "admit", "launch", "poll", "retire")

    def __init__(self, tracer, owner):
        self._tracer, self._owner = tracer, owner
        self._open = None    # (phase, its profiler span, Span, t0)
        self.seconds = dict.fromkeys(self.PHASES, 0.0)
        # the previous landing: (when, ``seconds`` then, gc seconds then)
        self._landing = (time.perf_counter(), dict(self.seconds),
                         _gc_watch.seconds_total())

    def _begin(self, name: str, now: float, **args):
        """A span opened at ``now``, in the tracer and in a running
        profile, as ``tracer.span`` opens one."""
        ann = (_profiler_annotation(name) if self._tracer.enabled
               else contextlib.nullcontext())
        ann.__enter__()
        return ann, self._tracer.begin(name, bound=True, owner=self._owner,
                                       at=now, **args)

    @staticmethod
    def _end(opened, now: float, **extra) -> None:
        ann, sp = opened
        sp.end_at(now, **extra)
        ann.__exit__(None, None, None)

    def _close(self, now: float) -> None:
        if self._open is not None:
            was, ann, sp, t0 = self._open
            self._open = None
            self._end((ann, sp), now)
            self.seconds[was] += now - t0
            if was in _SCHED_HOST_PHASE:
                _SCHED_HOST_PHASE[was].inc(now - t0)

    def switch(self, phase: str, now: Optional[float] = None) -> None:
        """Close the open phase and open ``phase`` at the same instant."""
        now = time.perf_counter() if now is None else now
        self._close(now)
        _gc_watch.sync()
        self._open = (phase, *self._begin("serve/" + phase, now), now)

    def close(self) -> None:
        """End the open phase: the scheduler thread is leaving."""
        self._close(time.perf_counter())

    @contextlib.contextmanager
    def tick(self, **args):
        """``serve/tick`` (its ``args``) opening with ``launch``; the body
        switches to ``poll``, and where it leaves, poll and the tick
        close and ``retire`` opens, all at one instant."""
        now = time.perf_counter()
        self._close(now)
        tick = self._begin("serve/tick", now, **args)
        self.switch("launch", now)
        error = {}
        try:
            yield
        except BaseException as e:
            error["error"] = type(e).__name__
            raise
        finally:
            now = time.perf_counter()
            self._close(now)
            self._end(tick, now, **error)
        self.switch("retire", now)

    def note(self, **args) -> None:
        if self._open is not None:
            self._open[2].note(**args)

    def landed(self):
        """At a poll that landed tokens: None, or -- when the time
        since the previous landing, less what ``idle`` held of it,
        passed ``STALL_S`` -- (that gap, {phase: its seconds in the
        gap, and ``gc``: the collections' seconds, which overlap the
        phase they fell in}).  The gap restarts here."""
        now = time.perf_counter()
        t0, was, gc0 = self._landing
        gc_s = _gc_watch.seconds_total()
        self._landing = (now, dict(self.seconds), gc_s)
        gap = now - t0
        if gap - (self.seconds["idle"] - was["idle"]) <= STALL_S:
            return None
        split = {p: self.seconds[p] - was[p] for p in self.PHASES}
        split["gc"] = gc_s - gc0
        return gap, split


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n (n >= 1) — scan lengths quantize to
    powers of two so the compile count stays log2(tick_batch), and a
    floor (never a ceil) means a drain scan never runs ticks past the
    longest live budget."""
    b = 1
    while b * 2 <= n:
        b *= 2
    return b


# One admission's block plan (host-side, built under _lock):
# ``phys`` — the slot's physical block ids in table order (cached
# prefix hits first, then fresh); ``matched`` — how many leading
# entries the admit program GATHERS as the cached key prefix
# (copy-free device hits PLUS host-tier restores); ``hashes`` — the
# prompt's full-block chain hashes (for registering the new blocks
# after the prefill COMMITS); ``n_fresh`` — blocks claimed off the
# free list; ``dphys`` — the DRAFT model's physical blocks
# (speculative decode: always fresh, never prefix-shared — same pool,
# same free list, so draft KV competes in the same admission
# economy); ``reg_from`` — the first hash index NOT already in the
# device prefix map (registration after commit covers tier-restored
# blocks and fresh full prompt blocks alike); ``fills`` — the
# host-tier entries to restore, ``(k, v)`` numpy pairs aligned with
# hash indices ``[reg_from, reg_from + len(fills))`` — their target
# pool blocks are the first ``len(fills)`` fresh claims, so ``phys``
# stays in table order.  ``dmatched`` — how many leading ``dphys``
# entries are DRAFT prefix-cache hits (ISSUE 20: draft blocks
# chain-hash and re-use exactly like target blocks, in their own hash
# domain — the hit-path admission gathers them and draft-prefills
# only the suffix instead of re-paying the full prompt).
_AdmitPlan = namedtuple("_AdmitPlan", ("phys", "matched", "hashes",
                                       "n_fresh", "dphys", "reg_from",
                                       "fills", "dmatched"),
                        defaults=((), 0, (), 0))


def _paged_blocks_walked(pos0, ticks, bs: int, chunk: int, last=None):
    """(live, dead) table entries of a decode scan's reads: slot i
    starts at position ``pos0[i]`` and runs ``ticks[i]`` live ticks, a
    position a tick — all slots and ticks at once, by the function the
    kernel sizes its loop with.  ``last``: the table is a window's ring,
    read up to position ``last`` at most."""
    tick = np.arange(int(ticks.max(initial=0)))[None, :]
    pos = pos0[:, None] + tick
    if last is not None:
        pos = np.minimum(pos, last)
    live, covered = paged_walk_extent(pos, bs, chunk)
    ran = tick < ticks[:, None]
    return int(live[ran].sum()), int((covered - live)[ran].sum())


def _lane_bytes_a_block(layers: int, h: int, dims, tails, dtype):
    """(kv, pad) bytes of ONE block-table entry's block over a kind's
    ``layers``: its ``h`` heads' numbers (``dims``: the keys' and the
    values' widths), and the rest of the K and V pool rows that hold
    them (``tails``: ``kernels.paged_pool_shape``)."""
    bs = tails[0][1]
    size = layers * bs * jnp.dtype(dtype).itemsize
    kv = size * h * sum(dims)
    return kv, size * sum(heads * width for heads, _, width in tails) - kv


def _kill_slots(state, mask):
    """Zero the remaining budget of masked slots — the device-side
    early-kill for cancelled / deadline-expired requests, so a zombie
    slot stops consuming scan ticks the moment the host notices
    instead of decoding out its budget.  Jitted with ``state`` donated
    (``GenerationServer._kill``)."""
    return dict(state, remaining=jnp.where(mask, 0, state["remaining"]))


#: the scalars at the head of an admission's packed operands
_ADMIT_HEAD = 8


def _pack_admission(req, slot: int, *rows) -> np.ndarray:
    """One admission's host operands as ONE int32 vector: the head's
    scalars (``t0``, slot, ``n_new``, ``eos_id``, ``top_k``, the seed's
    low 32 bits, ``temperature`` and ``top_p`` as their float32 bits),
    then ``rows`` end to end — the padded prompt, block ids, table
    rows — whose lengths the admit program knows from its compile key
    (:func:`_unpack_admission` is the other end)."""
    head = np.empty((_ADMIT_HEAD,), np.int32)
    head[:5] = (req.t0, slot, req.n_new, req.eos_id, req.top_k)
    head[5] = np.int64(req.seed).astype(np.int32)
    head[6:] = np.array((req.temperature, req.top_p),
                        np.float32).view(np.int32)
    return np.concatenate((head,) + rows)


def _unpack_admission(ops, *sizes):
    """In a trace: the head of :func:`_pack_admission`'s vector as
    ``_arm_slot`` takes it — ``(slot, t0, n_new, eos_id, key,
    temperature, top_k, top_p)`` — and its rows, cut at static offsets.
    The PRNG key is derived HERE from the seed word, to the bits
    ``jax.random.PRNGKey(seed)`` gives on the host in 32-bit mode
    (there the seed is cut to its low 32 bits first)."""
    t0, slot, n_new, eos_id, tk, seed = (ops[i] for i in range(6))
    temp, tp = jax.lax.bitcast_convert_type(ops[6:_ADMIT_HEAD],
                                            jnp.float32)
    rows, at = [], _ADMIT_HEAD
    for n in sizes:
        rows.append(ops[at:at + n])
        at += n
    return (slot, t0, n_new, eos_id, jax.random.PRNGKey(seed), temp, tk,
            tp), rows


def _pack_polled(toks, *columns):
    """In a trace: all the host reads of a decode dispatch as ONE int32
    array — the staged tokens [B, n], then each per-slot ``columns``
    entry [B] as one more column."""
    return jnp.concatenate([toks] + [c[:, None] for c in columns],
                           axis=1)


def _append_rows(polled, vector):
    """In a trace: ``polled`` [B, n] with the int32 ``vector`` below it,
    in as many more rows of n as hold it (zero-filled)."""
    n = polled.shape[1]
    rows = -(-vector.shape[0] // n)
    tail = jnp.zeros((rows * n,), jnp.int32).at[:vector.shape[0]].set(
        vector)
    return jnp.concatenate([polled, tail.reshape(rows, n)], axis=0)


def _refuse_cache_loaded_mesh_programs(devices) -> None:
    """On the installed jax 0.9.0 / libtpu 0.0.34 a decode scan over a
    two-chip slice that is LOADED from JAX's persistent compilation
    cache halts the TPU ("The program continuator has halted
    unexpectedly"); the same program freshly compiled — cache off, or
    the run that writes the entry — serves correctly (PERF.md, PR 21:
    every warm four-chip run failed, every cold or cache-off one
    passed; single-device programs and the whole-host train step load
    fine).  JAX decides once per process whether it uses that cache,
    so a replica cannot opt its own programs out: a multi-chip replica
    refuses to start in a process that has the cache on."""
    devices = list(devices)
    if (len(devices) > 1 and devices[0].platform == "tpu"
            and jax.config.jax_enable_compilation_cache
            and jax.config.jax_compilation_cache_dir):
        raise RuntimeError(
            "a GenerationServer over more than one TPU chip cannot run "
            "with JAX's persistent compilation cache on (a decode "
            "program loaded from it halts the chip on jax 0.9.0 / "
            "libtpu 0.0.34): start the process with "
            "jax.config.update('jax_enable_compilation_cache', False) "
            f"(cache dir: {jax.config.jax_compilation_cache_dir!r})")


class _Pending:
    """One submitted request.  ``result()`` blocks the caller; the
    scheduler thread fills ``_result``/``_error`` and sets the event.
    ``ttft`` (seconds) is populated when the first token lands."""

    __slots__ = ("prompt", "n_new", "eos_id", "seed", "temperature",
                 "top_k", "top_p", "t_submit", "deadline", "cancelled",
                 "t0", "emitted", "ttft", "trace_id", "spans",
                 "prefill_only", "tenant", "pkey", "_t_decode",
                 "_result", "_error", "_event")

    def __init__(self, prompt, n_new, eos_id, seed,
                 temperature: float = 0.0, top_k: int = 1,
                 top_p: float = 1.0,
                 deadline: Optional[float] = None,
                 trace_id: Optional[str] = None,
                 prefill_only: bool = False,
                 tenant: str = "default",
                 pkey=None):
        self.tenant = str(tenant)     # acceptance-controller + gauge key
        self.pkey = pkey              # leading-block chain hash (or
                                      # None) — the per-prefix half of
                                      # the controller's (tenant, pkey)
        self.trace_id = trace_id      # fleet-minted; None standalone
        self.spans = {}               # phase -> open telemetry.Span
        self.prefill_only = bool(prefill_only)  # disagg: admit +
                                      # prefill + cache-register, then
                                      # retire without a decode tick
        self._t_decode = None
        self.prompt = prompt
        self.n_new = n_new
        self.eos_id = eos_id
        self.seed = seed
        self.temperature = temperature   # resolved: <= 0 means greedy
        self.top_k = top_k               # resolved: vocab means "off"
        self.top_p = top_p               # resolved: 1.0 means "off"
        self.t_submit = time.perf_counter()
        self.deadline = deadline         # absolute time.monotonic(), or None
        self.cancelled = False
        self.t0 = len(prompt)
        self.emitted = 0
        self.ttft = None
        self._result = None
        self._error = None
        self._event = threading.Event()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request retires; returns the full sequence
        [t0 + n_emitted] (prompt + generated, EOS included when hit).
        A ``TimeoutError`` here leaves the request LIVE server-side —
        call :meth:`cancel` to release its queue entry / slot budget
        if the result is no longer wanted."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"generation result not ready within {timeout}s "
                f"(the request is still live; cancel() releases it)")
        if self._error is not None:
            raise self._error
        return self._result

    def cancel(self) -> bool:
        """Best-effort cancellation: marks the request; the scheduler
        releases its queue entry (if still waiting) or its slot (at
        the next tick boundary) and ``result()`` raises
        ``CancelledError``.  Returns False when the request already
        completed — the existing result/error stands."""
        if self._event.is_set():
            return False
        self.cancelled = True
        return True

    def close_spans(self, outcome: str) -> None:
        """End every phase span this request still holds (idempotent;
        any thread).  The retire path's normal close — ALSO called by
        the fleet router when it ABANDONS an unresolved handle on a
        dead replica whose scheduler will never retire anything: the
        abandoned placement's spans must flush (with the abandoning
        outcome) instead of orphaning forever."""
        for phase in ("queue", "prefill", "decode"):
            sp = self.spans.pop(phase, None)
            if sp is not None:
                sp.end(outcome=outcome, emitted=self.emitted)


class GenerationServer:
    """Thread-safe continuous-batching decode server over a causal
    decoder MLN (same stack contract as ``TransformerGenerator``).

    >>> srv = GenerationServer(net, n_slots=16, max_len=1024)
    >>> out = srv.submit(prompt_ids, n_new=64)           # blocking
    >>> h = srv.submit_async(prompt_ids, n_new=64)       # handle
    >>> out = h.result(); h.ttft                         # seconds
    >>> srv.shutdown(drain=True)                         # finish work

    ``temperature``/``top_k``/``top_p`` are per-request DEFAULTS
    (greedy by default — byte-identical to offline ``generate()``),
    overridable via ``submit(..., sampling={"temperature": ..,
    "top_k": .., "top_p": .., "seed": ..})``; ``eos_id`` per request
    stops decode early the tick the token is emitted.

    ``tick_batch`` fuses up to that many decode ticks into one
    device-side ``lax.scan`` so the host syncs once per scan instead
    of once per token (throughput knob; 1 restores per-tick host
    polling).  The TTFT cost is bounded: the scheduler drops back to
    single ticks whenever a request is waiting for admission, so a
    join waits at most one in-flight scan.

    KV memory is a PAGED pool: ``block_size`` tokens per block,
    ``kv_blocks`` blocks total (default ``n_slots * ceil(max_len /
    block_size)`` — the same HBM the old per-slot stripes held,
    repackaged; shrink it to trade capacity for per-chip concurrency),
    per-slot block tables device-resident.  A request pins
    ``ceil((t0 + n_new) / block_size)`` blocks, so admission queues on
    BLOCK availability, not slots.  ``prefix_cache=True`` (default)
    shares identical prompt-prefix blocks across requests copy-free
    and prefills only the uncached suffix; retired prefix blocks stay
    resident (LRU-evicted on demand).

    ``host_tier_blocks`` > 0 arms the TIERED block cache (ISSUE 14):
    LRU-evicted prefix blocks SPILL their bytes to a capacity-bounded
    host-RAM tier instead of dying, and a later admission whose chain
    walk hits a spilled block restores it with ONE batched H2D copy
    inside the admission dispatch — the effective prefix cache grows
    far past the HBM-resident pool, at one block copy per revival
    instead of a re-prefill.  ``prefill_async`` + ``export_prefix`` /
    ``import_blocks`` ride the same store for disaggregated
    prefill/decode handoff (see ``serving.ServingFleet`` roles).

    ``speculative`` turns on draft-verified multi-token decode: a
    dict with any of ``k`` (draft proposals per round, default 4),
    ``rounds`` (max rounds fused per dispatch, default 2),
    ``draft_layers`` (self-draft depth — the target truncated to its
    first layers, default half the stack) or ``draft_net`` (an
    external proposer; same vocab/heads/width, depth <= target).
    Greedy outputs stay byte-identical to ``speculative=None``; the
    win is committed tokens per expensive target pass (up to k+1),
    paid for with ~2x blocks per admission (the draft's table).

    ``devices`` pins the server to an EXPLICIT device slice and — with
    more than one device — mesh-shards the replica across it
    (ISSUE 17): ``tp`` (default: the whole slice) chips hold the
    head/output-column shards of the params and the KV block pool,
    ``len(devices) // tp`` becomes the ``data`` axis sharding per-slot
    state and block tables.  Greedy output stays byte-identical to a
    single-device server (see the module docstring); ``n_heads`` must
    divide by ``tp`` and ``n_slots`` by the data extent.  CPU CI
    exercises this with ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` virtual devices.

    Resilience knobs: ``tick_timeout_s`` arms the watchdog (None
    disables it; the stuck-tick deadline scales by the in-flight scan
    length — a K-tick scan legitimately runs ~K x longer);
    ``request_deadline_s`` is the default per-request deadline
    (``submit*``'s ``deadline_s`` overrides); blocking ``submit``
    retries ``RetryableServerError`` failures up to ``submit_retries``
    times with jittered exponential backoff from ``retry_backoff_s``."""

    def __init__(self, net, n_slots: int = 8,
                 max_len: Optional[int] = None,
                 compute_dtype: Optional[str] = None,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 tick_batch: int = 8,
                 block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 host_tier_blocks: int = 0,
                 speculative: Optional[dict] = None,
                 devices=None,
                 tp: Optional[int] = None,
                 queue_limit: int = 1024,
                 tick_timeout_s: Optional[float] = 30.0,
                 request_deadline_s: Optional[float] = None,
                 submit_retries: int = 0,
                 retry_backoff_s: float = 0.05):
        self._gen = TransformerGenerator(net, compute_dtype=compute_dtype)
        gen = self._gen
        # a stack may hold a kind of run whose rows K/V blocks cannot
        # bring back (a recurrent layer's per-slot state) or that has no
        # chunked sequence() / verify step() / shard points: whatever
        # needs one of those refuses, by name
        n_dev = len(list(devices)) if devices is not None else 1
        for on, what in ((prefix_cache, "prefix_cache=True"),
                         (speculative is not None, "speculative decode"),
                         (host_tier_blocks, "host_tier_blocks > 0"),
                         ((tp or n_dev) > 1, "tp > 1")):
            if on:
                self._refuse(what)
        self.n_slots = int(n_slots)
        if self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        self.max_len = int(max_len or gen.emb.max_len)
        if gen.emb.add_positional and self.max_len > gen.emb.max_len:
            raise ValueError(
                f"max_len {self.max_len} exceeds the model's positional "
                f"table ({gen.emb.max_len} rows)")
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        # table width: every slot can address a max-length request
        self.max_blocks = -(-self.max_len // self.block_size)
        # capacity-neutral default: the same HBM the old per-slot
        # stripes occupied, repackaged as shareable blocks (shrink it
        # to trade capacity for concurrency headroom per chip)
        self._spec = (_speculative.SpecConfig.build(gen, speculative)
                      if speculative is not None else None)
        # degradation-ladder switch (ISSUE 18): True suspends
        # speculative rounds without tearing the draft state down —
        # rung 3 is reversible by flipping it back
        self._spec_off = False
        if self._spec is not None:
            demb = self._spec.draft.gen.emb
            if demb.add_positional and self.max_len > demb.max_len:
                raise ValueError(
                    f"max_len {self.max_len} exceeds the DRAFT "
                    f"model's positional table ({demb.max_len} rows)")
        # a speculative slot pins TWO tables' worth of blocks (target
        # + draft), so the capacity-neutral default and the one-max-
        # length-request floor both double with speculation on
        blocks_per_max = self.max_blocks * (2 if self._spec else 1)
        self.kv_blocks = (int(kv_blocks) if kv_blocks is not None
                          else self.n_slots * blocks_per_max)
        if self.kv_blocks < blocks_per_max:
            raise ValueError(
                f"kv_blocks={self.kv_blocks} cannot hold one "
                f"max-length request ({blocks_per_max} blocks of "
                f"{self.block_size} tokens"
                + (", draft table included)" if self._spec else ")"))
        self.prefix_cache = bool(prefix_cache)
        # host-RAM tier under the device pool (ISSUE 14): evicted
        # prefix blocks spill here instead of dying, and admissions
        # restore spilled blocks with one batched H2D.  0 disables
        # spilling; import_blocks() lazily creates a default-sized
        # tier so handoffs work on an unconfigured server too.
        self.host_tier_blocks = int(host_tier_blocks or 0)
        if self.host_tier_blocks < 0:
            raise ValueError("host_tier_blocks must be >= 0")
        if self.host_tier_blocks and not self.prefix_cache:
            raise ValueError("host_tier_blocks needs prefix_cache=True "
                             "(the tier stores evicted prefix-cache "
                             "blocks)")
        self._tier = (HostKVTier(self.host_tier_blocks)
                      if self.host_tier_blocks else None)
        if (top_k is not None or top_p is not None) and temperature <= 0:
            raise ValueError("top_k/top_p need temperature > 0 "
                             "(greedy ignores the filtered tail)")
        self._vocab = gen.vocab_size
        if top_k is not None and not 1 <= int(top_k) <= self._vocab:
            raise ValueError(f"top_k={top_k} out of range "
                             f"[1, {self._vocab}] (vocab size)")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p={top_p} out of range (0, 1]")
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.tick_batch = int(tick_batch)
        if self.tick_batch < 1:
            raise ValueError("tick_batch must be >= 1")
        self.tick_timeout_s = (float(tick_timeout_s)
                               if tick_timeout_s else None)
        self.request_deadline_s = (float(request_deadline_s)
                                   if request_deadline_s else None)
        self.submit_retries = int(submit_retries)
        self.retry_backoff_s = float(retry_backoff_s)

        # Mesh-sharded replica (ISSUE 17): an explicit device slice
        # builds the ("data", "tp") shard ctx every dispatch below
        # threads through the decode/verify/prefill bodies.  A
        # one-device slice still gets a ctx — it PINS the replica to
        # that device (a fleet mixing single- and multi-chip replicas
        # hands each its own slice) — but tp=1 keeps the pallas route
        # and the constraints are no-ops on a 1-extent mesh.
        self._shard = None
        if devices is not None:
            _refuse_cache_loaded_mesh_programs(devices)
            ctx = TpShardCtx(serving_mesh(devices, tp))
            h = gen.kv_heads
            if h % ctx.tp:
                raise ValueError(
                    f"n_heads={h} must divide by tp={ctx.tp} (the KV "
                    "pool's head axis is the tp shard)")
            if self._spec is not None:
                self._spec.draft.check_tp(ctx.tp)
            if self.n_slots % ctx.data:
                raise ValueError(
                    f"n_slots={self.n_slots} must divide by the mesh "
                    f"data axis ({ctx.data}) to shard per-slot state")
            self._shard = ctx
        self.tp_degree = self._shard.tp if self._shard else 1
        #: per-device "platform:id" labels of the slice (profiler
        #: phase attribution); None = the profiler's default device
        self._device_labels = (
            [f"{d.platform}:{d.id}" for d in self._shard.devices]
            if self._shard is not None else None)
        _TP_DEGREE.set(self.tp_degree)

        # Scheduler state shared with the watchdog: _active/_pending/
        # _free and the device pool (_kc/_vc/_state) mutate only under
        # _lock; the epoch token fences a recovered-past scheduler
        # thread out of every commit point.  The lock exists BEFORE
        # _fresh_pool — the pool reset is also the watchdog's recovery
        # path and commits under it (CONC201).
        self._lock = threading.RLock()
        self._fresh_pool()
        self._ids = np.zeros((self.n_slots, self.max_len),
                             np.int32)                # host output rows
        self.refresh_params()
        # decode programs: keyed (scan length, any-sampled-slot) — the
        # all-greedy variant skips the sort/categorical sampler math
        # entirely, so a greedy-only server pays nothing for the
        # vectorized per-slot sampling support
        self._scan_cache = {}
        self._kill = jax.jit(_kill_slots, donate_argnums=(0,))
        self._admit_cache = {}
        self._queue: "queue.Queue[Optional[_Pending]]" = queue.Queue(
            maxsize=queue_limit)
        self._active = {}                # slot -> request
        self._staged = set()             # in _active, prefill not yet
                                         # COMMITTED (device rows are a
                                         # previous occupant's) — a
                                         # recovery must fail these,
                                         # never salvage them
        self._pending = []               # admitted-order wait line
        self._free = list(range(self.n_slots - 1, -1, -1))
        self._epoch = 0
        self._tick_started = None        # (epoch, monotonic ts) while a
                                         # dispatch is in flight
        self._shutdown = False
        self._drain = False
        self._admission_closed = False   # drain(): submits raise, the
                                         # scheduler keeps running
        # per-INSTANCE prefix-cache tallies beside the process-global
        # counters: a router comparing replicas' cache warmth needs
        # the split (the global series aggregates every replica)
        self._n_prefix_hits = 0
        self._n_prefix_misses = 0
        # admissions since the last decode scan read the routed tally
        self._routed_admits = 0
        # per-INSTANCE tier tallies (the process-global kv_tier_*
        # counters aggregate every replica; a router sizing handoffs
        # or a bench proving THIS replica fetched needs the split)
        self._n_tier_spills = 0
        self._n_tier_fetches = 0
        self._n_tier_hits = 0
        # per-INSTANCE speculative tallies (same reasoning: the fleet
        # router ranks replicas on THEIR acceptance, not the process's)
        self._n_spec_proposed = 0
        self._n_spec_accepted = 0
        # per-tenant acceptance tallies feeding the labeled gauge (the
        # controller's raw signal, aggregated for the scrape)
        self._tenant_spec = {}
        # degrade-ladder cap on the draft depth (shrink_draft_k rung):
        # None = uncapped; clamps BOTH the adaptive controller's k_max
        # and a fixed-K server's dispatch depth, reversibly
        self._draft_k_cap = None
        # acceptance-adaptive K (ISSUE 20): every speculative server
        # carries the controller — it observes acceptance per (tenant,
        # leading-prefix) key regardless, and drives the dispatch
        # depth when the config says adaptive (attach_history() seeds
        # a cold controller from the TSDB counter history)
        self._spec_ctl = None
        if self._spec is not None:
            self._spec_ctl = _speculative.AcceptanceController(
                self._spec.k_max,
                draft_cost=self._spec.draft.n_layers / gen.kv_layers)
        self._stop_event = threading.Event()   # ends the watchdog
        # retire prior DEAD servers' series before adding ours: the
        # last-known 0 stays scrapeable until the next construction,
        # but a long-lived process cycling servers does not leak
        # unbounded label cardinality
        for vals, child in _HEALTHY._items():
            if child.value == 0:
                _HEALTHY.remove(*vals)
        self._healthy = _HEALTHY.labels(server=str(next(_SERVER_SEQ)))
        self._worker = threading.Thread(target=self._run, args=(0,),
                                        daemon=True)
        self._worker.start()
        self._healthy.set(1)
        self._watchdog = None
        if self.tick_timeout_s:
            self._watchdog = threading.Thread(target=self._watch,
                                              daemon=True)
            self._watchdog.start()

    def _refuse(self, what: str) -> None:
        """Raise, by name, where the stack's run kinds cannot do
        ``what``."""
        if self._gen.refuses:
            raise ValueError(f"{what} is not supported for a net with "
                             f"{self._gen.refuses}")

    # the recurrent runs' state rides in the carried ``state`` as two
    # [layers, slots, ...] leaves -- d_inner on the lanes; donated, reset
    # and salvaged with the pool -- and a stack that keeps none has no
    # such leaf: this pair is where the generator's ``rec`` meets them
    # A WINDOW kind's rings ride there likewise: two pools [layers,
    # slots * window blocks + 1, kv heads, block_size, width] whose
    # block 1 + slot * window blocks .. a slot owns for life (no
    # allocator: the table is fixed), armed by an admission as after the
    # prompt's last real token.  And the routed layers' tally, int32
    # [held + 1], and the count of held experts reached, [1], which the
    # decode scan hands to the host in one vector and zeroes.
    _REC_KEYS = ("rec_h", "rec_conv")
    _WIN_KEYS = ("win_k", "win_v")
    _REC_LEAVES = {"rec_h": "h", "rec_conv": "conv", "win_k": "win_k",
                   "win_v": "win_v", "routed": "routed",
                   "reached": "reached"}

    @classmethod
    def _rec_of(cls, state):
        """``state``'s per-slot leaves besides the paged pool as the
        generator takes them (None: the stack keeps none)."""
        return {name: state[k] for k, name in cls._REC_LEAVES.items()
                if k in state} or None

    @classmethod
    def _with_rec(cls, state, rec):
        """``state`` with the generator's ``rec`` (None: as it is)."""
        if rec is None:
            return state
        return {**state, **{k: rec[name]
                            for k, name in cls._REC_LEAVES.items()
                            if name in rec}}

    def _slot_mask(self, key: str, m):
        """The slots' mask ``m`` [B] as it applies to state leaf
        ``key``: along the slots of a recurrent leaf, the blocks of a
        window pool (the scratch block 0 is nobody's)."""
        if key in self._WIN_KEYS:
            per = jnp.repeat(m, self._gen.window_blocks(self.block_size))
            return jnp.concatenate(
                [jnp.zeros((1,), bool), per])[None, :, None, None, None]
        return m[None, :, None, None]

    def _fresh_pool(self):
        """(Re)allocate the KV block pool and per-slot device state —
        every slot inactive, every block free, the prefix cache empty.
        Also the error-recovery reset: the tick/admit programs DONATE
        these buffers, so after a failed dispatch the old arrays may
        already be invalidated."""
        gen = self._gen
        B = self.n_slots
        # the pool is sized by the layers and heads that HOLD K/V
        h = gen.kv_heads
        n_layers = gen.kv_layers
        cd = gen.compute_dtype
        nb = self.kv_blocks + 1      # + block 0, the never-read
                                     # scratch sink for masked writes
        # a pool's rows are as the route reads them: a head a row, the
        # keys' or the values' own width; or, on the kernel route, whole
        # 128-lane rows of as many heads side by side as fit
        tails = paged_pool_shape(h, self.block_size, gen.qk_dim, gen.v_dim,
                                 self._shard)
        kc, vc = (jnp.zeros((n_layers, nb) + tail, cd) for tail in tails)
        self._lane_bytes = _lane_bytes_a_block(
            n_layers, h, (gen.qk_dim, gen.v_dim), tails, cd)
        # table entries a decode read covers at a time: the kernel's
        # chunk (of the POOL's heads, as the kernel sees them), or the
        # whole table where the reference gathers it
        on_kernel = paged_route(self._shard) == "pallas"
        self._walk_chunk = (
            paged_walk_blocks(self.block_size, kc.shape[2], kc.shape[-1],
                              cd, self.max_blocks, vc.shape[-1])[0]
            if on_kernel else self.max_blocks)
        # a window kind's table is its window's blocks and no more
        self._win_walk = None
        if gen.window_kind is not None:
            wh, wk, wv, window = gen.window_kind
            wb = gen.window_blocks(self.block_size)
            if on_kernel and wb > 1 and gen.window_sink:
                # the kernel's ring over several blocks (the row lands
                # in ANY block of the table) is compiled, tested and
                # measured without a sink only; tests/benchmark_suite
                # pins this refusal for a window kind that has one
                raise ValueError(
                    f"window {window} spans {wb} blocks of "
                    f"{self.block_size} and its layers have a sink: "
                    "the decode kernel's write into a ring of several "
                    "blocks is built without one, so with a sink on the "
                    "kernel route a window fits one block "
                    "(block_size >= window)")
            tails = paged_pool_shape(wh, self.block_size, wk, wv,
                                     self._shard)
            (heads, _, k_width), (_, _, v_width) = tails
            self._win_walk = (
                window,
                (paged_walk_blocks(self.block_size, heads, k_width, cd, wb,
                                   v_width)[0] if on_kernel else wb),
                _lane_bytes_a_block(gen.win_layers, wh, (wk, wv), tails, cd))
        if self._shard is not None:
            # pool HEADS shard along tp (each chip holds its head
            # slice of every block); the block axis stays GLOBAL —
            # blocks are one pool shared across slots and the host
            # allocator/free list is the single truth the autoscaler
            # reads (a data-sharded pool/allocator is a ROADMAP
            # remainder).  Per-slot state rows shard along data.
            kc = self._shard.put(kc, None, None, "tp", None, None)
            vc = self._shard.put(vc, None, None, "tp", None, None)
        state = {
            "pos": jnp.zeros((B,), jnp.int32),        # next write index
            "remaining": jnp.zeros((B,), jnp.int32),  # tokens to emit
            "eos": jnp.full((B,), -1, jnp.int32),     # -1 disables
            "logits": jnp.zeros((B, self._vocab), jnp.float32),
            "key": jnp.zeros((B, 2), jnp.uint32),     # per-slot PRNG
            # per-slot sampling params (vectorized inside the scanned
            # step): temp <= 0 decodes greedy, top_k == vocab and
            # top_p == 1.0 are "off"
            "temp": jnp.zeros((B,), jnp.float32),
            "tk": jnp.full((B,), self._vocab, jnp.int32),
            "tp": jnp.ones((B,), jnp.float32),
            # True while the slot's held "logits" are a RAW sampling
            # distribution (the speculative rejection residual, in
            # log-weights): the next token draw must sample it
            # directly — re-applying temperature/top-k/top-p would
            # double-filter and break the rejection-sampling guarantee
            # (ISSUE 20).  Both the plain scan and the spec rounds
            # consume + clear it, so a mid-request spec→plain fallback
            # stays exactly target-distributed.
            "rawlg": jnp.zeros((B,), jnp.bool_),
            # per-slot block table: logical block j of the slot lives
            # in pool block table[slot, j]; 0 = unallocated (scratch)
            "table": jnp.zeros((B, self.max_blocks), jnp.int32),
            # the DRAFT model's block table (speculative decode; rides
            # along as zeros when speculation is off — the draft's KV
            # occupies the first draft.n_layers layers of the same
            # pool leaves under these block ids)
            "dtable": jnp.zeros((B, self.max_blocks), jnp.int32),
        }
        if self._shard is not None:
            state = {k: self._shard.put_batch(v)
                     for k, v in state.items()}
        # zeros: a slot that has seen nothing
        state = self._with_rec(
            state, gen.fresh_rec(B, self.block_size, self._shard))
        nbytes = lambda keys: sum(state[k].nbytes for k in keys
                                  if k in state)
        _REC_BYTES.set(nbytes(self._REC_KEYS))
        _WINDOW_BYTES.set(nbytes(self._WIN_KEYS))
        _POOL_BYTES.labels(kind="full").set(kc.nbytes + vc.nbytes)
        _POOL_BYTES.labels(kind="window").set(nbytes(self._WIN_KEYS))
        # commit atomically: this also runs on the watchdog's recovery
        # path while the (fenced) scheduler may still be snapshotting.
        # The host allocator truth resets WITH the device pool — free
        # list (block 0 reserved), refcounts, prefix-cache map and the
        # LRU of cached refcount-0 blocks.
        with self._lock:
            self._kc, self._vc, self._state = kc, vc, state
            self._blocks_free = list(range(self.kv_blocks, 0, -1))
            self._block_ref = np.zeros((nb,), np.int64)
            self._prefix_map = {}        # chain hash -> (pool block
                                         #  id, block token bytes —
                                         #  verified on every hit)
            self._block_hash = {}        # pool block id -> chain hash
            self._evictable = OrderedDict()   # cached ref-0 blocks, LRU
            self._slot_blocks = {}       # slot -> [pool block ids]
            # DRAFT prefix cache (ISSUE 20): same chain hashes, its
            # own hash->block map — a block holds either target KV
            # (all layers) or draft KV (the first draft_layers only),
            # so the two domains can never share a physical block.
            # _draft_cached marks which _block_hash entries belong to
            # the draft domain (eviction/recovery must pop the right
            # map, and draft blocks never spill to the host tier —
            # the tier stores target-domain bytes only).
            self._dprefix_map = {}       # chain hash -> (blk, tok)
            self._draft_cached = set()   # draft-domain pool block ids
        _POOL_FREE.set(self.kv_blocks)
        _POOL_EVICTABLE.set(0)

    # -- public API ----------------------------------------------------
    def refresh_params(self):
        """Snapshot the net's params for serving: block params stacked
        on the [n_layers] scan axis and (when the server computes in
        bf16) every floating leaf cast ONCE — the decode tick re-reads
        every parameter each tick, and streaming f32-stored weights
        would cost 2x the bytes of the math performed.  Call again
        after the underlying net's weights change.  Runs made stacked
        are snapshot as the net holds them: the snapshot IS the tree (a
        same-dtype cast copies nothing), one copy of the weights."""
        gen = self._gen
        emb_p, blk_ps, head_p = gen._params()
        emb_p, blk_stack, head_p = _cast_floating(
            (emb_p, gen._stack_blocks(blk_ps), head_p), gen.compute_dtype)
        if self._shard is not None:
            emb_p, blk_stack, head_p = self._place_params(
                emb_p, blk_stack, head_p)
        self._params = (emb_p, blk_stack, head_p)
        if self._spec is not None:
            # the draft refreshes WITH the target (a self-draft
            # ALIASES the cast target params — its layer slice happens
            # in-trace, zero extra device memory; an external draft
            # re-snapshots its own net)
            self._draft_params = self._spec.draft.params(self._params)
            if self._shard is not None:
                # self-draft leaves are already placed (device_put at
                # an identical sharding is the identity); an external
                # draft's own snapshot spreads here
                self._draft_params = self._place_params(
                    *self._draft_params)

    #: output-axis shard maps of a serving snapshot (ISSUE 17), by
    #: leaf name: every named axis of a block's weights is an OUTPUT
    #: axis — qkv/mlp columns — so no contraction is ever split (the
    #: TpShardCtx parity contract); the embedding / positional tables
    #: shard their vocab / position ROWS (gathered by token id — pure
    #: data movement), the head its vocab columns; everything absent
    #: (layer norms, a run kind without shard points) replicates.
    _EMB_SHARD_AXES = {"W": ("tp", None), "P": ("tp", None)}
    _BLK_SHARD_AXES = {
        "Wqkv": (None, None, "tp"), "bqkv": (None, "tp"),
        "Wo": (None, None, "tp"), "bo": (None, "tp"),
        "W1": (None, None, "tp"), "b1": (None, "tp"),
        "W2": (None, None, "tp"), "b2": (None, "tp"),
    }
    _HEAD_SHARD_AXES = {"W": (None, "tp"), "b": ("tp",)}

    def _place_params(self, emb_p, blk_stack, head_p):
        """Spread one serving snapshot over the replica's mesh by the
        maps above.  ``put`` falls any axis the tp extent does not
        divide back to replication, so odd vocab sizes etc. cost
        memory, never parity."""
        put = lambda axes, p: {k: self._shard.put(v, *axes.get(k, ()))
                               for k, v in p.items()}
        return (put(self._EMB_SHARD_AXES, emb_p),
                tuple(put(self._BLK_SHARD_AXES, p) for p in blk_stack),
                put(self._HEAD_SHARD_AXES, head_p))

    def healthy(self) -> bool:
        """True while the scheduler thread is alive and admission is
        open (the ``server_healthy`` gauge, as a method)."""
        with self._lock:
            return (not self._shutdown and self._worker.is_alive())

    def stats(self) -> dict:
        """ONE lock-consistent snapshot of the serving state an
        admission router dispatches on (every field read under the
        same lock acquisition — a torn multi-call view could admit
        against blocks a concurrent retire already freed):

        ``healthy`` (scheduler alive, admission open), ``draining``
        (:meth:`drain` called — or shutdown), ``n_slots`` /
        ``live_slots`` / ``free_slots``, ``queue_depth`` (submitted,
        not yet in a slot), ``block_size`` / ``kv_blocks`` /
        ``free_blocks`` (free list + evictable cache entries — the
        admission headroom a least-loaded placement ranks on),
        ``cached_blocks`` (resident prefix-cache entries), and
        ``prefix_hits`` / ``prefix_misses`` — THIS instance's
        admissions (the process-global ``prefix_cache_*_total``
        counters aggregate every replica in the process, so a router
        proving one replica's cache is warm needs the per-instance
        split)."""
        with self._lock:
            return {
                "healthy": (not self._shutdown
                            and self._worker.is_alive()),
                "draining": self._admission_closed or self._shutdown,
                "n_slots": self.n_slots,
                "live_slots": len(self._active),
                "free_slots": len(self._free),
                "queue_depth": len(self._pending) + self._queue.qsize(),
                "block_size": self.block_size,
                "kv_blocks": self.kv_blocks,
                "free_blocks": (len(self._blocks_free)
                                + len(self._evictable)),
                # the ISSUE 14 split of free_blocks: a draining free
                # list against a full evictable set means every
                # admission is about to evict (tiered: spill)
                "free_list_blocks": len(self._blocks_free),
                "evictable_blocks": len(self._evictable),
                "cached_blocks": len(self._block_hash),
                "prefix_hits": self._n_prefix_hits,
                "prefix_misses": self._n_prefix_misses,
                # host-tier view (ISSUE 14): resident spilled blocks +
                # THIS instance's spill/fetch tallies
                "host_tier_blocks": (len(self._tier)
                                     if self._tier is not None else 0),
                "tier_spills": self._n_tier_spills,
                "tier_fetches": self._n_tier_fetches,
                "tier_hits": self._n_tier_hits,
                # speculative view for the fleet router: spec_k > 0
                # means an admission here pins ~2x blocks (target +
                # draft tables), and the acceptance rate is the
                # replica's effective tokens-per-verify multiplier
                "spec_k": (self._spec.k if self._spec else 0),
                "spec_adaptive": bool(self._spec.adaptive
                                      if self._spec else False),
                "spec_k_max": (self._spec.k_max if self._spec else 0),
                "spec_k_cap": self._draft_k_cap,
                "spec_proposed": self._n_spec_proposed,
                "spec_accepted": self._n_spec_accepted,
                "spec_acceptance_rate": (
                    self._n_spec_accepted / self._n_spec_proposed
                    if self._n_spec_proposed else 0.0),
                # mesh view (ISSUE 17): the slice THIS replica spans.
                # free_blocks above is already the GLOBAL pool truth —
                # the host allocator is unsharded (the pool's block
                # axis is global; only its head axis shards), so an
                # autoscaler reads one number, not per-shard counts.
                "tp": self.tp_degree,
                "devices": (list(self._device_labels)
                            if self._device_labels is not None
                            else None),
            }

    def prefix_warmth(self, prompt_ids) -> int:
        """Membership probe for prefix-affinity routing: how many of
        the prompt's leading FULL blocks are resident in THIS server's
        prefix cache right now (bytes-verified, nothing mutated, no
        refcount taken — the answer is advisory and may be stale by
        the time the request lands, which only costs a suffix prefill,
        never correctness).  0 when the cache is disabled, the prompt
        is shorter than one full block, or nothing matches."""
        if not self.prefix_cache:
            return 0
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            return 0
        hashes = self._chain_hashes(prompt)   # pure — outside the lock
        n = 0
        with self._lock:
            tier = self._tier
            for hsh, tok in hashes:
                entry = self._prefix_map.get(hsh)
                if entry is None or entry[1] != tok:
                    break
                n += 1
            if tier is not None:
                # host-tier warmth continues the chain: a spilled
                # block still saves its prefill (one H2D instead),
                # so affinity should still prefer this replica.
                # peek() — a probe must not touch the tier's LRU.
                for j in range(n, len(hashes)):
                    hsh, tok = hashes[j]
                    if tier.peek(hsh, tok) is None:
                        break
                    n += 1
        return n

    # -- disagg handoff + host tier (ISSUE 14) -------------------------
    def _ensure_tier(self) -> HostKVTier:
        """The host tier, created on demand for handoff imports on a
        server constructed without ``host_tier_blocks``.  Default
        capacity: FOUR device pools' worth — a tier sized exactly
        like the pool would let two concurrent handoffs LRU-evict
        each other's chain-head entries before either admission runs
        (the walk then misses at block 0 and the whole handoff is
        void; ``kv_tier_evictions_total`` is the signal when even 4x
        thrashes)."""
        with self._lock:
            if self._tier is None:
                self._tier = HostKVTier(max(4 * self.kv_blocks, 1))
            return self._tier

    def export_prefix(self, prompt_ids, max_wait_s: float = 1.0):
        """Serialize the prompt's leading cached full blocks for a
        cross-replica handoff: a list of ``(chain_hash, token_bytes,
        k, v)`` entries (host numpy K/V bytes per block) readable by
        :meth:`import_blocks` on any replica of the SAME model.
        Device-resident entries are read D2H; already-spilled entries
        come straight from the host tier.  Returns as many LEADING
        blocks as are resident right now (possibly none) — the
        importer's admission degrades gracefully: whatever was not
        handed off just prefills.

        Thread-safe against the scheduler: the D2H read can race a
        donating dispatch on accelerator backends, so it retries
        (bounded by ``max_wait_s``) until a committed pool snapshot
        reads clean."""
        self._refuse("export_prefix")
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            return []
        hashes = self._chain_hashes(prompt)   # pure — outside the lock
        deadline = time.monotonic() + float(max_wait_s)
        while True:
            payload, clean = [], True
            with self._lock:
                kc, vc, tier = self._kc, self._vc, self._tier
                for hsh, tok in hashes:
                    entry = self._prefix_map.get(hsh)
                    if entry is not None and entry[1] == tok:
                        blk = entry[0]
                        try:
                            k = self._block_to_host(kc, blk,
                                                    self._gen.qk_dim)
                            v = self._block_to_host(vc, blk,
                                                    self._gen.v_dim)
                        except (RuntimeError, ValueError):
                            # donated mid-read (jax raises ValueError
                            # for deleted/donated buffers on some
                            # backends): retry against the next commit
                            clean = False
                            break
                        payload.append((hsh, tok, k, v))
                        continue
                    spilled = (tier.peek(hsh, tok)
                               if tier is not None else None)
                    if spilled is None:
                        break               # chain ends here
                    payload.append((hsh, tok) + spilled)
            if clean or time.monotonic() >= deadline:
                return payload
            time.sleep(0.002)        # let the in-flight tick commit

    def import_blocks(self, payload) -> int:
        """Land an :meth:`export_prefix` payload in THIS replica's
        host tier (creating a default-capacity tier on first use):
        the next admission whose prompt chain-hashes onto the entries
        restores them into pool blocks with ONE batched H2D and
        registers them as device-resident prefix-cache entries —
        every later same-prefix admission then maps them copy-free.
        Entries whose chain hash is already device-resident (verified)
        are skipped.  Returns how many blocks landed."""
        self._refuse("import_blocks")
        n = n_bytes = 0
        tier = None
        for hsh, tok, k, v in payload:
            with self._lock:
                entry = self._prefix_map.get(hsh)
                if entry is not None and entry[1] == tok:
                    continue         # already device-resident here
            if tier is None:
                tier = self._ensure_tier()
            tier.put(hsh, tok, k, v)
            n += 1
            n_bytes += np.asarray(k).nbytes + np.asarray(v).nbytes
        if n:
            _HANDOFF_BLOCKS.inc(n)
            _HANDOFF_BYTES.inc(n_bytes)
        return n

    def drain(self) -> None:
        """Close admission WITHOUT stopping the server: subsequent
        ``submit*`` calls raise ``RuntimeError``, everything already
        queued or in flight runs to completion, and the scheduler —
        with its telemetry, :meth:`stats` and the watchdog — stays
        alive.  The router-side building block for rolling a replica
        out of a fleet; ``shutdown(drain=True)`` is the terminal
        variant that also stops the scheduler.  One-way: construct a
        fresh server to reopen admission."""
        with self._lock:
            self._admission_closed = True

    def set_spec_enabled(self, enabled: bool) -> None:
        """Suspend (False) or resume (True) speculative decoding on a
        live server — the ``spec_off`` rung of the fleet's degradation
        ladder (ISSUE 18).  Suspension skips draft+verify rounds
        entirely from the next tick on; the draft state stays
        resident, so resuming costs nothing but the stale-draft-KV
        acceptance dip the fallback already tolerates.  A no-op on a
        server built without ``speculative=``."""
        with self._lock:
            self._spec_off = not bool(enabled)

    def set_draft_k_cap(self, cap: Optional[int]) -> None:
        """Cap the speculative draft depth on a live server — the
        ``shrink_draft_k`` rung of the degradation ladder (ISSUE 20),
        one rung gentler than ``spec_off``: speculation keeps running
        (and keeps its tokens-per-verify win) but both the adaptive
        controller's ``k_max`` and a fixed-K server's dispatch depth
        clamp to ``cap`` from the next dispatch on, shrinking the
        draft compute and the rejected-work tail under pressure.
        ``None`` lifts the cap (the rung's reversible exit).  A no-op
        on a non-speculative server."""
        with self._lock:
            self._draft_k_cap = (None if cap is None
                                 else max(1, int(cap)))

    def attach_history(self, store) -> None:
        """Attach a :class:`~..telemetry.tsdb.TimeSeriesStore` so the
        acceptance controller can seed a cold start from the beaconed
        ``generation_server_spec_{proposed,accepted}_total`` history
        (PR 16 recorder) instead of guessing ``k_max`` until its own
        EWMA warms.  A no-op on a non-speculative server."""
        if self._spec_ctl is not None:
            self._spec_ctl.attach_store(store)

    def demote_waiting(self, n_new_factor: Optional[float] = None,
                       force_greedy: bool = False) -> int:
        """Cheapen the NOT-YET-ADMITTED queue in place (ISSUE 18, the
        degradation ladder's replica-side actuator): scale each
        waiting request's ``n_new`` by ``n_new_factor`` (floor 1,
        never grown) and/or flip it to greedy decode.  Active slots
        are untouched — their budgets are already spent device-side
        and a mid-decode sampling flip would break per-seed
        reproducibility.  Returns how many requests changed."""
        factor = None if n_new_factor is None else float(n_new_factor)
        if factor is not None and not 0.0 < factor <= 1.0:
            raise ValueError("n_new_factor must be in (0, 1]")
        changed = 0
        with self._lock:
            for r in self._pending:
                hit = False
                if factor is not None:
                    capped = max(1, int(r.n_new * factor))
                    if capped < r.n_new:
                        r.n_new = capped
                        hit = True
                if force_greedy and r.temperature > 0.0:
                    r.temperature = 0.0
                    hit = True
                changed += hit
        return changed

    def _resolve_sampling(self, sampling, seed):
        """Merge a per-request ``sampling`` dict over the server-wide
        defaults -> (temperature, effective top_k, effective top_p,
        seed).  top_k resolves to the vocab size and top_p to 1.0
        ("off") for greedy requests so the device-side [B] vectors
        always hold valid values."""
        samp = dict(sampling or {})
        unknown = set(samp) - {"temperature", "top_k", "top_p", "seed"}
        if unknown:
            raise ValueError(
                f"unknown sampling key(s) {sorted(unknown)} (expected "
                "temperature / top_k / top_p / seed)")
        temp = float(samp.get("temperature", self.temperature))
        tk = samp.get("top_k", None)
        if tk is not None:
            if temp <= 0:
                raise ValueError("sampling top_k needs temperature > 0 "
                                 "(greedy ignores the filtered tail)")
            tk = int(tk)
            if not 1 <= tk <= self._vocab:
                raise ValueError(f"sampling top_k={tk} out of range "
                                 f"[1, {self._vocab}] (vocab size)")
        elif temp > 0 and self.top_k is not None:
            tk = int(self.top_k)         # server-wide default
        tp = samp.get("top_p", None)
        if tp is not None:
            if temp <= 0:
                raise ValueError("sampling top_p needs temperature > 0 "
                                 "(greedy ignores the filtered tail)")
            tp = float(tp)
            if not 0.0 < tp <= 1.0:
                raise ValueError(f"sampling top_p={tp} out of range "
                                 "(0, 1]")
        elif temp > 0 and self.top_p is not None:
            tp = float(self.top_p)       # server-wide default
        tk_eff = self._vocab if tk is None else tk
        tp_eff = 1.0 if tp is None else tp
        seed = int(samp.get("seed", seed))
        if not -2 ** 63 <= seed < 2 ** 63:
            raise ValueError(f"sampling seed={seed} out of range (a "
                             "signed 64-bit integer)")
        return temp, tk_eff, tp_eff, seed

    # -- block allocator + prefix cache (host truth, under _lock) ------
    def _chain_hashes(self, prompt: np.ndarray):
        """(chain hash, block token bytes) per FULL prompt block —
        h_j folds h_{j-1}, so a hit at j certifies the whole prefix
        through j; the raw bytes ride along because a lookup VERIFIES
        them (``hash()`` is 64-bit and non-cryptographic — a collision
        must degrade to a miss, never silently map another prompt's KV
        into this request).  Capped at t0 - 1 tokens: a fully-cached
        prompt must still prefill >= 1 suffix token, because logits
        come from the suffix forward (K/V are cached; hidden states
        are not)."""
        bs = self.block_size
        hashes, h = [], 0
        for j in range((len(prompt) - 1) // bs):
            tok = prompt[j * bs:(j + 1) * bs].tobytes()
            h = hash((h, tok))
            hashes.append((h, tok))
        return hashes

    def _evict_lru_locked(self) -> None:
        """Evict the LRU refcount-0 cache block back to the free list
        — SPILLING its bytes to the host tier first when one is
        configured (ISSUE 14: an evicted prefix block used to die,
        capping the effective prefix cache at pool size; now the next
        same-prefix admission pays one H2D copy instead of a full
        re-prefill).  The D2H read happens under the server lock on
        the scheduler thread, where the committed pool is never
        donated-in-flight (the same invariant every admission snapshot
        relies on)."""
        blk, _ = self._evictable.popitem(last=False)        # LRU out
        hsh = self._block_hash.pop(blk)
        if blk in self._draft_cached:
            # draft-domain entry: its own map, and NEVER tier-spilled
            # — the tier holds target-domain bytes (a draft block is
            # d cheap layers of re-derivable KV; respilling it would
            # displace target blocks worth n expensive layers each)
            self._draft_cached.discard(blk)
            self._dprefix_map.pop(hsh, None)
            self._blocks_free.append(blk)
            return
        _, tok = self._prefix_map.pop(hsh)
        # spilling is the CONFIGURED knob (host_tier_blocks > 0), not
        # tier existence: a lazily-created handoff tier on an
        # unconfigured server must not start charging a D2H copy per
        # eviction the operator turned off (imported entries persist
        # in that tier regardless — fetch never removes them)
        if self._tier is not None and self.host_tier_blocks:
            try:
                k = self._block_to_host(self._kc, blk, self._gen.qk_dim)
                v = self._block_to_host(self._vc, blk, self._gen.v_dim)
            except (RuntimeError, ValueError):
                k = None                 # consumed donated buffer
                                         # (recovery in flight): the
            if k is not None:            # block just dies, pre-tier
                self._tier.put(hsh, tok, k, v)
                self._n_tier_spills += 1
                _TIER_SPILLS.inc()
                _FLIGHT.record("kv_spill", block=int(blk))
        self._blocks_free.append(blk)

    def _plan_admission_locked(self, req: _Pending):
        """Match cached prefix blocks and claim the rest off the free
        list (evicting LRU cache entries as needed); returns an
        ``_AdmitPlan``, or None when the pool cannot cover the request
        right now — BLOCKS are the scarce resource, so the caller
        leaves the request at the head of the wait line (a retiring
        request frees blocks, not just a slot).

        The chain walk is TWO-tier: device prefix map first, then the
        host tier continues the chain past the device segment — each
        tier hit claims a fresh pool block the admit program restores
        with one batched H2D (the whole point of spilling).  A
        mid-chain miss ends the walk in either tier: the chain hash at
        j certifies the whole prefix through j, so a gap can never be
        bridged."""
        bs = self.block_size
        total = -(-(req.t0 + req.n_new) // bs)
        hashes = (self._chain_hashes(req.prompt)
                  if self.prefix_cache else [])
        matched_ids = []
        for hsh, tok in hashes:
            entry = self._prefix_map.get(hsh)
            if entry is None or entry[1] != tok:
                break                # miss — or a hash collision,
            matched_ids.append(entry[0])   # which must NOT map in
        dev_matched = len(matched_ids)
        # host-tier walk: continue the chain where the device map
        # stopped (peek() verifies raw token bytes — a collision
        # degrades to a miss — WITHOUT touching the tier's LRU: a
        # blocked request is re-planned every scheduler pass, and a
        # plan that never commits must not pin its entries MRU at
        # other prompts' expense; the admit COMMIT touches them)
        fills = []
        if self._tier is not None:
            for j in range(dev_matched, len(hashes)):
                hsh, tok = hashes[j]
                entry = self._tier.peek(hsh, tok)
                if entry is None:
                    break
                fills.append(entry)
        # speculative decode: the DRAFT's KV table needs the same
        # block count — claimed from the SAME free list, so draft KV
        # competes in the same economy.  Full prompt draft blocks are
        # prefix-shareable exactly like target blocks (prefill-derived,
        # never written after — draft decode writes at pos >= t0), so
        # the chain walks the DRAFT hash domain too (ISSUE 20); the
        # walk only runs when the target side hit, which keeps the
        # draft reuse on the hit-path admit program (the common case —
        # both domains register together, so their residency tracks).
        # A prefill-ONLY request never decodes, so it claims no draft
        # table and skips the draft prefill entirely (a speculative
        # prefill replica would otherwise pin ~2x blocks per staged
        # request for KV that is discarded at retire)
        use_draft = self._spec is not None and not req.prefill_only
        dmatched_ids = []
        if use_draft and (dev_matched or fills):
            for hsh, tok in hashes:
                entry = self._dprefix_map.get(hsh)
                if entry is None or entry[1] != tok:
                    break
                dmatched_ids.append(entry[0])
        dmatched = len(dmatched_ids)
        dneed = (total - dmatched) if use_draft else 0
        need = total - dev_matched + dneed
        # matched hits sitting in the evictable LRU are about to be
        # CLAIMED, not evicted — they don't count as reclaimable
        ev_matched = sum(1 for blk in matched_ids + dmatched_ids
                         if self._block_ref[blk] == 0
                         and blk in self._evictable)
        if need > (len(self._blocks_free) + len(self._evictable)
                   - ev_matched):
            return None
        # claim the hits FIRST: a hit sitting in the evictable LRU must
        # leave it before the eviction loop below could reclaim it
        for blk in matched_ids + dmatched_ids:
            if self._block_ref[blk] == 0:
                self._evictable.pop(blk, None)
            self._block_ref[blk] += 1
        while need > len(self._blocks_free):
            self._evict_lru_locked()
        fresh = [self._blocks_free.pop() for _ in range(need)]
        for blk in fresh:
            self._block_ref[blk] = 1
        dphys = (dmatched_ids + fresh[need - dneed:]
                 if use_draft else [])
        fresh = fresh[:need - dneed]
        # table order: device hits, then the tier-restore targets (the
        # FIRST len(fills) fresh claims — aligned with hash indices
        # [dev_matched, dev_matched + len(fills))), then the suffix's
        # fresh blocks
        return _AdmitPlan(matched_ids + fresh,
                          dev_matched + len(fills), hashes,
                          len(fresh) + len(dphys) - dmatched, dphys,
                          reg_from=dev_matched, fills=tuple(fills),
                          dmatched=dmatched)

    def _register_prefix_locked(self, plan: _AdmitPlan):
        """After the prefill COMMITS, publish the request's new full
        prompt blocks into the prefix cache — tier-restored blocks
        (now device-resident with verified bytes) and fresh full
        prompt blocks alike; the device-matched prefix is already
        there.  Full prompt blocks are never written after prefill —
        decode writes land at pos >= t0, strictly past every full
        block — so sharing them is safe by construction."""
        for j in range(plan.reg_from, len(plan.hashes)):
            hsh, tok = plan.hashes[j]
            if hsh in self._prefix_map:
                continue                 # coincident entry stands
            blk = plan.phys[j]
            self._prefix_map[hsh] = (blk, tok)
            self._block_hash[blk] = hsh

    def _register_draft_prefix_locked(self, plan: _AdmitPlan):
        """Publish the DRAFT's full prompt blocks under the same chain
        hashes, in the draft-domain map (ISSUE 20).  Draft full prompt
        blocks are write-free after prefill for the same reason target
        ones are — draft decode writes at pos >= t0 — so a later
        same-prefix admission gathers them instead of re-prefilling
        the draft over the whole prompt."""
        for j in range(plan.dmatched,
                       min(len(plan.hashes), len(plan.dphys))):
            hsh, tok = plan.hashes[j]
            if hsh in self._dprefix_map:
                continue                 # coincident entry stands
            blk = plan.dphys[j]
            self._dprefix_map[hsh] = (blk, tok)
            self._block_hash[blk] = hsh
            self._draft_cached.add(blk)

    def _release_slot_blocks_locked(self, slot: int) -> int:
        """Decref a retiring slot's blocks; refcount-0 blocks return
        to the free list, unless prefix-cached — those stay resident
        as evictable LRU entries so the next same-prefix request still
        hits.  Returns the number of refcount-drains (the
        ``kv_blocks_freed_total`` increment, counted by the caller
        outside the lock)."""
        drained = 0
        for blk in self._slot_blocks.pop(slot, ()):
            self._block_ref[blk] -= 1
            if self._block_ref[blk] > 0:
                continue
            drained += 1
            if blk in self._block_hash:
                self._evictable[blk] = None
            else:
                self._blocks_free.append(blk)
        return drained

    def _update_free_gauge(self):
        with self._lock:
            n_free = len(self._blocks_free)
            n_ev = len(self._evictable)
        # split gauges (ISSUE 14): free list vs evictable cache —
        # their SUM is still the admission headroom, but a draining
        # free list with a full evictable set means every admission
        # is about to evict (and, tiered, spill) — pressure the old
        # summed gauge hid
        _POOL_FREE.set(n_free)
        _POOL_EVICTABLE.set(n_ev)

    def submit_async(self, prompt_ids, n_new: int,
                     eos_id: Optional[int] = None,
                     seed: int = 0,
                     deadline_s: Optional[float] = None,
                     sampling: Optional[dict] = None,
                     trace_id: Optional[str] = None,
                     tenant: str = "default") -> _Pending:
        """Enqueue one sequence; returns a handle whose ``result()``
        blocks.  ``prompt_ids`` is a 1-D int array; the request decodes
        until ``n_new`` tokens are emitted or ``eos_id`` is sampled.
        ``deadline_s`` (default: the server's ``request_deadline_s``)
        bounds the request's total residence — queue wait included;
        past it the request fails with ``DeadlineExceededError`` and
        its slot is reclaimed.  ``sampling`` overrides the server-wide
        sampling defaults for THIS request: a dict with any of
        ``temperature`` (<= 0 is greedy), ``top_k``, ``top_p``,
        ``seed`` — per-request values ride as [B] vectors in device
        state, so greedy and sampled requests share slots in one
        program."""
        with self._lock:
            if self._shutdown:
                raise RuntimeError("GenerationServer has been shut down")
            if self._admission_closed:
                raise RuntimeError(
                    "GenerationServer is draining (admission closed; "
                    "in-flight work continues)")
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt_ids must be a non-empty 1-D int "
                             f"array, got shape {prompt.shape}")
        n_new = int(n_new)
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        if len(prompt) + n_new > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + n_new ({n_new}) exceeds the "
                f"slot cache length ({self.max_len})")
        deadline_s = (self.request_deadline_s if deadline_s is None
                      else float(deadline_s))
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        temp, tk_eff, tp_eff, seed = self._resolve_sampling(sampling,
                                                            seed)
        # prefix key for the acceptance controller: the FIRST chain
        # hash — same prompt family, same key — so acceptance stats
        # pool per (tenant, prompt-prefix) workload, not per request
        bs = self.block_size
        pkey = (hash((0, prompt[:bs].tobytes()))
                if len(prompt) - 1 >= bs else None)
        req = _Pending(prompt, n_new,
                       -1 if eos_id is None else int(eos_id), seed,
                       temperature=temp, top_k=tk_eff, top_p=tp_eff,
                       deadline=deadline, trace_id=trace_id,
                       tenant=tenant, pkey=pkey)
        return self._enqueue(req)

    def prefill_async(self, prompt_ids,
                      deadline_s: Optional[float] = None,
                      trace_id: Optional[str] = None) -> _Pending:
        """Enqueue a PREFILL-ONLY request (disaggregated serving,
        ISSUE 14): the prompt admits into a slot, prefills through the
        normal chunked/prefix-cached machinery, registers its full
        prompt blocks in the prefix cache — and retires immediately
        WITHOUT a decode tick, releasing the slot and parking the
        blocks as evictable cache entries.  ``result()`` resolves to
        the prompt itself (nothing is generated).

        The prefill replica's half of the disagg handoff:
        ``prefill_async(p).result()`` → :meth:`export_prefix` →
        the decode replica's :meth:`import_blocks` — whose admission
        of the same prompt then prefills only the last partial
        block."""
        self._refuse("prefill_async")
        if not self.prefix_cache:
            raise ValueError("prefill_async needs prefix_cache=True "
                             "(a prefill-only request's sole product "
                             "is its cached prefix blocks)")
        with self._lock:
            if self._shutdown:
                raise RuntimeError("GenerationServer has been shut down")
            if self._admission_closed:
                raise RuntimeError(
                    "GenerationServer is draining (admission closed; "
                    "in-flight work continues)")
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("prompt_ids must be a non-empty 1-D int "
                             f"array, got shape {prompt.shape}")
        if len(prompt) > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) exceeds the slot cache "
                f"length ({self.max_len})")
        deadline_s = (self.request_deadline_s if deadline_s is None
                      else float(deadline_s))
        deadline = (time.monotonic() + deadline_s
                    if deadline_s is not None else None)
        req = _Pending(prompt, 0, -1, 0, deadline=deadline,
                       trace_id=trace_id, prefill_only=True)
        return self._enqueue(req)

    def _enqueue(self, req: _Pending) -> _Pending:
        """Queue-put shared by ``submit_async``/``prefill_async``."""
        # replica-queue span: opened on the CALLER's thread, ended by
        # the scheduler at admission (or by whatever retires a never-
        # admitted request) — the tracked-span API exists exactly for
        # this cross-thread close
        args = ({"trace": req.trace_id}
                if req.trace_id is not None else {})
        req.spans["queue"] = telemetry.get_tracer().begin(
            "request/replica_queue", **args)
        while True:
            try:
                self._queue.put(req, timeout=0.1)
                break
            except queue.Full:
                with self._lock:
                    down = self._shutdown
                if down:             # nobody will ever drain a slot
                    req.close_spans("rejected")
                    raise RuntimeError(
                        "GenerationServer has been shut down") from None
        with self._lock:
            dead = self._shutdown and not self._worker.is_alive()
        if dead:
            # raced shutdown(): the put may have landed AFTER the
            # worker's (and shutdown's) final drains — fail leftovers
            # ourselves so no caller's result() blocks forever
            self._fail_leftovers()
        return req

    def submit(self, prompt_ids, n_new: int,
               eos_id: Optional[int] = None, seed: int = 0,
               timeout: Optional[float] = None,
               deadline_s: Optional[float] = None,
               sampling: Optional[dict] = None,
               retries: Optional[int] = None,
               tenant: str = "default") -> np.ndarray:
        """Blocking ``submit_async().result()``.  ``retries`` (default:
        the server's ``submit_retries``) re-submits after a
        ``RetryableServerError`` — a watchdog/tick-failure recovery
        that failed this request through no fault of its own — with
        full-jitter exponential backoff so a herd of failed callers
        does not re-collide on the rebuilt pool."""
        retries = self.submit_retries if retries is None else int(retries)

        def attempt():
            return self.submit_async(prompt_ids, n_new, eos_id, seed,
                                     deadline_s=deadline_s,
                                     sampling=sampling,
                                     tenant=tenant).result(timeout)

        if retries <= 0:
            return attempt()
        return retry_call(attempt, retries=retries,
                          base_delay=self.retry_backoff_s,
                          op="generation_server.submit")

    def _fail_leftovers(self):
        """Drain and fail queued requests once the worker is gone —
        whichever of shutdown()/submit_async() observes the dead worker
        last runs this, so no request is stranded unconsumed."""
        err = RuntimeError("GenerationServer shut down with the "
                           "request in flight")
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return
            if item is not None:
                self._retire(item, -1, error=err)

    def shutdown(self, drain: bool = False, timeout: float = 30.0):
        """Stop the scheduler.  Default: in-flight and queued requests
        fail immediately with RuntimeError (collect results first).
        ``drain=True``: admission closes (new submits raise) but
        everything already submitted runs to completion before the
        scheduler exits — the rolling-restart mode.  ``timeout`` bounds
        the wait for the scheduler thread either way."""
        with self._lock:
            self._drain = bool(drain)
            self._shutdown = True
            worker = self._worker
        self._queue.put(None)
        worker.join(timeout=timeout)
        if worker.is_alive():
            log.warning("GenerationServer scheduler did not exit within "
                        "%.3gs (drain=%s); abandoning it and failing "
                        "its in-flight requests", timeout, drain)
            with self._lock:
                self._epoch += 1     # fence the hung scheduler out
            self._fail_all_in_flight(RuntimeError(
                "GenerationServer shut down while the scheduler was "
                "unresponsive; the request was abandoned in flight"))
        self._stop_event.set()           # watchdog stands down
        if self._watchdog is not None:
            self._watchdog.join(timeout=5)
        # a submit that passed the _shutdown check concurrently may
        # have enqueued AFTER the sentinel (the worker exits on the
        # first None it sees)
        self._fail_leftovers()
        self._healthy.set(0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- compiled programs ---------------------------------------------
    def _sampler(self, sampled: bool):
        """Token chooser for the scanned step: the all-greedy variant
        is pure argmax (no sort / categorical / key-split work in the
        program at all); the sampled variant vectorizes per-slot
        temperature/top-k/top-p and splits every slot's PRNG stream
        exactly once per tick — greedy rows select the argmax out of
        the same program, so one scan serves mixed greedy+sampled
        slots."""

        @jax.named_scope("sample")
        def pick_greedy(state):
            return jnp.argmax(state["logits"], axis=-1), state["key"]

        @jax.named_scope("sample")
        def pick_sampled(state):
            both = jax.vmap(jax.random.split)(state["key"])
            keys, subs = both[:, 0], both[:, 1]
            temp = state["temp"]
            safe = jnp.where(temp > 0, temp, 1.0)[:, None]
            lg = _filter_logits_rows(state["logits"] / safe,
                                     state["tk"], state["tp"])
            # rawlg rows hold a residual log-distribution left by a
            # rejected speculative round (ISSUE 20) — already
            # temperature/filter-shaped; sample it AS-IS (re-applying
            # the filters would skew the rejection-sampling residual
            # and break distribution exactness)
            lg = jnp.where(state["rawlg"][:, None],
                           state["logits"], lg)
            cand = jax.vmap(jax.random.categorical)(subs, lg)
            tok = jnp.where((temp > 0) | state["rawlg"], cand,
                            jnp.argmax(state["logits"], axis=-1))
            return tok, keys

        return pick_sampled if sampled else pick_greedy

    def _decode_scan(self, K: int, sampled: bool):
        """K static-shape decode ticks fused into ONE ``lax.scan``
        (cached per (K, sampled)): each tick samples every active
        slot's next token from its held logits, writes it at the
        slot's (block, offset) through its block table, advances every
        cache one step, decrements budgets, zeroes the budget on EOS.
        Inactive slots (free, or retired MID-SCAN by EOS / budget
        drain) flow through with a masked write into the SCRATCH
        block 0 (never referenced by a live table), NOT their stale
        pos: a just-finished max-length request parks pos == max_len,
        and an out-of-bounds positional-table take fills NaN — which
        a clamped write would smear into a live block and poison it.

        Returns ``(kc, vc, state, polled)``: ``polled`` is ONE int32
        array [B, K + 2], all the host reads of a scan — the staged
        tokens [B, K] (the host polls once per scan, not once per
        token), ``emitted`` (each slot's live ticks, so the host
        unpacks exactly the tokens that were really generated) and a
        copy of ``state["remaining"]`` (who is done; the device-truth
        occupancy at scan end is its count of non-zeros)."""
        key = (int(K), bool(sampled))
        fn = self._scan_cache.get(key)
        if fn is not None:
            return fn
        gen = self._gen
        pick = self._sampler(sampled)
        bs = self.block_size
        shard = self._shard

        # the jitted callable's __name__ names the program in a
        # profile (module ``jit_decode_scan``), the scope one tick's
        # operations: the benchmark's readers find both by name
        def decode_scan(emb_p, blk_stack, head_p, kc, vc, state):
            @jax.named_scope("decode_tick")
            def step(carry, _):
                kc, vc, state, emitted = carry
                active = state["remaining"] > 0
                logits = state["logits"]
                tok, keys = pick(state)
                tok = jnp.where(active, tok, 0).astype(jnp.int32)
                pos = jnp.where(active, state["pos"], 0)
                # route the write through the slot's block table;
                # inactive slots land in the scratch block 0 (never
                # read) — the paged analogue of the masked pos-0 write
                tbl = state["table"]
                bidx = jnp.take_along_axis(
                    tbl, (pos // bs)[:, None], axis=1)[:, 0]
                wblk = jnp.where(active, bidx, 0)
                woff = jnp.where(active, pos % bs, 0)
                # recurrent layers' state goes in and comes out too;
                # inactive slots keep theirs bit for bit (a retired
                # slot's may not drift to inf)
                new_logits, kc, vc, rec = gen._step_paged(
                    emb_p, blk_stack, head_p, kc, vc, tok, pos, tbl,
                    wblk, woff, shard=shard, kernel_writes=True,
                    rec=self._rec_of(state), active=active)
                hit_eos = active & (tok == state["eos"])
                remaining = jnp.where(active, state["remaining"] - 1, 0)
                remaining = jnp.where(hit_eos, 0, remaining)
                state = self._with_rec({
                    "pos": jnp.where(active, state["pos"] + 1,
                                     state["pos"]),
                    "remaining": remaining,
                    "eos": state["eos"],
                    "logits": jnp.where(active[:, None], new_logits,
                                        logits),
                    "key": keys,
                    "temp": state["temp"],
                    "tk": state["tk"],
                    "tp": state["tp"],
                    "table": tbl,
                    # untouched by the plain tick: a speculative
                    # server's fallback scans (sampled slots live)
                    # leave the draft's KV stale, which costs
                    # acceptance on later rounds, never correctness
                    "dtable": state["dtable"],
                    # a residual row is consumed by its FIRST sampled
                    # pick; the greedy program never sees one live
                    # (residuals only arise on sampled slots)
                    "rawlg": ((state["rawlg"] & ~active)
                              if sampled else state["rawlg"]),
                }, rec)
                emitted = emitted + active.astype(jnp.int32)
                return (kc, vc, state, emitted), tok

            emitted0 = jnp.zeros(state["remaining"].shape, jnp.int32)
            (kc, vc, state, emitted), toks = jax.lax.scan(
                step, (kc, vc, state, emitted0), None, length=K)
            polled = _pack_polled(toks.T, emitted, state["remaining"])
            if "routed" in state:
                # the routed layers' tally since the last scan, in rows
                # below the slots'; it starts again from nought
                polled = _append_rows(polled, jnp.concatenate(
                    [state["routed"], state["reached"]]))
                state = {**state,
                         "routed": jnp.zeros_like(state["routed"]),
                         "reached": jnp.zeros_like(state["reached"])}
            return kc, vc, state, polled

        # donate caches + state: the scan updates them in place instead
        # of copying both full [n_layers, B, h, L, dh] buffers per
        # dispatch (ignored with a warning on backends without
        # donation)
        fn = self._scan_cache[key] = jax.jit(decode_scan,
                                             donate_argnums=(3, 4, 5))
        return fn

    def _spec_fn2(self, R: int, K: int, sampled: bool):
        """R speculative rounds fused into ONE dispatch (cached per
        (R, K, sampled); the speculative analogue of ``_decode_scan``).
        Each round: anchor from the held target logits, K draft
        proposals through the slot's draft table (the first
        ``draft.n_layers`` pool layers), ONE batched W = K+1-token
        target verification through the slot's block table, then the
        acceptance rule — the committed tokens stage into a [B, R*W]
        device buffer at each slot's running cursor, so the host
        unpacks exactly the PR 5 way (``toks_h[slot, :emitted]``).

        Masking: a round's writes past a slot's remaining budget land
        in the scratch block 0 with embed positions clamped to 0 (the
        PR 2 OOB-positional NaN class), and rejected-suffix rows roll
        back by ``pos`` simply not advancing over them — the blocks
        were claimed at admission, so the next round overwrites in
        place.

        ``K`` is the dispatch depth (the pool max of the per-slot
        depths, ISSUE 20) and a per-slot ``kcap`` [B] operand masks
        each slot's proposals down to ITS depth.  With
        ``sampled=True`` temperature>0 rows ride the same flat-row
        verify through Leviathan rejection resampling:

        * the anchor of a sampled row is drawn from the slot's held
          distribution (its own temperature/top-k/top-p shaping, or
          the RAW residual when ``rawlg`` marks one held),
        * draft proposals are drawn from the DRAFT's identically
          filtered distribution (the rule requires q, the draft's
          actual sampling distribution — argmax proposals would make
          ``p/q`` ill-defined),
        * proposal i commits iff ``u_i < p_target(x_i)/p_draft(x_i)``
          and every earlier proposal committed
          (:func:`speculative.accept_mixed`; greedy rows run the
          UNCHANGED greedy rule through the same call, which is what
          keeps them byte-identical to non-spec decode in a mixed
          pool),
        * a genuine rejection holds the normalized residual
          ``max(0, p - q)`` as the slot's next-anchor distribution
          (``rawlg`` set; consumed by the next round's anchor or, on
          fallback to the plain scan, by ``pick_sampled``).

        Per-round PRNG: each active slot's stream splits ONCE, and
        every consumer (anchor, draft step j, acceptance uniforms)
        folds a fixed tag into the round key — so a slot's token
        sequence depends only on its seed and its own acceptance
        history, invariant to R batching and pool composition.

        Returns ``(kc, vc, state, polled)``, ``polled`` [B, R*W + 4]
        as ``_decode_scan`` packs it with two more columns, ``proposed``
        and ``accepted`` PER SLOT: the host attributes them to tenants,
        feeds the acceptance controller and sums them into the
        ``generation_server_spec_*`` counters."""
        key = ("spec", int(R), int(K), bool(sampled))
        fn = self._scan_cache.get(key)
        if fn is not None:
            return fn
        gen = self._gen
        spec = self._spec
        dgen = spec.draft.gen
        d = spec.draft.n_layers
        W = K + 1
        bs = self.block_size
        B = self.n_slots
        shard = self._shard

        def fold_rows(keys, tag):
            return jax.vmap(jax.random.fold_in,
                            in_axes=(0, None))(keys, tag)

        def spec_fn(emb_p, blk_stack, head_p, demb_p, dblk, dhead_p,
                    kc, vc, state, kcap):
            dblk = jax.tree_util.tree_map(lambda a: a[:d], dblk)
            jidx = jnp.arange(W)[None, :]

            def round_body(carry, _):
                kc, vc, state, staged, emitted, prop, acc = carry
                active = state["remaining"] > 0
                pos, rem = state["pos"], state["remaining"]
                tbl, dtbl = state["table"], state["dtable"]
                temp, tk, tp = state["temp"], state["tk"], state["tp"]
                greedy_row = temp <= 0.0
                g_anchor = jnp.argmax(state["logits"], axis=-1)
                if sampled:
                    both = jax.vmap(jax.random.split)(state["key"])
                    newk = jnp.where(active[:, None], both[:, 0],
                                     state["key"])
                    rkey = both[:, 1]
                    safe = jnp.where(temp > 0.0, temp, 1.0)
                    tflt = _filter_logits_rows(
                        state["logits"] / safe[:, None], tk, tp)
                    # a held residual is ALREADY the distribution to
                    # draw from — re-shaping it would break exactness
                    alg = jnp.where(state["rawlg"][:, None],
                                    state["logits"], tflt)
                    cand = jax.vmap(jax.random.categorical)(
                        fold_rows(rkey, 0), alg)
                    anchor = jnp.where(greedy_row, g_anchor, cand)
                else:
                    newk, rkey = state["key"], state["key"]
                    anchor = g_anchor
                anchor = jnp.where(active, anchor, 0).astype(jnp.int32)

                # -- draft: K proposals through the draft table.  The
                # scan runs W = K+1 consume steps, not K: step j
                # consumes chunk token v_j at pos+j (writing its draft
                # KV) and proposes v_{j+1}.  The LAST step's proposal
                # is discarded, but its WRITE matters — on a full
                # accept the round advances pos over v_K, and a draft
                # row never consumed would leave a hole in the draft's
                # context that degrades every later round's proposals
                # (measured: full-depth self-draft acceptance fell to
                # 2/3 without it; 1.0 with it).
                kcd, vcd = kc[:d], vc[:d]

                def dstep(c, j):
                    kcd, vcd, tok = c
                    ok = active & (j < rem)
                    p = jnp.where(ok, pos + j, 0)
                    bidx = jnp.take_along_axis(
                        dtbl, (p // bs)[:, None], axis=1)[:, 0]
                    wblk = jnp.where(ok, bidx, 0)
                    woff = jnp.where(ok, p % bs, 0)
                    lg, kcd, vcd, _ = dgen._step_paged(
                        demb_p, dblk, dhead_p, kcd, vcd, tok, p,
                        dtbl, wblk, woff, shard=shard)
                    if sampled:
                        dlp = _filtered_logprobs_rows(lg, temp, tk, tp)
                        dcand = jax.vmap(jax.random.categorical)(
                            fold_rows(rkey, j + 1), dlp)
                        nxt = jnp.where(greedy_row,
                                        jnp.argmax(lg, axis=-1), dcand)
                    else:
                        dlp = jnp.zeros((), jnp.float32)
                        nxt = jnp.argmax(lg, axis=-1)
                    nxt = jnp.where(ok, nxt, 0).astype(jnp.int32)
                    return (kcd, vcd, nxt), (tok, dlp)

                (kcd, vcd, _), (consumed, dlps) = jax.lax.scan(
                    dstep, (kcd, vcd, anchor), jnp.arange(W))
                kc = kc.at[:d].set(kcd)
                vc = vc.at[:d].set(vcd)
                v = consumed.T                            # [B, W]

                # -- verify: one batched W-token target pass (the
                # flat-row path greedy parity rides on)
                okv = active[:, None] & (jidx < rem[:, None])
                p = pos[:, None] + jidx
                epos = jnp.where(okv, p, 0)
                vtok = jnp.where(okv, v, 0)
                bidx = jnp.take_along_axis(
                    tbl, jnp.where(okv, p // bs, 0), axis=1)
                wblk = jnp.where(okv, bidx, 0)
                woff = jnp.where(okv, p % bs, 0)
                pos0 = jnp.where(active, pos, 0)
                G, kc, vc, _ = gen._verify_rows_paged(
                    emb_p, blk_stack, head_p, kc, vc, vtok, pos0,
                    epos, tbl, wblk, woff, shard=shard)
                g = jnp.argmax(G, axis=-1).astype(jnp.int32)

                if sampled:
                    # target's FILTERED log-dist at each proposal's
                    # position: G_j is the target after consuming v_j
                    # — the dist proposal v_{j+1} is judged against
                    Pfull = jax.vmap(
                        lambda Gj: _filtered_logprobs_rows(
                            Gj, temp, tk, tp),
                        in_axes=1, out_axes=1)(G[:, :K])
                    Qfull = jnp.swapaxes(dlps[:K], 0, 1)  # [B, K, V]
                    ptok = v[:, 1:, None]
                    logp = jnp.take_along_axis(Pfull, ptok,
                                               axis=2)[..., 0]
                    logq = jnp.take_along_axis(Qfull, ptok,
                                               axis=2)[..., 0]
                    u = jax.vmap(
                        lambda k: jax.random.uniform(k, (K,)))(
                        fold_rows(rkey, W + 1))
                    c, rem_after, n_eval, rej = \
                        _speculative.accept_mixed(
                            greedy_row, v, g, logp, logq, u, active,
                            rem, state["eos"], kcap=kcap)
                else:
                    c, rem_after = _speculative.accept_greedy(
                        v, g, active, rem, state["eos"], kcap=kcap)
                    n_eval = jnp.minimum(
                        jnp.clip(jnp.minimum(K, rem - 1), 0, K),
                        jnp.clip(kcap, 0, K))
                    n_eval = jnp.where(active, n_eval,
                                       0).astype(jnp.int32)
                    rej = jnp.zeros((B,), jnp.bool_)

                sel = jnp.maximum(c - 1, 0)
                base = G[jnp.arange(B), sel]
                if sampled:
                    ridx = jnp.clip(c - 1, 0, K - 1)
                    Prow = Pfull[jnp.arange(B), ridx]
                    Qrow = Qfull[jnp.arange(B), ridx]
                    res = _speculative.residual_logits(Prow, Qrow)
                    # clamp the residual's -inf zeros to a finite
                    # floor: exp(-1e30) is exactly 0 in f32 (same
                    # draw), but the watchdog's finiteness screen and
                    # the sanitizer would read -inf rows as poisoned
                    res = jnp.maximum(res, jnp.float32(-1e30))
                    new_logits = jnp.where(rej[:, None], res, base)
                    new_rawlg = jnp.where(active, rej, state["rawlg"])
                else:
                    new_logits = base
                    new_rawlg = state["rawlg"]
                state = {
                    "pos": jnp.where(active, pos + c, pos),
                    "remaining": jnp.where(active, rem_after, rem),
                    "eos": state["eos"],
                    "logits": jnp.where(active[:, None], new_logits,
                                        state["logits"]),
                    "key": newk,
                    "temp": temp,
                    "tk": tk,
                    "tp": tp,
                    "table": tbl,
                    "dtable": dtbl,
                    "rawlg": new_rawlg,
                }
                # -- stage the commits at each slot's cursor (the
                # [B, K]-buffer idiom from PR 5, cursor-scattered;
                # uncommitted columns dump into the extra column)
                rows = jnp.arange(B)[:, None]
                keep = active[:, None] & (jidx < c[:, None])
                cols = jnp.where(keep, emitted[:, None] + jidx, R * W)
                staged = staged.at[rows, cols].set(v)
                emitted = emitted + c
                # per-slot tallies of proposals that COULD commit
                # (n_eval: at most remaining-1 beyond the anchor — the
                # draft's tail past a slot's budget is masked garbage,
                # not a real proposal — and at most kcap); when a
                # committed EOS ended the stream (rem_after 0 with
                # budget left) everything behind the cut was flushed,
                # not rejected — so a perfect draft scores acceptance
                # exactly 1.0 through budget tails AND EOS-terminated
                # requests
                prop_i = jnp.where((rem_after == 0) & (c < rem),
                                   jnp.maximum(c - 1, 0), n_eval)
                prop = prop + jnp.where(active, prop_i, 0)
                acc = acc + jnp.maximum(c - 1, 0)
                return (kc, vc, state, staged, emitted, prop, acc), \
                    None

            staged0 = jnp.zeros((B, R * W + 1), jnp.int32)
            emitted0 = jnp.zeros((B,), jnp.int32)
            zeros_b = jnp.zeros((B,), jnp.int32)
            (kc, vc, state, staged, emitted, prop, acc), _ = \
                jax.lax.scan(round_body,
                             (kc, vc, state, staged0, emitted0,
                              zeros_b, zeros_b),
                             None, length=R)
            return kc, vc, state, _pack_polled(
                staged[:, :R * W], emitted, state["remaining"], prop,
                acc)

        fn = self._scan_cache[key] = jax.jit(spec_fn,
                                             donate_argnums=(6, 7, 8))
        return fn

    def _scatter_rows(self, pool, rows, phys):
        """Scatter prefill K/V rows into pool blocks: ``rows``
        [n_rows_layers, 1, h, T, dh] with T a block-size multiple,
        ``phys`` [T // block_size] int32 physical block ids (entries
        past the slot's allocation point at the scratch block 0 — pad
        rows land there harmlessly).  Writes the LEADING
        ``rows.shape[0]`` pool layers, so the target path (all layers)
        and the draft path (the draft's first d layers; the rest of a
        draft block stays zero, never read) share this."""
        bs = self.block_size
        nl, _, h, T, dh = rows.shape
        blocks = rows[:, 0].reshape(nl, h, T // bs, bs, dh) \
                           .transpose(0, 2, 1, 3, 4)
        return pool.at[:nl, phys].set(
            paged_pool_rows(blocks, pool.shape[2], pool.shape[-1]))

    def _block_to_host(self, pool, blk: int, dim: int):
        """Pool block ``blk`` of every layer as host bytes
        [layers, h, block_size, dim] — what the host tier and a prefix
        handoff carry, whatever the pool's rows hold here or there
        (``dim``: the keys' width for the K pool, the values' for V)."""
        return np.asarray(paged_head_rows(pool[:, blk], self._gen.kv_heads,
                                          dim))

    def _gather_rows(self, pool, phys, dim: int):
        """``_scatter_rows`` back: the pool blocks ``phys`` [n] of
        every layer of ``pool`` as K/V rows [layers, 1, h,
        n * block_size, dim], each head's own (``dim`` as
        ``_block_to_host`` takes it)."""
        nl, _, _, bs, _ = pool.shape
        h = self._gen.kv_heads
        blocks = paged_head_rows(jnp.take(pool, phys, axis=1), h, dim)
        return blocks.transpose(0, 2, 1, 3, 4) \
            .reshape(nl, 1, h, phys.shape[0] * bs, dim)

    def _arm_slot(self, state, logits, slot, t0, n_new, eos_id, key,
                  temp, tk, tp, table_row, dtable_row, rec=None):
        """Slot device-state update shared by both admit programs.
        ``rec`` (a stack with recurrent layers): the prefill's recurrent
        state of the one admitted row, as after its last real token."""
        armed = self._rec_of(state)
        if rec is not None:
            armed = {k: self._arm_leaf(k, all_rows, rec[k], slot)
                     for k, all_rows in armed.items()}
        return self._with_rec({
            "pos": state["pos"].at[slot].set(t0),
            "remaining": state["remaining"].at[slot].set(n_new),
            "eos": state["eos"].at[slot].set(eos_id),
            "logits": jax.lax.dynamic_update_slice(
                state["logits"], logits, (slot, 0)),
            "key": jax.lax.dynamic_update_slice(
                state["key"], key[None], (slot, 0)),
            "temp": state["temp"].at[slot].set(temp),
            "tk": state["tk"].at[slot].set(tk),
            "tp": state["tp"].at[slot].set(tp),
            "table": jax.lax.dynamic_update_slice(
                state["table"], table_row[None], (slot, 0)),
            "dtable": jax.lax.dynamic_update_slice(
                state["dtable"], dtable_row[None], (slot, 0)),
            "rawlg": state["rawlg"].at[slot].set(False),
        }, armed)

    def _arm_leaf(self, name: str, all_rows, row, slot):
        """One leaf of the generator's ``rec`` with the admitted row's
        in place: a recurrent leaf's row ``slot``; a window ring [layers,
        1, kv heads, window, dim] cut into the slot's own blocks, as
        the ring's pool holds rows; the routed tally, which the prefill
        adds to."""
        if name in ("routed", "reached"):
            return all_rows + row
        if name in self._WIN_KEYS:
            layers, _, h, window, _ = row.shape
            bs = self.block_size
            wb = self._gen.window_blocks(bs)
            ring = jnp.pad(row[:, 0], ((0, 0), (0, 0),
                                       (0, wb * bs - window), (0, 0)))
            blocks = paged_pool_rows(
                ring.reshape(layers, h, wb, bs, -1).transpose(0, 2, 1, 3, 4),
                all_rows.shape[2], all_rows.shape[-1])
            return jax.lax.dynamic_update_slice(
                all_rows, blocks.astype(all_rows.dtype),
                (0, 1 + slot * wb, 0, 0, 0))
        return jax.lax.dynamic_update_slice(
            all_rows, row.astype(all_rows.dtype), (0, slot, 0, 0))

    def _admit_miss_fn(self, tb: int, use_draft: bool = True):
        """Prefix-MISS admission program for prefill bucket ``tb`` (a
        block-size multiple; cached per bucket): batched causal
        prefill of the padded prompt — the SAME prefill numerics
        offline decode runs, parity depends on it — with the K/V rows
        scattered into the slot's fresh blocks and its table armed.
        ``use_draft=False`` traces the draft-free variant a
        speculative server uses for prefill-ONLY admissions (no draft
        table is claimed, so there is nothing to prefill)."""
        key = ("miss", tb, bool(use_draft))
        if key in self._admit_cache:
            return self._admit_cache[key]
        gen = self._gen
        spec = self._spec if use_draft else None
        shard = self._shard
        n_sc, mb = tb // self.block_size, self.max_blocks

        def admit_miss(emb_p, blk_stack, head_p, kc, vc, state, ops,
                       *more):
            if ops.ndim == 1:
                # everything the host gives an admission, packed
                # (``_admit`` is the other end of this order); then
                # the draft's params
                head, (prompt, phys, table_row, dtable_row, dphys) = \
                    _unpack_admission(ops, tb, n_sc, mb, mb,
                                      n_sc if spec is not None else 0)
                prompt, draft_params = prompt[None], more
            else:
                # the operands one by one — [1, tb] prompt, t0, slot,
                # n_new, eos_id, key, temp, tk, tp, phys, the two table
                # rows — as tests/benchmark_suite, frozen with the
                # benchmark, drives this program by hand (no scheduler
                # path: PERF.md section 7)
                (t0, slot, *head), (phys, table_row, dtable_row) = \
                    more[:8], more[8:]
                prompt, head = ops, (slot, t0, *head)
            t0 = head[1]
            # t0 picks the last REAL position's logits out of the
            # padded bucket (and, of a recurrent layer, the state as
            # after that position)
            logits, ks, vs, rec = gen._prefill_rows(
                emb_p, blk_stack, head_p, prompt, t0, shard=shard)
            kc = self._scatter_rows(kc, ks, phys)
            vc = self._scatter_rows(vc, vs, phys)
            if spec is not None:
                # draft prefill over the SAME padded prompt: the
                # draft's KV must cover the whole context before it
                # can propose (its logits are discarded — rounds
                # re-feed from the anchor).  In-trace layer slice: a
                # self-draft's operand is the target stack verbatim.
                demb_p, dblk, dhead_p = draft_params
                dblk = jax.tree_util.tree_map(
                    lambda a: a[:spec.draft.n_layers], dblk)
                _, dks, dvs, _ = spec.draft.gen._prefill_rows(
                    demb_p, dblk, dhead_p, prompt, t0, shard=shard)
                kc = self._scatter_rows(kc, dks, dphys)
                vc = self._scatter_rows(vc, dvs, dphys)
            state = self._arm_slot(state, logits, *head, table_row,
                                   dtable_row, rec)
            return kc, vc, state

        fn = self._admit_cache[key] = jax.jit(admit_miss,
                                              donate_argnums=(3, 4, 5))
        return fn

    def _admit_hit_fn(self, sb: int, matched: int, dtb: int = 0,
                      nfill: int = 0, use_draft: bool = True,
                      dmatched: int = 0, dsb: int = 0):
        """Prefix-HIT admission program (cached per (suffix bucket,
        matched blocks, draft bucket, tier fills)): gather the
        ``matched`` cached blocks as the key prefix, chunked-prefill
        ONLY the suffix, scatter the suffix K/V into the slot's fresh
        blocks.  The prefix gather is EXACT-length — padding inside
        the key axis would regroup XLA's softmax/matmul reductions and
        break byte parity with the full-prompt prefill, so ``matched``
        is a compile-key dimension (bounded by max_blocks) instead of
        a padded pow2.

        ``nfill`` > 0 restores that many host-tier blocks FIRST: the
        spilled bytes ride in as ONE stacked operand pair (the single
        batched H2D the tier exists for) and scatter into their
        claimed pool blocks before the gather reads them — so a
        tier-restored prefix is bit-identical to a device-resident
        one, and byte parity holds through the spill→fetch round
        trip.

        With speculation on, the DRAFT prefills too — over the FULL
        prompt at its own pow2 bucket ``dtb`` on a draft-cache miss,
        or (``dmatched`` > 0, ISSUE 20) chunked over only the suffix
        past its ``dmatched`` cached blocks (bucket ``dsb``), with
        the draft prefix gathered from the pool's first d layers the
        same way the target's is — so a warm prefix costs d cheap
        layers over the suffix instead of over the whole prompt."""
        key = ("hit", sb, matched, dtb, nfill, bool(use_draft),
               dmatched, dsb)
        if key in self._admit_cache:
            return self._admit_cache[key]
        gen = self._gen
        spec = self._spec if use_draft else None
        shard = self._shard
        bs, mb = self.block_size, self.max_blocks
        p0 = matched * bs
        # the draft's rows (speculative only): its suffix past
        # ``dmatched`` cached blocks, or the whole prompt at ``dtb``
        dm = dmatched if spec is not None else 0
        dlen = (dsb if dm else dtb) if spec is not None else 0

        def admit_hit(emb_p, blk_stack, head_p, kc, vc, state, ops,
                      *extra_ops):
            # ``ops``: everything the host gives an admission, packed
            # (``_admit`` is the other end of this order); a host-tier
            # restore's K/V payloads are data and ride beside it
            (head, (suffix, prefix_phys, phys, table_row, dtable_row,
                    fill_ids, dtokens, dprefix_phys, dphys)) = \
                _unpack_admission(ops, sb, matched, sb // bs, mb, mb,
                                  nfill, dlen, dm, dlen // bs)
            suffix, t0 = suffix[None], head[1]
            if nfill:
                # host-tier restore: land the spilled bytes in their
                # claimed pool blocks BEFORE the prefix gather below
                # reads them (one fused scatter per cache side)
                fill_k, fill_v = extra_ops[:2]
                draft_params = extra_ops[2:]
                kc = kc.at[:, fill_ids].set(
                    paged_pool_rows(fill_k, kc.shape[2], kc.shape[-1]))
                vc = vc.at[:, fill_ids].set(
                    paged_pool_rows(fill_v, vc.shape[2], vc.shape[-1]))
            else:
                draft_params = extra_ops
            pk = self._gather_rows(kc, prefix_phys, gen.qk_dim)
            pv = self._gather_rows(vc, prefix_phys, gen.v_dim)
            logits, ks, vs, rec = gen._prefill_rows_chunked(
                emb_p, blk_stack, head_p, suffix, pk, pv,
                jnp.int32(p0), t0 - p0 - 1, shard=shard)
            kc = self._scatter_rows(kc, ks, phys)
            vc = self._scatter_rows(vc, vs, phys)
            if spec is not None:
                dl = spec.draft.n_layers
                demb_p, dblk, dhead_p = draft_params
                dblk = jax.tree_util.tree_map(lambda a: a[:dl], dblk)
                if dm:
                    # draft-cache HIT: gather the draft prefix out of
                    # the pool's first d layers, chunk-prefill only
                    # the draft suffix (logits discarded — rounds
                    # re-feed from the anchor)
                    dpk = self._gather_rows(kc[:dl], dprefix_phys,
                                            gen.qk_dim)
                    dpv = self._gather_rows(vc[:dl], dprefix_phys,
                                            gen.v_dim)
                    dp0 = dm * bs
                    _, dks, dvs, _ = spec.draft.gen._prefill_rows_chunked(
                        demb_p, dblk, dhead_p, dtokens[None], dpk, dpv,
                        jnp.int32(dp0), t0 - dp0 - 1, shard=shard)
                else:
                    _, dks, dvs, _ = spec.draft.gen._prefill_rows(
                        demb_p, dblk, dhead_p, dtokens[None], t0,
                        shard=shard)
                kc = self._scatter_rows(kc, dks, dphys)
                vc = self._scatter_rows(vc, dvs, dphys)
            state = self._arm_slot(state, logits, *head, table_row,
                                   dtable_row, rec)
            return kc, vc, state

        fn = self._admit_cache[key] = jax.jit(admit_hit,
                                              donate_argnums=(3, 4, 5))
        return fn

    # -- scheduler -----------------------------------------------------
    # The two places the scheduler's thread moves data between host and
    # device, each an EXPLICIT transfer (under ``jax.transfer_guard``
    # nothing else on that thread may move any) counted by the site it
    # serves — next to where that site counts its dispatch, so a scrape
    # between the two is rare.
    @staticmethod
    def _to_device(site: str, host_array: np.ndarray):
        on_device = jax.device_put(host_array)
        _MOVED[site, "h2d"].inc()
        return on_device

    @staticmethod
    def _from_device(site: str, device_array) -> np.ndarray:
        _MOVED[site, "d2h"].inc()
        return jax.device_get(device_array)

    def _admit(self, req: _Pending, slot: int, plan: _AdmitPlan,
               my_epoch: int) -> bool:
        """Prefill dispatch + commit; returns False when a watchdog
        recovery superseded this scheduler mid-admission (the caller
        must exit without touching shared state — the recovery already
        reconciled the allocator off ``_slot_blocks``)."""
        bs = self.block_size
        matched = plan.matched
        p0 = matched * bs
        # prefill-only admissions skip the draft entirely (no dtable
        # blocks were claimed — plan.dphys is empty)
        use_draft = self._spec is not None and not req.prefill_only

        def padded(tokens, n):
            row = np.zeros((n,), np.int32)
            row[:len(tokens)] = tokens
            return row

        def bucket(n):
            """Prefill length for ``n`` tokens: the next power of two,
            in whole blocks."""
            return -(-_bucket(n, self.max_len) // bs) * bs

        # snapshot the pool atomically: a concurrent watchdog recovery
        # swaps all three together, and a torn read would scatter this
        # prefill into a mixed old/new pool
        with self._lock:
            kc, vc, state = self._kc, self._vc, self._state
        _sanitize.check_not_donated("serve/admit", kc, vc, state)
        # the packed operands' rows, in the order the admit programs
        # cut them (scatter targets past the slot's allocation stay 0,
        # the scratch block); then the payloads and the draft's params
        table_rows = (padded(plan.phys, self.max_blocks),
                      padded(plan.dphys, self.max_blocks))
        draft_params = self._draft_params if use_draft else ()
        if matched:
            # prefix HIT: gather the cached blocks, prefill only the
            # suffix — scatter targets start at the first fresh block
            suffix = req.prompt[p0:]
            sb = bucket(len(suffix))
            n_real, n_pad = len(suffix), sb - len(suffix)
            dmatched = plan.dmatched if use_draft else 0
            # the draft (ISSUE 20) chunk-prefills only the suffix past
            # ITS cached blocks, or on a draft-cache miss the whole
            # prompt at its own bucket
            dtokens = req.prompt[dmatched * bs:] if use_draft else ()
            dlen = bucket(len(dtokens)) if use_draft else 0
            dtb, dsb = (0, dlen) if dmatched else (dlen, 0)
            nfill = len(plan.fills)
            fn = self._admit_hit_fn(sb, matched, dtb, nfill, use_draft,
                                    dmatched, dsb)
            rows = (padded(suffix, sb),
                    np.asarray(plan.phys[:matched], np.int32),
                    padded(plan.phys[matched:matched + sb // bs],
                           sb // bs),
                    *table_rows,
                    # host-tier restore: the claimed blocks' ids here,
                    # their bytes as ONE stacked payload per cache side
                    np.asarray(
                        plan.phys[plan.reg_from:plan.reg_from + nfill],
                        np.int32),
                    padded(dtokens, dlen),
                    np.asarray(plan.dphys[:dmatched], np.int32),
                    padded(plan.dphys[dmatched:dmatched + dlen // bs],
                           dlen // bs))
            payloads = tuple(
                self._to_device("admit", np.stack(
                    [f[side] for f in plan.fills], axis=1))
                for side in ((0, 1) if nfill else ()))
        else:
            tb = bucket(req.t0)
            n_real, n_pad = req.t0, tb - req.t0
            fn = self._admit_miss_fn(tb, use_draft)
            # miss path: the draft shares the target's padded prompt
            rows = (padded(req.prompt, tb),
                    padded(plan.phys[:tb // bs], tb // bs),
                    *table_rows,
                    padded(plan.dphys[:tb // bs],
                           tb // bs if use_draft else 0))
            payloads = ()
        _PREFILL_REAL.inc(n_real)
        _PREFILL_PAD.inc(n_pad)
        for route, n in self._gen.prefill_routes(n_real + n_pad,
                                                 self._shard).items():
            _PREFILL_ROUTE[route].inc(n)
        ops = self._to_device("admit", _pack_admission(req, slot, *rows))
        _DISPATCHED["admit"].inc()
        # ledger-mark BEFORE the donating dispatch (a host-side weakref
        # record, not a buffer read): no name outlives its donation
        _sanitize.mark_donated("serve/admit", kc, vc, state)
        out = fn(*self._params, kc, vc, state, ops, *payloads,
                 *draft_params)
        with self._lock:
            if self._epoch != my_epoch:
                return False
            self._kc, self._vc, self._state = out
            self._staged.discard(slot)   # prefill committed: device
                                         # rows are THIS request's now
            # _ids row under the same lock: _retire copies from it
            self._ids[slot, :req.t0] = req.prompt
            if self.prefix_cache:
                self._register_prefix_locked(plan)
                if use_draft and plan.dphys:
                    self._register_draft_prefix_locked(plan)
            if matched:
                self._n_prefix_hits += 1
            else:
                self._n_prefix_misses += 1
            n_fills = len(plan.fills)
            if n_fills:
                self._n_tier_fetches += n_fills
                self._n_tier_hits += 1
                if self._tier is not None:
                    # LRU touch at COMMIT, not plan time (peek above)
                    for j in range(plan.reg_from,
                                   plan.reg_from + n_fills):
                        self._tier.touch(plan.hashes[j][0])
        _ADMITTED.inc()
        if self._gen.held_experts is not None:
            with self._lock:
                self._routed_admits += 1
        _FLIGHT.record("admit", slot=slot, trace=req.trace_id,
                       t0=req.t0, n_new=req.n_new, cached=matched,
                       tier_fills=n_fills,
                       prefill_only=bool(req.prefill_only))
        if n_fills:
            _FLIGHT.record("kv_fetch", slot=slot, blocks=n_fills)
        if matched:
            _PREFIX_HITS.inc()
            # device-map hits are COPY-FREE shares; tier restores are
            # counted as fetches, not shares
            if matched > n_fills:
                _KV_BLK_SHARED.inc(matched - n_fills)
            if n_fills:
                _TIER_FETCHES.inc(n_fills)
                _TIER_HITS.inc()
        else:
            _PREFIX_MISSES.inc()
        if plan.n_fresh:
            _KV_BLK_ALLOC.inc(plan.n_fresh)
        self._update_free_gauge()
        return True

    def _count_routed(self, tally, ticks: int) -> None:
        """A decode scan's look at the routed layers: ``tally`` is the
        rows each held expert got, then every token-expert pair made,
        then the held experts that got a row (a layer a call), since the
        last scan -- over this scan's ``ticks`` and the admissions
        dispatched before it."""
        per_expert, pairs, reached = tally[:-2], int(tally[-2]), int(tally[-1])
        held = int(per_expert.sum())
        with self._lock:
            admissions, self._routed_admits = self._routed_admits, 0
        _EXPERT_HELD.inc(held)
        _EXPERT_ABSENT.inc(pairs - held)
        _EXPERT_REACHED.inc(reached)
        _EXPERT_CALLS.inc((ticks + admissions) * self._gen.routed_layers
                          * len(per_expert))
        if held:
            _EXPERT_LOAD.observe(float(per_expert.max())
                                 * len(per_expert) / held)

    def _retire(self, req: _Pending, slot: int, error=None):
        if error is not None:
            req._error = error
        else:
            with self._lock:
                req._result = self._ids[slot,
                                        :req.t0 + req.emitted].copy()
        # close every phase span the request still holds, on WHATEVER
        # thread retires it (scheduler, watchdog recovery, shutdown) —
        # recovered requests produce complete traces instead of
        # orphaned never-flushed spans
        if req._t_decode is not None and "decode" in req.spans:
            _PHASE.labels(phase="decode").observe(
                time.perf_counter() - req._t_decode)
        req.close_spans("ok" if error is None else type(error).__name__)
        _RETIRED.inc()
        _FLIGHT.record("retire", slot=slot, trace=req.trace_id,
                       emitted=req.emitted,
                       error=(None if error is None
                              else type(error).__name__))
        req._event.set()

    def _reap_pending_locked(self, now: float):
        """Drop cancelled / deadline-expired requests from the wait
        line (caller holds the lock); returns the victims to retire
        outside it."""
        keep, victims = [], []
        for req in self._pending:
            if req.cancelled:
                victims.append((req, "cancel"))
            elif req.deadline is not None and now > req.deadline:
                victims.append((req, "deadline"))
            else:
                keep.append(req)
        self._pending = keep
        return victims

    def _retire_reaped(self, victims):
        for req, why in victims:
            if why == "cancel":
                _CANCELLED.inc()
                self._retire(req, -1, error=CancelledError(
                    "generation request cancelled"))
            else:
                _DEADLINE_EXCEEDED.inc()
                self._retire(req, -1, error=DeadlineExceededError(
                    "generation request deadline elapsed before "
                    "completion"))

    def _superseded(self, my_epoch: int) -> bool:
        """True when a watchdog recovery bumped the epoch past this
        scheduler (locked read — the fence must not be torn)."""
        with self._lock:
            return self._epoch != my_epoch

    def _mark_tick(self, my_epoch: int, value) -> None:
        """Set/clear the in-flight dispatch record ``(epoch, started,
        k)``, but only while this scheduler still owns the epoch — a
        superseded thread must not clobber the live scheduler's
        stuck-tick timer.  ``k`` is the in-flight scan length: the
        watchdog scales its stuck-tick deadline by it, because a
        K-tick scan legitimately runs ~K x longer than one tick
        (admission dispatches mark k=1)."""
        with self._lock:
            if self._epoch == my_epoch:
                self._tick_started = value

    def _fail_all_in_flight(self, err) -> None:
        """Clear active + pending under the lock and fail every caller;
        the slot pool/free list resets to empty.  The SHUTDOWN teardown
        — recovery paths use :meth:`_recover_pool`, which salvages."""
        with self._lock:
            victims = list(self._active.values()) + list(self._pending)
            self._active.clear()
            self._staged.clear()
            self._pending = []
            self._free = list(range(self.n_slots - 1, -1, -1))
            for slot in list(self._slot_blocks):
                self._release_slot_blocks_locked(slot)
        for req in victims:
            self._retire(req, -1, error=err)
        self._update_free_gauge()
        _SLOTS_BUSY.set(0)
        _QDEPTH.set(self._queue.qsize())

    def _recover_pool(self, my_epoch: int, err,
                      implicated=frozenset()) -> bool:
        """Surgical pool recovery: salvage the KV rows + per-slot
        device state of active slots NOT implicated in the failure,
        rebuild the pool, scatter the salvaged rows back in, and fail
        ONLY the implicated slots — unaffected in-flight requests keep
        their slot, their emitted prefix and their PRNG stream, and
        complete without resubmission (byte-identical to offline
        ``generate()``: the salvaged rows are the exact KV bytes the
        uninterrupted decode would have read).

        A slot is implicated when (a) the caller names it (the
        admission dispatch that raised), (b) its held state is
        non-finite (the poisoned-slot class — decoding on from NaN
        logits would emit garbage), or (c) its request was cancelled /
        deadline-expired (being torn down anyway).  When any pool leaf
        was consumed by a donating dispatch that never returned (a real
        hung XLA program — ``is_deleted`` on TPU) nothing is
        recoverable and every active slot drops: the pre-salvage
        behavior, now the worst case instead of the only case.

        Queued-but-unadmitted requests are never touched: they hold no
        pool state and simply wait out the recovery.  Runs under the
        epoch-checked lock (PR 4 discipline); returns False when a
        concurrent recovery superseded ``my_epoch``."""
        to_fail = []
        n_blk_salvaged = n_blk_dropped = 0
        with self._lock:
            if self._epoch != my_epoch:
                return False
            kc, vc, state = self._kc, self._vc, self._state
            try:
                pool_alive = not any(
                    getattr(leaf, "is_deleted", lambda: False)()
                    for leaf in jax.tree_util.tree_leaves(
                        (kc, vc, state)))
                if pool_alive:
                    # trust-but-verify the salvage source, at BLOCK
                    # granularity: a non-finite pool block (the PR 2
                    # poisoned class) implicates exactly the slots
                    # whose tables reference it — not whole stripes.
                    # One device-side reduce + [n_blocks]/[B]
                    # transfers, not a full pool pull.
                    blk_fin = np.asarray(
                        jnp.isfinite(kc).all(axis=(0, 2, 3, 4))
                        & jnp.isfinite(vc).all(axis=(0, 2, 3, 4)))
                    log_fin = np.asarray(
                        jnp.isfinite(state["logits"]).all(axis=1))
                    # a slot's recurrent state is salvaged with its
                    # blocks; a non-finite one implicates the slot
                    for k in self._REC_KEYS:
                        if k in state:
                            log_fin = log_fin & np.asarray(jnp.isfinite(
                                state[k]).all(axis=(0, 2, 3)))
                    # and so does a non-finite block of its window ring
                    for k in self._WIN_KEYS:
                        if k in state:
                            blk = np.asarray(jnp.isfinite(
                                state[k]).all(axis=(0, 2, 3, 4)))[1:]
                            log_fin = log_fin & blk.reshape(
                                self.n_slots, -1).all(axis=1)
                    pos_h = np.asarray(state["pos"])
                    rem_h = np.asarray(state["remaining"])
            except (RuntimeError, ValueError):
                # a still-running donating dispatch consumed a buffer
                # between the is_deleted probe and the read (backends
                # honor donation eagerly; jax raises ValueError for a
                # deleted/donated buffer, same as the export_prefix
                # race): nothing is salvageable
                pool_alive = False
            now = time.monotonic()
            victims = {}                     # slot -> why
            if not pool_alive:
                for slot in self._active:
                    victims[slot] = "unrecoverable"
            else:
                for slot, req in self._active.items():
                    blocks = self._slot_blocks.get(slot, ())
                    if slot in implicated:
                        victims[slot] = "implicated"
                    elif slot in self._staged:
                        # staged into _active but its prefill never
                        # COMMITTED: its device rows are a previous
                        # occupant's leftovers — salvaging would
                        # retire it as "done" with garbage bytes.
                        # Fail retryably: no work was applied.
                        victims[slot] = "unadmitted"
                    elif req.cancelled:
                        victims[slot] = "cancelled"
                    elif req.deadline is not None and now > req.deadline:
                        victims[slot] = "deadline"
                    elif not (bool(log_fin[slot]) and
                              all(bool(blk_fin[b]) for b in blocks)):
                        victims[slot] = "poisoned"
                    elif pos_h[slot] == 0 and rem_h[slot] == 0:
                        # device-truth backstop for the same class on
                        # a never-used slot (prefill sets pos >= 1)
                        victims[slot] = "unadmitted"
            keep = sorted(s for s in self._active if s not in victims)
            # block accounting BEFORE any release/rebuild mutates the
            # allocator: dropped = used-before minus carried-over
            used_before = set(self._block_hash)
            for s in self._active:
                used_before.update(self._slot_blocks.get(s, ()))
            if pool_alive and keep:
                # block-granular salvage: keep exactly the kept slots'
                # blocks plus finite prefix-cache blocks (the cache
                # stays WARM across a recovery) and zero every other
                # block in one masked pass — the old arrays are read
                # eagerly (no donation), so this IS the gather + fresh
                # pool + scatter-back, fused.  Kept slots carry their
                # exact KV bytes, tables, positions, budgets and PRNG
                # streams.
                mask = np.zeros((self.n_slots,), bool)
                mask[keep] = True
                m = jnp.asarray(mask)
                # poisoned cache entries drop out of the map first
                bad_cached = [b for b in self._block_hash
                              if not bool(blk_fin[b])]
                for b in bad_cached:
                    hsh = self._block_hash.pop(b)
                    if b in self._draft_cached:
                        self._draft_cached.discard(b)
                        self._dprefix_map.pop(hsh, None)
                    else:
                        del self._prefix_map[hsh]
                    self._evictable.pop(b, None)
                    if self._block_ref[b] == 0:
                        self._blocks_free.append(b)
                bmask = np.zeros((self.kv_blocks + 1,), bool)
                for s in keep:
                    bmask[self._slot_blocks.get(s, ())] = True
                for b in self._block_hash:
                    bmask[b] = True
                try:
                    # ledger-checked read (DL4J_TPU_SANITIZE=donation):
                    # the salvage source must not be a buffer some
                    # dispatch already owns — the dynamic mirror of the
                    # is_deleted guard above.  SanitizerError is a
                    # RuntimeError: a tripped ledger (a stuck tick DID
                    # mark the pool before hanging) demotes to the
                    # drop-all rebuild below instead of killing the
                    # watchdog thread.
                    _sanitize.check_not_donated("serve/salvage", kc,
                                                vc, state)
                    bm = jnp.asarray(bmask)
                    keep_blk = bm[None, :, None, None, None]
                    self._kc = jnp.where(keep_blk, kc, 0)
                    self._vc = jnp.where(keep_blk, vc, 0)
                    self._state = {
                        "pos": jnp.where(m, state["pos"], 0),
                        "remaining": jnp.where(m, state["remaining"],
                                               0),
                        "eos": jnp.where(m, state["eos"], -1),
                        "logits": jnp.where(m[:, None],
                                            state["logits"], 0),
                        "key": jnp.where(m[:, None], state["key"], 0),
                        "temp": jnp.where(m, state["temp"], 0.0),
                        "tk": jnp.where(m, state["tk"], self._vocab),
                        "tp": jnp.where(m, state["tp"], 1.0),
                        "table": jnp.where(m[:, None], state["table"],
                                           0),
                        "dtable": jnp.where(m[:, None],
                                            state["dtable"], 0),
                        # a kept sampled slot's held RESIDUAL survives
                        # with its flag (finite by the -1e30 clamp, so
                        # log_fin kept it); victims reset to plain
                        "rawlg": jnp.where(m, state["rawlg"], False),
                        **{k: jnp.where(self._slot_mask(k, m), state[k],
                                        0)
                           for k in self._REC_KEYS + self._WIN_KEYS
                           if k in state},
                        # the tally is no slot's: it goes on
                        **{k: state[k] for k in ("routed", "reached")
                           if k in state},
                    }
                    n_blk_salvaged = int(bmask.sum())
                    n_blk_dropped = len(used_before
                                        - set(np.nonzero(bmask)[0]))
                except RuntimeError:
                    # consumed mid-rebuild: demote every kept slot to
                    # unrecoverable and fall back to the clean rebuild
                    for slot in keep:
                        victims[slot] = "unrecoverable"
                    keep = []
                    self._fresh_pool()
                    n_blk_salvaged, n_blk_dropped = 0, len(used_before)
            else:
                # nothing salvageable (or nothing active): clean
                # rebuild — the donating dispatch may have consumed
                # the old buffers (allocator + prefix cache reset with
                # it).  RLock: _fresh_pool's own commit nests inside
                # this epoch-checked section.
                self._fresh_pool()
                n_blk_dropped = len(used_before)
            for slot, why in victims.items():
                to_fail.append((self._active.pop(slot), why))
                # reconcile the allocator (no-op after a fresh rebuild:
                # _slot_blocks was reset wholesale)
                self._release_slot_blocks_locked(slot)
            self._staged.clear()         # every staged slot just fell
                                         # into victims["unadmitted"]
            self._free = [s for s in range(self.n_slots - 1, -1, -1)
                          if s not in self._active]
            n_active = len(self._active)
            n_pending = len(self._pending)
        if keep:
            _KV_SALVAGED.inc(len(keep))
        if to_fail:
            _KV_DROPPED.inc(len(to_fail))
        if n_blk_salvaged:
            _KV_BLK_SALVAGED.inc(n_blk_salvaged)
        if n_blk_dropped:
            _KV_BLK_DROPPED.inc(n_blk_dropped)
        self._update_free_gauge()
        log.warning("pool recovery: salvaged %d in-flight slot(s) %s "
                    "(%d block(s)), dropped %d (%s; %d block(s))",
                    len(keep), keep, n_blk_salvaged, len(to_fail),
                    ", ".join(why for _, why in to_fail) or "none",
                    n_blk_dropped)
        for req, why in to_fail:
            if why == "cancelled":
                _CANCELLED.inc()
                self._retire(req, -1, error=CancelledError(
                    "generation request cancelled"))
            elif why == "deadline":
                _DEADLINE_EXCEEDED.inc()
                self._retire(req, -1, error=DeadlineExceededError(
                    "generation request deadline elapsed before "
                    "completion"))
            else:
                self._retire(req, -1, error=err)
        _SLOTS_BUSY.set(n_active)
        _QDEPTH.set(n_pending + self._queue.qsize())
        return True

    def _run(self, my_epoch: int):
        tracer = telemetry.get_tracer()
        # the phase and tick spans' owner: this scheduler
        # INCARNATION (id, epoch), not the raw thread ident — idents
        # of dead threads are recycled, and the watchdog must never
        # flush an unrelated thread's spans
        phases = _SchedPhases(tracer, (id(self), my_epoch))
        try:
            self._run_loop(my_epoch, tracer, phases)
        finally:
            phases.close()    # whichever edge left the loop

    def _run_loop(self, my_epoch: int, tracer, phases: _SchedPhases):
        prof = telemetry.get_profiler()
        stop = False
        prefill_t0 = None   # a sampled admission's dispatch, until the
                            # next poll shows the device past it
        while True:
            with self._lock:
                if self._epoch != my_epoch:
                    return
                idle = not self._active and not self._pending
            # ingest: block only when idle, else drain without waiting
            if idle and not stop:
                phases.switch("idle")
                item = self._queue.get()
                if self._superseded(my_epoch):
                    # recovered past us while we slept: hand the item
                    # to the live scheduler (sentinels included)
                    self._queue.put(item)
                    return
                if item is None:
                    stop = True
                else:
                    with self._lock:
                        self._pending.append(item)
            # serve/admit: ingest, the admission block with its admit
            # dispatches, and the choice of the scan length that
            # depends on who was admitted — up to the scan's dispatch
            phases.switch("admit")
            while True:          # opportunistic drain (also ingests
                try:             # requests raced in behind a sentinel)
                    item = self._queue.get_nowait()
                except queue.Empty:
                    break
                if self._superseded(my_epoch):
                    self._queue.put(item)
                    return
                if item is None:
                    stop = True
                else:
                    with self._lock:
                        self._pending.append(item)
            # chaos site (post-ingest, pre-dispatch, OUTSIDE the inline
            # try): an exception here escapes the scheduler thread
            # entirely — the watchdog must notice the corpse, fail the
            # in-flight requests and restart the scheduler
            _faults.maybe_fail("serve_tick_fail")
            with self._lock:
                drain = self._drain
            if stop and not drain:
                self._fail_all_in_flight(
                    RuntimeError("GenerationServer shut down with the "
                                 "request in flight"))
                _QDEPTH.set(0)
                return
            if stop:             # drain mode: exit once everything ran
                with self._lock:
                    done = not self._active and not self._pending
                if done and self._queue.empty():
                    _SLOTS_BUSY.set(0)
                    _QDEPTH.set(0)
                    return
            try:
                admitting = None    # slot mid-prefill, for implication
                now = time.monotonic()
                with self._lock:
                    if self._epoch != my_epoch:
                        return
                    reaped = self._reap_pending_locked(now)
                    admits = []
                    while self._free and self._pending:
                        req = self._pending[0]
                        # BLOCKS are the scarce resource: when the pool
                        # cannot cover the head request it waits at the
                        # head of the line (FIFO — no starvation by
                        # smaller requests behind it); a retiring
                        # request frees blocks, not just its slot
                        plan = self._plan_admission_locked(req)
                        if plan is None:
                            break
                        self._pending.pop(0)
                        slot = self._free.pop()
                        # active BEFORE the prefill dispatch: if the
                        # watchdog takes over mid-admission the request
                        # must be in the set it fails over — staged
                        # until the prefill COMMITS, so the recovery
                        # fails it instead of salvaging the previous
                        # occupant's device rows as its result.  The
                        # block claim registers here too, so a
                        # recovery can reconcile the allocator.
                        self._active[slot] = req
                        self._staged.add(slot)
                        # the DRAFT's blocks release through the same
                        # ledger (never prefix-cached, so a retire
                        # sends them straight back to the free list)
                        self._slot_blocks[slot] = (list(plan.phys)
                                                   + list(plan.dphys))
                        admits.append((req, slot, plan))
                    n_pending = len(self._pending)
                    n_active = len(self._active)
                phases.note(n=len(admits))
                self._retire_reaped(reaped)
                for req, slot, plan in admits:
                    t_adm = time.perf_counter()
                    sp_q = req.spans.pop("queue", None)
                    if sp_q is not None:
                        sp_q.end(slot=slot)
                    _PHASE.labels(phase="queue").observe(
                        t_adm - req.t_submit)
                    targs = ({"trace": req.trace_id}
                             if req.trace_id is not None else {})
                    req.spans["prefill"] = tracer.begin(
                        "request/prefill", slot=slot,
                        cached_blocks=plan.matched, **targs)
                    self._mark_tick(my_epoch,
                                    (my_epoch, time.monotonic(), 1))
                    admitting = slot     # a raising prefill implicates
                    if prof.sampled("prefill", _PROFILE_PREFILL_EVERY) \
                            and prefill_t0 is None:
                        prefill_t0 = time.perf_counter()
                    committed = self._admit(req, slot, plan, my_epoch)
                    admitting = None     # only ITS slot in recovery
                    self._mark_tick(my_epoch, None)
                    if committed:
                        sp_p = req.spans.pop("prefill", None)
                        if sp_p is not None:
                            sp_p.end()
                        t_done = time.perf_counter()
                        _PHASE.labels(phase="prefill").observe(
                            t_done - t_adm)
                        if req.prefill_only:
                            # disagg prefill-only: the cached prefix
                            # blocks ARE the product — release the
                            # slot now (blocks park evictable for
                            # export/the next same-prefix admission)
                            # instead of letting a 0-budget slot ride
                            # a decode tick
                            with self._lock:
                                if self._epoch != my_epoch:
                                    return
                                del self._active[slot]
                                self._free.append(slot)
                                n_drained = \
                                    self._release_slot_blocks_locked(
                                        slot)
                                n_active = len(self._active)
                            if n_drained:
                                _KV_BLK_FREED.inc(n_drained)
                            self._update_free_gauge()
                            self._retire(req, slot)
                            continue
                        req._t_decode = t_done
                        req.spans["decode"] = tracer.begin(
                            "request/decode", slot=slot, **targs)
                    if not committed:
                        return
                _QDEPTH.set(n_pending + self._queue.qsize())
                _SLOTS_BUSY.set(n_active)
                if not n_active:
                    continue
                # adaptive scan length: single ticks while ANY request
                # is waiting for admission (a join never waits behind a
                # long scan — TTFT does not regress), else the largest
                # power-of-two <= the longest live budget, capped at
                # tick_batch (pow2 quantization bounds compiles at
                # log2(tick_batch) variants; the floor means trailing
                # drain scans never run ticks past every slot's
                # retirement)
                with self._lock:
                    if self._epoch != my_epoch:
                        return
                    live_items = list(self._active.items())
                    live = [r for _, r in live_items]
                    k_drain = max(r.n_new - r.emitted for r in live)
                    slots_h = np.fromiter(self._active, np.int64)
                    pos_h = np.fromiter(
                        (r.t0 + r.emitted for r in live), np.int64)
                    sampled = any(r.temperature > 0.0 for r in live)
                    spec_off = self._spec_off
                    draft_cap = self._draft_k_cap
                queue_busy = n_pending > 0 or not self._queue.empty()
                # speculative rounds serve MIXED pools (ISSUE 20):
                # greedy rows run the unchanged greedy acceptance,
                # sampled rows Leviathan rejection resampling — both
                # through one flat-row verify.  Only the degradation
                # ladder's ``spec_off`` rung suspends speculation
                # outright (no draft compute at all); the flag flips
                # back when the rung clears, and the only cost in
                # between is stale draft KV (a held residual survives
                # the fallback — ``rawlg`` rows sample it through the
                # plain scan's pick_sampled)
                use_spec = self._spec is not None and not spec_off
                kcap_arr = None
                if use_spec:
                    # per-slot draft depth: the acceptance
                    # controller's pick (adaptive) or the fixed k,
                    # both clamped by the degrade ladder's cap; the
                    # dispatch compiles at the pool max and a [B] kcap
                    # operand masks each slot down to its own depth
                    # (depths change per tick without recompiling)
                    kcap_arr = np.zeros((self.n_slots,), np.int32)
                    ctl = self._spec_ctl
                    for slot, r in live_items:
                        if self._spec.adaptive:
                            k_i = ctl.k_for((r.tenant, r.pkey),
                                            cap=draft_cap)
                        elif draft_cap is not None:
                            k_i = max(1, min(self._spec.k, draft_cap))
                        else:
                            k_i = self._spec.k
                        kcap_arr[slot] = k_i
                    K_disp = int(max(1, kcap_arr.max()))
                    # adaptive round count, the scan-length rule's
                    # analogue: a single round while admission is
                    # pending (a join waits at most one W-wide round
                    # — bounded TTFT cost), else pow2-quantized by
                    # the longest live budget (each round commits
                    # >= 1 token, so R <= k_drain never runs a round
                    # past every slot's retirement)
                    R = (1 if queue_busy
                         else min(self._spec.rounds,
                                  _pow2_floor(k_drain)))
                    k = R * (K_disp + 1)   # watchdog scale: the
                    # dispatch legitimately runs ~R draft scans + R
                    # W-wide verifications
                else:
                    k = (1 if queue_busy
                         else min(self.tick_batch, _pow2_floor(k_drain)))
                # serve/tick: serve/launch (the dispatch) -> serve/poll
                # (the one host read), then serve/retire
                with phases.tick(active=n_active, queued=n_pending,
                                 k=k, spec=int(use_spec)):
                    self._mark_tick(my_epoch,
                                    (my_epoch, time.monotonic(), k))
                    # chaos site: a hung dispatch — the host blocks in
                    # here past the (k-scaled) deadline and the
                    # watchdog takes over; on wake the epoch check
                    # fences us out
                    _faults.maybe_stall("serve_tick_stall")
                    # snapshot the pool atomically under the epoch
                    # check — a concurrent recovery swaps all three
                    # together, and a torn read would tick a mixed
                    # old/new pool
                    with self._lock:
                        if self._epoch != my_epoch:
                            return
                        kc_in, vc_in, state_in = (self._kc, self._vc,
                                                  self._state)
                    _sanitize.check_not_donated("serve/tick", kc_in,
                                                vc_in, state_in)
                    if use_spec:
                        fn = self._spec_fn2(R, K_disp, sampled)
                        operands = (*self._draft_params, kc_in, vc_in,
                                    state_in,
                                    self._to_device("scan", kcap_arr))
                        n_tok = R * (K_disp + 1)
                    else:
                        fn = self._decode_scan(k, sampled)
                        operands = (kc_in, vc_in, state_in)
                        n_tok = k
                    # device-phase sample (ISSUE 13): dispatch ->
                    # host-sync is the device time of this tick; the
                    # site already syncs (the poll), so the continuous
                    # profile costs one perf_counter pair
                    with prof.measure("verify" if use_spec
                                      else "decode_tick",
                                      devices=self._device_labels):
                        # the dispatch call, and the packed result's
                        # copy to the host started behind it — before
                        # the host asks
                        kc, vc, state, polled = fn(
                            *self._params, *operands)
                        polled.copy_to_host_async()
                        _sanitize.mark_donated("serve/tick", kc_in,
                                               vc_in, state_in)
                        _DISPATCHED["scan"].inc()
                        # THE host sync, one read per dispatch of either
                        # kind — tokens staged device-side, per-slot
                        # live-tick counts, budgets left (a speculative
                        # round: its per-slot tallies too, so the host
                        # can attribute acceptance to tenants and feed
                        # the controller)
                        phases.switch("poll")
                        polled_h = self._from_device("scan", polled)
                # serve/retire: unpack the poll, retire, free blocks
                if prefill_t0 is not None:
                    # the device is past every admission dispatched
                    # before this scan: the sampled one's clock ends
                    for dev in self._device_labels or (None,):
                        prof.observe(
                            "prefill",
                            time.perf_counter() - prefill_t0,
                            device=dev)
                    prefill_t0 = None
                routed_h = None
                if len(polled_h) > self.n_slots:
                    # below the slots' rows: the routed layers' tally
                    routed_h = polled_h[self.n_slots:].reshape(-1)[
                        :self._gen.held_experts + 2]
                    polled_h = polled_h[:self.n_slots]
                toks_h = polled_h[:, :n_tok]
                emit_h, rem_h = (polled_h[:, n_tok],
                                 polled_h[:, n_tok + 1])
                alive_h = int(np.count_nonzero(rem_h))
                prop_h = acc_h = None
                n_prop = n_acc = 0
                if use_spec:
                    prop_h, acc_h = (polled_h[:, n_tok + 2],
                                     polled_h[:, n_tok + 3])
                    n_prop = int(prop_h.sum())
                    n_acc = int(acc_h.sum())
                _HOST_SYNCS.inc()
                self._mark_tick(my_epoch, None)
                n_emitted = int(emit_h.sum())
                stall = phases.landed() if n_emitted else None
                if stall is not None:
                    gap, split = stall
                    _STALLS.inc()
                    for phase, secs in split.items():
                        _STALL_SECONDS.labels(phase=phase).inc(secs)
                    _FLIGHT.record("stall", seconds=gap, k=k,
                                   active=n_active, **split)
                # device-truth occupancy at scan end (the host view is
                # reconciled below after retire/cancel bookkeeping)
                _SLOTS_BUSY.set(alive_h)
                if _sanitize.active("nan"):
                    # the decode-tick finite check (the PR 2 poisoned-
                    # slot bug class): only ACTIVE slots' held logits
                    # must be finite — free slots park stale garbage
                    with self._lock:
                        mask = np.zeros((self.n_slots,), bool)
                        for s in self._active:
                            mask[s] = True
                    _sanitize.check_finite_rows(
                        "serve/tick logits", np.asarray(state["logits"]),
                        mask, detail="slot KV cache poisoned?")
                if use_spec:
                    # one verification pass per round is the
                    # expensive target "tick"; the k label marks the
                    # dispatch shape (R rounds x W-wide verify)
                    _TICKS.inc(R)
                    _SCANS.labels(
                        k=f"spec{R}x{K_disp + 1}").inc()
                    _SPEC_ADAPTIVE_K.set(K_disp)
                    if n_prop:
                        _SPEC_PROPOSED.inc(n_prop)
                    if n_acc:
                        _SPEC_ACCEPTED.inc(n_acc)
                    tenant_rows, obs = [], []
                    with self._lock:
                        self._n_spec_proposed += n_prop
                        self._n_spec_accepted += n_acc
                        if self._n_spec_proposed:
                            _SPEC_ACCEPT_RATE.set(
                                self._n_spec_accepted
                                / self._n_spec_proposed)
                        if prop_h is not None:
                            for slot, r in live_items:
                                p_i = int(prop_h[slot])
                                if p_i <= 0:
                                    continue
                                a_i = int(acc_h[slot])
                                ent = self._tenant_spec.setdefault(
                                    r.tenant, [0, 0])
                                ent[0] += p_i
                                ent[1] += a_i
                                tenant_rows.append(
                                    (r.tenant, ent[0], ent[1]))
                                obs.append(((r.tenant, r.pkey),
                                            p_i, a_i))
                    # gauges + controller OUTSIDE the server lock (the
                    # controller has its own; registry sets are
                    # independently locked)
                    for tenant, p_tot, a_tot in tenant_rows:
                        _TENANT_SPEC_ACCEPT.labels(
                            tenant=tenant).set(a_tot / p_tot)
                    ctl = self._spec_ctl
                    if ctl is not None:
                        for okey, p_i, a_i in obs:
                            ctl.observe(okey, p_i, a_i)
                else:
                    _TICKS.inc(k)
                    _SCANS.labels(k=str(k)).inc()
                    n_live, n_dead = _paged_blocks_walked(
                        pos_h, emit_h[slots_h], self.block_size,
                        self._walk_chunk)
                    walked = [(n_live + n_dead, self._lane_bytes)]
                    if self._win_walk is not None:
                        # the window kind's table too: a ring's one
                        # block is live from the first token
                        window, chunk, lane_bytes = self._win_walk
                        w_live, w_dead = _paged_blocks_walked(
                            pos_h, emit_h[slots_h], self.block_size, chunk,
                            last=window - 1)
                        n_live, n_dead = n_live + w_live, n_dead + w_dead
                        walked.append((w_live + w_dead, lane_bytes))
                    _PAGED_LIVE.inc(n_live)
                    _PAGED_DEAD.inc(n_dead)
                    _PAGED_LANES_KV.inc(sum(n * kv for n, (kv, _) in walked))
                    _PAGED_LANES_PAD.inc(
                        sum(n * pad for n, (_, pad) in walked))
                    if routed_h is not None:
                        self._count_routed(routed_h, k)
                _TOKENS_EMITTED.inc(n_emitted)
                _SLOT_TICKS.inc(n_active * (R if use_spec else k))
                _OCC.observe(n_active / self.n_slots)
                now_p = time.perf_counter()
                now_m = time.monotonic()
                finished = []
                n_drained = 0
                with self._lock:
                    if self._epoch != my_epoch:
                        return
                    self._kc, self._vc, self._state = kc, vc, state
                    kill = []
                    for slot in list(self._active):
                        req = self._active[slot]
                        # unpack exactly the tokens this slot really
                        # generated: emit_h counts its live ticks in
                        # the scan (EOS / budget drain retire mid-scan)
                        e = int(emit_h[slot])
                        if e:
                            base = req.t0 + req.emitted
                            self._ids[slot, base:base + e] = \
                                toks_h[slot, :e]
                            req.emitted += e
                            if req.ttft is None:
                                req.ttft = now_p - req.t_submit
                                _TTFT.observe(req.ttft)
                        done = rem_h[slot] == 0
                        expired = (req.deadline is not None
                                   and now_m > req.deadline)
                        if done or req.cancelled or expired:
                            del self._active[slot]
                            self._free.append(slot)
                            # blocks back to the pool (cached prefix
                            # blocks park in the evictable LRU)
                            n_drained += \
                                self._release_slot_blocks_locked(slot)
                            finished.append((req, slot, done))
                            if not done:
                                kill.append(slot)
                    n_active = len(self._active)
                    n_pending = len(self._pending)
                if n_drained:
                    _KV_BLK_FREED.inc(n_drained)
                if finished:
                    self._update_free_gauge()
                for req, slot, done in finished:
                    if done:
                        self._retire(req, slot)
                    elif req.cancelled:
                        # slot freed host-side AND budget zeroed
                        # device-side (the kill dispatch above) — no
                        # zombie ticks
                        _CANCELLED.inc()
                        self._retire(req, slot, error=CancelledError(
                            "generation request cancelled"))
                    else:
                        _DEADLINE_EXCEEDED.inc()
                        self._retire(req, slot,
                                     error=DeadlineExceededError(
                                         "generation request deadline "
                                         "elapsed mid-decode"))
                if kill:
                    # device-side early-kill: zero the cancelled /
                    # expired slots' budgets so they stop burning scan
                    # ticks as zombies (the slot is already freed
                    # host-side; its row goes inactive the very next
                    # dispatch).  Dispatched AFTER the finished
                    # requests retired: if this dispatch fails, their
                    # callers already have results/errors and the
                    # inline recovery below rebuilds a zeroed pool —
                    # nobody is left hanging on an unset event.
                    mask = np.zeros((self.n_slots,), bool)
                    mask[kill] = True
                    mask = self._to_device("kill", mask)
                    with self._lock:
                        if self._epoch != my_epoch:
                            return
                        st = self._state
                        _sanitize.check_not_donated("serve/kill", st)
                        # ledger-mark BEFORE the donating dispatch (a
                        # host-side weakref record, not a buffer read)
                        # so no name outlives its donation
                        _sanitize.mark_donated("serve/kill", st)
                        _DISPATCHED["kill"].inc()
                        self._state = self._kill(st, mask)
                # post-tick refresh so an idle pool scrapes as 0 busy
                # (the loop blocks on the queue next, with no tick to
                # update the gauges)
                _SLOTS_BUSY.set(n_active)
                _QDEPTH.set(n_pending + self._queue.qsize())
            except Exception as e:  # surface to the implicated callers
                # the recovery frees slots and blocks, as retire does
                phases.switch("retire")
                self._mark_tick(my_epoch, None)
                with self._lock:
                    if self._epoch != my_epoch:
                        return
                _TICK_FAILURES.inc()
                _FLIGHT.record("tick_failure",
                               error=type(e).__name__)
                if self.tp_degree > 1:
                    # a multi-chip replica's failed dispatch is, from
                    # the host, indistinguishable from losing one chip
                    # of the tp group mid-tick — record the mesh-loss
                    # event the chaos drill (and a postmortem bundle)
                    # keys on, with the slice it spanned
                    _FLIGHT.record("tp_device_loss",
                                   tp=self.tp_degree,
                                   devices=",".join(
                                       self._device_labels or ()),
                                   error=type(e).__name__)
                err = RetryableServerError(
                    "decode dispatch failed and the slot pool was "
                    "rebuilt; the request was not applied — safe to "
                    "retry")
                err.__cause__ = e
                log.exception("GenerationServer tick/admit failed; "
                              "salvaging unaffected slots")
                # surgical rebuild: a raising ADMISSION implicates only
                # the admitting slot (its prefill never committed);
                # everything else salvages unless the failed dispatch
                # consumed the donated pool buffers mid-update
                implicated = (frozenset((admitting,))
                              if admitting is not None else frozenset())
                if not self._recover_pool(my_epoch, err,
                                          implicated=implicated):
                    return       # a watchdog recovery superseded us

    # -- watchdog ------------------------------------------------------
    def _watch(self):
        """Detect a stuck dispatch (``tick_timeout_s`` exceeded) or a
        dead scheduler thread, then fail in-flight work with a
        retryable error, rebuild the pool and restart the scheduler —
        graceful degradation instead of a dead server."""
        interval = max(0.01, min(self.tick_timeout_s / 4.0, 0.5))
        while True:
            if self._stop_event.wait(interval):
                return
            with self._lock:
                if self._shutdown:   # shutdown owns the thread now
                    return
                worker = self._worker
                started = self._tick_started
                epoch = self._epoch
            # the stuck-tick deadline scales by the in-flight scan
            # length: a K-tick scan legitimately runs ~K x one tick,
            # and a fixed deadline would trip a spurious recovery
            # (full KV-pool rebuild) on every long scan
            stuck = (started is not None and started[0] == epoch and
                     time.monotonic() - started[1] >
                     self.tick_timeout_s * max(1, started[2]))
            if stuck:
                self._recover(f"dispatch exceeded tick_timeout_s="
                              f"{self.tick_timeout_s:g} x k={started[2]}")
            elif not worker.is_alive():
                self._recover("scheduler thread died")

    def _recover(self, reason: str):
        with self._lock:
            if self._stop_event.is_set() or self._shutdown:
                return
            self._epoch += 1     # fences the old scheduler out of
            new_epoch = self._epoch  # every commit point
            self._tick_started = None
            self._healthy.set(0)
        # close-on-owner-death: the superseded scheduler may be hung
        # INSIDE its tick span forever — flush its bound spans now so
        # the trace shows the recovery instead of silently losing the
        # dispatch (request-phase spans are unbound and stay open:
        # salvaged requests complete their traces under the new
        # scheduler, failed ones close at _retire).  Keyed by the
        # superseded INCARNATION (id, epoch), never a raw thread
        # ident — dead threads' idents are recycled.
        _WATCHDOG_RESTARTS.inc()
        _FLIGHT.record("watchdog", reason=reason,
                       epoch=int(new_epoch))
        if self.tp_degree > 1:
            # stuck/dead dispatch on a multi-chip replica: same
            # mesh-loss event as the inline path — a hung collective
            # after losing a tp peer lands HERE, not in the inline
            # except (the dispatch never returns)
            _FLIGHT.record("tp_device_loss", tp=self.tp_degree,
                           devices=",".join(self._device_labels or ()),
                           error="watchdog")
        # freeze the black box BEFORE the owner-death span flush and
        # the pool rebuild: the bundle must hold the hung dispatch's
        # still-open tick span and the pre-recovery ring — the "what
        # was it doing" a postmortem exists to answer
        _FLIGHT.request_dump(f"watchdog: {reason}")
        telemetry.get_tracer().end_owned_by(
            (id(self), new_epoch - 1), error="watchdog_recovery")
        log.warning("GenerationServer watchdog: %s — salvaging "
                    "unaffected slots and restarting the scheduler",
                    reason)
        # surgical: unimplicated in-flight slots keep their KV rows and
        # device state and complete under the NEW scheduler without
        # resubmission; only unrecoverable slots fail retryably
        self._recover_pool(new_epoch, RetryableServerError(
            f"decode scheduler recovered ({reason}); the request "
            f"failed in flight and was not applied — safe to retry"))
        with self._lock:
            if self._stop_event.is_set() or self._shutdown:
                return
            self._worker = threading.Thread(target=self._run,
                                            args=(new_epoch,),
                                            daemon=True)
            self._worker.start()
            self._healthy.set(1)
