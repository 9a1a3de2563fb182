"""Continuous-batching decode engine over a paged KV pool (vLLM-core peer).

Design (TPU-first; contrast reference vllm/ + PPModelWorker
pipeline_parallel.py:482-928 which rely on vLLM's paged attention):

- **paged KV**: one static pool ``[L, P, H, page, D]`` shared by every row,
  per-row block tables (kv.PagedKVCache) — HBM scales with TOKENS IN USE,
  not rows x S_max, so concurrency is bounded by real load, and the decode
  step still compiles exactly once (all shapes static);
- **prefix caching**: full pages of a prompt are content-hashed (a chained
  hash, so a page's identity covers everything before it); a new request
  reuses matching pages from earlier requests with refcounts and prefills
  only the remainder — the vLLM prefix-cache equivalent;
- **chunked prefill**: every prefilling row advances by one ragged chunk
  of a per-tick token budget INSIDE the tick that also decodes, so a
  2k-token prefill never stalls in-flight streams by more than one chunk
  forward;
- **one program per tick** (``_ragged_tick``): it decodes ALL rows in one
  jitted call; inactive rows are masked, so join/leave never recompiles;
  per-row temperature/top-p ride as traced vectors;
- **one program in flight behind the running one**: in a run of steady
  ticks the next tick's program is dispatched BEFORE this one's results
  are read (``_queue_next``; its inputs are the device arrays this one
  returned), so the host's turn — the wait's tail, drain, commit, record,
  plan, checkpoint, dispatch — runs beside the device.  A tick still
  reads and commits ONE program, and takes the serial order whenever the
  host cannot tell, before reading, that the next tick is a plain steady
  one (``_ahead_blocked``: an arrival, a dry pool, a finish, a rollback,
  a recurrent state, speculation, a fused horizon, a mesh).  A rollback
  or a stop throws a queued program away unread (``_discard_ahead``).

The engine thread owns the device; asyncio handlers talk to it through
queues (reference fastapi server uses the same queue pattern,
api_server.py).
"""

from __future__ import annotations

import hashlib
import queue
import threading
import time
import uuid
from collections import OrderedDict, deque
from contextlib import nullcontext
from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ipex_llm_tpu.hostutil import d2h, h2d, upload
from ipex_llm_tpu.kv import (PagedKVCache, index_page_bytes, model_page_bytes,
                             recurrent_state, state_bytes, state_counters,
                             state_leaves,
                             with_recurrent_state)
from ipex_llm_tpu.models.config import ModelConfig
from ipex_llm_tpu.models.decoder import (TokenBlock, decoder_forward,
                                         forward_blocks, fuses_blocks)
from ipex_llm_tpu.serving.epoch import (HELD_FIELDS, EpochLayout, ResultLayout,
                                         unpack_rows)
from ipex_llm_tpu.serving.faults import (EngineOverloaded, FaultInjector,
                                         is_transient)
from ipex_llm_tpu.serving.observe import (FAST_LATENCY_BUCKETS_S,
                                          LATENCY_BUCKETS_S, FlightRecorder,
                                          Histogram, Tracer, span)
from ipex_llm_tpu.serving.perfwatch import SLOW_SYNC_WAIT_S
from ipex_llm_tpu.serving.planner import make_planner

NEG_INF = -1e30

# _bisect_culprit outcome: the fault did not reproduce on re-run (distinct
# from "engine-level fault", which is None)
_FAULT_VANISHED = object()

# Every engine program is compiled to round exactly where the program says.
# By default XLA may skip an intermediate rounding (f32 -> bf16 -> f32) when
# it fuses two ops, and whether it fuses depends on the shape of the batch:
# measured on a v5e, ``x + linear(x)`` differs in 29% of its elements between
# the same 128 rows as [1, 128, H] and as [8, 16, H].  A token's value would
# then depend on the batch it rides in: a mixed tick, a prefix hit or a
# different admission wave would give a request other bits than it gets
# served alone.  (ops/norms.py pins the one shape-dependent reduction the same
# way.)
_EXACT_ROUNDING = {"xla_allow_excess_precision": False}

# The copying upload helper was born here (PR 2's stream-corruption fix:
# jnp.asarray zero-copy-aliases mutable numpy buffers while async dispatch
# is still reading them) and is now shared by every async-dispatch module
# from ipex_llm_tpu.hostutil; jaxlint rule JL001 enforces its use.  The
# old private name stays importable for compatibility.
_h2d = h2d


@dataclass(frozen=True)
class EngineConfig:
    max_rows: int = 16          # concurrent sequences
    max_seq_len: int = 4096     # per-row KV capacity (block-table width)
    page_size: int = 128        # KV page length (slots)
    pool_pages: int = 0         # 0 = auto: max_rows * max_seq_len / page / 2
    # KV pool storage format (kv.KV_STORAGE_DTYPES): "bf16" full width, or
    # "fp8" e5m2 codes — the reference DynamicFp8Cache / xe_addons.sdp_fp8
    # format on the PAGED pool.  Decode is KV-bandwidth-bound, so fp8
    # halves the per-token HBM read; at a fixed kv_pool_bytes budget it
    # also doubles the page count — fewer pool-contention horizon clamps,
    # fewer prefix-cache LRU evictions, higher sustainable concurrency.
    # e5m2 storage is lossy vs bf16 (engine output stays bit-identical
    # ACROSS tick shapes — alone or in a wave, H8/H1 — for a given
    # storage); see docs/quickstart/serving.md "KV storage & memory
    # budget" for the quality expectations.
    kv_storage: str = "bf16"
    # KV pool byte budget: when > 0, the pool page count is DERIVED as
    # kv_pool_bytes // page_bytes(model, page_size, kv_storage), so
    # capacity follows the storage width automatically (fp8 => 2x pages)
    # and operators size the pool in the unit they actually provision
    # (HBM bytes).  Overrides pool_pages.  0 = pool_pages/auto sizing.
    kv_pool_bytes: int = 0
    # host-RAM KV spill tier (serving/pagestore.py): when > 0, a prefix
    # page evicted under pool pressure — and a finished row's decode
    # pages (the multi-turn follow-up's prefix) — DEMOTES into a
    # byte-budgeted host LRU instead of being lost, and swaps back into
    # the pool on the next prefix hit: a PCIe copy through the audited
    # hostutil.h2d/d2h boundary instead of a recompute.  Swapped-in
    # pages are byte-identical to ones that never left the pool.  All
    # spill/swap work happens at epoch boundaries (allocation, admission,
    # finish), never inside the fused tick — JP106's one-dispatch tick
    # is untouched.  0 = evictions stay losses (the pre-spill engine).
    kv_spill_bytes: int = 0
    # weight-quantization axis (the reference's identity feature,
    # load_in_low_bit="sym_int4", applied to the SERVING hot path): at
    # engine build, every native-width (bf16/fp16) linear weight in the
    # stacked layer params — qkv/o/gate_up/down stacks, the lm head —
    # re-packs into block-quantized QTensor planes
    # (models/build.requantize_params, the same quantize/core.py codecs a
    # low-bit checkpoint load uses), and the single compiled layer body
    # routes those matmuls through ops/linear.qmatmul with dequant fused
    # next to the MXU (Pallas on TPU, XLA-fused block dequant on CPU).
    # Decode is HBM-bandwidth bound, so ~4.5 bits/weight instead of 16
    # is a direct tok/s and — at a fixed HBM byte budget, weights + KV
    # pool together — a concurrency win (bytes the weights stop using
    # become KV pages).  Zero new device programs: the QTensor planes
    # ride the existing param pytree through the one-dispatch tick, so
    # JP106 stays ==1, and the JP107 trace rule fails the audit if a
    # hot-path program ever materializes a full-width copy of a stacked
    # packed weight.  None = serve the params at the width they were
    # handed over (a tree loaded with load_in_low_bit is ALREADY packed
    # and passes through untouched — requantizing packed codes would
    # stack error, so weight_qtype on such a tree is a no-op).
    weight_qtype: str | None = None
    prefill_bucket: int = 128   # chunked-prefill chunk length
    # speculative serving (reference ipex_llm_worker.py:57 `speculative`
    # load flag): >0 enables prompt-lookup speculative decode steps — each
    # step verifies spec_k n-gram candidates per row in ONE batched
    # T=spec_k+1 forward.  Every position samples with the row's own
    # params, so greedy AND sampled rows emit the accepted prefix with
    # the spec-off engine's distribution (seeded rows bit-identically; see
    # ``_spec_horizon_loop``).  Decode is bandwidth-bound, so the wider
    # step costs ~one weight pass but can emit up to spec_k+1 tokens.
    #
    # The WHOLE loop is on-device and composes with the decode horizon: a
    # jitted prompt-lookup proposer scans each row's device-resident
    # token history (ops/speculate.py), the [R, spec_k+1] verify forward
    # and the acceptance walk ride INSIDE ``_decode_horizon_loop``'s
    # while_loop, and a horizon step emits 1..spec_k+1 tokens per
    # iteration with no extra dispatch (JP106 still gates the tick at
    # ==1) and no per-step sync.
    spec_k: int = 0
    spec_ngram: int = 3         # n-gram length for the lookup proposer
    # fused decode horizon: >1 runs up to H decode+sample steps in ONE
    # jitted on-device loop (``_decode_horizon_loop``: ``lax.while_loop``
    # that exits early once every row is dead) with device-resident engine
    # state, so the host pays ONE blocking sync per H tokens instead
    # of per token — the fix for the dispatch-bound regime (per-stream
    # tok/s collapsing under concurrency from host orchestration, not
    # FLOPs; the vLLM multi-step / MaxText on-device generate-loop
    # peers).  Per-row EOS/length early-stop is masked on
    # device, so fused output is bit-identical to H=1 under the seeded-
    # stream contract.  Streaming granularity becomes up to H tokens
    # (times spec_k+1 when speculative decode rides the same loop).
    decode_horizon: int = 1
    # mixed prefill+decode step: per-tick prefill token budget for the
    # admission wave.  While ANY row is prefilling, the engine runs
    # ``_mixed_step`` — ONE fused program (``_ragged_tick``) advances
    # EVERY prefilling row by a ragged chunk, samples-and-merges first
    # tokens on device for prompts that complete, and runs the decode
    # step for every active row, all in a single dispatch (the TPU
    # ragged-paged-attention superkernel tick; JP106 locks it).  The budget
    # fair-shares across joining rows in power-of-two per-row chunk
    # widths (bounded retraces); decode rows keep their ordinary [R, 1]
    # step cost.  None = auto (prefill_bucket); otherwise positive.
    step_token_budget: int | None = None
    # fault domain (PR 3): the unit of failure is a request, not the
    # engine.  Transient step faults (device preemption, pool pressure,
    # dropped connections — faults.is_transient) retry up to max_step_retries
    # times with exponential backoff after rolling host bookkeeping back
    # to the last committed tick; deterministic faults bisect the tick's
    # row set and quarantine exactly one culprit row with
    # finish_reason="error", keeping survivors bit-identical to an
    # unfaulted run.  _fail_all remains only for faults bisection cannot
    # localize (engine-level).
    max_step_retries: int = 3
    retry_backoff_s: float = 0.02   # base of the exponential backoff
    # admission control: submit() raises EngineOverloaded once this many
    # requests are queued (inbox + pending, not counting in-flight rows);
    # the HTTP surfaces map it to 429.  0 = unbounded (the pre-PR3
    # behaviour).
    max_queue: int = 256
    # default per-request deadline covering queue wait + generation
    # (Request.deadline_s overrides); enforced at admission (an expired
    # request finishes "timeout" without ever occupying a row) and at
    # every emission epoch.  0 = no deadline.
    request_deadline_s: float = 0.0
    # request-lifecycle tracing (serving/observe.py): when True the
    # engine records per-request spans — queue wait, swap-ins, prefill
    # chunks, first token, every decode horizon, spec rounds with accept
    # counts, retries, quarantine, finish — staged inside the
    # transactional tick and flushed only on commit (a rolled-back tick
    # never leaks a span), retrievable per request via /trace/{id} and
    # exportable as Chrome trace-event JSON.  All timestamps are host
    # clock reads at points the tick already visits: no new device
    # syncs, JP106's one-dispatch tick untouched.  False = the tracer is
    # None and every trace site is one `is None` check.  The tick flight
    # recorder and the latency histograms are always on — they are a dict
    # append per working tick and a few float ops per token.
    trace_requests: bool = False
    trace_buffer: int = 256     # traces retained (LRU); spans/trace capped
    flight_ring: int = 256      # tick records the flight recorder retains
    # device-time observatory (serving/perfwatch.py): per-tick wall-clock
    # attribution (dispatch / device-execute / host-sync / host-
    # bookkeeping buckets per program family, rollback-covered
    # histograms on /metrics + per-tick flight-recorder fields), the
    # runtime recompile sentinel (JP104's twin: jax.monitoring compile
    # events classified against the manifest-locked grid in
    # analysis/programs.lock.json — warm-path and out-of-grid compiles
    # flagged in /health's perf block), and MFU/roofline accounting
    # joining measured device time against the manifest's cost_analysis
    # for the dispatched grid point.  All host clock reads at points the
    # tick already visits — no new device programs or syncs, JP106 stays
    # ==1.  False = no PerfWatch at all.
    perfwatch: bool = True
    # multi-chip collective wire family (ops/collectives.py, the EQuARX
    # axis): what the manual-mesh tick's row-parallel AllReduces carry.
    # "bf16" = the exact family (f32 accumulation; tp2 output is
    # bit-stable against tp1 — the bit-identity gate's family); "e5m2" /
    # "int8" = quantized payloads, bounded error for less ICI traffic —
    # decode's real multi-chip bottleneck.  None = resolve through
    # collectives.resolve_qtype (the IPEX_LLM_TPU_COLLECTIVE_QTYPE env,
    # else the exact default) — an explicit value here always wins.
    # Ignored off-mesh and on the GSPMD fallback path (XLA owns those
    # collectives).
    collective_qtype: str | None = None
    # tick planner (serving/planner.py): ONE host-side decision function
    # runs at the top of every tick (pure bookkeeping — zero new device
    # programs, JP106's one-dispatch tick untouched) and owns the tick's
    # whole shape: prefill chunk budget, decode horizon, per-row
    # speculative draft caps, and admission count.  "mpc" (the default)
    # is the model-predictive goodput planner — it joins the manifest's
    # cost_analysis with perfwatch's measured per-family tick history and
    # the rolling spec accept window, and deviates from the static
    # decisions only on evidence (deadline slack, draft economics); with
    # no deadlines and no adverse spec signal it makes the static
    # choices, selecting ONLY among grid points the config already
    # bounds, so the recompile sentinel stays structurally quiet.
    # "static" is the escape hatch: the pre-planner engine's decisions
    # verbatim (fixed step_token_budget, the admission-wave H-clamp,
    # static spec_k, unbounded admission) — bit-identical by
    # construction.  The plan is computed BEFORE the tick checkpoint, so
    # a rollback/retry (and every bisection probe) replays the same plan.
    planner: str = "mpc"

    def __post_init__(self):
        if self.step_token_budget is not None and self.step_token_budget <= 0:
            raise ValueError(
                f"step_token_budget={self.step_token_budget}: the per-tick "
                "prefill budget must be positive or None (= prefill_bucket)")

    @property
    def n_pages(self) -> int:
        if self.pool_pages:
            return self.pool_pages
        # 2x oversubscription: the paged pool holds half the worst case,
        # which real mixed-length traffic rarely approaches (the point of
        # paging); raise pool_pages for pathological all-max-len loads.
        # +2: page 0 is the reserved scratch page
        return max(self.max_rows * self.max_seq_len // self.page_size // 2,
                   self.max_rows + 2)

    @property
    def max_pages(self) -> int:
        return self.max_seq_len // self.page_size


def resolve_load_low_bit(engine_config: EngineConfig | None,
                         low_bit: str | None) -> str | None:
    """The load-width half of the serving width rule (one definition for
    both server entry points): a pinned ``EngineConfig.weight_qtype``
    outranks the ``low_bit`` load argument — loading packed at one width
    and asking the engine for another would leave the request a
    warn-and-ignore (requantizing packed codes stacks error), so the
    pinned width drives the checkpoint load itself."""
    if engine_config is not None and engine_config.weight_qtype:
        return engine_config.weight_qtype
    return low_bit


def default_weight_qtype(engine_config: EngineConfig | None,
                         low_bit: str | None) -> EngineConfig:
    """The config half of the serving width rule, beside
    :func:`resolve_load_low_bit`: thread the width the checkpoint was
    loaded at into ``EngineConfig.weight_qtype`` unless the caller
    already pinned one.  Only meaningful when the server also LOADED the
    checkpoint at that width (the repack is then a pass-through and the
    config records the width truthfully) — callers handing in their own
    full-width model must opt into repacking explicitly via
    ``weight_qtype``, never get it silently."""
    ec = engine_config or EngineConfig()
    if ec.weight_qtype is None and low_bit:
        ec = replace(ec, weight_qtype=low_bit)
    return ec


@dataclass
class Request:
    prompt_ids: list[int]
    max_new_tokens: int = 128
    temperature: float = 0.0    # 0 = greedy
    top_p: float = 1.0
    top_k: int = 0              # 0 = off
    seed: int | None = None     # deterministic per-request sampling stream
    eos_token_id: tuple[int, ...] = ()
    stream_queue: "queue.Queue[int | None]" = field(default_factory=queue.Queue)
    request_id: str = ""
    # filled by the engine
    output_ids: list[int] = field(default_factory=list)
    logprobs: list[float] = field(default_factory=list)  # per output token
    finish_reason: str | None = None
    first_token_s: float = 0.0
    submitted_s: float = field(default_factory=time.perf_counter)
    queue_wait_s: float = 0.0   # submit -> row assigned (set by _admit)
    cancelled: bool = False  # set via ServingEngine.abort (client disconnect)
    stop_strings: list[str] = field(default_factory=list)
    # None = engine default (on when EngineConfig.spec_k > 0); False opts a
    # request out of speculative acceptance (it still rides the wide step)
    speculative: bool | None = None
    # per-request draft width, clamped to EngineConfig.spec_k (the trace
    # width); None = engine default
    spec_k: int | None = None
    # wall-clock budget covering queue wait + generation, from submission;
    # None = EngineConfig.request_deadline_s (0 there = no deadline).  An
    # expired request finishes with finish_reason="timeout" — at admission
    # without ever occupying a row, or mid-generation at the next tick.
    deadline_s: float | None = None
    # lifecycle-trace identity: the W3C traceparent trace id the HTTP
    # surfaces parse from the router/client (None = the engine keys the
    # trace on request_id), so one trace assembles across processes
    trace_id: str | None = None
    # last emission wall time (token-latency histogram bookkeeping;
    # checkpointed with the tick so a rolled-back emission never skews
    # the inter-token distribution)
    _last_tok_s: float = 0.0
    # when the engine's commit released the items in ``stream_queue``:
    # one ``[perf_counter, items]`` a committed tick that emitted for this
    # request, appended BEFORE the items are put (``stream_stamp``)
    commit_stamps: "deque[list]" = field(default_factory=deque, repr=False)

    def abort(self):
        self.cancelled = True


@dataclass
class _Queued:
    """A steady tick's program, dispatched and not yet read: what reading
    it takes, and what throwing it away takes."""

    sent: tuple               # what ``_sync_window`` takes
    active: np.ndarray        # the rows it decodes, as the host saw them
    bind: list                # row -> request at its dispatch
    key: Any                  # the engine's PRNG key it was handed
    t0_w: float               # wall clock at its dispatch (request spans)
    horizon: int              # steps it runs (the plan's, pool-clamped)
    planned: int              # the plan's horizon
    fused_spec: bool
    family: str               # perfwatch: the tick that reads it is of
    point: dict               # this family, at this grid point
    attrs: dict               # its window's ``engine/dispatch`` stats
    ahead: bool = False       # queued before its predecessor was read
    stray: int = 0            # rows the host retired while it was queued
    counts: dict = field(default_factory=dict)   # work counters, its reader's


class PageAllocator:
    """Host-side page pool bookkeeping: free list, refcounts, and the
    chained-hash prefix cache (LRU-evicted when the pool runs dry).

    ``spill``: optional callback ``spill([(key, pid), ...])`` invoked
    BEFORE a batch of cached prefix pages is dropped — the engine's hook
    into the host-RAM page store, turning an eviction from a loss into a
    demotion.  Batched so an allocation burst (``reserve``) pays ONE
    device gather + sync for all its evictions instead of one each."""

    def __init__(self, n_pages: int, spill=None):
        # page 0 is the device scratch page (kv.PagedKVCache.update_layer
        # routes out-of-range/pad writes there) — never handed out
        self.n_pages = n_pages
        self.spill = spill
        self.free: list[int] = list(range(n_pages - 1, 0, -1))
        self.ref = np.zeros((n_pages,), np.int32)
        # prefix cache: chain-hash -> page id; insertion order ~ LRU
        self.prefix: "OrderedDict[bytes, int]" = OrderedDict()
        self._page_key: dict[int, bytes] = {}
        # pool-pressure trace: cached prefix pages dropped to satisfy new
        # allocations (each one is a future prefix miss a bigger pool —
        # or a narrower storage — would have kept; with a spill tier the
        # page demotes to host RAM instead of being lost, so the counter
        # becomes "demotions", not "losses" — the router's affinity
        # freshness check reads it together with the spill block)
        self.prefix_evictions = 0

    def alloc(self) -> int | None:
        if not self.free and not self._evict(1):
            return None
        pid = self.free.pop()
        self.ref[pid] = 1
        return pid

    def reserve(self, n: int):
        """Pre-evict so the next ``n`` allocations are covered: exactly
        the pages lazy per-alloc eviction would drop (same LRU order,
        same count), but spilled in ONE batch — an allocation burst
        under pressure pays one device gather, not one per page."""
        short = n - len(self.free)
        if short > 0:
            self._evict(short)

    def addref(self, pid: int):
        self.ref[pid] += 1

    def decref(self, pid: int):
        self.ref[pid] -= 1
        if self.ref[pid] == 0:
            self.free.append(pid)

    def _evict(self, n: int) -> int:
        """Drop up to ``n`` least-recently-used prefix pages held only
        by the cache, spilling them to the host tier first (one batched
        callback) when one is wired.  Returns how many were dropped."""
        picks = []
        for key, pid in self.prefix.items():
            if self.ref[pid] == 1:  # only the cache references it
                picks.append((key, pid))
                if len(picks) == n:
                    break
        if not picks:
            return 0
        if self.spill is not None:
            # before the bookkeeping drop, while the pages are still
            # owned: a raise here (injected fault) leaves every cache
            # entry intact for the retry
            self.spill(picks)
        for key, pid in picks:
            del self.prefix[key]
            del self._page_key[pid]
            self.decref(pid)
            self.prefix_evictions += 1
        return len(picks)

    def register_prefix(self, key: bytes, pid: int):
        if key in self.prefix or pid in self._page_key:
            return
        self.prefix[key] = pid
        self._page_key[pid] = key
        self.addref(pid)  # the cache's own reference

    def lookup_prefix(self, key: bytes) -> int | None:
        pid = self.prefix.get(key)
        if pid is not None:
            self.prefix.move_to_end(key)  # LRU touch
        return pid

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - 1 - len(self.free)


def _chain_hashes(prompt: np.ndarray, page_size: int) -> list[bytes]:
    """Chained content hash per full page: page i's key commits to every
    token before it, so equal keys imply equal K/V contents."""
    keys, h = [], b""
    for i in range(len(prompt) // page_size):
        h = hashlib.sha256(
            h + prompt[i * page_size : (i + 1) * page_size].tobytes()
        ).digest()
        keys.append(h)
    return keys


def _decode_horizon_loop(cfg: ModelConfig, params, cache, toks, row_lens,
                         active, temps, top_ps, key, seeds, steps, top_ks,
                         eos, remain, horizon: int, hist=None, spec_ks=None,
                         spec_k: int = 0, spec_ngram: int = 3):
    """The fused decode horizon BODY: up to ``horizon`` decode+sample
    steps over the whole row pool (a ``lax.while_loop`` — not
    ``lax.scan``, because the loop must exit early the moment every row
    is dead), traced into ``_ragged_tick`` as its decode stage.

    toks [R] current token per row; row_lens [R] slots already in cache;
    eos [R, E] per-row stop ids (-1 pad); remain [R] output-token budget
    left.  A row that hits EOS or exhausts its budget INSIDE the horizon
    goes dead on device: its later positions emit masked padding (0), its
    toks/row_lens freeze, and the one KV slot it keeps rewriting is dead
    state the host reclaims at finish — the speculative-rollback
    convention (rejected slots are free to leave dirty).  Every live
    position computes exactly what the H=1 step computes (same forward,
    same split-per-step key chain, same fold_in(seed, output_index)
    stream), so fused output is bit-identical to H=1.

    ``spec_k > 0`` (static) selects the SPECULATIVE loop body: each
    iteration proposes up to ``spec_k`` prompt-lookup drafts per row from
    the device-resident token history ``hist`` [R, S] (``hist[r,
    row_lens[r]]`` is the row's current token — ops/speculate.py), runs
    ONE [R, spec_k+1] verify forward, samples every position with the
    row's params keyed by OUTPUT INDEX (``_sample_verify_positions``), and
    walks the acceptance chain ON DEVICE: emit s_0; while the draft fed at
    position j equals the token just emitted, s_j is a draw from the true
    conditional (the reference's speculative.py:805 distribution-
    preservation contract, generalized to temperature > 0) — so every
    emitted token has the spec-off engine's distribution (seeded rows
    bit-identically, greedy rows token-identically), and an iteration
    emits 1..spec_k+1 tokens.  EOS/budget truncation happens inside the
    accepted window; rejected drafts' KV slots are dead until overwritten
    (the paged pool's free rollback — the write cursor just doesn't
    advance past them).  ``spec_ks`` [R] caps the proposed run per row
    (0 = a plain step for that row: per-request opt-outs and the pool-
    contention fallback ride as traced masks, not separate programs).
    Returns the plain tuple extended with (take_block [R, H], hist,
    proposed, accepted); tok/lp blocks become [R, H, spec_k+1], positions
    past a row's per-iteration take masked to padding (0).
    """
    from ipex_llm_tpu.ops.sampling import sample_rows_with_logprobs

    if spec_k > 0:
        return _spec_horizon_loop(
            cfg, params, cache, toks, row_lens, active, temps, top_ps,
            key, seeds, steps, top_ks, eos, remain, horizon, hist,
            spec_ks, spec_k, spec_ngram)

    def step(n, cache, toks, row_lens, alive, key, steps, remain):
        # dead/masked rows route their (masked) K/V write to the scratch
        # page instead of rewriting the slot at their frozen row_lens: a
        # masked row may be mid-prefill with its DEVICE length stale (the
        # mixed step advances the host copy between epochs), and a
        # garbage write at a stale slot would corrupt KV a later chunk
        # already filled.  Live rows' offsets are untouched, so fused
        # output stays bit-identical.
        write_at = jnp.where(
            alive, row_lens,
            h2d(cache.tables.shape[1] * cache.page_size,
                        jnp.int32))
        logits, cache = decoder_forward(
            cfg, params, toks[:, None], cache, row_lens[:, None],
            last_token_only=True, slot_offsets=write_at,
            # a dead row is an idle row (chunk_len 0): its attention walks
            # no page and emits zeros, and recurrent state does not advance
            # for it (KV has the scratch page for a dead row's write; a
            # state has no scratch slot).  A live row's kv_len is the same
            # ``row_lens + 1`` either way.
            chunk_lens=alive.astype(jnp.int32),
        )
        key, sub = jax.random.split(key)
        nxt, lp = sample_rows_with_logprobs(logits, temps, top_ps, sub,
                                            seeds=seeds, steps=steps,
                                            top_ks=top_ks, active=alive)
        # on-device early-stop: EOS emission or budget exhaustion kills the
        # row for the rest of the horizon (it keeps riding the batch fully
        # masked); the host's _emit walks the same boundary when draining
        hit_eos = (nxt[:, None] == eos).any(axis=1) & alive
        adv = alive.astype(jnp.int32)
        row_lens = row_lens + adv
        steps = steps + adv
        remain = remain - adv
        alive = alive & ~hit_eos & (remain > 0)
        toks = jnp.where(alive, nxt, toks)
        return (n + 1, cache, toks, row_lens, alive, key, steps, remain,
                nxt, lp)

    if horizon == 1:
        # the H=1 program is the loop body inlined
        (n, cache, toks, row_lens, active, key, steps, remain, nxt,
         lp) = step(jnp.asarray(0, jnp.int32), cache, toks, row_lens,
                    active, key, steps, remain)
        tok_block, lp_block = nxt[:, None], lp[:, None]
    else:
        r = toks.shape[0]

        def body(carry):
            n, cache, toks, row_lens, alive, key, steps, remain, tb, \
                lb = carry
            (n1, cache, toks, row_lens, alive, key, steps, remain,
             nxt, lp) = step(n, cache, toks, row_lens, alive, key,
                             steps, remain)
            tb = jax.lax.dynamic_update_index_in_dim(tb, nxt, n, 0)
            lb = jax.lax.dynamic_update_index_in_dim(lb, lp, n, 0)
            return (n1, cache, toks, row_lens, alive, key, steps,
                    remain, tb, lb)

        init = (jnp.asarray(0, jnp.int32), cache, toks, row_lens,
                active, key, steps, remain,
                jnp.zeros((horizon, r), jnp.int32),
                jnp.zeros((horizon, r), jnp.float32))
        (n, cache, toks, row_lens, active, key, steps, remain, tb,
         lb) = jax.lax.while_loop(
            lambda c: (c[0] < horizon) & c[4].any(), body, init)
        tok_block, lp_block = tb.T, lb.T               # [H, R] -> [R, H]
    return (tok_block, lp_block, n, cache, toks, row_lens, active, steps,
            remain, key)


def _spec_horizon_loop(cfg: ModelConfig, params, cache, toks, row_lens,
                       active, temps, top_ps, key, seeds, steps, top_ks,
                       eos, remain, horizon: int, hist, spec_ks,
                       spec_k: int, spec_ngram: int):
    """The speculative form of ``_decode_horizon_loop`` (see its
    docstring for the contract) — split out only to keep the plain body
    byte-identical to the pre-spec program."""
    from ipex_llm_tpu.ops.speculate import propose_ngram_rows

    r = toks.shape[0]
    k1 = spec_k + 1
    scratch = h2d(cache.tables.shape[1] * cache.page_size, jnp.int32)
    s_hist = hist.shape[1]

    def spec_step(n, cache, toks, row_lens, alive, key, steps, remain,
                  hist, prop, acc):
        # draft: most-recent-n-gram continuation from the device history
        # (hist[:, :row_lens+1] is prompt + emitted tokens, current token
        # last); dead/opted-out/contention rows propose nothing and take
        # a plain step through the same wide program
        drafts, n_prop = propose_ngram_rows(hist, row_lens + 1, spec_k,
                                            spec_ngram)
        n_prop = jnp.where(alive, jnp.minimum(n_prop, spec_ks), 0)
        drafts = jnp.where(jnp.arange(spec_k)[None, :] < n_prop[:, None],
                           drafts, 0)
        # verify: ONE [R, k+1] ragged forward over [cur_tok; drafts].
        # Dead rows scratch-route their whole window (stale device lens
        # must never corrupt live pages — the plain body's rule); live
        # rows write slots row_lens..row_lens+k, of which only the
        # accepted prefix survives (unbacked tail slots land on the
        # scratch page via update_layer's valid mask)
        write_at = jnp.where(alive, row_lens, scratch)
        tokens = jnp.concatenate([toks[:, None], drafts], axis=1)
        pos = write_at[:, None] + jnp.arange(k1)[None, :]
        logits, cache = decoder_forward(
            cfg, params, tokens, cache, pos, slot_offsets=write_at,
            # a dead row's window is an idle row of the attention kernel,
            # as in the plain body; a live row's is all k+1 positions
            chunk_lens=alive.astype(jnp.int32) * k1,
        )
        t_all, lp_all, key = _sample_verify_positions(
            logits, alive, temps, top_ps, key, seeds, steps, top_ks,
            spec_k)
        # acceptance chain: position j+1's sample is a draw from the true
        # conditional only while the draft fed there equals the token just
        # emitted
        okm = (drafts == t_all[:, :spec_k]) & (
            jnp.arange(spec_k)[None, :] < n_prop[:, None])
        n_acc = jnp.argmin(jnp.concatenate(
            [okm, jnp.zeros((r, 1), bool)], axis=1).astype(jnp.int32),
            axis=1).astype(jnp.int32)
        # EOS/budget truncation INSIDE the accepted window — the same
        # boundary the host's _emit walk stops at
        eos_hit = (t_all[:, :, None] == eos[:, None, :]).any(-1)
        ehit = eos_hit & (jnp.arange(k1)[None, :] <= n_acc[:, None])
        any_eos = ehit.any(axis=1)
        first_eos = jnp.argmax(ehit, axis=1).astype(jnp.int32)
        n_stop = jnp.where(any_eos, first_eos + 1, n_acc + 1)
        n_take = jnp.where(alive, jnp.minimum(n_stop, remain), 0)
        keep = jnp.arange(k1)[None, :] < n_take[:, None]
        tok_step = jnp.where(keep, t_all, 0)
        lp_step = jnp.where(keep, lp_all, 0.0)
        # append the emitted run to the device history (next iteration's
        # proposer input); masked positions scatter-drop past the buffer
        hpos = jnp.where(keep, row_lens[:, None] + 1
                         + jnp.arange(k1)[None, :], s_hist)
        hist = hist.at[jnp.arange(r)[:, None], hpos].set(t_all,
                                                         mode="drop")
        died_eos = any_eos & (first_eos < n_take)
        row_lens = row_lens + n_take
        steps = steps + n_take
        remain = remain - n_take
        alive = alive & ~died_eos & (remain > 0)
        new_tok = jnp.take_along_axis(
            t_all, jnp.maximum(n_take - 1, 0)[:, None], axis=1)[:, 0]
        toks = jnp.where(alive, new_tok, toks)
        prop = prop + n_prop.sum()
        acc = acc + jnp.maximum(n_take - 1, 0).sum()
        return (n + 1, cache, toks, row_lens, alive, key, steps, remain,
                hist, prop, acc, tok_step, lp_step, n_take)

    zero = jnp.asarray(0, jnp.int32)
    if horizon == 1:
        (n, cache, toks, row_lens, active, key, steps, remain, hist,
         prop, acc, tok_step, lp_step, n_take) = spec_step(
            zero, cache, toks, row_lens, active, key, steps, remain,
            hist, zero, zero)
        tok_block = tok_step[:, None, :]
        lp_block = lp_step[:, None, :]
        take_block = n_take[:, None]
    else:
        def body(carry):
            (n, cache, toks, row_lens, alive, key, steps, remain, hist,
             prop, acc, tb, lb, kb) = carry
            (n1, cache, toks, row_lens, alive, key, steps, remain, hist,
             prop, acc, ts, ls, nt) = spec_step(
                n, cache, toks, row_lens, alive, key, steps, remain,
                hist, prop, acc)
            tb = jax.lax.dynamic_update_index_in_dim(tb, ts, n, 0)
            lb = jax.lax.dynamic_update_index_in_dim(lb, ls, n, 0)
            kb = jax.lax.dynamic_update_index_in_dim(kb, nt, n, 0)
            return (n1, cache, toks, row_lens, alive, key, steps, remain,
                    hist, prop, acc, tb, lb, kb)

        init = (zero, cache, toks, row_lens, active, key, steps, remain,
                hist, zero, zero,
                jnp.zeros((horizon, r, k1), jnp.int32),
                jnp.zeros((horizon, r, k1), jnp.float32),
                jnp.zeros((horizon, r), jnp.int32))
        (n, cache, toks, row_lens, active, key, steps, remain, hist,
         prop, acc, tb, lb, kb) = jax.lax.while_loop(
            lambda c: (c[0] < horizon) & c[4].any(), body, init)
        tok_block = tb.transpose(1, 0, 2)          # [H, R, k1] -> [R, H, k1]
        lp_block = lb.transpose(1, 0, 2)
        take_block = kb.T
    return (tok_block, lp_block, n, cache, toks, row_lens, active, steps,
            remain, key, take_block, hist, prop, acc)


def _sample_verify_positions(logits, active, temps, top_ps, key, seeds,
                             steps, top_ks, k: int):
    """Per-position sampling of the speculative verify window: position j
    draws from p(.|ctx, d_1..d_j) with the row's params, seeded rows keyed
    by fold_in(seed, output_index) — the key a spec-off step would use for
    that output, which is what makes spec on bit-identical to spec off."""
    from ipex_llm_tpu.ops.sampling import sample_rows_with_logprobs

    key, sub = jax.random.split(key)
    subkeys = jax.random.split(sub, k + 1)            # per-position keys
    steps_mat = steps[:, None] + jnp.arange(k + 1)[None, :]  # [R, k+1]
    t_all, lp_all = jax.vmap(
        lambda lg_j, key_j, st_j: sample_rows_with_logprobs(
            lg_j, temps, top_ps, key_j, seeds=seeds, steps=st_j,
            top_ks=top_ks, active=active),
        in_axes=(1, 0, 1), out_axes=1,
    )(logits, subkeys, steps_mat)                     # [R, k+1] each
    return t_all, lp_all, key


def _merge_wave(prefill, first_t, toks, row_lens, active, steps, remain,
                eos, hist=None):
    """A tick's ``tick/merge``: the prefill wave joins the decode state on
    the device exactly as a host epoch upload would publish it (pad slots
    drop).  Lengths advance for EVERY prefill row; a row whose prompt
    completes (``p_emit``) joins with its first token pre-published
    (toks=first, steps=1, remain-=1, active unless the first token hit EOS
    / exhausted the budget / ``p_canjoin`` says the host could not back
    its decode KV slot), so no epoch upload stands between a prompt's last
    chunk and its first decode step.  ``hist`` (a speculating tick's token
    history): a completing row's gains its first token here (the prompt
    itself landed with the admission epoch upload), so the decode stage
    that follows can already draft for it.  Returns (toks, row_lens,
    active, steps, remain, hist)."""
    _, _, p_base, p_nvalid, p_emit, p_canjoin, p_rowmap = prefill
    new_len = p_base + p_nvalid
    row_lens = row_lens.at[p_rowmap].set(new_len, mode="drop")
    hit_eos = (first_t[:, None] == eos[p_rowmap]).any(axis=1)
    rem_after = remain[p_rowmap] - 1
    join = p_emit & p_canjoin & ~hit_eos & (rem_after > 0)
    toks = toks.at[p_rowmap].set(
        jnp.where(p_emit, first_t, toks[p_rowmap]), mode="drop")
    steps = steps.at[p_rowmap].set(
        jnp.where(p_emit, 1, steps[p_rowmap]), mode="drop")
    remain = remain.at[p_rowmap].set(
        jnp.where(p_emit, rem_after, remain[p_rowmap]), mode="drop")
    active = active.at[p_rowmap].set(
        jnp.where(p_emit, join, active[p_rowmap]), mode="drop")
    if hist is not None:
        # (pad slots and non-emitting rows drop)
        hpos = jnp.where(p_emit, new_len, hist.shape[1])
        hist = hist.at[p_rowmap, hpos].set(first_t, mode="drop")
    return toks, row_lens, active, steps, remain, hist


def fuses_mixed_tick(cfg: ModelConfig, horizon: int, with_decode: bool,
                     spec_k: int, mesh) -> bool:
    """Whether a tick with a prefill block takes the FUSED form
    (``_fused_mixed_body``), from ``cfg`` and the tick's static shape: a
    dense model (``decoder.fuses_blocks``), one decode step, no
    speculation, no mesh.  Every other mixed tick is staged
    (``_tick_body``).  The host asks the same question of the same values
    (``_mixed_step``): a joiner's first decode step waits for the next tick
    in the fused form."""
    return (with_decode and horizon == 1 and spec_k == 0 and mesh is None
            and fuses_blocks(cfg))


def _fused_mixed_body(cfg: ModelConfig, params, cache, toks, row_lens,
                      active, temps, top_ps, key, seeds, steps, top_ks, eos,
                      remain, prefill):
    """A dense model's mixed tick in ONE pass over the weights: the prefill
    block's ``P * W`` tokens and the ``R`` decode rows' ride one forward
    (``decoder.forward_blocks``: every linear once, at M = P * W + R; the
    pool write and attention per block), where the staged form
    (``_tick_body``) streams and dequantizes the weights once for the block
    and once more for the rows.  ``_tick_body``'s arguments and results.

    The decode rows step from the row state the tick was HANDED (the
    epoch's), and the merge comes after: a row whose prompt completes here
    gets its first token here and takes its first decode step in the NEXT
    tick (the staged form gives it that step in the same program, which is
    what forces the second pass).  No token and no bit of any row changes:
    a joiner's second token is delivered one tick later.  The key chain is
    the staged tick's (one split for the first tokens, one for the step)."""
    from ipex_llm_tpu.ops.sampling import sample_rows_with_logprobs

    p_tokens, p_tables, p_base, p_nvalid, p_emit, _, p_rowmap = prefill
    alive = active
    with jax.named_scope("tick/decode"):
        # a dead row's write goes to the scratch page and its attention
        # walks no page, as in ``_decode_horizon_loop``'s step
        write_at = jnp.where(
            alive, row_lens,
            h2d(cache.tables.shape[1] * cache.page_size, jnp.int32))
        (p_logits, logits), cache = forward_blocks(cfg, params, cache, (
            TokenBlock(p_tokens,
                       p_base[:, None] + jnp.arange(p_tokens.shape[1])[None],
                       p_base, p_nvalid, p_tables,
                       jnp.maximum(p_nvalid - 1, 0)),
            TokenBlock(toks[:, None], row_lens[:, None], write_at,
                       alive.astype(jnp.int32), cache.tables)))
        key, sub = jax.random.split(key)
        first_t, first_lp = sample_rows_with_logprobs(
            p_logits, temps[p_rowmap], top_ps[p_rowmap], sub,
            seeds=seeds[p_rowmap], steps=jnp.zeros_like(p_nvalid),
            top_ks=top_ks[p_rowmap], active=p_emit)
        key, sub = jax.random.split(key)
        nxt, lp = sample_rows_with_logprobs(
            logits, temps, top_ps, sub, seeds=seeds, steps=steps,
            top_ks=top_ks, active=alive)
        hit_eos = (nxt[:, None] == eos).any(axis=1) & alive
        adv = alive.astype(jnp.int32)
        row_lens = row_lens + adv
        steps = steps + adv
        remain = remain - adv
        active = alive & ~hit_eos & (remain > 0)
        toks = jnp.where(active, nxt, toks)
    with jax.named_scope("tick/merge"):
        # AFTER the step: a prefill row was not alive above, so the step
        # left it untouched
        toks, row_lens, active, steps, remain, _ = _merge_wave(
            prefill, first_t, toks, row_lens, active, steps, remain, eos)
    return (first_t, first_lp, nxt[:, None], lp[:, None],
            jnp.asarray(1, jnp.int32), cache, toks, row_lens, active, steps,
            remain, key)


# Donation covers the cache AND every dead-after-call piece of the
# device-resident row state (toks/row_lens/active/steps/remain): the host
# rebinds its _dev handles to the returned arrays each call, so the
# inputs alias their advanced outputs instead of being copied per tick.
# temps/top_ps/seeds/top_ks/eos are HELD — the host re-passes the same
# buffers until the next epoch upload — and must never be donated.  The
# PRNG key is held too, less obviously: _checkpoint snapshots self.key BY
# REFERENCE for the bit-identical transient-retry contract, so donating
# it would hand _rollback a deleted buffer whenever a fault lands after
# the dispatch (the d2h sync is exactly where async XLA faults surface).
# ``hist`` (the speculative token history, spec_k > 0 only) is
# device-resident dead-after-call state like toks — the host rebinds
# _dev["hist"] to the returned buffer — so it donates by name.  The
# packed epoch (``prefill``) and ``spec_ks`` are fresh per-tick uploads,
# too small to matter; a tick that takes the packed epoch takes no row
# state in (those arguments are None), so it has only the cache to
# donate.  JP101 locks both directions.
def _tick_body(cfg: ModelConfig, params, cache, toks, row_lens,
               active, temps, top_ps, key, seeds, steps, top_ks,
               eos, remain, prefill=None, horizon: int = 1,
               with_decode: bool = True, hist=None, spec_ks=None,
               spec_k: int = 0, spec_ngram: int = 3, state_src=None):
    """The fused tick BODY (see ``_ragged_tick`` for the contract):
    traced either directly under GSPMD dispatch, or — the manual-mesh
    serving form — once per shard inside ``parallel/manual.tp_tick``'s
    fully-manual shard_map region with a shard-local cfg/params/pool."""
    from ipex_llm_tpu.ops.sampling import sample_rows_with_logprobs

    r = toks.shape[0]
    first_t = first_lp = None
    if state_src is not None:
        # a hybrid model's recurrent state: READ from the committed buffer
        # (``state_src``, not donated: the tick's checkpoint holds it for a
        # rollback) and WRITTEN to the spare one (``cache.state``,
        # donated).  A steady tick touches every layer's slots exactly
        # once, so each layer reads one buffer and writes the other and
        # nothing is copied; any other tick copies once, then works in
        # place.
        if prefill is None and horizon == 1 and with_decode:
            cache = replace(cache, state=dict(
                cache.state, stats=state_src["stats"], src=state_src))
        else:
            # INTO the donated buffers (a fresh copy would be a third state
            # on the device beside the source and the unused spare)
            cache = replace(cache, state=jax.tree_util.tree_map(
                lambda dst, src: jax.lax.dynamic_update_slice(
                    dst, src, (0,) * dst.ndim), cache.state, state_src))
    if prefill is not None:
        (p_tokens, p_tables, p_base, p_nvalid, p_emit, p_canjoin,
         p_rowmap) = prefill
        w = p_tokens.shape[1]
        with jax.named_scope("tick/prefill"):
            row_cache = replace(cache, tables=p_tables)
            pos = p_base[:, None] + jnp.arange(w)[None, :]
            logits, row_cache = decoder_forward(
                cfg, params, p_tokens, row_cache, pos,
                slot_offsets=p_base,
                gather_positions=jnp.maximum(p_nvalid - 1, 0),
                chunk_lens=p_nvalid,
                state_rows=p_rowmap if cfg.recurrent_layers else None,
            )
            cache = replace(cache, k=row_cache.k, v=row_cache.v,
                            state=row_cache.state)
            key, sub = jax.random.split(key)
            first_t, first_lp = sample_rows_with_logprobs(
                logits, temps[p_rowmap], top_ps[p_rowmap], sub,
                seeds=seeds[p_rowmap], steps=jnp.zeros_like(p_nvalid),
                top_ks=top_ks[p_rowmap], active=p_emit)
        with jax.named_scope("tick/merge"):
            toks, row_lens, active, steps, remain, hist = _merge_wave(
                prefill, first_t, toks, row_lens, active, steps, remain,
                eos, hist if spec_k > 0 else None)
    with jax.named_scope("tick/decode"):
        if with_decode and spec_k > 0:
            (tok_block, lp_block, n_exec, cache, toks, row_lens, active,
             steps, remain, key, take_block, hist, prop,
             acc) = _decode_horizon_loop(
                cfg, params, cache, toks, row_lens, active, temps,
                top_ps, key, seeds, steps, top_ks, eos, remain, horizon,
                hist=hist, spec_ks=spec_ks, spec_k=spec_k,
                spec_ngram=spec_ngram)
        elif with_decode:
            (tok_block, lp_block, n_exec, cache, toks, row_lens, active,
             steps, remain, key) = _decode_horizon_loop(
                cfg, params, cache, toks, row_lens, active, temps,
                top_ps, key, seeds, steps, top_ks, eos, remain, horizon)
        else:
            tok_block = jnp.zeros((r, horizon), jnp.int32)
            lp_block = jnp.zeros((r, horizon), jnp.float32)
            n_exec = jnp.asarray(0, jnp.int32)
    if cache.state is not None:
        # the state's counters ride the fetch the host already makes
        n_exec = jnp.concatenate([n_exec[None], cache.state["stats"]])
    if spec_k > 0:
        return (first_t, first_lp, tok_block, lp_block, n_exec, cache,
                toks, row_lens, active, steps, remain, key, take_block,
                hist, prop, acc)
    return (first_t, first_lp, tok_block, lp_block, n_exec, cache, toks,
            row_lens, active, steps, remain, key)


@partial(jax.jit,
         static_argnames=("cfg", "horizon", "with_decode", "spec_k",
                          "spec_ngram", "mesh", "tp_manual",
                          "collective_qtype", "epoch"),
         donate_argnums=(2, 3, 4, 5, 10, 13), donate_argnames=("hist",),
         compiler_options=_EXACT_ROUNDING)
def _ragged_tick(cfg: ModelConfig, params, cache, toks, row_lens,
                    active, temps, top_ps, key, seeds, steps, top_ks,
                    eos, remain, prefill=None, horizon: int = 1,
                    with_decode: bool = True, hist=None, spec_ks=None,
                    spec_k: int = 0, spec_ngram: int = 3, mesh=None,
                    tp_manual: bool = False,
                    collective_qtype: str = "bf16", state_src=None,
                    epoch: EpochLayout | None = None):
    """ONE device program per engine tick, whatever the admission mix —
    the ragged-paged-attention superkernel tick (ROADMAP item 1; the
    JP106 gate counts exactly this entry).

    Internally three fused stages, each optional per the tick's shape:

    0. **the epoch** (``prefill`` is not None): a tick with a prefill
       block is an epoch, and ``prefill`` is the host's PACKED EPOCH
       (serving/epoch.py): one int32 buffer, one upload, laid out by the
       static ``epoch``.  Under scope ``tick/epoch`` the program takes it
       apart, REPLACES the block table and the row state with what the
       host sent (the row-state arguments are None then: nothing is
       carried into an epoch), and gathers the prefill rows' table view
       itself, cut to ``epoch.p_maxp`` pages.  The arrays no tick
       advances (``temps``, ``top_ps``, ``seeds``, ``top_ks``, ``eos``)
       come back out as one more, last, element of the result, for the
       host to re-pass until the next epoch.
    1. **ragged prefill**: the batched ragged chunk forward over the
       prefilling rows — ``(p_tokens [P, W], p_tables [P, p_maxp],
       p_base [P], p_nvalid [P], p_emit [P], p_canjoin [P], p_rowmap
       [P])``, a row-sliced table view plus the map from prefill slot to
       engine row (pad slots carry ``p_rowmap == R`` so their scatters
       drop).  Attention rides the per-row ``chunk_lens`` causal contract
       of ops/pallas/ragged_paged_attention.py, and the per-row
       last-valid hidden gather (``gather_positions``) is fused in.
    2. **first-token sampling + state merge**: rows whose prompt
       completes this tick (``p_emit``) sample their first token here —
       fold_in(seed, 0), output index 0 of the row's stream — and join
       the decode state ON DEVICE exactly as a host epoch upload would
       publish them (toks=first, steps=1, remain-=1, active unless the
       first token hit EOS / exhausted the budget / ``p_canjoin`` says
       the host could not back the decode KV slot).  Every prefill row's
       device length advances to its true value, so the steady tick that
       follows a pure-chunk tick needs no epoch of its own.
    3. **the fused decode horizon** (``with_decode``):
       ``_decode_horizon_loop`` over the merged state; a steady-state
       tick (prefill=None) is this stage alone.

    ``with_decode=False`` (a pure-chunk tick with no decoding rows)
    skips stage 3 entirely: no wasted all-masked forward, and the key
    chain only advances by the prefill split.

    A DENSE model's mixed tick (``fuses_mixed_tick``: one decode step, no
    speculation, no mesh) runs stages 1-3 as ONE forward
    (``_fused_mixed_body``): the block's tokens and the decode rows' ride
    one token axis, every linear is called once, and the merge follows the
    step, so a completing row takes its first decode step in the next
    tick.  Same arguments, same results, same one dispatch.

    4. **the way home** (scope ``tick/home``): everything the HOST reads
       of the tick — steps executed, the [R, H] token and log-probability
       blocks, with a prefill block first_t [P] and first_lp [P] — is
       packed into ONE int32 buffer (serving/epoch.py ``ResultLayout``,
       whose slots follow from these shapes; floats as their bits), so it
       crosses in one transfer.  Returns (home, cache, toks, row_lens,
       active, steps, remain, key): the buffer, then what stays on the
       device.

    ``spec_k > 0`` (static) runs stage 3 as the SPECULATIVE horizon loop
    (``_spec_horizon_loop``: on-device draft from ``hist``, [R, spec_k+1]
    verify, on-device acceptance — still ONE dispatch, JP106 unchanged);
    stage 2 additionally publishes a completing row's first token into
    ``hist`` so a prompt that finishes this tick can speculate on its
    very first decode iteration.  The blocks are [R, H, spec_k+1] then,
    ``home`` also holds take_block [R, H], draft_proposed and
    draft_accepted, and the return tuple extends to (..., key, hist).

    ``state_src``: a hybrid model's committed recurrent state (kv.py), the
    one argument of the tick that is neither donated nor rebound: the tick
    reads it and writes ``cache.state`` (see ``_tick_body``); ``home``'s
    n_exec slot then carries [steps executed, the state's counters].
    """
    from ipex_llm_tpu.ops import dispatch

    held = ()
    if prefill is not None:
        with jax.named_scope("tick/epoch"):
            e = epoch.unpack(prefill)
            cache = replace(cache, tables=e["tables"])
            (toks, row_lens, active, temps, top_ps, seeds, steps, top_ks,
             eos, remain) = (e[k] for k in (
                 "toks", "row_lens", "active", "temps", "top_ps", "seeds",
                 "steps", "top_ks", "eos", "remain"))
            # a pad slot (``p_rowmap == R``) reads row 0's table: its base
            # lies past the table and every write it makes goes to the
            # scratch page
            p_row = jnp.where(e["p_rowmap"] < epoch.rows, e["p_rowmap"], 0)
            prefill = (e["p_toks"], e["tables"][p_row][:, :epoch.p_maxp],
                       e["p_base"], e["p_nvalid"], e["p_emit"],
                       e["p_canjoin"], e["p_rowmap"])
            held = (tuple(e[k] for k in HELD_FIELDS),)
    if tp_manual:
        from ipex_llm_tpu.parallel.manual import tp_tick

        out = tp_tick(
            _tick_body, cfg, mesh, collective_qtype, params, cache,
            (toks, row_lens, active, temps, top_ps, key, seeds, steps,
             top_ks, eos, remain),
            prefill=prefill, horizon=horizon, with_decode=with_decode,
            hist=hist, spec_ks=spec_ks, spec_k=spec_k,
            spec_ngram=spec_ngram)
    elif fuses_mixed_tick(cfg, horizon, with_decode and prefill is not None,
                          spec_k, mesh):
        out = _fused_mixed_body(
            cfg, params, cache, toks, row_lens, active, temps, top_ps, key,
            seeds, steps, top_ks, eos, remain, prefill)
    else:
        with dispatch.spmd(mesh):
            out = _tick_body(
                cfg, params, cache, toks, row_lens, active, temps, top_ps,
                key, seeds, steps, top_ks, eos, remain, prefill=prefill,
                horizon=horizon, with_decode=with_decode, hist=hist,
                spec_ks=spec_ks, spec_k=spec_k, spec_ngram=spec_ngram,
                state_src=state_src)
    # outside the manual region, where every result is replicated: what
    # the host reads of this tick leaves as ONE buffer
    first_t, first_lp, tok_block, lp_block, n_exec = out[:5]
    home = {"n_exec": n_exec, "tok": tok_block, "lp": lp_block}
    if prefill is not None:
        home.update(first_t=first_t, first_lp=first_lp)
    carried = out[5:12]
    if spec_k > 0:
        take_block, hist, prop, acc = out[12:]
        home.update(take=take_block, prop=prop, acc=acc)
        carried += (hist,)
    with jax.named_scope("tick/home"):
        home = ResultLayout.of(home).pack(**home)
    return (home,) + carried + held


class ServingEngine:
    """Threaded continuous-batching engine around one model."""

    def __init__(self, cfg: ModelConfig, params: dict,
                 engine_config: EngineConfig | None = None,
                 default_eos: tuple[int, ...] = (),
                 mesh=None, fault_injector: FaultInjector | None = None,
                 weight_imatrix: dict | None = None):
        """``mesh``: a ``jax.sharding.Mesh`` for TP serving — params are
        placed under the AutoTP rules and the paged pool's kv heads are
        sharded, the reference's vLLM-TP-worker serving mode
        (vllm/xpu/engine/engine.py:40) expressed as SPMD instead of Ray
        workers.  None = single-chip (the r3 behaviour).

        ``fault_injector``: a ``faults.FaultInjector`` whose scripted
        exceptions fire at the engine's guarded sites — the deterministic
        test/chaos harness for the fault-domain layer.

        ``weight_imatrix``: optional llama.cpp importance-matrix dict
        (quantize/imatrix.load_imatrix) calibrating the
        ``EngineConfig.weight_qtype`` repack — the reference's
        ``ggml_quantize_tensor_with_weights`` path, applied at engine
        build."""
        if cfg.rope_2d:
            # chatglm v1 block positions need each row's prompt boundary
            # threaded through every step; generate() supports it, the paged
            # engine does not (a 2018-era model is not a serving target)
            raise NotImplementedError(
                "2D-rope (chatglm v1) models are generate()-only")
        if "embed" not in params:
            # disk_embedding models keep the table in host RAM; the jitted
            # engine step cannot host-gather per token (model.py:186)
            raise NotImplementedError(
                "disk_embedding (streamed host table) models are "
                "generate()-only — the paged engine needs the embed table "
                "in HBM")
        self.cfg = cfg
        self.ec = engine_config or EngineConfig()
        if self.ec.spec_k > 0 and self.ec.spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1 when spec_k > 0")
        if self.ec.decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        if mesh is not None and mesh.shape.get("pp", 1) > 1:
            raise ValueError(
                f"mesh has pp={mesh.shape['pp']}: serving shards over tp "
                "(tensor_parallel_size); parallel/pipeline.py's "
                "pipeline_forward remains for training and generate()")
        if self.ec.kv_pool_bytes < 0:
            raise ValueError("kv_pool_bytes must be >= 0 (0 = size the "
                             "pool in pages via pool_pages)")
        if self.ec.kv_spill_bytes < 0:
            raise ValueError("kv_spill_bytes must be >= 0 (0 disables "
                             "the host-RAM KV spill tier)")
        # KV storage axis: bytes ONE page costs at this model shape and
        # storage width — the unit kv_pool_bytes divides by (validates
        # kv_storage, raising with the valid names)
        # (a latent-attention model's page holds the latent, one row a
        # token shared by every head: kv.py ``pool_geometry``)
        self._index_page_bytes = index_page_bytes(cfg, self.ec.page_size)
        self.page_bytes = model_page_bytes(cfg, self.ec.page_size,
                                           self.ec.kv_storage)
        if self.ec.kv_pool_bytes:
            # byte-budgeted pool: capacity in pages follows the storage
            # width (fp8 pages are half the bytes => twice the pages)
            pages = self.ec.kv_pool_bytes // self.page_bytes
            floor = self.ec.max_rows + 2   # one page per row + scratch
            if pages < floor:
                # refuse rather than silently overshoot the operator's
                # explicit byte cap: the budget cannot even back one page
                # per row — shrink max_rows, the page size, or the model,
                # or switch to fp8 storage (half the bytes per page)
                raise ValueError(
                    f"kv_pool_bytes={self.ec.kv_pool_bytes} holds only "
                    f"{pages} {self.ec.kv_storage} pages of "
                    f"{self.page_bytes} bytes — max_rows={self.ec.max_rows}"
                    f" needs at least {floor} ({floor * self.page_bytes} "
                    f"bytes)")
            self.ec = replace(self.ec, pool_pages=pages)
        # weight-quantization axis: re-pack native-width linear weights
        # into block-quantized planes BEFORE device placement/sharding
        # (shard_params stamps tp_mode on whatever planes it is handed).
        # Already-low-bit trees pass through untouched; an unknown or
        # non-requantizable qtype raises here, before any pool allocates.
        from ipex_llm_tpu.models.build import param_bytes, requantize_params

        if self.ec.weight_qtype is not None:
            params = requantize_params(params, self.ec.weight_qtype,
                                       imatrix_data=weight_imatrix)
        # weight byte accounting for /health's weights block: what the
        # tree costs as stored vs at bf16
        # full width, plus the packed formats actually present (an
        # already-quantized tree reports its real width even when
        # weight_qtype is None)
        self._weight_bytes, self._weight_dense_bytes = param_bytes(params)
        from ipex_llm_tpu.quantize.core import QTensor as _QT
        from ipex_llm_tpu.quantize.qtypes import resolve as _qresolve

        self._weight_qtypes = tuple(sorted({
            leaf.qtype for leaf in jax.tree_util.tree_leaves(
                params, is_leaf=lambda x: isinstance(x, _QT))
            if isinstance(leaf, _QT)
            and _qresolve(leaf.qtype).kind != "native"}))
        # the SERVED width (what /health's weights.qtype reports): the
        # configured axis when it matches the planes; the planes' own
        # format when the tree arrived already packed at a different (or
        # no configured) width — the axis is a request, the planes are
        # the truth.  A mismatching explicit request warns loudly: the
        # pass-through is by design (requantizing packed codes stacks
        # error), but the operator asked for a width they are not getting.
        # canonical name from the start: an alias axis ("woq_int4",
        # "fp8") must report the format the planes actually carry
        resolved = (_qresolve(self.ec.weight_qtype).name
                    if self.ec.weight_qtype is not None else None)
        self._served_qtype = resolved
        if self._weight_qtypes:
            if len(self._weight_qtypes) > 1:
                # more than one packed width in the tree (mixed-precision
                # int8 head over an int4 body, heterogeneous GGUF): no
                # single name is the served width — even one matching the
                # request — so report "mixed" and let packed_qtypes carry
                # the list
                self._served_qtype = "mixed"
            elif resolved not in self._weight_qtypes:
                # the request (or its absence) names no plane actually in
                # the tree: report the one format that IS served
                self._served_qtype = self._weight_qtypes[0]
            if resolved is not None and resolved not in self._weight_qtypes:
                import warnings

                warnings.warn(
                    f"weight_qtype={self.ec.weight_qtype!r} requested but "
                    f"the param tree is already packed as "
                    f"{list(self._weight_qtypes)} — requantizing packed "
                    "codes would stack quantization error, so the tree "
                    "serves as-is (/health's weights block reports the "
                    "served width)", stacklevel=2)
        elif resolved is not None \
                and _qresolve(resolved).kind != "native":
            # a packed width was requested but nothing packed: the tree
            # holds plain-array weights (a dequantized/dense twin), which
            # the repack does not cover — it cannot tell a linear weight
            # from an embed table in a bare array.  Report the truth
            # (nothing is served at that width) and say so.
            self._served_qtype = None
            import warnings

            warnings.warn(
                f"weight_qtype={self.ec.weight_qtype!r} requested but the "
                "param tree carries no quantizable QTensor leaves (plain "
                "arrays repack does not cover) — serving full width; "
                "build the tree through models/build (or load_in_low_bit)"
                " for a packable tree", stacklevel=2)
        self.default_eos = default_eos
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        r = self.ec.max_rows
        if cfg.is_mla and mesh is not None and mesh.size > 1:
            raise ValueError(
                f"model_type {cfg.model_type!r} attends over a latent "
                "(MLA) pool: a mesh is not supported, the latent row"
                + (" and the indexer's key are" if cfg.index_topk
                   else " is")
                + " shared by every head and neither tick shards "
                + ("them" if cfg.index_topk else "it")
                + " (parallel/manual.py)")
        cache = PagedKVCache.for_model(
            cfg, self.ec.n_pages, r, self.ec.max_pages, self.ec.page_size,
            storage=self.ec.kv_storage)
        # a hybrid model's second kind of state, beside the pool in the
        # same cache object: per-ROW recurrent slots.  Two buffers: the
        # committed one in ``cache.state`` and a spare the next tick writes
        # (``_state_call``), so the state a tick started from stays whole
        # until the tick commits.  ``_state_held``: the states the programs
        # in flight started from, oldest first (one in the serial order);
        # ``_state_free``: the spares.  What recurrent state cannot do yet
        # is refused here by name (derived from the model, no config field)
        self._recurrent = cfg.recurrent_layers > 0
        self._state_free: list = []
        self._state_held: list = []
        self._state_counters = state_counters(cfg)
        self._stats_seen = np.zeros((len(self._state_counters),), np.uint32)
        if self._recurrent:
            self._refuse_for_recurrent_state(cfg, mesh)
        # (a model whose expert layers hold a share of their experts and
        # has no Mamba-2 block carries the device counters alone, the same
        # way: two buffers of five numbers)
        cache = with_recurrent_state(cache, cfg, r)
        self._stateful = cache.state is not None
        if self._stateful:
            self._state_free.append(recurrent_state(cfg, r))
        # multi-chip serving: on a PURE-tp mesh whose shapes divide, the
        # engine takes the MANUAL tick — the whole fused tick inside one
        # fully-manual shard_map region (parallel/manual.py), per-shard
        # pools and explicit quantized collectives, GSPMD out of the loop.
        # Anything the manual layout does not cover (composed meshes, MoE,
        # MLA, non-dividing heads) falls back to the per-op GSPMD path,
        # with the reason recorded for /health-side debugging.
        self._tp_manual = False
        self._tp_fallback_reason: str | None = None
        from ipex_llm_tpu.ops import collectives

        # config wins, then the IPEX_LLM_TPU_COLLECTIVE_QTYPE env, then
        # the exact family; raises on an unknown family name
        self._collective_qtype = collectives.resolve_qtype(
            self.ec.collective_qtype)
        if self.mesh is not None:
            from ipex_llm_tpu.parallel import manual
            from ipex_llm_tpu.parallel.shard import (shard_paged_cache,
                                                     shard_params)

            reason = manual.ineligible_reason(cfg, params, self.mesh)
            if reason is None:
                params = manual.shard_params_manual(params, cfg,
                                                    self.mesh)
                self._tp_manual = True
            else:
                # re-placing already-sharded params is an idempotent
                # device_put
                params = shard_params(params, self.mesh)
                self._tp_fallback_reason = reason
            cache = shard_paged_cache(cache, self.mesh)
        self.params = params
        self.cache = cache
        # the per-tick prefill token budget of the admission wave
        # whether a mixed tick's table view is cut to a power-of-two bucket
        # of the pages in use: only where attention GATHERS the table's
        # width (the XLA path: the CPU, a mesh).  Where the page walk runs
        # the width costs nothing (the walk is bounded by the live pages),
        # and every bucket a prompt can reach is one more variant of every
        # mixed tick to compile: at 4096-token prompts six buckets made 76
        # programs and 1,650 s of warm-up (my chip run, PR 37)
        from ipex_llm_tpu.ops import dispatch

        self._slice_tables = self.ec.max_pages > 8 and not (
            self.mesh is None and dispatch.use_pallas())
        self._step_budget = (self.ec.prefill_bucket
                             if self.ec.step_token_budget is None
                             else int(self.ec.step_token_budget))
        # host-RAM KV spill tier: evicted prefix pages (and finished
        # rows' decode pages) demote here instead of being lost, and
        # swap back on a prefix hit (serving/pagestore.py)
        self.pagestore = None
        if self.ec.kv_spill_bytes > 0:
            from ipex_llm_tpu.serving.pagestore import PageStore

            self.pagestore = PageStore(self.ec.kv_spill_bytes)
        self.alloc = PageAllocator(
            self.ec.n_pages,
            spill=self._spill_pages if self.pagestore is not None else None)
        self.tables = np.full((r, self.ec.max_pages), -1, np.int32)
        # every host-side mutation of ``self.tables`` sets this, and the
        # next packed epoch carries the WHOLE table over (a few KB: cheaper
        # than any dirty-row bookkeeping on the device).
        # PagedKVCache.init and self.tables both start all -1, so host and
        # device are in sync from construction.
        self._tables_dirty = False
        self.rows: list[Request | None] = [None] * r
        self.row_lens = np.zeros((r,), np.int32)
        self.row_budget = np.zeros((r,), np.int32)
        self.toks = np.zeros((r,), np.int32)
        self.temps = np.zeros((r,), np.float32)
        self.top_ps = np.ones((r,), np.float32)
        self.seeds = np.full((r,), -1, np.int32)
        self.top_ks = np.zeros((r,), np.int32)
        # chunked prefill: rows still consuming their prompt
        self._prefilling: dict[int, np.ndarray] = {}  # row -> remaining ids
        self._row_keys: dict[int, list[bytes]] = {}   # row -> prefix hashes
        # under a mesh, the packed epoch and the PRNG key are uploaded
        # replicated over it: that is the sharding the tick hands them
        # back in, so an upload and a carried array present the same jit
        # signature and each grid point compiles once, not twice
        self._replicated = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            self._replicated = NamedSharding(self.mesh, PartitionSpec())
        self.key = jax.device_put(jax.random.PRNGKey(0), self._replicated)
        self._inbox: "queue.Queue[Request]" = queue.Queue()
        # engine-thread host operations (KV page-set export/import):
        # closures enqueued here run BETWEEN transactional ticks on the
        # engine thread — over committed state, with exclusive access to
        # the pool/allocator/prefix cache — via run_on_engine().  Gathers
        # and scatters they perform are epoch-boundary work, never tick
        # work (JP106's one-dispatch tick is untouched).
        self._host_ops: "queue.Queue[tuple]" = queue.Queue()
        # host-side FIFO the engine thread owns: submissions drain from the
        # (cross-thread) inbox into this deque, admission pops its head,
        # and a pool-dry requeue puts the head BACK AT THE HEAD — the old
        # inbox.put() requeue rotated it behind later arrivals (the same
        # bug class as the _wait_for_work peek fix).  Being engine-owned
        # it also checkpoints/rolls back with the rest of the tick state.
        self._pending: "deque[Request]" = deque()
        self._work = threading.Event()   # set on submit: idle-loop wakeup
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # fault domain: the scripted fault source (tests), the
        # per-tick emission staging buffer (client-visible queue puts are
        # deferred until the tick commits, so a rolled-back tick never
        # leaks a token), requests masked out of the step during bisection
        # probes, arrivals drained mid-transaction (re-appended on
        # rollback so they are never lost), and the transient-retry and
        # drain lifecycle state.
        self.injector = fault_injector
        self._staging: list[tuple["queue.Queue", int | None]] | None = None
        self._masked: set[str] = set()
        self._tick_arrivals: list[Request] = []
        self._retries = 0
        # a bisection probe is running: it re-runs the tick in the serial
        # order, as a retry does
        self._serial = False
        # the ONE steady tick program queued behind the one being awaited
        # (``_queue_next``), read by the next tick; None in the serial order
        self._ahead: _Queued | None = None
        # device dispatches issued by the current tick (flight-recorder
        # bookkeeping; the JP106 audit pins the fused tick's at 1)
        self._tick_dispatches = 0
        self._draining = False
        self._drain_deadline: float | None = None
        self._drain_abort = threading.Event()
        # device-resident hot state (toks / row_lens / active / sampling
        # params / eos / budgets): uploaded ONLY on epochs — admission,
        # prefill progress, finish, page allocation — and otherwise carried
        # forward on device by the fused decode step.  ``_dirty`` marks
        # that host-side state diverged from the device copies.
        self._dev: dict[str, jnp.ndarray] | None = None
        self._dirty = True
        # request-lifecycle tracing (observe.py): None unless the config
        # enables it — every trace site below guards on that None, so the
        # disabled engine pays one attribute check per site.  Spans stage
        # in _span_staging during a transactional tick and flush only on
        # _commit (the _queue_put discipline applied to telemetry).
        self.tracer = (Tracer(self.ec.trace_buffer)
                       if self.ec.trace_requests else None)
        self._span_staging: list[tuple[str, dict]] = []
        # tick flight recorder: always on (one small dict per committed
        # working tick); _fail_all and quarantine freeze it automatically
        self.flight = FlightRecorder(self.ec.flight_ring)
        # recovery-evidence baselines: retries and injector hits land
        # BETWEEN records (the failed tick rolls back and never records,
        # _recover bumps afterwards, and the next checkpoint absorbs the
        # bump into its m0) — so per-record deltas key off the last
        # RECORDED tick, not the per-tick checkpoint, or the ring would
        # show retries=0 and no fault_sites for exactly the faults it
        # exists to explain
        self._flight_retries0 = 0
        self._flight_hits0: dict = {}
        # honest latency histograms (fixed Prometheus buckets, fleet-
        # summable, checkpoint/rollback-safe): TTFT, client-visible
        # inter-token latency (bursty by design under a fused horizon —
        # the distribution SHOWS the H-token delivery granularity),
        # blocking tick-sync time, and swap-in measured through the
        # completion barrier (the vacuous enqueue-only p95 fix)
        self.hists: dict[str, Histogram] = {
            "ttft_s": Histogram(LATENCY_BUCKETS_S),
            "token_latency_s": Histogram(LATENCY_BUCKETS_S),
            "tick_sync_s": Histogram(FAST_LATENCY_BUCKETS_S),
            "swap_in_s": Histogram(FAST_LATENCY_BUCKETS_S),
        }
        # device-time observatory (serving/perfwatch.py): attribution
        # histograms register into self.hists so checkpoint/rollback and
        # the committed /metrics exposition cover them for free; the
        # manifest + audit-model flops give the MFU join its cost basis
        # (a stripped install without the analysis package keeps serving
        # — the sentinel then counts compiles without grid membership
        # and the MFU join reports None).
        self.perf = None
        self._perf_asserted = False
        if self.ec.perfwatch:
            from ipex_llm_tpu.serving.perfwatch import (PerfWatch,
                                                        model_flops_per_token)

            manifest = None
            scales: dict[str, float] = {}
            try:
                from ipex_llm_tpu.analysis.trace import manifest as _mf
                from ipex_llm_tpu.analysis.trace.registry import (
                    audit_cfg, audit_cfg_tp)

                loaded = _mf.load()
                mine = model_flops_per_token(cfg)
                scales = {
                    "bf16": mine / model_flops_per_token(audit_cfg("bf16")),
                    "sym_int4": mine / model_flops_per_token(
                        audit_cfg("sym_int4")),
                    "tp": mine / model_flops_per_token(audit_cfg_tp()),
                }
                # assigned only once the scales computed: a manifest
                # without its model scale would join MFU at scale 1.0 —
                # the audit model's flops reported as this model's,
                # silently wrong by orders of magnitude (None is the
                # honest degraded mode)
                manifest = loaded
            except Exception:
                scales = {}
            self.perf = PerfWatch(hists=self.hists, manifest=manifest,
                                  flops_scales=scales)
        # the COMMITTED view /metrics serves: `self.hists` mutates
        # mid-tick and reverts on rollback, so a scrape reading it live
        # could observe counts a rollback then subtracts — a Prometheus
        # counter going backwards reads as a reset and fabricates rates.
        # _commit republishes this dict (atomic reference swap; the
        # published Histograms are never mutated after publication).
        self._hists_committed: dict[str, Histogram] = {
            k: h.copy() for k, h in self.hists.items()}
        # rolling TTFT window for /health (what the admission-wave mixed
        # step is judged on)
        self._ttfts: "deque[float]" = deque(maxlen=128)
        # rolling speculative-acceptance window for /health: per-tick
        # (drafts proposed, drafts accepted) pairs — checkpoint/rollback-
        # safe like the TTFT window, so a retried tick never double-counts
        self._spec_window: "deque[tuple[int, int]]" = deque(maxlen=128)
        self.metrics = {"requests": 0, "tokens": 0, "steps": 0,
                        # committed transactional ticks — monotonic even
                        # when idle (the loop keeps ticking), so a frozen
                        # value with uptime advancing is the router's
                        # wedged-replica liveness signal (/health replica
                        # block)
                        "ticks": 0,
                        "prefix_hits": 0, "prefix_pages_shared": 0,
                        # host-sync economics (the fused-horizon story):
                        # decode iterations per blocking device->host sync,
                        # seconds spent blocked, uploads of row state
                        "host_syncs": 0, "host_sync_s": 0.0,
                        "tokens_per_sync": 0.0, "epoch_syncs": 0,
                        # every host->device transfer the engine thread
                        # makes inside a tick, before its dispatch: one for
                        # a packed epoch, none for a steady tick with no
                        # news (beside: a speculating tick's draft widths
                        # and token history, a swap-in's pages)
                        "h2d_total": 0,
                        # its mirror: every device->host fetch the engine
                        # thread makes inside a tick (three a steady tick,
                        # five a mixed one with a completion; a spill's
                        # pages beside it)
                        "d2h_total": 0,
                        # committed ticks whose first fetch waited over
                        # perfwatch.SLOW_SYNC_WAIT_S for the program to
                        # end: each froze the flight ring (``slow_tick``)
                        "slow_ticks_total": 0,
                        "decode_horizon_effective": 0,
                        # admission-wave economics (the mixed-step story):
                        # mixed ticks run, prompt tokens prefilled per
                        # tick, epochs that carried a changed block table,
                        # rolling TTFT p95; of the mixed ticks, those that
                        # made ONE pass over the weights (a dense model's:
                        # ``_fused_mixed_body``)
                        "mixed_steps": 0, "mixed_prefill_tokens": 0,
                        "mixed_fused_steps": 0,
                        "prefill_tokens_per_step": 0.0,
                        "table_row_syncs": 0, "ttft_p95_s": 0.0,
                        # where a first token's wait went, as sums a
                        # scraper divides: submit -> row assigned (at
                        # _admit), row assigned -> first token on the
                        # host (at _finish_prompt), and the count of the
                        # latter
                        "queue_wait_s_total": 0.0,
                        "admit_to_first_token_s_total": 0.0,
                        "first_tokens": 0,
                        # what the attention kernel had to read against
                        # the table width it was handed, in pages, summed
                        # over dispatched ticks (_count_tick_work)
                        "attn_pages_live_total": 0,
                        "attn_pages_table_total": 0,
                        # how often the sampler's stages engage, over
                        # dispatched ticks (_count_sample_stages): sampler
                        # calls (one a decode stage, one a first-token
                        # stage), those with a live row that draws, those
                        # with a live drawing row that set a mask (a sort)
                        "sample_stages_total": 0,
                        "sample_draw_stages_total": 0,
                        "sample_sort_stages_total": 0,
                        # fault-domain observability: per-request failures
                        # isolated by bisection, transient step retries,
                        # load-shed submissions, expired deadlines, and
                        # the current admission backlog
                        "errors_isolated": 0, "retries": 0, "rejected": 0,
                        "timeouts": 0, "queue_depth": 0,
                        # kv-pool pressure: allocation failures that forced
                        # a clamp/fallback (paired with the allocator's
                        # prefix_evictions in /health's kv block)
                        "alloc_fail_clamps": 0,
                        # one program in flight behind the running one:
                        # committed ticks whose program was queued before
                        # its predecessor was read, queued programs thrown
                        # away unread (a rollback, a stop), row-steps one
                        # spent on rows the host had retired meanwhile, and
                        # why a steady tick's successor was NOT queued
                        # (``_ahead_blocked``; a dry pool: ``pages``)
                        "ahead_ticks_total": 0, "ahead_discarded_total": 0,
                        "ahead_stray_steps_total": 0,
                        **{f"ahead_blocked_{why}_total": 0 for why in
                           ("queue", "pages", "dirty", "state", "plan")}}
        # tick planner (serving/planner.py): the decision function that
        # owns the tick's shape.  Constructed last so the initial plan —
        # what a pre-loop _step_once caller runs under — sees a fully
        # built engine; _tick re-plans every fresh tick (retries and
        # bisection probes replay the checkpointed plan instead).
        self.planner = make_planner(self.ec)
        self._plan = self.planner.plan(self)
        # mid-tick evidence that the page-pool safety clamp cut the
        # planned horizon (the flight ring's plan_clamped field); reset
        # per tick attempt
        self._plan_overrun = False
        # device-resident spec token history goes stale when a decode
        # emits through the plain steady program (the planner masking
        # spec off for a tick): the next spec tick forces an epoch
        # re-upload, which rebuilds hist from the host-side ids
        self._hist_stale = False

    # -- public API ---------------------------------------------------------

    def _refuse_for_recurrent_state(self, cfg: ModelConfig, mesh):
        """Engine features a model with per-row recurrent state (Mamba-2
        blocks, Kimi Delta Attention layers) cannot have yet, each refused
        by name at construction; the state's kind is read off the model."""
        kinds = [f"{n} {what}" for n, what in (
            (cfg.ssm_layers, "Mamba-2 blocks"),
            (cfg.kda_layers, "Kimi Delta Attention layers")) if n]
        why = (f"model_type {cfg.model_type!r} carries recurrent state "
               f"({' and '.join(kinds)}): ")
        if self.ec.spec_k > 0:
            raise ValueError(
                why + "spec_k > 0 is not supported: a rejected draft has "
                "already advanced the state, and there is no snapshot to "
                "roll it back to")
        if self.ec.kv_spill_bytes > 0:
            raise ValueError(
                why + "kv_spill_bytes > 0 is not supported: a page of KV "
                "is useless without the state that follows it, and the "
                "spill tier holds no state")
        if mesh is not None and mesh.size > 1:
            raise ValueError(
                why + "a mesh is not supported: neither the state nor the "
                "expert layer is sharded (parallel/manual.py)")

    def _state_call(self) -> tuple:
        """(the cache to hand the tick, its ``state_src``) for a model
        with recurrent state: the state the tick starts from goes in as the
        read-only source and a spare buffer, in the donated cache, is
        written.  ``_state_done`` books the source after the call."""
        if not self._stateful:
            return self.cache, None
        # none left: a dispatch that raised took the spare with it, or (a
        # model that carries the counters alone) a second program is in
        # flight for the first time
        spare = (self._state_free.pop() if self._state_free
                 else recurrent_state(self.cfg, self.ec.max_rows))
        return replace(self.cache, state=spare), self.cache.state

    def _state_done(self, src):
        """After a hybrid tick's dispatch: the written buffer is in
        ``self.cache`` already; the one the program started from is held
        for a rollback until the tick that reads the program commits, and
        is a spare from then on."""
        if src is not None:
            self._state_held.append(src)

    def _state_rewind(self):
        """Every program in flight is given up (a rollback, a discard):
        the state the oldest of them started from is the committed one
        again, and the newest buffer written is the spare."""
        if self._state_held:
            self._state_free.append(self.cache.state)
            self.cache = replace(self.cache, state=self._state_held[0])
            self._state_held = []

    def _count_state(self, n_exec) -> int:
        """Steps executed from the tick's n_exec slot; a hybrid tick's
        also carries the state's device counters, published as deltas (the
        device's are int32 and wrap)."""
        arr = np.asarray(n_exec).reshape(-1)
        if arr.size > 1:
            seen = arr[1:].astype(np.uint32)
            delta = (seen - self._stats_seen).astype(np.uint32)
            self._stats_seen = seen
            for name, d in zip(self._state_counters, delta):
                self.metrics[name + "_total"] = (
                    self.metrics.get(name + "_total", 0) + int(d))
        return int(arr[0])

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=30)

    def submit(self, req: Request) -> Request:
        """Enqueue a request; raises ``EngineOverloaded`` when the engine
        is draining (HTTP surfaces map it to 503) or the bounded queue is
        full (→ 429) — load shedding instead of unbounded backlog."""
        if self._draining:
            self.metrics["rejected"] = self.metrics.get("rejected", 0) + 1
            raise EngineOverloaded("engine is draining",
                                   queue_depth=self.queue_depth,
                                   draining=True)
        depth = self.queue_depth
        if self.ec.max_queue and depth >= self.ec.max_queue:
            self.metrics["rejected"] = self.metrics.get("rejected", 0) + 1
            raise EngineOverloaded(
                f"queue full ({depth} requests waiting)", queue_depth=depth)
        if not req.request_id:
            # quarantine/bisection and injector scoping key on request_id
            req.request_id = uuid.uuid4().hex
        if not req.eos_token_id:
            req.eos_token_id = self.default_eos
        self._inbox.put(req)
        self._work.set()
        return req

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a row (inbox + pending, not in-flight)."""
        return self._inbox.qsize() + len(self._pending)

    def run_on_engine(self, fn, timeout: float = 120.0):
        """Run ``fn()`` on the engine thread BETWEEN transactional ticks
        — over committed state, with exclusive pool/allocator/prefix-
        cache access — and return its result (raising whatever ``fn``
        raised).  The transport surface (export_prefix / import_pages)
        routes through here so its gathers/scatters are epoch-boundary
        work that can never interleave with a half-done tick.  Called
        FROM the engine thread, or with no live engine thread (tests
        driving ``_tick`` directly), it runs inline."""
        t = self._thread
        if (t is None or not t.is_alive()
                or threading.current_thread() is t):
            return fn()
        box: "queue.Queue" = queue.Queue()
        self._host_ops.put((fn, box))
        self._work.set()
        try:
            ok, res = box.get(timeout=timeout)
        except queue.Empty:
            # the engine thread died/stopped with the op still queued
            # (the loop's exit drain failures-out stragglers, but a
            # thread killed hard never reaches it): fail clean instead
            # of leaking a bare queue.Empty to the HTTP handler
            raise RuntimeError(
                "engine did not service the host operation "
                f"within {timeout}s (stopped or wedged)") from None
        if not ok:
            raise res
        return res

    def _drain_host_ops(self):
        """Run queued host operations at the tick boundary: they see
        only committed state, and what they mutate IS committed state
        for the next tick's checkpoint."""
        while True:
            try:
                fn, box = self._host_ops.get_nowait()
            except queue.Empty:
                return
            try:
                box.put((True, fn()))
            except Exception as e:      # delivered to the waiting caller
                box.put((False, e))

    def kv_stats(self) -> dict:
        """KV-pool observability for /health: what
        the pool costs (storage format, page/pool bytes), how full it is,
        and the pressure trace (prefix-cache LRU evictions, allocation
        failures that forced a clamp) — the numbers the fp8-vs-bf16
        fixed-byte-budget story is judged on."""
        a = self.alloc
        out = {
            "storage": self.ec.kv_storage,
            "page_size": self.ec.page_size,
            "pages_total": a.n_pages,       # page 0 = reserved scratch
            "pages_free": len(a.free),
            "pages_in_use": a.pages_in_use,
            "page_bytes": self.page_bytes,
            "pool_bytes": a.n_pages * self.page_bytes,
            # a latent-attention model's page holds ONE row a token a
            # layer, shared by every head (0: the pages hold K and V)
            "latent_bytes_per_token": (
                (self.page_bytes - self._index_page_bytes)
                // (self.ec.page_size * self.cfg.kv_layers)
                if self.cache.latent else 0),
            # a model with an indexer keeps a third kind of page under the
            # same block table: the index keys of its full layers (0: none)
            "index_cache_bytes": a.n_pages * self._index_page_bytes,
            "prefix_pages_cached": len(a.prefix),
            "prefix_evictions": a.prefix_evictions,
            "alloc_fail_clamps": self.metrics.get("alloc_fail_clamps", 0),
            "horizon_clamped": self.metrics.get("horizon_clamped", 0),
            # a hybrid model's recurrent state beside the pool (both
            # buffers); with one, a page of KV is useless without the
            # state that follows it, so the prefix cache is off
            "state_cache_bytes": 2 * state_bytes(self.cache.state),
            # the leaves this family's state carries (kv.recurrent_state)
            "state_leaves": state_leaves(self.cache.state),
            "prefix_cache": ("off: recurrent state" if self._recurrent
                             else "on"),
        }
        # spill-tier block (flat numeric keys ride the replica /metrics
        # exposition and the router's fleet aggregation unchanged; the
        # router's affinity freshness check reads spill_enabled /
        # spill_pages / swap_in_hit_rate to tell a demotion from a loss)
        if self.pagestore is not None:
            out.update(self.pagestore.stats())
        else:
            out["spill_enabled"] = False
        return out

    def weight_stats(self) -> dict:
        """Weight-pool observability for /health's ``weights`` block:
        the serving width axis
        (``EngineConfig.weight_qtype`` plus the packed formats actually
        present in the tree), what the params cost in HBM as stored, what
        the same tree would cost at bf16 full width, and the bytes the
        packing freed — the budget the KV pool is co-planned with
        (``kv_pool_bytes`` + ``weight_bytes`` side by side is the one
        HBM cap an operator provisions).  ``qtype`` is the width actually
        SERVED (derived from the planes when the tree arrived packed at a
        different width than requested — the mismatch warns at build);
        ``requested_qtype`` echoes the config axis."""
        return {
            "qtype": self._served_qtype,
            "requested_qtype": self.ec.weight_qtype,
            "packed_qtypes": list(self._weight_qtypes),
            "weight_bytes": self._weight_bytes,
            "dense_bytes": self._weight_dense_bytes,
            "bytes_saved": self._weight_dense_bytes - self._weight_bytes,
        }

    def spec_stats(self) -> dict:
        """Speculative-decoding observability for /health: lifetime draft
        economics, the rolling accept rate (128-
        tick window — what the operator tunes spec_k/spec_ngram against),
        and tokens emitted per spec-tick dispatch (the amortization the
        on-device loop buys; 0 when spec is off or nothing ran)."""
        m = self.metrics
        win = list(self._spec_window)
        w_prop = sum(p for p, _ in win)
        w_acc = sum(a for _, a in win)
        return {
            "spec_k": self.ec.spec_k,
            "spec_ngram": self.ec.spec_ngram,
            "draft_proposed": m.get("draft_proposed", 0),
            "draft_accepted": m.get("draft_accepted", 0),
            "accept_rate": round(w_acc / w_prop, 4) if w_prop else 0.0,
            "accept_rate_lifetime": m.get("spec_accept_rate", 0.0),
            "tokens_per_dispatch": m.get("spec_tokens_per_dispatch", 0.0),
        }

    # -- observability (serving/observe.py) ---------------------------------

    def _trace(self, req: Request | None, name: str, t0: float | None = None,
               t1: float | None = None, **attrs):
        """Record one lifecycle span/event on ``req``'s trace.  Inside a
        transactional tick the span STAGES (beside the token emissions)
        and flushes only on ``_commit`` — a rolled-back tick never leaks
        a span; outside a tick (recovery, quarantine) it lands directly.
        One ``is None`` check when tracing is disabled."""
        if self.tracer is None or req is None:
            return
        s = span(name, time.time() if t0 is None else t0, t1,
                 origin="engine", **attrs)
        tid = req.trace_id or req.request_id
        if self._staging is not None:
            self._span_staging.append((tid, s))
        else:
            self.tracer.add(tid, s)

    def trace_view(self, trace_id: str) -> dict | None:
        """Assembled span list for one trace (/trace/{id}); None when
        tracing is disabled or the trace aged out of the LRU."""
        if self.tracer is None:
            return None
        return self.tracer.get(trace_id)

    def histograms(self) -> dict[str, Histogram]:
        """The engine's latency histograms (real Prometheus
        ``_bucket/_sum/_count`` series on /metrics; fleet-summed by the
        router).  Returns the last COMMITTED view, not the live tick
        state: mid-tick observations a rollback would subtract are never
        scrape-visible, so the exposed series stay monotonic."""
        return self._hists_committed

    def _count_tick_work(self, active, decode: bool = True, chunks=(),
                         base=None, n_valid=None,
                         prefill_table: int = 0, ahead: int = 0,
                         into: dict | None = None) -> dict:
        """What the tick about to be dispatched asks, from lengths the host
        holds: of a model's hyper-connections in tokens
        (:meth:`_count_mixed_tokens`), of an indexer and its sparse walk
        (:meth:`_count_selection`), and of the attention kernel in pages.
        The attention kernel's: ``attn_pages_live``
        the sum over the rows that attend of ``cdiv(kv_len, page_size)``
        (decode rows at ``row_lens + 1``, prefill chunks at their end),
        ``attn_pages_table`` the rows of each stage times the table width
        handed to it (``prefill_table`` the prefill stage's).  A lower bound
        on what is read: a row that joins the decode stage inside the tick
        and the later steps of a horizon are left out.  ``ahead``: steps
        the decode rows have taken on the device that the host has not
        read (a tick queued behind another).  Added to the
        ``attn_pages_*_total`` counters (rollback-covered with the metrics;
        of a program queued behind another to ``into``, which the tick
        that reads it adds) and returned as the ``engine/dispatch`` span's
        stats."""
        m = self.metrics if into is None else into
        ps = self.ec.page_size
        live, table = 0, prefill_table
        lens = self.row_lens[active].astype(np.int64) + 1 + ahead
        if decode:
            live = int((-(-lens // ps)).sum())
            table += len(self.rows) * self.ec.max_pages
        live += sum(-(-(int(base[i]) + int(n_valid[i])) // ps)
                    for i, _, _ in chunks)
        m["attn_pages_live_total"] = m.get("attn_pages_live_total", 0) + live
        m["attn_pages_table_total"] = (m.get("attn_pages_table_total", 0)
                                       + table)
        out = {"attn_pages_live": live, "attn_pages_table": table}
        if self.cfg.hc_mult > 1:
            out.update(self._count_mixed_tokens(active, decode, chunks,
                                                n_valid, m))
        if self.cfg.index_topk:
            out.update(self._count_selection(lens, decode, chunks, base,
                                             n_valid))
        if self.cfg.kda_layers:
            # what the tick asks of the Kimi Delta Attention layers
            # (ops/kda.py), the same lower bound: tokens through the chunked
            # form in the rows that bring a chunk, rows stepped, each in
            # every KDA layer (perfbench/SPANS_kda.md)
            kda = self.cfg.kda_layers
            out.update(
                kda_chunk_tokens=kda * sum(int(n_valid[i])
                                           for i, _, _ in chunks),
                kda_chunk_rows=kda * len(chunks),
                kda_step_rows=(kda * int(np.count_nonzero(active))
                               if decode else 0))
        return out

    def _count_mixed_tokens(self, active, decode, chunks, n_valid,
                            m: dict) -> dict:
        """What the same tick asks of a model's HYPER-CONNECTIONS
        (ops/hyper.py), the same lower bound: ``mhc_tokens`` the tokens it
        mixes, a decode row one and a chunk its valid ones, in each of a
        layer's two sublayers.  Added to ``mhc_tokens_mixed_total`` of
        ``m`` (the metrics, rollback-covered)."""
        tokens = ((int(np.count_nonzero(active)) if decode else 0)
                  + sum(int(n_valid[i]) for i, _, _ in chunks))
        mixed = tokens * 2 * self.cfg.num_layers
        m["mhc_tokens_mixed_total"] = (
            m.get("mhc_tokens_mixed_total", 0) + mixed)
        return {"mhc_tokens": mixed}

    def _count_selection(self, lens, decode, chunks, base, n_valid) -> dict:
        """What the same tick asks of a model's INDEXER and of its sparse
        walk, from the same lengths (``lens``: the decode rows', their
        step counted), the same lower bound: a query at slot
        ``p`` has ``p + 1`` index keys scored in every layer whose indexer
        is full (``dsa_keys_scored``) and attends ``min(p + 1, index_topk)``
        cached tokens in every layer (``dsa_tokens_selected``; of them
        ``dsa_decode_selected`` by decode rows, which gather a row a token).
        The device's own counts are the ``dsa_*_total`` counters; these ride
        the ``engine/dispatch`` span for the trace's readers."""
        top, kp = self.cfg.index_topk, self.cfg.index_kpool

        def scored(n):
            # (pooled keys: the complete blocks that end before the query)
            return n if kp == 1 else (n - 1) // kp

        def attended(n):
            # (pooled keys: the chosen blocks and the query's own tail)
            return np.minimum(n, top if kp == 1 else top + (n - 1) % kp + 1)

        seen = sel = dec = 0
        if decode:
            seen += int(scored(lens).sum())
            dec = int(attended(lens).sum())
        for i, _, _ in chunks:
            slots = int(base[i]) + 1 + np.arange(int(n_valid[i]),
                                                 dtype=np.int64)
            seen += int(scored(slots).sum())
            sel += int(attended(slots).sum())
        layers = self.cfg.kv_layers
        return {"dsa_keys_scored": seen * self.cfg.index_layers,
                "dsa_tokens_selected": (sel + dec) * layers,
                "dsa_decode_selected": dec * layers}

    def _count_sample_stages(self, *stages, into: dict | None = None):
        """What the tick about to be dispatched asks of the sampler
        (ops/sampling.py decides the same on the device, from the same row
        parameters): each of ``stages`` is the engine rows live in one
        sampler call, or None for a stage the program does not hold.  A
        stage DRAWS if a live row has ``temperature > 0`` and SORTS if such
        a row set ``top_p < 1`` or ``top_k > 0``.  An upper bound by the
        rows a first token ends before they decode, as
        ``_count_tick_work`` is a lower one; rollback-covered with the
        metrics (``into``: as ``_count_tick_work``'s)."""
        m = self.metrics if into is None else into
        for rows in stages:
            if rows is None:
                continue
            rows = np.asarray(rows, np.int64)
            draws = rows[self.temps[rows] > 0]
            for name, n in (
                    ("sample_stages_total", 1),
                    ("sample_draw_stages_total", int(draws.size > 0)),
                    ("sample_sort_stages_total", int(
                        ((self.top_ps[draws] < 1)
                         | (self.top_ks[draws] > 0)).any()))):
                m[name] = m.get(name, 0) + n

    def _perf_dispatch(self, family: str, point: dict | None = None,
                       tick: bool = True, **attrs):
        """Perfwatch timing window around ONE device dispatch (no-op
        context when the observatory is off).  ``tick=True`` windows
        count toward the JP106 runtime cross-check against the
        hand-maintained ``_tick_dispatches`` counter.  ``attrs`` ride on
        the window's ``engine/dispatch`` span."""
        if self.perf is None:
            return nullcontext()
        return self.perf.dispatch(family, point=point, tick=tick, **attrs)

    def _phase(self, name: str, **attrs):
        """One host phase of the tick (``perfwatch.PHASES``): a span on
        the profiler's timeline and a ``perf_phase_<name>_s`` counter."""
        if self.perf is None:
            return nullcontext()
        return self.perf.phase(name, **attrs)

    def _phase_to(self, name: str | None):
        """The step functions' form of ``_phase``: from here on the tick
        is in phase ``name``, until the next ``_phase_to``."""
        if self.perf is not None:
            self.perf.phase_to(name)

    def _sent_home(self, home, horizon: int, p_b: int = 0,
                   spec: bool = False) -> tuple:
        """Right after a tick's dispatch, still in its ``dispatch`` phase:
        start the copy of its packed results (``_ragged_tick``'s ``home``)
        to the host, queued behind the program, so that no host round trip
        stands between the program's end and the bytes' arrival.  Returns
        what ``_sync_window`` takes: the buffer and its layout, which
        follows from the tick's shapes as the program's own does."""
        home.copy_to_host_async()
        return home, ResultLayout(
            rows=self.ec.max_rows, horizon=horizon,
            k1=self.ec.spec_k + 1 if spec else 0, p_b=p_b,
            n_stats=len(self._state_counters) if self._stateful else 0)

    def _sync_window(self, home, layout: ResultLayout
                     ) -> dict[str, np.ndarray]:
        """THE tick's blocking device->host materialization, the ``sync``
        phase, counted by ``_count_sync`` when it returns.  Everything the
        host reads of a tick is ONE int32 buffer the program packed
        (``home``, laid out by ``layout``) whose copy ``_sent_home``
        started at dispatch: ONE fetch blocks until the program has ended
        and the bytes are here (phase ``sync_wait``), then the buffer is
        taken apart into views (phase ``sync_fetch``: no transfer, a few
        microseconds of numpy)."""
        with self._phase("sync"):
            t0 = time.perf_counter()
            with self._phase("sync_wait", bytes=home.nbytes):
                # jaxlint: disable=JL002 -- THE per-tick designed sync: one blocking materialization for the whole fused tick
                buf = d2h(home)
            with self._phase("sync_fetch"):
                out = layout.unpack(buf)
            self.metrics["d2h_total"] += 1
            self._count_sync(time.perf_counter() - t0)
        return out

    def _perf_point(self, horizon: int, width: int = 0,
                    with_decode: bool = True, spec: bool = False,
                    pb: int = 0, maxp: int = 0, ew: int = 0) -> dict:
        """The dispatched tick's grid point — the SAME axes the trace
        audit's registry grid keys ``serving.ragged_tick`` entries on
        (rows/width/horizon/kv plus the structural spec/wq/wd/tp/cq
        axes), which is what lets the sentinel classify a runtime
        compile against the manifest and the MFU join find its
        cost_analysis entry.  ``pb``/``maxp``/``ew`` are the
        retrace-driving pad axes the audit does not lock: they ride the
        sentinel's warm/cold identity only."""
        pt: dict = {"rows": self.ec.max_rows, "width": int(width),
                    "horizon": int(horizon), "kv": self.ec.kv_storage}
        if not with_decode:
            pt["wd"] = False
        if spec:
            pt["spec"] = self.ec.spec_k
        if self._served_qtype is not None:
            pt["wq"] = self._served_qtype
        if self._tp_manual:
            pt["tp"] = int(self.mesh.shape.get("tp", 1))
            if self._collective_qtype != "bf16":
                pt["cq"] = self._collective_qtype
        if pb:
            pt["pb"] = int(pb)
        if maxp:
            pt["maxp"] = int(maxp)
        if ew:
            pt["ew"] = int(ew)
        return pt

    def perf_view(self) -> dict | None:
        """The /health ``perf`` block (None when perfwatch is off)."""
        return self.perf.view() if self.perf is not None else None

    def perf_numeric(self) -> dict:
        """Flat ``perf_``-prefixed counters for the /metrics exposition."""
        if self.perf is None:
            return {}
        return {f"perf_{k}": v
                for k, v in self.perf.metrics_numeric().items()}

    def _flight_pending(self) -> dict:
        """Recovery evidence accumulated since the last RECORDED tick:
        a failed tick rolls back and never records, and _recover bumps
        its counters afterwards — so retries and injector site hits are
        invisible to per-tick checkpoint deltas and must be carried
        against the last-record baseline instead (the next committed
        record absorbs them; dumps taken at the recovery decision carry
        them immediately)."""
        out = {"retries": self.metrics.get("retries", 0)
               - self._flight_retries0}
        if self.injector is not None:
            hits = {k: v - self._flight_hits0.get(k, 0)
                    for k, v in self.injector.site_hits.items()
                    if v != self._flight_hits0.get(k, 0)}
            if hits:
                out["fault_sites"] = hits
        return out

    def _flight_record(self, m0: dict, snap: dict, t_wall: float):
        """Append one committed tick's record to the flight recorder —
        per-tick DELTAS against the pre-tick checkpoint (recovery
        evidence against the last-record baseline; see
        ``_flight_pending``), so the ring reads as what each tick did,
        not cumulative counters.  Pure idle ticks are skipped (the idle
        loop runs ~50 ticks/s; recording them would flush real work out
        of the ring in seconds)."""
        m = self.metrics

        def d(key):
            return m.get(key, 0) - m0.get(key, 0)

        pend = self._flight_pending()
        tokens, admitted = d("tokens"), d("requests")
        working = (tokens or admitted or d("mixed_prefill_tokens")
                   or pend["retries"] or pend.get("fault_sites")
                   or d("errors_isolated") or d("timeouts")
                   or self._tick_dispatches or d("host_syncs"))
        if not working:
            if self.perf is not None:   # discard the idle tick's scratch
                self.perf.tick_finish(self._tick_dispatches, working=False)
            self.flight.skip_idle()
            return
        pages_before = self.ec.n_pages - 1 - len(snap["alloc"][0])
        rec = {
            "t": round(t_wall, 3),
            "tick": m.get("ticks", 0),
            "dispatches": self._tick_dispatches,
            "sync_s": round(m.get("host_sync_s", 0.0)
                            - m0.get("host_sync_s", 0.0), 6),
            "rows_active": int(sum(1 for i, r in enumerate(self.rows)
                                   if r is not None
                                   and i not in self._prefilling)),
            "rows_prefilling": len(self._prefilling),
            "queue_depth": m.get("queue_depth", 0),
            "tokens": tokens,
            "admitted": admitted,
            "pages_in_use": self.alloc.pages_in_use,
            "pages_delta": self.alloc.pages_in_use - pages_before,
            "prefix_evictions": self.alloc.prefix_evictions
            - snap["alloc"][4],
            "alloc_fail_clamps": d("alloc_fail_clamps"),
            "retries": pend["retries"],
        }
        if self.pagestore is not None and snap["pagestore"] is not None:
            rec["pages_spilled"] = (self.pagestore.spills
                                    - snap["pagestore"]["spills"])
            rec["swap_ins"] = (self.pagestore.swap_ins
                               - snap["pagestore"]["swap_ins"])
        if pend.get("fault_sites"):
            rec["fault_sites"] = pend["fault_sites"]
        # device-time observatory: attribution buckets (summing to the
        # tick's wall clock), the MFU join for the dispatched grid
        # point, any compile events the sentinel attributed to this
        # tick, and the JP106 dispatch cross-check — committed ticks
        # only, so a rollback leaves no attribution residue
        pf = {}
        if self.perf is not None:
            pf = self.perf.tick_finish(self._tick_dispatches, working=True)
            rec.update(pf)
        # plan vs. actual: the tick's plan stamp, whether the grid or the
        # page-pool safety clamp cut it, and the prediction error against
        # the measured wall clock (the perf_plan_error histogram the
        # planner is judged on) — then the measured tick feeds the
        # planner's EWMA rates (committed working ticks only, so a
        # rolled-back tick leaves no rate residue)
        plan = self._plan
        if plan is not None:
            rec["plan"] = plan.flight_fields()
            if plan.clamped or self._plan_overrun:
                rec["plan_clamped"] = True
            actual_s = pf.get("wall_s") or (time.time() - t_wall)
            if self.perf is not None and plan.predicted_s > 0:
                rec["plan_err"] = self.perf.note_plan_error(
                    plan.predicted_s, actual_s)
            self.planner.observe(
                family=pf.get("perf_family"), wall_s=actual_s,
                executed=d("steps"),
                prefill_tokens=d("mixed_prefill_tokens"))
        # consumed: the next record's recovery deltas start here
        self._flight_retries0 = m.get("retries", 0)
        if self.injector is not None:
            self._flight_hits0 = dict(self.injector.site_hits)
        self.flight.record(rec)
        waited = pf.get("phases", {}).get("sync_wait", 0.0)
        if waited > SLOW_SYNC_WAIT_S:
            # an enqueued program that did not start for seconds (PERF.md
            # section 7): keep the ring around the tick that lost them
            self.metrics["slow_ticks_total"] += 1
            self.flight.dump("slow_tick", tick=rec["tick"],
                             sync_wait_s=waited)
        # the runtime enforcement of JP106's hand-maintained `+= 1`
        # bookkeeping: the observed dispatch-window count must equal
        # _tick_dispatches.  Debug assert AFTER the ring has the
        # evidence (under -O only the recorded field remains) — and
        # ONCE per engine: a deterministic divergence would otherwise
        # re-raise every tick, escalating an observability discrepancy
        # into a permanent fail-all loop (later ticks keep recording
        # the field and bumping perf.dispatch_mismatches).
        if pf.get("dispatch_mismatch") and not self._perf_asserted:
            self._perf_asserted = True
            assert False, (
                "JP106 runtime cross-check diverged: "
                f"{pf['dispatch_mismatch']} (see the flight ring)")

    def planner_view(self) -> dict:
        """/health ``planner`` block: mode, monotonic decision counters,
        the last plan, the measured EWMA rates, and the deadline-miss
        rate (timeouts over admitted-plus-expired submissions — an
        approximation: queue-expired requests never admit, in-flight
        timeouts count in both terms)."""
        v = self.planner.view()
        t = self.metrics.get("timeouts", 0)
        v["deadline_miss_rate"] = round(
            t / max(self.metrics.get("requests", 0) + t, 1), 4)
        return v

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drain_seconds_left(self) -> float:
        """Seconds until the graceful-drain window closes (0.0 when not
        draining, or when drain was flagged without a recorded deadline)
        — what a 503 Retry-After is derived from: by then this replica
        has either finished restarting or shed everything."""
        if not self._draining or self._drain_deadline is None:
            return 0.0
        return max(0.0, self._drain_deadline - time.monotonic())

    def abort(self, req: Request):
        """Cancel a request (e.g. client disconnect); its row frees at the
        next step boundary."""
        req.cancelled = True

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful shutdown: reject new submissions (503), let queued and
        in-flight requests finish, then abort stragglers at the deadline.
        Returns True when everything finished inside ``timeout``.  The
        engine thread keeps running (call ``stop()`` afterwards); /health
        reports "draining" for the duration."""
        self._draining = True
        # recorded so the HTTP surfaces can derive an honest Retry-After
        # on the 503 draining path (drain_seconds_left)
        self._drain_deadline = time.monotonic() + timeout

        def busy():
            return (any(r is not None for r in self.rows)
                    or bool(self._pending) or not self._inbox.empty())

        deadline = time.monotonic() + timeout
        while busy() and time.monotonic() < deadline:
            time.sleep(0.01)
        clean = not busy()
        if not clean:
            # deadline passed: have the engine thread shed what remains
            # (rows abort at the next tick boundary, queued requests fail
            # immediately) — cross-thread state stays engine-owned
            self._drain_abort.set()
            self._work.set()
            hard = time.monotonic() + 10.0
            while busy() and time.monotonic() < hard:
                time.sleep(0.01)
        return clean

    # -- fault domain --------------------------------------------------------

    def _fault_point(self, site: str, rows=(), reqs=()):
        """Guarded site: the injector may raise here, BEFORE the device or
        allocator operation the site names, so an injected fault never
        leaves half-committed device state behind (the recovery contract).
        ``rows``/``reqs`` name the participating requests — what scopes a
        poisoned-request spec and what bisection masks."""
        if self.injector is None:
            return
        ids = [r.request_id for r in reqs if r is not None]
        for i in rows:
            r = self.rows[i]
            if r is not None:
                ids.append(r.request_id)
        self.injector.hit(site, ids)

    def _queue_put(self, req: Request, item: int | None):
        """Client-visible emission: staged during a transactional tick and
        flushed only on commit, so a rolled-back (retried/bisected) tick
        never leaks a token or a terminal None to a stream consumer."""
        if self._staging is not None:
            self._staging.append((req, item))
        else:
            req.stream_queue.put(item)

    def _checkpoint(self) -> dict:
        """Snapshot every piece of host state a tick can mutate — row
        bookkeeping, the page allocator (free list, refcounts, prefix
        cache), the pending FIFO, the PRNG key chain, metrics, and the
        mutable fields of every in-flight/queued Request.  Device state is
        deliberately NOT snapshotted: the recovery contract is that KV
        writes beyond the committed ``row_lens`` are scratch (a retried
        tick rewrites the same slots with the same values), and rollback
        forces a full epoch (row state and block table re-published) so
        the device copies converge back to the restored host state."""
        reqs = [r for r in self.rows if r is not None] + list(self._pending)
        return {
            "rows": list(self.rows),
            "row_lens": self.row_lens.copy(),
            "row_budget": self.row_budget.copy(),
            "toks": self.toks.copy(),
            "temps": self.temps.copy(),
            "top_ps": self.top_ps.copy(),
            "seeds": self.seeds.copy(),
            "top_ks": self.top_ks.copy(),
            "tables": self.tables.copy(),
            "prefilling": dict(self._prefilling),
            "row_keys": dict(self._row_keys),
            "pending": list(self._pending),
            "alloc": (list(self.alloc.free), self.alloc.ref.copy(),
                      OrderedDict(self.alloc.prefix),
                      dict(self.alloc._page_key),
                      self.alloc.prefix_evictions),
            "key": self.key,
            # the program queued behind the last one read, by reference:
            # a rollback throws it away unread (``_discard_ahead``)
            "ahead": self._ahead,
            # (recurrent state is covered BY REFERENCE, outside the
            # snapshot: a tick reads one buffer and writes a spare one,
            # ``_state_call``, and ``_state_rewind`` puts the reference
            # back)
            "stats_seen": self._stats_seen.copy(),
            "metrics": dict(self.metrics),
            "ttfts": list(self._ttfts),
            "spec_window": list(self._spec_window),
            # the tick's plan (immutable TickPlan): a rolled-back tick's
            # retry — and every bisection probe — replays it verbatim
            "plan": self._plan,
            # the spill tier mutates mid-tick (evictions demote pages,
            # swap-ins consume entries): bookkeeping-only snapshot, so a
            # rolled-back tick leaves the store residue-free
            "pagestore": (self.pagestore.snapshot()
                          if self.pagestore is not None else None),
            # the latency histograms revert with the tick (PR 5's counter
            # rule): a rolled-back tick's TTFT/token-latency observations
            # were never client-visible — O(buckets) per histogram
            "hists": {k: h.snapshot() for k, h in self.hists.items()},
            "reqs": [(r, len(r.output_ids), len(r.logprobs),
                      r.finish_reason, r.first_token_s, r._last_tok_s)
                     for r in reqs],
        }

    def _rollback(self, snap: dict):
        """Restore the checkpoint: the tick never happened.  Staged
        emissions are discarded (clients saw nothing), arrivals drained
        mid-tick re-append to the pending FIFO (they were never admitted),
        and the device copies are marked fully stale."""
        self._staging = None
        self.rows = list(snap["rows"])
        self.row_lens = snap["row_lens"].copy()
        self.row_budget = snap["row_budget"].copy()
        self.toks = snap["toks"].copy()
        self.temps = snap["temps"].copy()
        self.top_ps = snap["top_ps"].copy()
        self.seeds = snap["seeds"].copy()
        self.top_ks = snap["top_ks"].copy()
        self.tables = snap["tables"].copy()
        self._prefilling = dict(snap["prefilling"])
        self._row_keys = dict(snap["row_keys"])
        free, ref, prefix, pkey, evictions = snap["alloc"]
        self.alloc.free = list(free)
        self.alloc.ref = ref.copy()
        self.alloc.prefix = OrderedDict(prefix)
        self.alloc._page_key = dict(pkey)
        self.alloc.prefix_evictions = evictions
        if self.pagestore is not None and snap["pagestore"] is not None:
            # undone spills vanish, consumed swap-in entries come back;
            # data a doomed swap-in scattered into a (now re-freed) pool
            # page is unreferenced garbage, exactly like a rolled-back
            # tick's KV writes past the committed row_lens
            self.pagestore.restore(snap["pagestore"])
        self.key = snap["key"]
        if self._stateful:
            self._state_rewind()    # the doomed programs wrote the spares
            self._stats_seen = snap["stats_seen"].copy()
        # the rolling TTFT window reverts too: a first token recorded by
        # the doomed tick (or a bisection probe) was never emitted, and the
        # retried tick will record it again
        self._ttfts = deque(snap["ttfts"], maxlen=self._ttfts.maxlen)
        self._spec_window = deque(snap["spec_window"],
                                  maxlen=self._spec_window.maxlen)
        self._plan = snap["plan"]
        # metrics revert wholesale except the cross-thread counter submit()
        # bumps (a rejection during the doomed tick really happened)
        m = dict(snap["metrics"])
        m["rejected"] = max(self.metrics.get("rejected", 0),
                            m.get("rejected", 0))
        self.metrics = m
        for k in list(self.hists):
            if k in snap["hists"]:
                self.hists[k].restore(snap["hists"][k])
            else:
                # a perfwatch family histogram born inside the rolled-
                # back tick (lazy registration): it never existed at the
                # checkpoint, so it does not exist now
                del self.hists[k]
        # staged spans discard with the tick: clients saw no tokens, the
        # trace must show no spans (the retry/quarantine events recovery
        # writes are post-rollback, so they survive by construction)
        self._span_staging = []
        for r, n_out, n_lp, fin, fts, lts in snap["reqs"]:
            del r.output_ids[n_out:]
            del r.logprobs[n_lp:]
            r.finish_reason = fin
            r.first_token_s = fts
            r._last_tok_s = lts
        self._pending = deque(snap["pending"])
        for r in self._tick_arrivals:   # drained mid-tick: fresh again
            r.output_ids.clear()
            r.logprobs.clear()
            r.finish_reason = None
            r.first_token_s = 0.0
            self._pending.append(r)
        self._tick_arrivals = []
        # whatever is in flight is thrown away unread: the program this
        # tick found queued (its key comes back) and any it queued itself
        self._ahead = snap["ahead"]
        self._discard_ahead()
        # device copies are now ahead of the restored host state: the
        # next dispatch publishes the row state and the table anew
        self._dev = None
        self._dirty = True
        self._tables_dirty = True

    def _commit(self):
        """Flush the tick's staged emissions to the client queues, in
        emission order — the only point tokens become externally visible."""
        staged, self._staging = self._staging, None
        staged_spans, self._span_staging = self._span_staging, []
        self._tick_arrivals = []
        if self._state_held:
            # the state the program this tick read started from is a spare
            self._state_free.append(self._state_held.pop(0))
        # the stamp a consumer measures the way to its socket from: one
        # clock read a tick, one record a request (a horizon's block
        # shares it), there before the items it belongs to
        now = time.perf_counter()
        stamped: dict[int, list] = {}
        for req, _ in staged:
            stamp = stamped.get(id(req))
            if stamp is None:
                stamp = stamped[id(req)] = [now, 0]
                req.commit_stamps.append(stamp)
            stamp[1] += 1
        for req, item in staged:
            req.stream_queue.put(item)
        if self.tracer is not None:
            for tid, s in staged_spans:
                self.tracer.add(tid, s)
        self.metrics["queue_depth"] = self.queue_depth
        # republish the scrape-visible histogram view (O(buckets), same
        # cost class as the per-tick checkpoint snapshots).  With the
        # observatory on, the republish happens at the end of _tick
        # instead (attribution observes in _flight_record, after this
        # point) — doing it here too would copy every histogram twice
        # per tick for nothing.
        if self.perf is None:
            self._hists_committed = {k: h.copy()
                                     for k, h in self.hists.items()}

    def _tick(self):
        """ONE transactional engine tick: checkpoint, run the step,
        commit; on a step fault, roll back (clients saw nothing) and run
        the recovery policy — transient retry, or bisection + per-request
        quarantine.  This is the unit of failure isolation.  Returns True
        when the tick committed cleanly (no recovery ran)."""
        if self._drain_abort.is_set():
            self._shed_remaining()
            self._drain_abort.clear()
        with self._phase("tick", tick=self.metrics.get("ticks", 0) + 1):
            return self._tick_phases()

    def _tick_phases(self):
        """``_tick`` inside its ``engine/tick`` span, phase by phase."""
        # plan the tick BEFORE the checkpoint: the plan snapshots with
        # the tick state, so a transient-retry re-run and every bisection
        # probe replay the SAME plan — recovery reproduces the failed
        # tick's exact shape instead of re-deciding against post-fault
        # queue state.  (Planner decision counters are sentinel-style
        # monotonic for the same reason compile counters are: a
        # rolled-back tick's planning really happened.)
        if self._retries == 0 or self._plan is None:
            with self._phase("plan"):
                self._plan = self.planner.plan(self)
        self._plan_overrun = False
        with self._phase("checkpoint"):
            snap = self._checkpoint()
        self._staging = []
        self._span_staging = []
        self._tick_arrivals = []
        self._tick_dispatches = 0
        if self.perf is not None:
            self.perf.tick_begin()
        t_wall = time.time()
        try:
            self._step_once()
            self._phase_to(None)
        except Exception as exc:
            if self.perf is not None:
                self.perf.tick_abort()   # a rolled-back tick measures nothing
            self._rollback(snap)
            self._recover(exc)
            return False
        with self._phase("commit"):
            self._commit()
        self._retries = 0
        # post-commit on purpose: a rolled-back tick never advances the
        # liveness counter, so `ticks` moves iff the engine makes progress
        self.metrics["ticks"] = self.metrics.get("ticks", 0) + 1
        with self._phase("record"):
            self._flight_record(snap["metrics"], snap, t_wall)
            if self.perf is not None:
                # the attribution observations land in _flight_record
                # (post-commit, committed ticks only) — republish so the
                # scrape view includes THIS tick's buckets, not last tick's
                self._hists_committed = {k: h.copy()
                                         for k, h in self.hists.items()}
        return True

    def _recover(self, exc: BaseException):
        """Post-rollback recovery policy.  Transient → bounded exponential
        backoff, then the loop re-runs the tick from the committed state
        (same key chain, so the retried tick is bit-identical).  Exhausted
        retries or deterministic → bisect the participating request set
        and quarantine the culprit.  Only when bisection cannot localize
        the fault (it fires with every request masked — an engine-level
        failure) does ``_fail_all`` run."""
        if is_transient(exc) and self._retries < self.ec.max_step_retries:
            self._retries += 1
            self.metrics["retries"] = self.metrics.get("retries", 0) + 1
            if self.tracer is not None:
                # post-rollback, so these land directly: the trace shows
                # the retry/rollback the client never saw tokens from
                for req in [r for r in self.rows if r is not None] + \
                        list(self._pending):
                    self._trace(req, "retry", attempt=self._retries,
                                error=f"{type(exc).__name__}: {exc}")
            self._stop.wait(
                self.ec.retry_backoff_s * (2 ** (self._retries - 1)))
            return
        self._retries = 0
        culprit = self._bisect_culprit()
        if culprit is _FAULT_VANISHED:
            # the fault did not reproduce on an immediate re-run: treat it
            # as transient-resolved and carry on from the committed state
            self.metrics["last_error"] = f"{type(exc).__name__}: {exc}"
            return
        if culprit is None:
            self._fail_all(exc)     # engine-level: the blast-radius backstop
            return
        self._quarantine(culprit, exc)

    def _probe(self, masked_ids: set) -> BaseException | None:
        """Bisection probe: re-run the tick with ``masked_ids`` sat out
        (inactive on device, skipped by admission/prefill), emissions
        muted, and EVERYTHING rolled back afterwards — probes only
        observe whether the fault fires, they never commit."""
        snap = self._checkpoint()
        self._staging = []
        self._span_staging = []     # probes mute spans like emissions
        self._tick_arrivals = []
        self._masked = set(masked_ids)
        self._serial = True
        self._dirty = True   # the active mask changed vs the device copy
        try:
            self._step_once()
            return None
        except Exception as e:
            return e
        finally:
            self._phase_to(None)    # a probe's spans show; it counts nothing
            self._masked = set()
            self._serial = False
            self._rollback(snap)

    def _bisect_culprit(self):
        """Localize a deterministic fault to ONE request by re-running the
        tick with suspect subsets masked.  Returns the culprit Request,
        ``None`` when the fault is engine-level (fires with every suspect
        masked), or ``_FAULT_VANISHED`` when it does not reproduce."""
        suspects = [r for r in self.rows if r is not None]
        suspects += [r for r in self._pending if r not in suspects]
        if not suspects:
            return None
        if self._probe(set()) is None:
            return _FAULT_VANISHED
        all_ids = {r.request_id for r in suspects}
        if self._probe(all_ids) is not None:
            return None
        cands = suspects
        while len(cands) > 1:
            half = cands[:len(cands) // 2]
            if self._probe({r.request_id for r in half}) is None:
                cands = half            # fault silenced → culprit masked
            else:
                cands = cands[len(cands) // 2:]
        culprit = cands[0]
        # confirm: the culprit alone (everyone else masked) reproduces the
        # fault — guards against a fault that stopped firing mid-bisection
        # quarantining an innocent request
        if self._probe(all_ids - {culprit.request_id}) is None:
            return _FAULT_VANISHED
        return culprit

    def _quarantine(self, req: Request, exc: BaseException):
        """Finish exactly the culprit with ``finish_reason="error"`` —
        whether it holds a row (pages released) or is still queued — and
        keep everything else running.  The next tick re-runs without it
        and commits normally, so survivor streams are bit-identical to an
        unfaulted run (independent per-row sampling streams)."""
        self.metrics["errors_isolated"] = (
            self.metrics.get("errors_isolated", 0) + 1)
        self.metrics["last_error"] = (
            f"isolated to request {req.request_id[:12]}: "
            f"{type(exc).__name__}: {exc}")
        # the postmortem artifact, captured at the blast-radius decision:
        # the flight ring shows what the last N working ticks did leading
        # up to this isolation
        self.flight.dump("quarantine", request_id=req.request_id,
                         error=f"{type(exc).__name__}: {exc}",
                         # the failed ticks leading here rolled back and
                         # never recorded — their retries/injector hits
                         # ride the dump itself
                         **{f"{k}_pending": v for k, v
                            in self._flight_pending().items() if v},
                         **(self.perf.dump_fields()
                            if self.perf is not None else {}))
        self._trace(req, "quarantine",
                    error=f"{type(exc).__name__}: {exc}")
        for i, r in enumerate(self.rows):
            if r is req:
                self._finish(i, "error")
                return
        try:
            self._pending.remove(req)
        except ValueError:
            pass
        if req.finish_reason is None:
            req.finish_reason = "error"
        req.stream_queue.put(None)

    def _shed_remaining(self):
        """Drain-deadline enforcement (engine thread): abort whatever is
        still in flight or queued so ``drain`` can return bounded."""
        for i, r in enumerate(self.rows):
            if r is not None:
                self._finish(i, "abort")
        self._drain_inbox()
        while self._pending:
            req = self._pending.popleft()
            if req.finish_reason is None:
                req.finish_reason = "abort"
            req.stream_queue.put(None)
        self.metrics["queue_depth"] = self.queue_depth

    def _deadline_of(self, req: Request) -> float | None:
        d = (req.deadline_s if req.deadline_s is not None
             else self.ec.request_deadline_s)
        return d if d and d > 0 else None

    def _expire_deadlines(self):
        """Finish requests past their wall-clock budget: in-flight rows at
        this emission epoch, queued requests before they ever occupy a row
        (admission-time enforcement)."""
        now = time.perf_counter()
        for i, r in enumerate(self.rows):
            if r is None:
                continue
            d = self._deadline_of(r)
            if d is not None and now - r.submitted_s > d:
                self.metrics["timeouts"] = (
                    self.metrics.get("timeouts", 0) + 1)
                self._finish(i, "timeout")
        if self._pending:
            keep: "deque[Request]" = deque()
            for r in self._pending:
                d = self._deadline_of(r)
                if d is not None and now - r.submitted_s > d:
                    self.metrics["timeouts"] = (
                        self.metrics.get("timeouts", 0) + 1)
                    if r.finish_reason is None:
                        r.finish_reason = "timeout"
                    self._queue_put(r, None)
                else:
                    keep.append(r)
            self._pending = keep

    # -- page bookkeeping ----------------------------------------------------

    def _ensure_pages(self, row: int, upto_slot: int,
                      req: Request | None = None) -> bool:
        """Allocate pages so slots [0, upto_slot) are backed; False = dry.

        ``upto_slot`` past the table width is tolerated: the overflow is
        only ever right-padded prefill slack, which update_layer routes to
        the scratch page (admission caps real tokens at capacity).
        """
        self._fault_point("page-alloc", rows=(row,), reqs=(req,))
        need = min(-(-upto_slot // self.ec.page_size), self.ec.max_pages)
        # every decoding row comes here every tick and almost always has
        # nothing missing: one vector compare, no walk over the row's pages
        missing = np.flatnonzero(self.tables[row, :need] < 0)
        if missing.size > 1:
            # batch the burst's evictions: one spill gather instead of
            # one per page (drops the same pages lazy eviction would)
            self.alloc.reserve(int(missing.size))
        for j in missing:
            pid = self.alloc.alloc()
            if pid is None:
                # every caller clamps on a dry pool (shorter horizon,
                # requeued admission, spec fallback, 'length' finish);
                # count the event so pool pressure is visible in
                # /health's kv block instead of only via its symptoms
                self.metrics["alloc_fail_clamps"] = (
                    self.metrics.get("alloc_fail_clamps", 0) + 1)
                return False
            self.tables[row, j] = pid
            self._tables_dirty = True
        return True

    def _release_row_pages(self, row: int):
        for j in np.flatnonzero(self.tables[row] >= 0):
            self.alloc.decref(int(self.tables[row, j]))
            self.tables[row, j] = -1
            self._tables_dirty = True

    # -- host-RAM spill tier (serving/pagestore.py) -------------------------

    def _spill_pages(self, pairs):
        """PageAllocator eviction hook: demote a batch of cache-owned
        prefix pages' bytes to the host store just before their pool
        slots are recycled — ONE gather + one blocking sync for the
        whole batch (``PageAllocator.reserve`` batches an allocation
        burst's evictions into a single call here).  Epoch-boundary work
        (page allocation is an epoch); a raise fires before any store
        mutation, so rollback + retry see every cache entry intact."""
        self._fault_point("spill-store")
        pids = np.asarray([p for _, p in pairs], np.int32)
        k_pages, v_pages = self.cache.gather_pages(pids)
        t0 = time.perf_counter()
        # jaxlint: disable=JL002 -- designed epoch-boundary sync: the batch's bytes must reach host RAM before the pool slots are recycled (the demotion itself)
        k_np = d2h(k_pages)
        v_np = d2h(v_pages)  # jaxlint: disable=JL002 -- same designed spill sync; already blocked on k_np above
        self.metrics["d2h_total"] += 2
        self._count_sync(time.perf_counter() - t0)
        for i, (key, _) in enumerate(pairs):
            self.pagestore.spill(key,
                                 np.ascontiguousarray(k_np[:, i]),
                                 np.ascontiguousarray(v_np[:, i]))

    def _swap_in_chain(self, entries: list, req: Request | None = None
                       ) -> dict:
        """Promote a chain of spilled pages back into the pool in ONE
        batch: ``reserve()`` pre-evicts for the whole burst, allocation
        stops at the first dry pid (chain order — what fits is the
        unbroken head; the rest hand their entries back via
        ``untake``), one stacked scatter lands every accepted page, and
        ONE completion barrier covers the batch — per-page barriers
        serialized N full device round-trips on exactly the spill-heavy
        admission path the swap-in histogram monitors.  ``entries`` is
        ``[(key, (k_np, v_np)), ...]``; returns {key: pid} for the
        promoted head, each page registered cache-owned at ref 1 —
        byte-identical to one that never left the pool."""
        if not entries:
            return {}
        self._fault_point("swap-in")
        self.alloc.reserve(len(entries))
        pids: list[int] = []
        for _ in entries:
            pid = self.alloc.alloc()
            if pid is None:
                break                       # dry pool: keep what fit
            pids.append(pid)
        taken = entries[:len(pids)]
        for key, entry in entries[len(pids):]:
            self.pagestore.untake(key, entry)   # failed promotion
        if not taken:
            return {}
        t0 = time.perf_counter()
        t0_w = time.time()
        epoch = (self.perf.epoch_window("swap_in")
                 if self.perf is not None else nullcontext())
        with epoch:
            k_stack = np.stack([e[0] for _, e in taken], axis=1)
            v_stack = np.stack([e[1] for _, e in taken], axis=1)
            with self._perf_dispatch("swap_in", tick=False):
                self.cache = self.cache.scatter_pages(
                    np.asarray(pids, np.int32), h2d(k_stack),
                    h2d(v_stack))
            self.metrics["h2d_total"] += 3      # the page ids, K, V
            t_bar = time.perf_counter()
            # completion barrier: swap-in latency must cover the scatter
            # REACHING the pool, not just its enqueue — on an async
            # backend the enqueue-only figure was vacuous (microseconds
            # regardless of page size), and the admission that depends on
            # these pages blocks on exactly this work anyway.  Epoch-
            # boundary sync, not tick work (JP106 untouched).
            # jaxlint: disable=JL002 -- designed epoch-boundary completion barrier: the swap-in p95 /health reports must measure transfer completion, not dispatch enqueue (the PR 11 vacuous-timing fix)
            self.cache.k.block_until_ready()
            self.cache.v.block_until_ready()  # jaxlint: disable=JL002 -- rides the same designed swap-in barrier; k already blocked above
            if self.perf is not None:
                self.perf.note_sync(time.perf_counter() - t_bar)
        seconds = time.perf_counter() - t0
        self.pagestore.record_swap_in(seconds, pages=len(taken))
        self.hists["swap_in_s"].observe(seconds)
        self._trace(req, "swap_in", t0=t0_w, t1=time.time(),
                    seconds=round(seconds, 6), pages=len(taken))
        out = {}
        for (key, _), pid in zip(taken, pids):
            # transfer alloc()'s caller reference to the prefix cache
            # (register_prefix addrefs, so drop ours): the page ends
            # cache-owned at ref 1 — exactly a registered page no row
            # holds
            self.alloc.register_prefix(key, pid)
            self.alloc.decref(pid)
            out[key] = pid
        return out

    def _spill_finished_row(self, row: int, req: Request):
        """Cold-row spill at finish: a cleanly-finished row's decode
        pages hold the KV of prompt+output — the prefix a multi-turn
        follow-up request will arrive with.  Full pages past the prompt
        registration bound demote to the host store (keyed by the chain
        hash over prompt+output, the identity a future prompt computes)
        just before ``_finish`` recycles the pool slots; the device
        prefix cache itself keeps only prompt pages, exactly as before.
        Valid KV covers every prompt slot plus outputs[:-1] — the last
        emitted token's KV would have been written by the step that
        never ran — so only pages fully inside that bound spill."""
        ids = np.concatenate([
            np.asarray(req.prompt_ids, np.int32),
            np.asarray(req.output_ids, np.int32)])
        n_p = len(req.prompt_ids)
        ps = self.ec.page_size
        n_valid = n_p + max(len(req.output_ids) - 1, 0)
        reg = (n_p - 1) // ps                   # _finish_prompt's bound
        hi = min(n_valid // ps, self.ec.max_pages)
        if hi <= reg:
            return
        keys = _chain_hashes(ids[: hi * ps], ps)
        picks = [(keys[j], int(self.tables[row, j]))
                 for j in range(reg, hi)
                 if int(self.tables[row, j]) >= 0
                 and keys[j] not in self.alloc.prefix]
        if not picks:
            return
        self._fault_point("spill-store", rows=(row,))
        pids = np.asarray([p for _, p in picks], np.int32)
        k_pages, v_pages = self.cache.gather_pages(pids)
        t0 = time.perf_counter()
        # jaxlint: disable=JL002 -- designed finish-epoch sync: one batched gather spills the finished row's pages before their pool slots are recycled
        k_np = d2h(k_pages)
        v_np = d2h(v_pages)  # jaxlint: disable=JL002 -- rides the same designed finish-epoch sync; already blocked on k_np above
        self.metrics["d2h_total"] += 2
        self._count_sync(time.perf_counter() - t0)
        for i, (key, _) in enumerate(picks):
            self.pagestore.spill(key,
                                 np.ascontiguousarray(k_np[:, i]),
                                 np.ascontiguousarray(v_np[:, i]))

    # -- transportable page sets (serving/kv_transport.py) ------------------

    def _pool_shape(self) -> dict:
        l, _, h, ps, d = self.cache.k.shape
        return {"n_layers": l, "n_kv_heads": h, "page_size": ps,
                "head_dim": d, "v_head_dim": self.cache.v.shape[4]}

    def export_prefix(self, prompt_ids, wire: str = "auto") -> bytes | None:
        """Serialize the cached prefix pages covering ``prompt_ids`` as
        a transportable page set — the disaggregated prefill/decode
        handoff's export half.  Walks the chained-hash prefix exactly
        like admission does, serving each page from the device prefix
        cache or the host spill tier, and stops at the first miss (a
        chain is only useful up to its unbroken head).  ``wire="auto"``
        ships e5m2 codes — an fp8 pool's codes natively (lossless), a
        bf16 pool recoded (half the handoff bytes, lossy exactly like
        fp8 KV storage; pass ``wire="bf16"`` for bit-exact bf16
        handoff).  Returns None when no full page is cached.  Runs on
        the engine thread between ticks (epoch-boundary gathers, not
        tick work — JP106 unchanged)."""
        self._refuse_handoff()
        ids = np.asarray(list(prompt_ids), np.int32)
        return self.run_on_engine(lambda: self._export_prefix_op(ids, wire))

    def _refuse_handoff(self):
        """What the page-set wire format (serving/kv_transport.py) cannot
        carry, refused by name."""
        if self._recurrent:
            raise NotImplementedError(
                "KV handoff for a model with recurrent state: a page of KV "
                "is useless without the state that follows it")
        if self.cache.latent:
            raise NotImplementedError(
                "KV handoff of a latent (MLA) pool: the wire format is "
                "shaped [layers, kv heads, page, head] for K and for V, and "
                "a latent page is neither")

    def _export_prefix_op(self, ids: np.ndarray, wire: str):
        epoch = (self.perf.epoch_window("handoff")
                 if self.perf is not None else nullcontext())
        with epoch:
            return self._export_prefix_inner(ids, wire)

    def _export_prefix_inner(self, ids: np.ndarray, wire: str):
        from ipex_llm_tpu.serving import kv_transport

        if wire == "auto":
            # e5m2 on the wire: native codes for fp8 pools, recoded
            # (halved) handoff bytes for bf16 pools
            wire = "fp8"
        n_p = len(ids)
        keys = _chain_hashes(ids, self.ec.page_size)
        shareable = min(len(keys), (n_p - 1) // self.ec.page_size)
        order: list[tuple[str, bytes, Any]] = []
        for key in keys[:shareable]:
            pid = self.alloc.prefix.get(key)
            if pid is not None:
                order.append(("dev", key, pid))
                continue
            entry = (self.pagestore.peek(key)
                     if self.pagestore is not None else None)
            if entry is None:
                break
            order.append(("host", key, entry))
        if not order:
            return None
        self._fault_point("kv-export")
        pids = np.asarray([p for kind, _, p in order if kind == "dev"],
                          np.int32)
        if len(pids):
            k_all, v_all = self.cache.gather_pages(pids)
            t0 = time.perf_counter()
            # jaxlint: disable=JL002 -- designed export sync: one batched gather materializes the page set for serialization (between-ticks host op)
            k_np = d2h(k_all)
            v_np = d2h(v_all)  # jaxlint: disable=JL002 -- rides the same designed export sync; already blocked on k_np above
            self._count_sync(time.perf_counter() - t0)
        pages, di = [], 0
        for kind, key, payload in order:
            if kind == "dev":
                pages.append((key, k_np[:, di], v_np[:, di]))
                di += 1
            else:
                pages.append((key, payload[0], payload[1]))
        self.metrics["kv_pages_exported"] = (
            self.metrics.get("kv_pages_exported", 0) + len(pages))
        return kv_transport.pack_pages(self._pool_shape(), pages,
                                       wire=wire)

    def import_pages(self, blob: bytes) -> dict:
        """Import a transportable page set into this engine's pool and
        prefix cache — the handoff's import half.  The blob is verified
        first (``TransportError`` on corruption / truncation / version /
        pool-shape mismatch — unverified bytes are never scattered),
        then pages land in chain order: already-cached keys are skipped,
        the rest are allocated (evicting/spilling under pressure like
        any allocation), scattered through the h2d boundary, and
        registered cache-owned — so the next admitted request with this
        prompt prefix-hits them like home-grown pages and joins the
        fused tick with only the uncovered tail left to prefill.  A dry
        pool stops the import early (what fit is registered).  Runs on
        the engine thread between ticks."""
        self._refuse_handoff()
        return self.run_on_engine(lambda: self._import_pages_op(blob))

    def _import_pages_op(self, blob: bytes) -> dict:
        epoch = (self.perf.epoch_window("handoff")
                 if self.perf is not None else nullcontext())
        with epoch:
            return self._import_pages_inner(blob)

    def _import_pages_inner(self, blob: bytes) -> dict:
        from ipex_llm_tpu.serving import kv_transport

        meta, pages = kv_transport.unpack_pages(blob)
        kv_transport.check_pool_shape(meta, **self._pool_shape())
        self._fault_point("kv-import")
        t0 = time.perf_counter()
        # batched import: reserve() pre-evicts for the whole burst (one
        # spill gather instead of one per page — the PageAllocator's
        # allocation-burst contract), allocation stops at the first dry
        # pid (chain order: what fits is the unbroken head), and ONE
        # scatter lands every accepted page — the per-page
        # allocate/scatter loop cost len(pages) dispatches and len(pages)
        # h2d uploads for a blob that arrives as one contiguous set
        fresh = [(key, k_page, v_page) for key, k_page, v_page in pages
                 if key not in self.alloc.prefix]
        skipped = len(pages) - len(fresh)
        self.alloc.reserve(len(fresh))
        pids: list[int] = []
        for _ in fresh:
            pid = self.alloc.alloc()
            if pid is None:
                break                       # dry pool: keep what fit
            pids.append(pid)
        taken = fresh[:len(pids)]
        if taken:
            k_stack = np.stack([k for _, k, _ in taken], axis=1)
            v_stack = np.stack([v for _, _, v in taken], axis=1)
            self.cache = self.cache.scatter_pages(
                np.asarray(pids, np.int32), h2d(k_stack), h2d(v_stack))
            for (key, _, _), pid in zip(taken, pids):
                self.alloc.register_prefix(key, pid)
                self.alloc.decref(pid)      # cache-owned at ref 1
        imported = len(taken)
        self.metrics["kv_pages_imported"] = (
            self.metrics.get("kv_pages_imported", 0) + imported)
        return {"imported_pages": imported, "skipped_pages": skipped,
                "tokens_covered": (imported + skipped) * self.ec.page_size,
                "wire": meta["wire"],
                "import_s": round(time.perf_counter() - t0, 5)}

    # -- device-resident engine state ---------------------------------------

    def _active_mask(self) -> np.ndarray:
        """Rows currently decoding: occupied and past prefill — THE
        host/device activity predicate; the epoch upload and both
        scheduler paths must agree on it exactly.  Rows masked by a
        bisection probe sit the step out (their device row goes inactive,
        so the injector never sees them participate)."""
        return np.array([
            r is not None and i not in self._prefilling
            and r.request_id not in self._masked
            for i, r in enumerate(self.rows)
        ])

    def _row_state(self) -> dict:
        """The host's view of the rows, as the packed epoch's fields."""
        rows = self.rows
        steps = np.asarray([len(r.output_ids) if r is not None else 0
                            for r in rows], np.int32)
        remain = np.asarray([
            int(self.row_budget[i]) - len(r.output_ids) if r is not None
            else 0 for i, r in enumerate(rows)
        ], np.int32)
        # per-row EOS ids, -1-padded to a power-of-two width so an unusual
        # request can only ever trigger a bounded number of fused retraces
        e_w = max([1] + [len(r.eos_token_id) for r in rows if r is not None])
        e_w = 1 << (e_w - 1).bit_length()
        eos = np.full((len(rows), e_w), -1, np.int32)
        for i, r in enumerate(rows):
            if r is not None and r.eos_token_id:
                ids = list(r.eos_token_id)
                eos[i, :len(ids)] = ids
        return dict(toks=self.toks, row_lens=self.row_lens,
                    active=self._active_mask(), temps=self.temps,
                    top_ps=self.top_ps, seeds=self.seeds,
                    top_ks=self.top_ks, steps=steps, remain=remain,
                    eos=eos, tables=self.tables)

    def _upload_epoch(self, p_b: int = 0, width: int = 0, p_maxp: int = 0,
                      **block):
        """Publish the host's view of the rows for one epoch (admission /
        prefill progress / finish / page allocation): the row state, the
        whole block table and, a mixed tick, its prefill ``block`` cross
        as ONE packed buffer in ONE transfer (serving/epoch.py).  Returns
        (the device buffer, its layout, the speculative token history or
        None).  A steady tick with no news never comes here:
        request-static sampling params cross the PCIe link once per
        epoch, not once per token (the tier-1 re-upload regression test
        counts calls)."""
        hist = self._epoch_hist()     # reads ``_dirty``, cleared below
        fields = self._row_state()
        layout = EpochLayout(
            rows=len(self.rows), max_pages=self.ec.max_pages,
            e_w=fields["eos"].shape[1], p_b=p_b, width=width, p_maxp=p_maxp)
        # replicated under a mesh: the sharding the tick hands state back in
        buf = upload(layout.pack(**fields, **block), self._replicated)
        self.metrics["epoch_syncs"] += 1
        self.metrics["h2d_total"] += 1
        if self._tables_dirty:
            self.metrics["table_row_syncs"] += 1
        self._dirty = self._tables_dirty = False
        return buf, layout, hist

    def _epoch_hist(self):
        """The device-resident token history of the on-device prompt-
        lookup proposer (``spec_k > 0``; None otherwise), for the epoch
        ``_upload_epoch`` is about to publish.  The FULL prompt lands at the admission epoch (it is
        known in whole then, so mid-prefill rows need no per-chunk
        scatter), emitted tokens are appended inside the device loop, and
        a row-state epoch rebuilds it from the host's own bookkeeping —
        the same discipline as toks/row_lens, in an upload of its own
        ([R, max_seq_len]: too large to ride every epoch's buffer)."""
        if self.ec.spec_k <= 0:
            return None
        if not self._dirty and self._dev is not None:
            return self._dev["hist"]
        hist = np.zeros((len(self.rows), self.ec.max_seq_len), np.int32)
        for i, r in enumerate(self.rows):
            if r is None:
                continue
            ids = list(r.prompt_ids) + list(r.output_ids)
            hist[i, :len(ids)] = ids
        self.metrics["h2d_total"] += 1
        return upload(hist, self._replicated)

    def _sync_device_state(self) -> dict:
        """The device-resident row state of a tick with no prefill block
        (and of any caller outside a tick), publishing only when there is
        news: a row-state epoch (a completion, a finish, a rollback) or a
        page allocation crosses as one packed buffer and is taken apart
        by one small program (``epoch.unpack_rows``), which also replaces
        the device's block table; with no news nothing is uploaded."""
        if self._dirty or self._tables_dirty or self._dev is None:
            buf, layout, hist = self._upload_epoch()
            dev = unpack_rows(buf, layout)
            self.cache = replace(self.cache, tables=dev.pop("tables"))
            if hist is not None:
                dev["hist"] = hist
            self._dev = dev
        return self._dev

    def _publish_tables(self):
        """The block table alone, with a program in flight (a page's edge
        inside a run of steady ticks): the epoch's buffer and its unpack as
        they are, of which the table is kept.  The row state the buffer
        carries is a step behind the device's and is dropped; ``_dev``
        stays what the program in flight returned."""
        buf, layout, _ = self._upload_epoch()
        self.cache = replace(self.cache,
                             tables=unpack_rows(buf, layout)["tables"])

    # -- engine loop --------------------------------------------------------

    def _free_row(self) -> int | None:
        for i, r in enumerate(self.rows):
            if r is None:
                return i
        return None

    def _drain_inbox(self):
        """Move submissions from the cross-thread inbox into the engine-
        owned pending FIFO.  Arrivals landing mid-transaction are recorded
        so a rollback re-appends them instead of losing them (the inbox
        itself is never rolled back)."""
        while True:
            try:
                req = self._inbox.get_nowait()
            except queue.Empty:
                return
            self._pending.append(req)
            if self._staging is not None:
                self._tick_arrivals.append(req)

    def _pop_pending(self) -> Request | None:
        """Head of the pending FIFO, skipping bisection-masked requests
        (a masked suspect stays queued, in order, while a probe runs)."""
        for i, req in enumerate(self._pending):
            if req.request_id not in self._masked:
                del self._pending[i]
                return req
        return None

    def _admit(self):
        """Join pending requests into free rows (host-side work only —
        prefix matching + page allocation; prefill happens chunk-wise).
        The plan's ``admit_max`` caps successful admissions this tick
        (None = unbounded, the static planner's choice; the MPC planner
        defers a wave that would blow a critical row's deadline)."""
        cap = self._plan.admit_max if self._plan is not None else None
        admitted = 0
        while cap is None or admitted < cap:
            row = self._free_row()
            if row is None:
                return
            req = self._pop_pending()
            if req is None:
                return
            if req.cancelled:
                req.finish_reason = "abort"
                self._queue_put(req, None)
                continue
            prompt = np.asarray(req.prompt_ids, np.int32)
            n_p = len(prompt)
            ps = self.ec.page_size
            # addressable capacity: the block-table width floors
            # max_seq_len/page_size, and a request can never hold more
            # pages than the pool owns (page 0 is reserved scratch)
            capacity = min(self.ec.max_seq_len, self.ec.max_pages * ps,
                           (self.ec.n_pages - 1) * ps)
            if n_p + req.max_new_tokens > capacity or n_p == 0:
                req.finish_reason = "length"
                self._queue_put(req, None)
                continue

            # prefix cache: reuse the longest chain of full pages covering
            # at most the first n_p - 1 tokens (at least one token must run
            # through the model to produce logits)
            # (off for a model with recurrent state: no lookup here and,
            # with no keys kept, no registration at _finish_prompt; every
            # prompt prefills from position 0)
            keys = [] if self._recurrent else _chain_hashes(prompt, ps)
            shareable = min(len(keys), (n_p - 1) // ps)
            # plan the chain: device prefix hits take their row ref
            # immediately (protecting them from the batched promotion's
            # evictions), store misses are take()n so the spill-tier
            # promotion — a PCIe copy instead of re-prefilling the chunk —
            # lands as ONE batched scatter + barrier for the whole chain
            plan: list[tuple] = []      # ("dev", pid) | ("host", key, entry)
            for i in range(shareable):
                pid = self.alloc.lookup_prefix(keys[i])
                if pid is not None:
                    self.alloc.addref(pid)
                    plan.append(("dev", pid))
                    continue
                entry = (self.pagestore.take(keys[i])
                         if self.pagestore is not None else None)
                if entry is None:
                    break
                plan.append(("host", keys[i], entry))
            promoted = self._swap_in_chain(
                [(e[1], e[2]) for e in plan if e[0] == "host"], req=req)
            shared = 0
            for e in plan:
                if e[0] == "host":
                    pid = promoted.get(e[1])
                    if pid is None:
                        break           # dry pool broke the chain here
                    self.alloc.addref(pid)
                else:
                    pid = e[1]          # row ref taken in the plan walk
                self.tables[row, shared] = pid
                self._tables_dirty = True
                shared += 1
            for e in plan[shared:]:
                if e[0] == "dev":       # past the break: drop the row ref
                    self.alloc.decref(e[1])

            base = shared * ps
            if not self._ensure_pages(row, n_p, req=req):
                # pool dry even after eviction: release everything this row
                # touched (shared refs AND partial fresh allocations)
                self._release_row_pages(row)
                if any(r is not None for r in self.rows) or self._prefilling:
                    # retry once in-flight rows free pages — AT THE HEAD,
                    # preserving arrival order (the old inbox.put sent the
                    # head request behind everything queued after it)
                    self._pending.appendleft(req)
                else:
                    # nothing running, nothing evictable: it will never fit
                    req.finish_reason = "length"
                    self._queue_put(req, None)
                return

            if shared:
                # counted only on successful admission: a dry-pool
                # requeue above releases the shared refs and re-admits
                # the same request later — bumping here would count that
                # request's hits twice (hit rate could exceed 1.0 under
                # exactly the pool pressure the kv sweep measures)
                self.metrics["prefix_hits"] += 1
                self.metrics["prefix_pages_shared"] += shared
            if self.tracer is not None:
                # queue-wait span: submission wall time is reconstructed
                # from the perf_counter stamp submit() recorded
                now_w = time.time()
                sub_w = now_w - (time.perf_counter() - req.submitted_s)
                self._trace(req, "queue", t0=sub_w, t1=now_w,
                            queue_depth=self.queue_depth,
                            prompt_tokens=n_p, shared_pages=shared)
            req.queue_wait_s = time.perf_counter() - req.submitted_s
            self.metrics["queue_wait_s_total"] = round(
                self.metrics["queue_wait_s_total"] + req.queue_wait_s, 6)
            self.rows[row] = req
            self.row_lens[row] = base
            self.row_budget[row] = req.max_new_tokens
            self.temps[row] = req.temperature
            self.top_ps[row] = req.top_p
            self.seeds[row] = -1 if req.seed is None else int(req.seed)
            self.top_ks[row] = max(0, int(req.top_k or 0))
            self._prefilling[row] = prompt[base:]
            self._row_keys[row] = keys
            self.metrics["requests"] += 1
            admitted += 1
            self._dirty = True  # admission epoch: new row state to upload

    def _finish_prompt(self, row: int, first: int, logprob: float):
        """Prompt-completion bookkeeping: prefix-page registration bound,
        TTFT record, first-token emission."""
        n_p = int(self.row_lens[row])
        keys = self._row_keys.pop(row, [])
        for j in range(min(len(keys), (n_p - 1) // self.ec.page_size)):
            self.alloc.register_prefix(keys[j], int(self.tables[row, j]))
        req = self.rows[row]
        if req is None:
            return
        req.first_token_s = time.perf_counter() - req.submitted_s
        self.metrics["admit_to_first_token_s_total"] = round(
            self.metrics["admit_to_first_token_s_total"]
            + req.first_token_s - req.queue_wait_s, 6)
        self.metrics["first_tokens"] += 1
        self._record_ttft(req.first_token_s)
        self._trace(req, "first_token",
                    ttft_s=round(req.first_token_s, 6))
        self.toks[row] = first
        self._emit(row, first, logprob)

    def _record_ttft(self, seconds: float):
        """Rolling TTFT percentile for /health (128-request window) +
        the fixed-bucket histogram /metrics exposes in Prometheus form."""
        self._ttfts.append(seconds)
        self.hists["ttft_s"].observe(seconds)
        self.metrics["ttft_p95_s"] = round(
            float(np.percentile(np.fromiter(self._ttfts, np.float64), 95)),
            4)

    def _emit(self, row: int, token: int, logprob: float = 0.0):
        req = self.rows[row]
        if req.cancelled:
            self._finish(row, "abort")
            return
        req.output_ids.append(token)
        req.logprobs.append(logprob)
        # client-visible inter-token latency (first token measures TTFT
        # in its own histogram): under a fused horizon this is honestly
        # BURSTY — H tokens drain in one commit, so the distribution
        # shows ~0 within a block and the tick interval between blocks,
        # which is exactly the granularity a streaming client observes
        now = time.perf_counter()
        if req._last_tok_s:
            self.hists["token_latency_s"].observe(now - req._last_tok_s)
        req._last_tok_s = now
        self._queue_put(req, token)
        self.metrics["tokens"] += 1
        if token in req.eos_token_id:
            self._finish(row, "stop")
        elif len(req.output_ids) >= self.row_budget[row]:
            self._finish(row, "length")

    def _finish(self, row: int, reason: str):
        req = self.rows[row]
        q = self._ahead
        if (q is not None and q.active[row] and q.bind[row] is req
                and reason not in ("stop", "length")):
            # the host retires a row that the queued program still steps
            # (the device itself retires one on EOS or on its budget)
            q.stray += 1
        # first writer wins: the HTTP handler may have already recorded
        # 'stop' (stop-string truncation) before asking for the abort —
        # overwriting it here would misreport the finish reason
        if req.finish_reason is None:
            req.finish_reason = reason
        if (self.pagestore is not None and req.output_ids
                and req.finish_reason in ("stop", "length",
                                          "stop_string")):
            # cold-row spill: a cleanly-finished row's decode KV is the
            # multi-turn follow-up's prefix — demote it before the pool
            # slots are recycled (aborts/errors spill nothing: their KV
            # may be incomplete)
            self._spill_finished_row(row, req)
        self._trace(req, "finish", reason=req.finish_reason,
                    output_tokens=len(req.output_ids))
        self._queue_put(req, None)
        self.rows[row] = None
        self.row_lens[row] = 0
        self.toks[row] = 0
        self._prefilling.pop(row, None)
        self._row_keys.pop(row, None)
        self._release_row_pages(row)
        self._dirty = True  # finish epoch: row freed

    def _fail_all(self, exc: BaseException):
        """Engine-level failure (the blast-radius backstop — reached only
        when bisection cannot localize a fault to one request, or the
        recovery machinery itself failed): finish every in-flight/queued
        request so no client blocks forever, then keep serving."""
        self._staging = None    # emissions flush directly from here on
        self._span_staging = []
        self._tick_arrivals = []
        self._masked = set()
        self._discard_ahead()   # every row it steps ends here
        self.flight.dump("fail_all",
                         error=f"{type(exc).__name__}: {exc}",
                         **{f"{k}_pending": v for k, v
                            in self._flight_pending().items() if v},
                         **(self.perf.dump_fields()
                            if self.perf is not None else {}))
        for i, req in enumerate(self.rows):
            if req is not None:
                self._finish(i, "error")
        self._drain_inbox()
        while self._pending:
            req = self._pending.popleft()
            if req.finish_reason is None:
                req.finish_reason = "error"
            req.stream_queue.put(None)
        self.metrics["errors"] = self.metrics.get("errors", 0) + 1
        self.metrics["last_error"] = f"{type(exc).__name__}: {exc}"
        self.metrics["queue_depth"] = self.queue_depth

    def _row_spec_k(self, req: Request) -> int:
        """ONE definition of a request's draft width: the engine spec_k,
        capped by Request.spec_k, zero when opted out (speculative=False)
        — every reservation/mask site must agree on it exactly."""
        if req.speculative is False:
            return 0
        return (self.ec.spec_k if req.spec_k is None
                else max(0, min(int(req.spec_k), self.ec.spec_k)))

    def _plan_spec_cap(self, row: int) -> int:
        """The plan's draft-width CAP for a row (composed with the
        per-request knobs via min at every reservation/mask site): the
        static planner caps at spec_k everywhere (a no-op), the MPC
        planner masks drafts off when the measured accept window prices
        them underwater.  Rows admitted after planning take the plan's
        ``spec_cap``."""
        plan = self._plan
        if plan is None:
            return self.ec.spec_k
        if row < len(plan.spec_ks):
            return int(plan.spec_ks[row])
        return plan.spec_cap

    def _spec_widths(self, active: np.ndarray) -> np.ndarray:
        """Per-row draft width for a speculative tick — the per-request
        knobs AND the plan's caps as TRACED MASKS, so one compiled
        program serves every opt-out mix."""
        ks = np.zeros((len(self.rows),), np.int32)
        for i, req in enumerate(self.rows):
            if req is None or not active[i]:
                continue
            ks[i] = min(self._row_spec_k(req), self._plan_spec_cap(i))
        return ks

    def _spec_metrics(self, take_block: np.ndarray, s_prop: np.ndarray,
                      s_acc: np.ndarray, executed: int):
        """Spec tick accounting, from what ``_sync_window`` fetched: the
        verify-round counters
        (spec_steps/spec_emitted/accept_rate — one loop iteration is one
        verify round; normalized by ACTIVE ROW-STEPS, so concurrent rows
        cannot push the rate past 1.0) plus the draft-economics
        pair the /health spec block reports.  All of
        it lives in the checkpointed metrics dict / rolling window, so a
        rolled-back tick never double-counts."""
        k = self.ec.spec_k
        emitted = int(take_block.sum())
        row_steps = int((take_block > 0).sum())
        prop, acc = int(s_prop), int(s_acc)
        m = self.metrics
        m["spec_steps"] = m.get("spec_steps", 0) + executed
        m["spec_ticks"] = m.get("spec_ticks", 0) + 1
        m["spec_emitted"] = m.get("spec_emitted", 0) + emitted
        m["spec_row_steps"] = m.get("spec_row_steps", 0) + row_steps
        m["spec_accept_rate"] = round(
            m["spec_emitted"] / ((k + 1) * max(m["spec_row_steps"], 1)), 4)
        m["draft_proposed"] = m.get("draft_proposed", 0) + prop
        m["draft_accepted"] = m.get("draft_accepted", 0) + acc
        m["spec_tokens_per_dispatch"] = round(
            m["spec_emitted"] / max(m["spec_ticks"], 1), 2)
        self._spec_window.append((prop, acc))

    def _drain_spec_block(self, tok_block, lp_block, take_block,
                          active: np.ndarray, h: int):
        """Walk an [R, h, k+1] spec token/logprob block through the exact
        per-token emission path: iteration j of row i emitted
        ``take_block[i, j]`` tokens (device-truncated at the same
        EOS/budget boundary the host's _emit walks)."""
        for i in range(len(self.rows)):
            if not active[i] or self.rows[i] is None:
                continue
            for j in range(h):
                for t in range(int(take_block[i, j])):
                    self.row_lens[i] += 1
                    tok = int(tok_block[i, j, t])
                    self.toks[i] = tok
                    self._emit(i, tok, float(lp_block[i, j, t]))
                    if self.rows[i] is None:   # finished mid-run
                        break
                if self.rows[i] is None:
                    break

    def _loop(self):
        while not self._stop.is_set():
            if self._ahead is None:
                # over committed state on the device too: with a program
                # queued the next tick only reads it (``_ahead_blocked``)
                self._drain_host_ops()
            try:
                committed = self._tick()
                # a committed tick means the engine recovered: clear the
                # sticky error so /health goes back to "ok" (the isolated
                # error lives on in errors_isolated for chaos tooling)
                if committed and self.metrics.get("last_error"):
                    self.metrics["last_error"] = ""
            except Exception as exc:  # recovery machinery itself failed
                self._fail_all(exc)
        # a program still queued is thrown away unread: its tokens were
        # never staged, so no stream sees them now or twice
        self._discard_ahead()
        # shutdown drain: host ops enqueued after the loop's last drain
        # must not leave their callers blocked until timeout — fail them
        # with a clean "engine stopped" instead
        while True:
            try:
                _, box = self._host_ops.get_nowait()
            except queue.Empty:
                break
            box.put((False, RuntimeError(
                "engine stopped before servicing the host operation")))

    def _step_once(self):
        """Scheduler: admit, then ONE ``_ragged_tick`` dispatch.
        Admission wave (any row prefilling) → ``_mixed_step`` fuses every
        prefill chunk, on-device first-token merge, and the decode step;
        steady state → the fused decode horizon through the SAME entry.
        ``spec_k`` rides INSIDE that program (on-device
        draft+verify+accept)."""
        with self._phase("admit"):
            self._drain_inbox()
            self._expire_deadlines()
            self.metrics["queue_depth"] = self.queue_depth
            if self._ahead is None:
                # a row is not handed on while a queued program may still
                # step it: that program is read first, admission follows
                self._admit()
            for i, req in enumerate(self.rows):  # drop disconnected clients
                if req is not None and req.cancelled:
                    self._finish(i, "abort")
        # every step function below builds its inputs (prepare), makes its
        # one dispatch, and walks what came back (drain)
        self._phase_to("prepare")
        if self._ahead is not None:
            self._horizon_step(None)
            return
        if self._prefilling:
            self._mixed_step()
            return
        active = self._active_mask()
        if not active.any():
            self._phase_to(None)    # the idle wait is no phase's work
            self._wait_for_work()
            return
        self._horizon_step(active)

    def _wait_for_work(self, timeout: float = 0.02):
        """Idle sleep that wakes the moment a request arrives WITHOUT
        consuming the inbox: the old get()+put() peek rotated the head
        request behind anything submitted during the peek window, breaking
        FIFO admission order under a burst.  The event is a pure wakeup
        hint — clearing it late never loses work, because the next tick's
        ``_admit`` drains the queue regardless."""
        if self._inbox.empty():
            self._work.wait(timeout)
        self._work.clear()

    def _mixed_step(self):
        """One admission-wave tick = ONE device program
        (``_ragged_tick``): ragged prefill chunks for ALL prefilling
        rows, on-device first-token sampling AND state merge for prompts
        completing this tick, and the decode step for every active row —
        all inside a single jitted entry, so a mixed tick pays one
        dispatch and at most one blocking sync (completion ticks fetch
        first tokens and the decode block from the same program).  The
        JP106 trace gate locks the one-dispatch invariant.  Where the
        program makes ONE pass over the weights (``fuses_mixed_tick``: a
        dense model's) a row that completes its prompt here decodes from
        the next tick on, and the drain below stages no decode token for it.

        Budget split: the per-tick token budget divides across prefilling
        rows in a power-of-two per-row chunk width (so every joining row
        advances every tick and the tick program retraces at most once
        per width), decode rows keep their [R, 1] step cost inside the
        fused program — one token per tick, the steady tick's loop body,
        so their streams do not depend on who is joining."""
        rows = sorted(r for r in self._prefilling
                      if self.rows[r] is not None
                      and self.rows[r].request_id not in self._masked)
        if not rows:
            # every prefilling row is masked by a bisection probe: the
            # decode rows (if any) still take their step below
            active = self._active_mask()
            if active.any():
                self._horizon_step(active)
            return
        # per-row chunk width: the budget fair-shares across joining rows
        # (power-of-two floor, capped at the prefill bucket); width
        # depends only on the row count, so the program set is one trace
        # per power-of-two batch size.  Floored at 4: slivers of 1-2
        # tokens per row make the wave tick-bound (per-dispatch overhead
        # and trace churn dominate), so a huge admission wave briefly
        # overshoots the budget rather than crawling
        budget = (self._plan.chunk_budget if self._plan is not None
                  else self._step_budget)
        # a model with a CHUNK GRID (``cfg.chunk_grid``: the block of its
        # chunked recurrence, ops/kda.py) has its prompts cut at multiples
        # of that block only, a prompt's last piece apart, so that a
        # token's bits do not depend on the ticks its prompt rode in: the
        # widths under the block are not used, and where the budget does
        # not give every joining row TWO blocks, the rows admitted first go
        # first and the others wait their turn.  Two, not one: a prefill
        # block of 8 rows x 64 tokens beside a decode stage gave a token
        # other bits than the same tokens as [4, 128], [2, 256] or [1, 512]
        # (measured on a v5e, PERF.md section 6, PR 50: XLA lays a leading
        # dimension of 8 out on the sublanes and plans float32 reductions
        # otherwise), so blocks of 8 rows are not formed for such a model
        grid = self.cfg.chunk_grid
        if grid:
            rows = sorted(rows, key=lambda r: self.rows[r].submitted_s)[
                :max(1, budget // (2 * grid))]
        share = max(1, budget // len(rows))
        width = min(max(1 << (share.bit_length() - 1), grid or 4),
                    max(self.ec.prefill_bucket, grid))
        p_b = 1 << (len(rows) - 1).bit_length()        # pow2 batch pad

        toks = np.zeros((p_b, width), np.int32)
        # pad batch slots carry a base past the table width: every write
        # they make routes to the scratch page (update_layer's valid mask)
        base = np.full((p_b,), self.ec.max_pages * self.ec.page_size,
                       np.int32)
        n_valid = np.zeros((p_b,), np.int32)
        emit = np.zeros((p_b,), bool)
        canjoin = np.ones((p_b,), bool)
        # prefill slot -> engine row; pad slots carry R so their on-device
        # state scatters DROP instead of touching row 0
        rowmap = np.full((p_b,), self.ec.max_rows, np.int32)
        chunks: list[tuple[int, int, int]] = []  # (slot, row, n_i)
        for i, row in enumerate(rows):
            rem = self._prefilling[row]
            n_i = min(len(rem), width)
            b = int(self.row_lens[row])
            if not self._ensure_pages(row, b + n_i):
                self._finish(row, "error")  # pool exhausted mid-prefill
                continue
            toks[i, :n_i] = rem[:n_i]
            base[i] = b
            n_valid[i] = n_i
            emit[i] = n_i == len(rem)      # prompt completes this tick
            rowmap[i] = row
            chunks.append((i, row, n_i))
        if not chunks:
            active = self._active_mask()
            if active.any():
                self._horizon_step(active)
            return
        # a completing row's first decode step runs INSIDE this same
        # program (the next tick's, in the one-pass form) and writes KV at
        # slot b+n_i — back it now, or the row
        # sits the decode stage out and finishes 'length' after its
        # first token (decided pre-dispatch).  This runs AFTER every row's
        # chunk pages are ensured: under pool pressure the extra decode
        # page must never starve a later row's prefill chunk (which would
        # turn that request's graceful progress into a hard 'error').
        # Speculating joiners additionally reserve their draft window
        # (min(k+1, budget after the first token) slots) so a prompt
        # completing this tick can speculate on its first decode
        # iteration; a pool that can back only the plain slot zeroes the
        # row's traced spec width instead (no_spec as a mask).  The plan
        # can mask speculation off for the tick (draft economics): the
        # spec-free program variant dispatches instead — a locked grid
        # point, not a new trace.
        fused = (self.ec.spec_k > 0
                 and (self._plan.spec_on if self._plan is not None
                      else True))
        spec_ks = (np.zeros((len(self.rows),), np.int32)
                   if fused else None)
        for i, row, n_i in chunks:
            if not emit[i]:
                continue
            req = self.rows[row]
            k_i = (min(self._row_spec_k(req), self._plan_spec_cap(row))
                   if spec_ks is not None else 0)
            want = max(min(k_i + 1, req.max_new_tokens - 1), 1)
            canjoin[i] = self._ensure_pages(
                row, int(base[i]) + n_i + want, req=req)
            if not canjoin[i] and k_i:
                k_i = 0
                canjoin[i] = self._ensure_pages(
                    row, int(base[i]) + n_i + 1, req=req)
            if spec_ks is not None:
                spec_ks[row] = k_i
        # decode participants need their next KV slot backed BEFORE the
        # single dispatch: a row the pool cannot back finishes 'length'
        # here and is excluded from the uploaded active mask.  (No horizon
        # clamp like _horizon_step's: at want=1 a failed ensure always
        # means zero backed slots remain.)  Speculating rows reserve their
        # draft window first and drop to the plain width under pressure.
        active = self._active_mask()
        for i in range(len(self.rows)):
            if not active[i]:
                continue
            k_i = (min(self._row_spec_k(self.rows[i]),
                       self._plan_spec_cap(i))
                   if spec_ks is not None else 0)
            rem_i = (int(self.row_budget[i])
                     - len(self.rows[i].output_ids))
            want = max(min(k_i + 1, rem_i), 1)
            if not self._ensure_pages(i, int(self.row_lens[i]) + want):
                if k_i and self._ensure_pages(i,
                                              int(self.row_lens[i]) + 1):
                    k_i = 0
                else:
                    self._finish(i, "length")
                    active[i] = False
                    continue
            if spec_ks is not None:
                spec_ks[i] = k_i
        # pure-chunk ticks with nothing decoding skip the decode stage
        # entirely (statically): no all-masked forward, and the key chain
        # advances only by the prefill split.  emit is decidable
        # pre-dispatch but the first token's EOS/budget fate is not, so a
        # completion whose row dies at its first token (no other rows
        # active) still runs an all-dead decode stage and splits the key
        # once more.  Greedy and seeded streams are untouched (seeded
        # rows key on fold_in(seed, step), never the engine chain); only
        # unseeded temperature>0 draws after that corner differ, and
        # those carry no reproducibility contract (same distribution,
        # different stream).
        with_decode = bool(active.any() or emit.any())
        if self.ec.spec_k > 0 and with_decode and not fused:
            # decode emits through the spec-free variant (the plan masked
            # speculation off): the device-resident token history is not
            # maintained this tick
            self._hist_stale = True
        elif fused and with_decode and self._hist_stale:
            # epoch re-upload rebuilds hist from host-side ids before the
            # proposer scans it (the device sync below honors _dirty)
            self._dirty = True
            self._hist_stale = False
        self._fault_point("mixed-step", rows=[r for _, r, _ in chunks])
        # decode participants = rows already decoding PLUS completions
        # that can join the decode stage this tick: a request-scoped
        # fault at this site must fire on the tick its request first
        # decodes, or the rollback contract would let its first tokens
        # commit
        # (in the one-pass form a completion's first decode step is the
        # NEXT tick's, whose own dispatch is that site)
        one_pass = fuses_mixed_tick(
            self.cfg, 1, True, self.ec.spec_k if fused else 0, self.mesh)
        decode_rows = [i for i in range(len(self.rows)) if active[i]]
        if not one_pass:
            decode_rows += [row for s, row, _ in chunks
                            if emit[s] and canjoin[s]]
        if with_decode and decode_rows:
            self._fault_point("decode-dispatch", rows=decode_rows)
        t0_w = time.time()
        # the prefill stage's table view is cut to the pages the batch
        # actually uses (power-of-two bucketed): the jnp fallback gathers
        # each row's whole table width per layer, so early chunks of a
        # long prompt would otherwise pay the full-capacity gather;
        # dropped positions are exactly-masked (zero-probability) slots,
        # so chunk values stay bitwise identical.  Narrow tables skip the
        # cut — the gather saving there is smaller than the cost of extra
        # program traces per width bucket — and so does an engine whose
        # attention WALKS the pages (``_slice_tables``)
        if self._slice_tables:
            ps = self.ec.page_size
            maxp_used = max(-(-(int(base[i]) + int(n_valid[i])) // ps)
                            for i, _, _ in chunks)
            maxp_b = min(1 << (max(maxp_used, 1) - 1).bit_length(),
                         self.ec.max_pages)
        else:
            maxp_b = self.ec.max_pages
        # a mixed tick is an epoch: its prefill block, the row state and
        # the block table cross in ONE upload, and the program takes them
        # apart, gathers the prefill rows' tables and merges the row state
        # itself (``_ragged_tick`` stage 0), so nothing is carried in
        buf, layout, hist = self._upload_epoch(
            p_b, width, maxp_b, p_toks=toks, p_base=base,
            p_nvalid=n_valid, p_emit=emit, p_canjoin=canjoin,
            p_rowmap=rowmap)
        # a pure-chunk tick (with_decode=False) has no decode stage for
        # spec to ride, so it dispatches the spec-free program variant —
        # the device history needs no maintenance there (prompts land
        # whole at epoch uploads, and nothing is emitted)
        tick_spec = fused and with_decode
        perf_pt = self._perf_point(
            1, width=width, with_decode=with_decode, spec=tick_spec,
            pb=p_b, maxp=maxp_b, ew=layout.e_w)
        rows_active = int(active.sum())
        tick_work = self._count_tick_work(
            active, with_decode, chunks, base, n_valid,
            prefill_table=p_b * maxp_b)
        self._count_sample_stages(
            [row for s, row, _ in chunks if emit[s]],
            decode_rows if with_decode else None)
        kw = {}
        if tick_spec:
            self.metrics["h2d_total"] += 1
            kw = dict(hist=hist, spec_ks=h2d(spec_ks),
                      spec_k=self.ec.spec_k, spec_ngram=self.ec.spec_ngram)
        cache_in, state_src = self._state_call()
        none5 = (None,) * 5        # an epoch carries no row state in
        with self._perf_dispatch(
                "tick.spec" if tick_spec else "tick.admission",
                point=perf_pt, rows_active=rows_active, **tick_work):
            out = _ragged_tick(
                self.cfg, self.params, cache_in, *none5, self.key, *none5,
                prefill=buf, epoch=layout, horizon=1,
                with_decode=with_decode, mesh=self.mesh,
                tp_manual=self._tp_manual,
                collective_qtype=self._collective_qtype,
                state_src=state_src, **kw)
            if with_decode:
                sent = self._sent_home(out[0], 1, p_b=p_b, spec=tick_spec)
        self._state_done(state_src)
        self._tick_dispatches += 1
        self.cache, *carried, self.key = out[1:8]
        dev = dict(zip(("toks", "row_lens", "active", "steps", "remain"),
                       carried))
        dev.update(zip(HELD_FIELDS, out[-1]))
        if tick_spec:
            hist = out[8]
        if hist is not None:
            dev["hist"] = hist
        self._dev = dev
        self._phase_to("drain")
        # advance bookkeeping; completed prompts run _finish_prompt once
        # their token arrives
        completing: list[tuple[int, int]] = []   # (slot, row)
        for i, row, n_i in chunks:
            self.row_lens[row] += n_i
            self._trace(self.rows[row], "prefill_chunk", t0=t0_w,
                        t1=time.time(), tokens=n_i,
                        base=int(base[i]), fused=True)
            rem = self._prefilling[row]
            if n_i == len(rem):
                self._prefilling.pop(row)
                completing.append((i, row))
            else:
                self._prefilling[row] = rem[n_i:]
        self.metrics["mixed_steps"] += 1
        # (a pure-chunk tick of such an engine is the one pass it always was)
        self.metrics["mixed_fused_steps"] += one_pass
        self.metrics["mixed_prefill_tokens"] += sum(
            n for _, _, n in chunks)
        self.metrics["prefill_tokens_per_step"] = round(
            self.metrics["mixed_prefill_tokens"]
            / self.metrics["mixed_steps"], 2)
        self.metrics["pages_in_use"] = self.alloc.pages_in_use
        if not with_decode:
            # pure-chunk tick, nothing decoding: no sync at all — the
            # program advanced every prefill row's device length in
            # place, so this is not even an epoch
            return
        if completing:
            self._dirty = True
            self._fault_point("sample",
                              rows=[row for _, row in completing])
        # the first tokens of prompts completing this tick must reach the
        # host to emit; n_exec is 0 only when no row decoded; a speculating
        # tick's accepted counts (for the drain walk) and draft economics
        # ride the same buffer
        got = self._sync_window(*sent)
        tok_np, lp_np = got["tok"], got["lp"]
        executed = self._count_state(got["n_exec"])
        nxt, lp = got["first_t"], got["first_lp"]
        for i, row in completing:
            # jaxlint: disable=JL002 -- host arrays: _sync_window fetched them above
            self._finish_prompt(row, int(nxt[i]), float(lp[i]))
            if not canjoin[i] and self.rows[row] is not None:
                # the pool could not back its decode slot: the program
                # kept it out of the decode stage
                self._finish(row, "length")
        self.metrics["steps"] += executed
        self.metrics["decode_horizon_effective"] = 1
        # the drain walk covers the decode participants: rows already
        # decoding plus completions that joined on device; rows finished
        # above (first-token EOS/budget/length) are None and skip
        mask = self._active_mask()
        if one_pass:
            # a row that got its first token in this tick did not step
            mask[[row for _, row in completing]] = False
        parts = self._decode_parts(mask)
        if tick_spec:
            take_np = got["take"]
            self._spec_metrics(take_np, got["prop"], got["acc"], executed)
            self._drain_spec_block(tok_np, lp_np, take_np, mask, executed)
        else:
            take_np = None
            self._drain_block(tok_np, lp_np, mask, executed)
        self._trace_decode(parts, t0_w, executed, take_np)
        self.metrics["tokens_per_sync"] = round(
            self.metrics["tokens"] / max(self.metrics["host_syncs"], 1), 2)
        self._publish_ahead(1)

    def _horizon_step(self, active: np.ndarray | None):
        """Fused decode: up to ``decode_horizon`` decode+sample steps in one
        device program, drained token-by-token through ``_emit`` so SSE
        streaming and finish semantics are exactly the H=1 path's.

        One program is READ a tick: the one an earlier tick queued
        (``_ahead``; ``active`` is not looked at then), else the one
        dispatched here.  Before it is read the tick after it is queued
        behind it where the host can tell that it is a plain steady tick
        with everything in place (``_queue_next``), so the wait, the drain
        and the rest of the host's turn run beside the device."""
        came = self._ahead
        q = came if came is not None else self._launch_planned(active)
        if q is None:
            return
        self._ahead = self._queue_next()
        if came is not None and self.perf is not None:
            self.perf.awaited(q.family, q.point)
        self._phase_to("drain")
        # h tokens per host round trip; n_exec < h only if every row died
        # early; a speculating tick's accepted counts (for the drain walk)
        # and draft economics ride the same buffer
        got = self._sync_window(*q.sent)
        if self._ahead is not None and self.perf is not None:
            self.perf.running(self._ahead.attrs)
        tok_block, lp_block = got["tok"], got["lp"]
        executed = self._count_state(got["n_exec"])
        if self.perf is not None:
            # the MFU join's loop multiplier: XLA's cost analysis counts
            # the horizon body once, the tick executed it `executed` times
            self.perf.note_executed(executed)
        self.metrics["steps"] += executed
        self.metrics["decode_horizon_effective"] = q.horizon
        self.metrics["pages_in_use"] = self.alloc.pages_in_use
        if q.ahead:
            self.metrics["ahead_ticks_total"] += 1
            self.metrics["ahead_stray_steps_total"] += q.stray
            for name, n in q.counts.items():
                self.metrics[name] = self.metrics.get(name, 0) + n
        # the rows the program decoded that are still its requests': one
        # the host retired while the program was queued is dropped here
        live = q.active & np.array([r is not None and r is b for r, b
                                    in zip(self.rows, q.bind)])
        parts = self._decode_parts(live)
        if q.fused_spec:
            take_np = got["take"]
            self._spec_metrics(take_np, got["prop"], got["acc"], executed)
            self._drain_spec_block(tok_block, lp_block, take_np,
                                   live, executed)
        else:
            take_np = None
            self._drain_block(tok_block, lp_block, live, executed)
        self._trace_decode(parts, q.t0_w, executed, take_np)
        self.metrics["tokens_per_sync"] = round(
            self.metrics["tokens"] / self.metrics["host_syncs"], 2)
        self._publish_ahead(q.planned)

    def _launch_planned(self, active: np.ndarray) -> "_Queued | None":
        """The tick's own program, in the serial order: back the pages its
        horizon writes, clamp to what the pool gives, dispatch.  None
        where no row is left to decode."""
        # the horizon target comes from the tick's plan (serving/
        # planner.py), which owns the old inline heuristics: the static
        # planner folds the admission-wave clamp (streams joining => H=1,
        # so a joining row never waits out a horizon and the batch fills
        # at the H=1 engine's pace; a full house with a queue keeps the
        # full horizon) over PRE-TICK queue state, the MPC planner
        # additionally caps the horizon a deadline-critical row rides.
        # One visible difference from the inline era: an arrival racing
        # into the inbox AFTER planning waits out at most one
        # already-planned horizon (streams stay bit-identical either way
        # — the H8==H1 contract).
        H = self._planned_horizon()
        # pre-allocate pages for the whole horizon; a tight pool shortens
        # the horizon for the step (power-of-two buckets bound recompiles)
        # instead of truncating requests the plain engine could still
        # serve — the mid-tick safety clamp under the planner: page-pool
        # reality outranks any prediction, and a cut planned horizon is
        # recorded for the flight ring (plan_clamped).
        # A speculating row wants min(H * (k_row+1), remaining budget)
        # slots — accepted tokens never outrun the budget, and writes past
        # the backed range are rejected drafts the scratch page absorbs —
        # and falls back to the plain width (spec off for this tick, as a
        # traced mask) before the whole tick's horizon is clamped on its
        # account.
        h = H
        plan = self._plan
        fused_spec = (self.ec.spec_k > 0
                      and (plan.spec_on if plan is not None else True))
        if self.ec.spec_k > 0 and not fused_spec:
            # the plan masked speculation off: this tick emits through
            # the plain steady program, which does not maintain the
            # device-resident token history
            self._hist_stale = True
        elif fused_spec and self._hist_stale:
            # epoch re-upload rebuilds hist from host-side ids before
            # the proposer scans it (_sync_device_state honors _dirty)
            self._dirty = True
            self._hist_stale = False
        spec_ks = self._spec_widths(active) if fused_spec else None
        for i in range(len(self.rows)):
            if not active[i]:
                continue
            lens = int(self.row_lens[i])
            # a near-finished row only reserves what its budget can write —
            # never H-1 dead slots that could starve another row's ensure
            # (its post-death masked rewrites route to the scratch page)
            rem_i = int(self.row_budget[i]) - len(self.rows[i].output_ids)
            k_i = int(spec_ks[i]) if spec_ks is not None else 0
            want = min(H * (k_i + 1), rem_i)
            if self._ensure_pages(i, lens + max(want, 1)):
                continue
            if k_i:
                spec_ks[i] = 0      # pool pressure: plain step this tick
                if self._ensure_pages(i, lens + max(min(H, rem_i), 1)):
                    continue
            backed = (int((self.tables[i] >= 0).sum()) * self.ec.page_size
                      - lens)
            if backed < 1:
                self._finish(i, "length")
                active[i] = False
            else:
                h = min(h, backed)
        if not active.any():
            return None
        if h < H:
            h = 1 << (h.bit_length() - 1)      # largest power of two <= h
            self.metrics["horizon_clamped"] = (
                self.metrics.get("horizon_clamped", 0) + 1)
            self._plan_overrun = True   # the pool cut the planned horizon
        self._fault_point("decode-dispatch",
                          rows=[i for i in range(len(self.rows))
                                if active[i]])
        return self._launch(active, h, H, spec_ks)

    def _planned_horizon(self) -> int:
        plan = self._plan
        return plan.horizon if plan is not None else self.ec.decode_horizon

    def _launch(self, active: np.ndarray, h: int, planned: int,
                spec_ks: np.ndarray | None = None,
                ahead: bool = False) -> "_Queued":
        """Dispatch ONE steady tick program over the rows ``active`` and
        start its results' way home.  ``ahead``: behind a program that has
        not been read, so the device's row state is one step past the
        host's: the device arrays that program returned go in as they
        are, and nothing is published."""
        t0_w = time.time()
        dev = self._dev if ahead else self._sync_device_state()
        fused_spec = spec_ks is not None
        family = "tick.spec" if fused_spec else "tick.steady"
        perf_pt = self._perf_point(h, width=0, spec=fused_spec,
                                   ew=int(dev["eos"].shape[1]))
        # what the program asks of the kernels and the sampler is counted
        # by the tick that READS it: a program queued behind another keeps
        # its counts until then (and a discarded one takes them with it)
        counts = {} if ahead else None
        attrs = dict(rows_active=int(active.sum()),
                     **self._count_tick_work(active, ahead=int(ahead),
                                             into=counts))
        self._count_sample_stages(np.flatnonzero(active), into=counts)
        key_in = self.key
        if fused_spec:
            # the spec-enabled form of the SAME single entry: drafting,
            # the [R, k+1] verify, and acceptance all ride inside the
            # horizon loop — still one dispatch (JP106 unchanged)
            self.metrics["h2d_total"] += 1      # the draft widths
            with self._perf_dispatch(family, point=perf_pt, **attrs):
                (home, self.cache, dev["toks"], dev["row_lens"],
                 dev["active"], dev["steps"], dev["remain"], self.key,
                 dev["hist"]) = _ragged_tick(
                    self.cfg, self.params, self.cache, dev["toks"],
                    dev["row_lens"], dev["active"], dev["temps"],
                    dev["top_ps"], self.key, dev["seeds"], dev["steps"],
                    dev["top_ks"], dev["eos"], dev["remain"],
                    prefill=None, horizon=h, hist=dev["hist"],
                    spec_ks=h2d(spec_ks), spec_k=self.ec.spec_k,
                    spec_ngram=self.ec.spec_ngram, mesh=self.mesh,
                    tp_manual=self._tp_manual,
                    collective_qtype=self._collective_qtype)
                sent = self._sent_home(home, h, spec=True)
        else:
            # the steady-state tick is the SAME single jitted entry the
            # mixed tick uses, with no prefill block: one program either
            # way, which is what lets JP106 pin the tick's jitted callees
            # to exactly 1
            cache_in, state_src = self._state_call()
            with self._perf_dispatch(family, point=perf_pt, ahead=ahead,
                                     **attrs):
                (home, self.cache, dev["toks"], dev["row_lens"],
                 dev["active"], dev["steps"], dev["remain"],
                 self.key) = _ragged_tick(
                    self.cfg, self.params, cache_in, dev["toks"],
                    dev["row_lens"], dev["active"], dev["temps"],
                    dev["top_ps"], self.key, dev["seeds"], dev["steps"],
                    dev["top_ks"], dev["eos"], dev["remain"],
                    prefill=None, horizon=h, mesh=self.mesh,
                    tp_manual=self._tp_manual,
                    collective_qtype=self._collective_qtype,
                    state_src=state_src)
                sent = self._sent_home(home, h)
            self._state_done(state_src)
            # the returned cache owns the (donated) tables buffer now
        self._tick_dispatches += 1
        if ahead and self.perf is not None:
            # what ``PerfWatch.running`` marks its window with
            attrs = self.perf.span_attrs(family, perf_pt, **attrs)
        return _Queued(sent=sent, active=active, bind=list(self.rows),
                       key=key_in, t0_w=t0_w, horizon=h, planned=planned,
                       fused_spec=fused_spec, family=family, point=perf_pt,
                       attrs=attrs, ahead=ahead, counts=counts or {})

    def _ahead_blocked(self) -> str | None:
        """Why the tick after the one awaited cannot be queued behind it,
        by what the host holds BEFORE that one is read; None where it is a
        plain steady tick as far as the host can tell (its pages are
        ``_queue_next``'s to back)."""
        if self._recurrent:
            # the state a tick started from IS the rollback's source and
            # the spare buffer is written (_state_call): a second program
            # in flight would write over what the first one's rollback
            # needs, and a third buffer of that size does not fit a chip.
            # (A model that carries the device counters alone takes a third
            # buffer of a few numbers.)
            return "state"
        if (self.ec.spec_k > 0 or self._planned_horizon() != 1
                or (self.mesh is not None and self.mesh.size > 1)):
            return "plan"
        if (self._prefilling
                or (self.queue_depth and self._free_row() is not None)
                or not self._host_ops.empty()):
            return "queue"      # a mixed tick, or a host operation, is due
        if self._dirty or self._retries or self._serial:
            # a finish, a completed prompt or a rollback wants a full
            # epoch, which only a host that has read everything can
            # publish; retries and bisection probes run in the serial order
            return "dirty"
        return None

    def _queue_next(self) -> "_Queued | None":
        """Dispatch the steady tick AFTER the one awaited before that one
        is read, or say why not (``ahead_blocked_<reason>_total``).  What
        it needs is known without reading: a live row advances by exactly
        one step, the program itself retires a row on EOS or on its budget,
        and the device arrays the awaited program returned are its inputs.
        A page's edge does not break the run: the page is backed here and
        the table crosses while the awaited program runs."""
        why = self._ahead_blocked()
        if why is None:
            # a row whose budget ends inside the awaited program is gone
            nxt = self._active_mask()
            for i in np.flatnonzero(nxt):
                nxt[i] = (int(self.row_budget[i])
                          - len(self.rows[i].output_ids)) > 1
            if not nxt.any():
                return None
            if not self._back_next_tick(1, inflight=1):
                why = "pages"   # dry: the tick that needs it clamps, counts
        if why is not None:
            self.metrics[f"ahead_blocked_{why}_total"] += 1
            return None
        self._fault_point("decode-dispatch", rows=np.flatnonzero(nxt))
        if self._tables_dirty:
            self._publish_tables()
        return self._launch(nxt, 1, 1, ahead=True)

    def _discard_ahead(self):
        """Throw away, unread, the program queued behind the last one read
        (a rollback, a stop): what it wrote lies past the committed
        lengths, its tokens were never staged, and the engine's key goes
        back to the one it was handed.  The device's row state is a step
        past the host's then: the caller marks it stale."""
        q, self._ahead = self._ahead, None
        if q is not None:
            self.key = q.key
            self._state_rewind()
            self.metrics["ahead_discarded_total"] += 1

    def _publish_ahead(self, horizon: int):
        """What the NEXT steady tick would publish before its dispatch,
        published now, before this tick commits: the pages its rows will
        write (a row that ends this tick on a page's edge) and, after a
        finish or a completed prompt, the row state.

        An upload and the unpack's dispatch each give up the interpreter's
        lock.  Between a commit and the next dispatch every stream's
        consumer wants it (a token's way to its socket), so an engine that
        published there waited for the lock twice with the device idle:
        3.5 ms an epoch at 16 streams, beside the 1.4 ms the work takes
        (PERF.md section 6, PR 44).  Before the commit the consumers sleep
        on their queues.  Not where the next tick is a mixed one (a row
        still prefilling, a request waiting beside a free row): that tick
        publishes an epoch of its own.  Nor where the next tick is queued
        already (``_ahead``): it went with its pages, and its row state is
        the device's own.  A dry pool is left to the tick that needs the
        page, which clamps and counts."""
        if self._ahead is not None or self._prefilling or (
                self.queue_depth and self._free_row() is not None):
            return
        if not self._back_next_tick(horizon):
            return
        if self._dirty or self._tables_dirty:
            self._phase_to("prepare")
            self._sync_device_state()

    def _back_next_tick(self, horizon: int, inflight: int = 0) -> bool:
        """Back the pages the next steady tick's rows will write, from
        lengths and budgets the host holds; ``inflight`` programs are
        dispatched and not read, each a step a live row has taken since.
        False where no row decodes or the pool is dry (nothing is taken
        then)."""
        active = np.flatnonzero(self._active_mask())
        if not active.size:
            return False               # nothing decodes: no steady tick
        page = self.ec.page_size
        short = {}                     # row -> (pages it will need, missing)
        for i in active:
            rem = (int(self.row_budget[i]) - len(self.rows[i].output_ids)
                   - inflight)
            lens = int(self.row_lens[i]) + inflight
            need = min(-(-(lens + min(horizon, rem)) // page),
                       self.ec.max_pages)
            if rem > 0 and self.tables[i, need - 1] < 0:
                short[i] = need, int((self.tables[i, :need] < 0).sum())
        if short:
            missing = sum(n for _, n in short.values())
            self.alloc.reserve(missing)
            if len(self.alloc.free) < missing:
                return False
            for i, (need, _) in short.items():
                self._ensure_pages(i, need * page)
        return True

    def _decode_parts(self, active: np.ndarray):
        """Tracing pre-capture for a decode drain: the participating
        (row, request, tokens-so-far) triples, so the per-request
        decode-horizon span can report the tokens THIS tick emitted.
        None when tracing is off (zero cost)."""
        if self.tracer is None:
            return None
        return [(i, self.rows[i], len(self.rows[i].output_ids))
                for i in range(len(self.rows))
                if active[i] and self.rows[i] is not None]

    def _trace_decode(self, parts, t0_w: float, executed: int, take_np):
        """Per-request span for one committed decode tick: the fused
        horizon (`decode_horizon`, steps + tokens) or the speculative
        loop (`spec_round`, iterations + accept counts from the device's
        take block).  Timestamps are the tick's own host window — the
        existing one-per-tick sync, no new device reads."""
        if not parts:
            return
        t1 = time.time()
        for i, req, n0 in parts:
            toks = len(req.output_ids) - n0
            if toks == 0 and req.finish_reason is None:
                continue            # masked out / spec-width-0 idle row
            if take_np is not None:
                row = take_np[i]
                rounds = int((row > 0).sum())
                self._trace(req, "spec_round", t0=t0_w, t1=t1,
                            rounds=rounds, tokens=toks,
                            accepted=max(int(row.sum()) - rounds, 0))
            else:
                self._trace(req, "decode_horizon", t0=t0_w, t1=t1,
                            steps=executed, tokens=toks)

    def _drain_block(self, tok_block, lp_block, active: np.ndarray, h: int):
        """Walk an [R, h] token/logprob block through the exact per-token
        emission path: the host stops a row at its EOS/budget/abort
        boundary, which is the same boundary the device masked at."""
        for i in range(len(self.rows)):
            if not active[i] or self.rows[i] is None:
                continue
            for j in range(h):
                self.row_lens[i] += 1
                tok = int(tok_block[i, j])
                self.toks[i] = tok
                self._emit(i, tok, float(lp_block[i, j]))
                if self.rows[i] is None:   # finished mid-block
                    break

    def _count_sync(self, seconds: float):
        """One blocking device->host materialization (the per-step cost the
        fused horizon amortizes over H tokens)."""
        self.metrics["host_syncs"] += 1
        self.hists["tick_sync_s"].observe(seconds)
        if self.perf is not None:
            self.perf.note_sync(seconds)
        self.metrics["host_sync_s"] = round(
            self.metrics["host_sync_s"] + seconds, 6)


def next_stream_item(engine: "ServingEngine", req: Request,
                     poll_s: float = 0.5) -> int | None:
    """Blocking fetch of one stream item, waiting in bounded slices so a
    dead engine thread fails the request (``finish_reason="error"``)
    instead of hanging the consumer forever.  The shared dead-engine
    detection protocol for every HTTP frontend — returns the next token,
    or None at end of stream / engine death."""
    while True:
        try:
            return req.stream_queue.get(timeout=poll_s)
        except queue.Empty:
            t = engine._thread
            if t is None or not t.is_alive():
                if req.finish_reason is None:
                    req.finish_reason = "error"
                return None


def stream_stamp(req: Request) -> float | None:
    """When the commit released the item its ONE consumer just took from
    ``req.stream_queue`` (``perf_counter``; call once an item, after the
    take).  None for an item put outside a tick's commit (the terminal
    None of a failed, shed or quarantined request)."""
    stamps = req.commit_stamps
    if not stamps:
        return None
    head = stamps[0]
    head[1] -= 1
    if head[1] <= 0:
        stamps.popleft()
    return head[0]


def stream_tokens(req: Request, timeout: float | None = None):
    """Yield tokens from a submitted request until completion.

    ``timeout`` is the max wait between tokens; None aligns it with the
    request's own deadline (plus grace for the engine's timeout tick to
    land) when one is set, else the historical 120 s."""
    if timeout is None:
        timeout = (req.deadline_s + 30.0) if req.deadline_s else 120.0
    while True:
        tok = req.stream_queue.get(timeout=timeout)
        if tok is None:
            return
        yield tok
