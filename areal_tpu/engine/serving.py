"""Continuous-batching generation engine over a paged KV pool.

TPU-native replacement for the reference's patched-SGLang server stack
(realhf/impl/model/backend/sglang.py:192-500 + patch/sglang/
v0.4.6.post2.patch): a pool of B sequence slots whose KV lives in a
shared paged pool (engine/paged.py), a jitted multi-step decode block,
batched bucketed prefill, per-slot sampling params, and interruption
BETWEEN blocks — which is what makes weight updates cheap: the loop
drains at a block boundary, partial outputs return to the clients (who
resubmit with the concatenated prefix, recomputing KV under the new
weights), and the new params are swapped in.

Differences from the round-2 dense engine (VERDICT r2 missing #1):
- KV memory scales with tokens in flight (`kv_pool_tokens`), not
  `B * max_seq_len`: long-context workloads (the reference benchmark's
  31k generation) fit because slots only hold pages they use.
- Pool exhaustion preempts the requesting slot via the normal interrupt
  path — the partial-rollout protocol (system/partial_rollout.py)
  resubmits with the prefix, so memory pressure degrades to extra
  prefill work instead of a crash.
- Prefill is batched across queued requests (one forward per admit
  round, row-count bucketed to cap compile variants).
- The engine accepts a `jax.sharding.Mesh` (see `serving_mesh`):
  params are tensor-sharded megatron-style (parallel/sharding.py), the
  KV pool is sharded over kv heads, and the Pallas paged-attention
  kernel runs under shard_map (paged.py).

Host<->device discipline: ALL per-slot control state lives on device
between blocks, admits land in one fused update (paged.apply_admits),
and each decode block costs exactly ONE device fetch (the packed result
array). Per-array pushes/fetches are serial host<->device round trips.

Static shapes throughout: the decode block is one compiled program
reused for the server's lifetime.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.base import env_registry, logging, tracing
from areal_tpu.base.fault_injection import faults
from areal_tpu.base.latency import LatencyHistogram
from areal_tpu.engine.paged import (
    TRASH_PAGE,
    PageAllocator,
    apply_admits,
    apply_deactivations,
    paged_chunk_prefill,
    paged_chunk_prefill_packed,
    paged_decode_block,
    pages_needed,
    quantize_kv,
    scatter_prefill,
    update_page_rows,
    warp_sample,
)
from areal_tpu.models.config import TransformerConfig
from areal_tpu.utils.jaxenv import say

logger = logging.getLogger("serving")


@dataclasses.dataclass
class GenRequest:
    qid: str
    input_ids: List[int]
    max_new_tokens: int = 256
    min_new_tokens: int = 0
    greedy: bool = False
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = -1
    stop_token_ids: Tuple[int, ...] = ()
    # Admission class, lower admits first: 0 = session continuation /
    # interrupted re-prefill (the server maps these from resubmissions),
    # 1 = fresh request. The engine additionally promotes any request
    # whose qid holds a parked prefix to class 0 — its pages are already
    # paid for, and finishing the session releases budget fastest.
    priority: int = 1
    # resolved by the engine loop:
    done_cb: Optional[Callable[["GenResult"], None]] = None
    submit_time: float = 0.0
    # Admission rounds this request sat in the backlog while higher-
    # priority work admitted ahead of it (starvation-aging counter).
    starved_rounds: int = 0


@dataclasses.dataclass
class GenResult:
    qid: str
    output_ids: List[int]
    output_logprobs: List[float]
    no_eos: bool  # True if stopped for a non-EOS reason (budget/interrupt)
    interrupted: bool
    version_start: int
    version_end: int
    latency: float = 0.0
    # Set iff the engine's serve loop died before this request finished
    # (e.g. an XLA compile error): outputs are empty/partial and the
    # engine accepts no further submits.
    error: Optional[str] = None


def _round_up(n: int, multiple: int) -> int:
    return max(multiple, -(-n // multiple) * multiple)


def _pow2_at_least(n: int, cap: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return min(p, cap)


def serving_mesh(
    n_devices: Optional[int] = None, axis: str = "tensor"
) -> "jax.sharding.Mesh":
    """Single-axis serving mesh: 4 axes so model-side sharding
    constraints (parallel/sharding.py) resolve, with only ``axis`` > 1
    ("tensor" for TP serving, "fsdp" for expert-parallel serving —
    experts shard over fsdp)."""
    from jax.sharding import Mesh

    devs = jax.devices()
    n = n_devices or len(devs)
    names = ("data", "fsdp", "seq", "tensor")
    shape = [1, 1, 1, 1]
    shape[names.index(axis)] = n
    arr = np.asarray(devs[:n]).reshape(shape)
    return Mesh(arr, names)


@functools.partial(jax.jit, static_argnames=("cfg", "pad_len", "mesh"))
def _prefill_batch(params, cfg: TransformerConfig, input_ids, lengths,
                   pad_len: int, mesh=None):
    """Batched prefill at a bucketed length.

    input_ids: [n, pad_len] right-padded; lengths: [n]. Returns
    (last_logits [n, V], k_pref, v_pref each [L, n, pad_len, Hkv, hd])."""
    from areal_tpu.models.transformer import forward as packed_forward

    n = input_ids.shape[0]
    pos = jnp.arange(pad_len)[None, :]
    seg = (pos < lengths[:, None]).astype(jnp.int32)
    positions = jnp.where(seg > 0, pos, 0).astype(jnp.int32)
    logits, (k, v) = packed_forward(
        params, cfg, input_ids, seg, positions, return_kv=True, mesh=mesh
    )
    last = jnp.take_along_axis(
        logits, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1
    )[:, 0]
    return last, k, v


# Machine-checked engine-loop thread contract (areal_tpu/lint,
# checker `loop-only`; docs/static_analysis.md). The attrs listed here
# are owned by the engine loop thread and have NO locks by design —
# the loop is the only writer/reader; `_run_on_loop` is the one legal
# cross-thread door (closures run between laps). Off-loop code needing
# a value reads a loop-maintained snapshot (e.g. `_backlog_len`,
# `_kv_pages_free`) instead. `instance_hints` extends the check to
# other modules: `self.engine.<attr>` in an HTTP handler is the same
# race spelled differently.
AREAL_LINT_LOOP_ONLY = {
    "ServingEngine": {
        "roots": ["_loop"],
        "door": "_run_on_loop",
        "attrs": [
            "_backlog", "_prefix_cache", "_allocator",
            "_k_pages", "_v_pages", "_dstate", "_page_table",
            "_pt_dirty", "_pt_dirty_slots", "_pt_dev", "_len",
            "_pending_deact",
            "_slot_req", "_slot_out", "_slot_lp", "_slot_vstart",
            "_slot_pages", "_slot_emit_t", "_rng", "_history",
            "_admit_inflight", "_blocks_since_admit",
            # Tiered-KV spill state: the parked-qids snapshot clock is
            # loop-owned (other threads read the _parked_qids snapshot
            # dict itself, replaced wholesale — the _backlog_len
            # pattern — plus the thread-safe _spill_q / kv_tier store).
            "_parked_snap_t",
        ],
        "init_ok": ["__init__"],
        "instance_hints": ["engine", "eng"],
    },
}


@jax.jit
def _first_sample_packed(logits, rng, temps, top_ps, top_ks, greedy_mask,
                         forbid_rows, eos_rows):
    """First-token sampling packed as ONE [n, 2] f32 fetch (tok, logprob)."""
    toks, lps = warp_sample(
        logits, rng, temps, top_ps, top_ks, greedy_mask, forbid_rows, eos_rows
    )
    return jnp.stack([toks.astype(jnp.float32), lps], axis=1)


class ServingEngine:
    """Slot-pool continuous-batching engine driven by a background thread."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params,
        max_batch_size: int = 8,
        max_seq_len: int = 2048,
        decode_block_steps: int = 16,
        prompt_bucket: int = 64,
        eos_token_id: Optional[int] = None,
        seed: int = 1,
        page_size: int = 128,
        kv_pool_tokens: Optional[int] = None,
        mesh=None,
        attn_impl: str = "auto",
        prefill_max_batch: int = 8,
        prefill_chunk: Optional[int] = None,
        chunked_prefill_per_lap: int = 2,
        prefix_cache_tokens: Optional[int] = None,
        kv_cache_dtype: Optional[str] = None,
        speculative_draft_len: int = 0,
        speculative_ngram: int = 2,
        speculative_window: Optional[int] = None,
        decode_weight_dtype: Optional[str] = None,
        prefill_token_budget: Optional[int] = None,
        decode_blocks_per_admit: int = 1,
        kv_tier_bytes: Optional[int] = None,
        kv_tier_disk_dir: Optional[str] = None,
        kv_tier_disk_bytes: Optional[int] = None,
        kv_spill_dtype: Optional[str] = None,
        decode_resident: Optional[bool] = None,
    ):
        self.cfg = cfg
        cfg.require_plain_stack("engine/serving.py ServingEngine")
        # Pin AREAL_CE_CHUNK now: retraces mid-run must not mix tuning
        # settings, and bad values must fail at init.
        from areal_tpu.ops import snapshot_env_tuning

        snapshot_env_tuning()
        # Sampled token ids round-trip through float32 in the packed
        # single-fetch decode result (paged.py); exact only below 2^24.
        assert cfg.vocab_size < 2**24, (
            f"vocab_size {cfg.vocab_size} >= 2^24 would corrupt token ids "
            "in the packed float32 decode fetch"
        )
        self.mesh = mesh
        if mesh is not None:
            from areal_tpu.parallel.sharding import shard_params

            params = shard_params(params, mesh)
        self.params = params
        self.B = max_batch_size
        self.page_size = page_size
        self.max_pages = pages_needed(max_seq_len, page_size)
        self.S = self.max_pages * page_size
        self.block_steps = decode_block_steps
        self.prompt_bucket = prompt_bucket
        self.prefill_max_batch = prefill_max_batch
        # Prompts longer than this prefill chunk-by-chunk through ONE
        # fixed-shape program (paged.paged_chunk_prefill) instead of the
        # per-length-bucket batched path — essential at 16-32k contexts
        # where every new bucket is a fresh multi-second XLA compile.
        assert prefill_chunk is None or prefill_chunk > 0, (
            f"prefill_chunk must be a positive chunk size or None, "
            f"got {prefill_chunk}"
        )
        self.prefill_chunk = prefill_chunk
        assert chunked_prefill_per_lap >= 1, (
            f"chunked_prefill_per_lap must be >= 1, got "
            f"{chunked_prefill_per_lap}"
        )
        self.chunked_prefill_per_lap = chunked_prefill_per_lap
        # Token-budget continuous batching: each admission round admits
        # new prompts only while their UNCACHED prefill tokens fit this
        # budget (the first candidate always admits, so one oversized
        # prompt can't starve). Bounds the prefill work interleaved into
        # a scheduler iteration — the knob that trades TTFT for decode
        # latency (ITL) under load. None = unbounded (legacy behavior).
        assert prefill_token_budget is None or prefill_token_budget >= 1, (
            f"prefill_token_budget must be >= 1 or None, got "
            f"{prefill_token_budget}"
        )
        self.prefill_token_budget = prefill_token_budget
        # Prefill/decode interleave ratio: run this many decode blocks
        # between admission rounds (1 = admit every lap). Raising it
        # favors running requests' ITL over queued requests' TTFT.
        assert decode_blocks_per_admit >= 1, (
            f"decode_blocks_per_admit must be >= 1, got "
            f"{decode_blocks_per_admit}"
        )
        self.decode_blocks_per_admit = decode_blocks_per_admit
        # First lap always admits (counter starts saturated).
        self._blocks_since_admit = decode_blocks_per_admit
        # qid-keyed prefix KV reuse (the radix-cache role of the
        # reference's serving backend): finished/interrupted requests
        # park their pages here; a resubmission with the same qid whose
        # prompt extends the cached tokens prefills only the delta
        # (partial rollouts resubmit prompt+generated with one qid per
        # sample, system/partial_rollout.py:88 — the whole-prefix
        # recompute was their dominant cost). Budget-bounded in tokens;
        # evicted LRU-first under any pool pressure; flushed on weight
        # swaps (old-weight KV is invalid). None disables.
        assert prefix_cache_tokens is None or prefix_cache_tokens >= 0
        self.prefix_cache_tokens = prefix_cache_tokens or 0
        self._prefix_cache: "collections.OrderedDict[str, Tuple[List[int], List[int]]]" = (
            collections.OrderedDict()
        )
        self._cached_tokens = 0
        self.prefix_cache_hits = 0
        self.prefix_tokens_reused = 0
        # Cumulative admissions: fleet hit-rate denominator (the manager
        # aggregates sum(hits)/sum(requests) across servers).
        self.total_requests = 0
        self.eos_token_id = eos_token_id
        self.attn_impl = attn_impl
        self.version = 0

        # KV pool precision: None/"model" stores the compute dtype;
        # "int8" stores (data, scales) pairs — half the decode-side HBM
        # traffic and double the tokens a pool budget holds (paged.py
        # "int8 KV pools"). AREAL_KV_CACHE_DTYPE flips the default so
        # bench/probe A/Bs need no plumbing.
        if kv_cache_dtype is None:
            kv_cache_dtype = env_registry.get_str("AREAL_KV_CACHE_DTYPE")
        if kv_cache_dtype not in (None, "model", "int8"):
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r}: expected None, "
                f"'model', or 'int8'"
            )
        self.kv_cache_dtype = kv_cache_dtype
        # N-gram (prompt-lookup) speculative decoding (engine/
        # spec_decode.py): draft_len > 0 feeds 1+draft_len rows per slot
        # per step and keeps the verified prefix — lossless (greedy
        # bit-identical; sampled distribution-exact) and device-resident.
        if speculative_draft_len == 0:
            # A/B hook, like AREAL_KV_CACHE_DTYPE: flips the default
            # without plumbing (bench/probe runs). Empty string == unset.
            speculative_draft_len = env_registry.get_int("AREAL_SPEC_DRAFT")
        assert speculative_draft_len >= 0 and speculative_ngram >= 1, (
            f"bad speculative config: draft_len={speculative_draft_len}, "
            f"ngram={speculative_ngram}"
        )
        self.spec_draft_len = speculative_draft_len
        self.spec_ngram = speculative_ngram
        # Backward search window for the draft lookup (ADVICE r5 #4): the
        # n-gram match otherwise scans all max_seq_len positions per step,
        # so draft cost scales with the CONFIGURED context, not the live
        # one. Default 1k recent tokens — where math-RL repeats live.
        # None = default/env; 0 = unbounded full-history scan.
        if speculative_window is None:
            env_w = env_registry.get_int("AREAL_SPEC_WINDOW")
            speculative_window = env_w if env_w is not None else 1024
        assert speculative_window >= 0, (
            f"speculative_window must be >= 0 (0 = unbounded), got "
            f"{speculative_window}"
        )
        self.spec_window = speculative_window
        # Acceptance telemetry: tokens emitted / (block steps * active
        # slots) — the realized speculation yield.
        self._spec_emitted = 0
        self._spec_steps = 0
        # int8 DECODE weights (W8A16, ops/wquant.py): halves the weight
        # stream per decode step; prefill keeps the bf16 params, so
        # prompt processing is identical to the unquantized engine.
        if decode_weight_dtype is None:
            decode_weight_dtype = env_registry.get_str(
                "AREAL_DECODE_WEIGHT_DTYPE"
            )
        if decode_weight_dtype not in (None, "model", "int8"):
            raise ValueError(
                f"decode_weight_dtype={decode_weight_dtype!r}: expected "
                f"None, 'model', or 'int8'"
            )
        # int8 + TP mesh IS supported: the quantize transform runs under
        # jit on the sharded params, so GSPMD places the scales (absmax
        # reduces axis -2 — an all-reduce max for row-parallel weights,
        # free for column-parallel) and the decode block consumes the
        # (q, s) pairs like any other sharded leaf. Greedy parity vs the
        # unsharded int8 engine is pinned by tests/engine/test_wquant_tp.
        self.decode_weight_dtype = decode_weight_dtype
        self._qparams = None
        self._refresh_qparams()
        # Token history per slot (prompt + emitted; one scratch column
        # for masked scatter writes). int32 [B, S+1]: tiny next to KV.
        self._history = (
            jnp.zeros((max_batch_size, self.S + 1), jnp.int32)
            if speculative_draft_len > 0
            else None
        )
        pool_tokens = kv_pool_tokens or max_batch_size * self.S
        self.n_pages = pages_needed(pool_tokens, page_size) + 1  # + trash
        self._allocator = PageAllocator(self.n_pages)
        self._k_pages = None
        self._v_pages = None

        # Device-resident control state (see module docstring); order
        # matches paged.apply_admits.
        B = self.B
        self._dstate = (
            jnp.zeros((B,), jnp.int32),  # lengths
            jnp.zeros((B,), jnp.int32),  # next_input
            jnp.zeros((B,), bool),  # active
            jnp.zeros((B,), jnp.int32),  # remaining
            jnp.zeros((B,), jnp.int32),  # min_remaining
            jnp.ones((B,), jnp.float32),  # temps
            jnp.ones((B,), jnp.float32),  # top_ps
            jnp.full((B,), -1, jnp.int32),  # top_ks
            jnp.zeros((B,), bool),  # greedy
        )
        self._rng = jax.random.PRNGKey(seed)

        # Device-resident decode dispatch (snapshot knob, A/B-able per
        # engine): page-table edits land as donated per-slot row
        # scatters (paged.update_page_rows) and chunked-prefill control
        # crosses as ONE fused array (paged_chunk_prefill_packed), so
        # between decode blocks only admission/eviction DELTAS pay H2D.
        # False restores the legacy full-table restage + per-scalar
        # staging; greedy-token parity between the modes is pinned in
        # tests/engine/test_decode_resident.py.
        if decode_resident is None:
            decode_resident = env_registry.get_bool("AREAL_DECODE_RESIDENT")
        self.decode_resident = bool(decode_resident)

        # Host mirrors + page bookkeeping.
        self._page_table = np.full((B, self.max_pages), TRASH_PAGE, np.int32)
        self._pt_dirty = True
        # Slots whose page-table row changed since the last device flush
        # (engine-thread only): the resident path stages exactly these
        # rows; _pt_dirty stays the "full restage" flag (init, legacy
        # mode, too-many-dirty fallback).
        self._pt_dirty_slots: set = set()
        self._pt_dev = None
        self._len = np.zeros((B,), np.int64)
        self._pending_deact = np.zeros((B,), bool)

        # Decode-dispatch H2D telemetry (engine-thread writers; metrics()
        # reads the plain ints off-thread like total_generated). Counts
        # every host->device staging on the admit/decode hot path
        # (tests/engine/test_decode_resident.py reads them per block).
        self.h2d_transfers = 0
        self.h2d_bytes = 0
        self.decode_blocks = 0

        # Decode-time MoE router telemetry: last-block layer-mean drop
        # rate / router entropy from the two extra packed columns the
        # decode block emits for MoE models (zeros for dense models and
        # on the spec-decode path, which keeps its own packed layout).
        self.moe_drop_rate = 0.0
        self.moe_router_entropy = 0.0

        # host-side slot bookkeeping
        self._slot_req: List[Optional[GenRequest]] = [None] * self.B
        self._slot_out: List[List[int]] = [[] for _ in range(self.B)]
        self._slot_lp: List[List[float]] = [[] for _ in range(self.B)]
        self._slot_vstart: List[int] = [0] * self.B
        self._slot_pages: List[List[int]] = [[] for _ in range(self.B)]

        self._queue: "queue.Queue[GenRequest]" = queue.Queue()
        self._backlog: List[GenRequest] = []  # engine-thread only
        # qid -> pending (accepted, not yet admitted) request count:
        # the eviction pin set (_pinned_qids). Updated under _fatal_lock
        # at submit and at backlog pop.
        self._queued_qids: Dict[str, int] = {}
        # Loop-thread command queue (disaggregation handoff): closures
        # that must run between laps because they touch engine-thread
        # state (_prefix_cache, the page allocator, the donated KV pool
        # arrays). Drained at the top of every serve-loop lap.
        self._cmds: "queue.Queue" = queue.Queue()
        # Admit entries (slot, req, plen, pages, cached_use) currently
        # inside _admit_impl — reachable by _fail_all on mid-admit death.
        self._admit_inflight: List[Tuple[int, GenRequest, int, List[int], int]] = []
        self._lock = threading.Lock()
        self._interrupt = threading.Event()
        self._pending_params = None
        self._pending_version: Optional[int] = None
        # Serializes concurrent update_params callers (e.g. a manager
        # retry racing the original request after a flush timeout): an
        # older staging finishing last must not overwrite a newer one,
        # and HBM must never hold three weight copies at once.
        self._stage_lock = threading.Lock()
        # Pinned-version history lives in its OWN namespace, never mixed
        # with self.version: unversioned updates bump self.version too,
        # and comparing a trainer-pinned version against that counter
        # would silently blackhole a genuine update (e.g. unversioned
        # apply bumps live to v10, then the trainer's real v10 arrives
        # and would compare stale).
        self._highest_pinned = -1   # highest pinned version staged (not cancelled)
        self._applied_pinned = -1   # highest pinned version actually applied
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.fatal_error: Optional[BaseException] = None
        self._fatal_lock = threading.Lock()
        # metrics
        self.n_running = 0
        self.n_used_tokens = 0
        # Per-request latency SLO telemetry, recorded on the engine loop:
        # TTFT = submit -> first sampled token; ITL = decode-block wall
        # time amortized over the tokens the block emitted for a slot.
        self.ttft_hist = LatencyHistogram()
        self.itl_hist = LatencyHistogram()
        # Prompt tokens sitting in the queue + backlog (not yet admitted)
        # — the server's admission watermark reads this. Updated under
        # _fatal_lock on submit, on the engine thread at each pop.
        self.queued_prompt_tokens = 0
        self.total_generated = 0
        self.n_preempted = 0
        self.last_weight_swap_s = 0.0
        self.last_weight_stage_s = 0.0
        self.last_weight_cutover_s = 0.0
        # Per-slot wall time of the last token delivery: ITL samples
        # measure now - last_emit (NOT bare decode-block wall), so
        # admission-prefill stalls between blocks — the interference
        # disaggregation removes — show up in the histogram.
        self._slot_emit_t = [0.0] * self.B
        # Off-thread telemetry snapshots of loop-only state, refreshed
        # once per serve-loop lap (and by _fail_all): queue_depth and
        # metrics() are polled from the server/manager threads, and
        # len(self._backlog) / self._allocator.n_free there were
        # unlocked reads of engine-thread state (areal-lint loop-only).
        # One-lap staleness is fine for an admission watermark; plain
        # int stores are atomic under the GIL.
        self._backlog_len = 0
        self._kv_pages_free = self._allocator.n_free
        # Disaggregated-serving handoff telemetry.
        self.kv_exports = 0
        self.kv_export_bytes = 0
        self.last_kv_export_ms = 0.0
        self.kv_imports = 0
        self.kv_import_bytes = 0
        self.last_kv_import_ms = 0.0

        # Tiered KV plane (engine/kv_tier.py, docs/serving.md): prefix
        # evictions SPILL to a host-RAM (+ optional disk) tier in the
        # handoff wire format instead of being freed; a returning
        # session restores through the import scatter path instead of
        # paying a full re-prefill. The gather is dispatched ON the
        # loop thread (pool arrays are donated by the decode block),
        # but the device fetch + hashing + quantize run on a dedicated
        # spill thread — the PR 10 blocking-async discipline applied to
        # the serve loop itself.
        if kv_tier_bytes is None:
            kv_tier_bytes = env_registry.get_int("AREAL_KV_TIER_BYTES")
        if kv_tier_disk_dir is None:
            kv_tier_disk_dir = env_registry.get_str("AREAL_KV_TIER_DISK_DIR")
        if kv_tier_disk_bytes is None:
            kv_tier_disk_bytes = env_registry.get_int(
                "AREAL_KV_TIER_DISK_BYTES"
            )
        if kv_spill_dtype is None:
            kv_spill_dtype = env_registry.get_str("AREAL_KV_SPILL_DTYPE")
        if kv_spill_dtype not in (None, "model", "int8", "fp8"):
            raise ValueError(
                f"kv_spill_dtype={kv_spill_dtype!r}: expected None, "
                f"'model', 'int8', or 'fp8'"
            )
        self.kv_spill_dtype = (
            None if kv_spill_dtype == "model" else kv_spill_dtype
        )
        self.kv_tier = None
        if kv_tier_bytes and int(kv_tier_bytes) > 0:
            from areal_tpu.engine.kv_tier import KVTierStore

            self.kv_tier = KVTierStore(
                int(kv_tier_bytes),
                disk_dir=kv_tier_disk_dir,
                disk_capacity_bytes=int(kv_tier_disk_bytes or (1 << 30)),
            )
        # Bounded: each item pins one gathered-KV device array pair
        # until the spill thread drains it; overflow drops the spill
        # (counted as prefix loss) rather than holding device memory.
        self._spill_q: "queue.Queue" = queue.Queue(maxsize=64)
        self._spill_thread: Optional[threading.Thread] = None
        # Weight-swap tier flush, executed BY the spill thread: the
        # clear does per-entry disk unlinks under the store lock —
        # work the serve loop must never pay mid-swap.
        self._tier_clear = threading.Event()
        self.kv_spills = 0          # spill thread
        self.kv_spill_bytes = 0     # spill thread
        self.kv_spill_tokens = 0    # spill thread
        self.kv_restores = 0        # restore callers (server executor)
        self.kv_restore_host = 0
        self.kv_restore_disk = 0
        self.kv_restore_tokens = 0
        # Residual TRUE prefix loss (ISSUE 11 satellite): pages freed
        # while their KV was still valid and could not be spilled —
        # tier disabled, spill queue overflow, or a spill-thread
        # failure. Split per writer thread so the increments never
        # race; /metrics exposes the sum as kv_prefix_lost_total.
        self._kv_lost_evict = 0     # engine loop
        self._kv_lost_spill = 0     # spill thread
        # Off-thread snapshot of the parked-prefix qids (loop-only
        # _prefix_cache must never be read from server threads; the
        # loop refreshes this dict wholesale every ~0.2s — same pattern
        # as _backlog_len / _kv_pages_free).
        self._parked_qids: Dict[str, int] = {}
        self._parked_snap_t = 0.0

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        if self.kv_tier is not None:
            self._spill_thread = threading.Thread(
                target=self._spill_worker, daemon=True
            )
            self._spill_thread.start()

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=10)
        if self._spill_thread:
            # Best-effort wake only: the worker polls with a short get
            # timeout, and a blocking put on a full queue with a
            # stopped consumer would deadlock shutdown.
            try:
                self._spill_q.put_nowait(None)
            except queue.Full:
                pass
            self._spill_thread.join(timeout=10)

    def submit(self, req: GenRequest):
        # _fatal_lock closes the submit-vs-_fail_all race: without it a
        # request enqueued between the fatal check and the queue drain
        # would sit in the dead queue with no one to fire its done_cb.
        with self._fatal_lock:
            if self.fatal_error is not None:
                raise RuntimeError(
                    f"serving engine loop died: {self.fatal_error!r}"
                ) from self.fatal_error
            req.submit_time = time.monotonic()
            self.total_requests += 1
            self.queued_prompt_tokens += len(req.input_ids)
            self._queued_qids[req.qid] = (
                self._queued_qids.get(req.qid, 0) + 1
            )
            self._queue.put(req)

    def warm(
        self,
        prompt_lens: List[int],
        max_new_tokens: Optional[int] = None,
        timeout_s: float = 1800.0,
    ) -> float:
        """AOT warm hook: compile every program serving these prompt
        lengths needs — the bucketed (or chunked) prefill, the jitted
        decode block, first-token sampling — by running one throwaway
        greedy request per length through the live loop. Serving has no
        trainable state, so executing is the honest way to cover the
        whole dispatch surface; with a persistent compilation cache the
        XLA work outlives this process (the bench compile pass banks it,
        production servers use `warm_on_start` to pre-compile before
        registering for traffic). Returns seconds spent.

        Must be called after start(). Raises on timeout — a warm that
        cannot finish means the engine cannot serve."""
        assert self._thread is not None, "warm() requires start()"
        if max_new_tokens is None:
            max_new_tokens = 2 * self.block_steps
        done = threading.Event()
        got: List[GenResult] = []
        n = len(prompt_lens)

        def cb(res):
            got.append(res)
            if len(got) == n:
                done.set()

        t0 = time.perf_counter()
        for i, plen in enumerate(prompt_lens):
            # Token 1 everywhere: content is irrelevant, shapes compile.
            self.submit(GenRequest(
                qid=f"__warm{i}",
                input_ids=[1] * max(1, int(plen)),
                max_new_tokens=max_new_tokens,
                min_new_tokens=max_new_tokens,  # don't let EOS cut the
                greedy=True,                    # decode block short
                done_cb=cb,
            ))
        if not done.wait(timeout_s):
            raise TimeoutError(
                f"serving warm stalled: {len(got)}/{n} within {timeout_s:.0f}s"
            )
        errs = [r.error for r in got if r.error]
        if errs:
            raise RuntimeError(f"serving warm failed: {errs[0]}")
        dt = time.perf_counter() - t0
        logger.info(f"serving warm: {n} request(s), {dt:.1f}s")
        return dt

    # ------------------------------------------------------------------
    # Disaggregated prefill/decode: KV-handoff export/import
    # ------------------------------------------------------------------

    def _run_on_loop(self, fn, timeout_s: float = 60.0):
        """Run ``fn()`` on the engine loop thread between laps and return
        its result. Engine-thread state (_prefix_cache, the allocator,
        the donated pool arrays) has no locks by design — the loop owns
        it; this is the one cross-thread door."""
        if threading.current_thread() is self._thread:
            return fn()
        done = threading.Event()
        cell: Dict[str, Any] = {}
        self._cmds.put((fn, done, cell))
        deadline = time.monotonic() + timeout_s
        while not done.wait(0.05):
            if self.fatal_error is not None:
                raise RuntimeError(
                    f"serving engine loop died: {self.fatal_error!r}"
                ) from self.fatal_error
            if (
                self._thread is None
                or not self._thread.is_alive()
                or self._stop.is_set()
            ):
                raise RuntimeError("serving engine loop is not running")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"engine-loop command not served within {timeout_s}s"
                )
        if "exc" in cell:
            raise cell["exc"]
        return cell.get("ret")

    def _drain_cmds(self):
        while True:
            try:
                fn, done, cell = self._cmds.get_nowait()
            except queue.Empty:
                return
            try:
                cell["ret"] = fn()
            except BaseException as e:  # delivered to the waiting caller
                cell["exc"] = e
            finally:
                done.set()

    def export_kv_handoff(
        self, qid: str, compress: Optional[str] = None
    ) -> Tuple[Dict[str, Any], bytes]:
        """Export the parked KV prefix for ``qid`` as a versioned
        handoff blob (meta, payload) — the prefill side of disaggregated
        serving (engine/kv_handoff.py wire format).

        The entry is consumed: its pages transfer to the blob and are
        freed here (the decode pool owns the sequence now). Raises
        KeyError when ``qid`` holds no parked prefix (the request never
        finished, pool pressure evicted it, or the prompt was shorter
        than one page — callers fall back to serving locally).
        ``compress="int8"`` quantizes a float pool's KV on the wire
        (quantize_kv) and ``compress="fp8"`` onto the e4m3 wire
        (kv_handoff.quantize_kv_fp8); int8 pools always ship their
        (data, scales) form.
        """
        from areal_tpu.engine import kv_handoff as kvh
        from areal_tpu.engine.paged import gather_kv_tokens

        t0 = time.monotonic()

        def _peek_and_gather():
            # PEEK, don't pop: if the caller's loop-door wait times out,
            # the entry (and its pages) stay owned by the cache — a
            # popping closure executed after the caller abandoned it
            # would leak the pages forever (nobody left to free them).
            ent = self._prefix_cache.get(qid)
            if ent is None:
                raise KeyError(f"no parked KV prefix for qid {qid!r}")
            toks, pages = ent
            n = len(toks)
            n_pg = pages_needed(n, self.page_size)
            # Dispatch the gather HERE, on the loop thread: the decode
            # block donates the pool arrays, so a stale off-thread
            # reference could point at a freed buffer. The gathered
            # slices are fresh arrays, safe to device_get off-loop.
            k = gather_kv_tokens(self._k_pages, pages[:n_pg], n)
            v = gather_kv_tokens(self._v_pages, pages[:n_pg], n)
            return ent, toks, pages, self.version, k, v

        def _consume(ent):
            # Self-contained pop+free (identity-checked: an admission
            # may have consumed the entry meanwhile — ownership moved,
            # nothing to free here). Safe to run arbitrarily late.
            cur = self._prefix_cache.get(qid)
            if cur is ent:
                self._prefix_cache.pop(qid, None)
                self._cached_tokens -= len(ent[0])
                self._allocator.free(ent[1])

        try:
            ent, toks, pages, version, k, v = self._run_on_loop(
                _peek_and_gather
            )
        except KeyError:
            # Pool pressure spilled the park to the host tier: serve the
            # blob from there — the tier makes the old evicted-before-
            # export silent-loss window a served export instead. The
            # entry is consumed, like the HBM pop (the decode side owns
            # the sequence now).
            got = (
                self.kv_tier.get(qid, count=False)
                if self.kv_tier is not None else None
            )
            if got is None:
                raise
            meta, payload, _tier = got
            self.kv_tier.discard(qid)
            self.kv_exports += 1
            self.kv_export_bytes += len(payload)
            self.last_kv_export_ms = (time.monotonic() - t0) * 1000.0
            return meta, payload
        try:
            arrays, wire = self._pack_kv_wire(k, v, compress)
            segments, chunks, payload = kvh.pack_arrays(arrays)
            meta = kvh.build_meta(
                qid, version, toks, wire, self.cfg, segments, chunks
            )
        finally:
            self._run_on_loop(lambda: _consume(ent))
        self.kv_exports += 1
        self.kv_export_bytes += len(payload)
        self.last_kv_export_ms = (time.monotonic() - t0) * 1000.0
        return meta, payload

    def import_kv_handoff(self, meta: Dict[str, Any], payload: bytes):
        """Import a handoff blob: allocate pages, scatter the KV into the
        pool, park it as ``qid``'s prefix — the decode side. The caller
        then submits the continuation request (prompt + first token,
        priority 0); admission finds the parked prefix and prefills only
        the one-token delta.

        Raises KVHandoffVersionMismatch when the blob's weight version
        differs from the live engine's (checked ON the loop thread,
        atomically with the park, so a concurrent weight swap can never
        leave stale KV parked), and KVHandoffError on geometry/hash
        problems or pool exhaustion."""
        from areal_tpu.engine import kv_handoff as kvh
        from areal_tpu.engine.paged import scatter_prefill_int8

        t0 = time.monotonic()
        kvh.check_geometry(meta, self.cfg)
        qid = str(meta["qid"])
        toks = [int(t) for t in meta["tokens"]]
        n = len(toks)
        n_pg = pages_needed(n, self.page_size)
        pad = n_pg * self.page_size

        if meta["kv_wire"] == "int8" and self.kv_cache_dtype == "int8":
            # int8-preserving fast path (ISSUE 11 satellite): the wire's
            # (data, scales) pairs ARE an int8 pool's encoding, so they
            # scatter straight in — no dequantize→re-quantize round
            # trip (a spill + restore is bit-exact) and a quarter the
            # staged host/transfer bytes of the float path.
            kd, ks, vd, vs = kvh.unpack_kv_int8(meta, payload)
            if n != int(meta["n_tokens"]) or kd.shape[2] != n:
                raise kvh.KVHandoffError(
                    f"token/KV length mismatch: {n} tokens, KV {kd.shape}"
                )

            def pad_d(x):
                L, H, _, hd = x.shape
                out = np.zeros((L, H, pad, hd), x.dtype)
                out[:, :, :n] = x
                return out

            def pad_s(s):
                L, H, _ = s.shape
                out = np.zeros((L, H, pad), np.float32)
                out[:, :, :n] = s
                return out

            kd_dev, ks_dev = jnp.asarray(pad_d(kd)), jnp.asarray(pad_s(ks))
            vd_dev, vs_dev = jnp.asarray(pad_d(vd)), jnp.asarray(pad_s(vs))

            # Pools in, pools out: the loop-only attr writes stay inside
            # the door-passed _write below (areal-lint loop-only).
            def scatter(k_pages, v_pages, pages_dev):
                return scatter_prefill_int8(
                    k_pages, v_pages,
                    kd_dev, ks_dev, vd_dev, vs_dev, pages_dev,
                )
        else:
            kf, vf = kvh.unpack_kv_float(meta, payload)  # [L, Hkv, n, hd]
            if n != int(meta["n_tokens"]) or kf.shape[2] != n:
                raise kvh.KVHandoffError(
                    f"token/KV length mismatch: {n} tokens, KV {kf.shape}"
                )

            def to_pref(x):
                # [L, Hkv, n, hd] -> scatter_prefill's [L, 1, pad, Hkv, hd]
                L, H, _, hd = x.shape
                out = np.zeros((L, 1, pad, H, hd), np.float32)
                out[:, 0, :n] = x.transpose(0, 2, 1, 3)
                return out

            # Stage the (small) host->device transfers off the loop
            # thread; only the scatter dispatch runs on it.
            k_dev = jnp.asarray(to_pref(kf))
            v_dev = jnp.asarray(to_pref(vf))

            def scatter(k_pages, v_pages, pages_dev):
                return scatter_prefill(
                    k_pages, v_pages, k_dev, v_dev, pages_dev,
                )

        def _write():
            if int(meta["version"]) != self.version:
                raise kvh.KVHandoffVersionMismatch(
                    f"blob v{meta['version']} vs engine v{self.version}"
                )
            self._ensure_pool()
            pages = self._alloc_pages(n_pg)
            if pages is None:
                raise kvh.KVHandoffError(
                    f"pool exhausted: need {n_pg} pages, "
                    f"{self._allocator.n_free} free"
                )
            self._k_pages, self._v_pages = scatter(
                self._k_pages, self._v_pages, jnp.asarray(pages, jnp.int32)
            )
            old = self._prefix_cache.pop(qid, None)
            if old is not None:
                self._allocator.free(old[1])
                self._cached_tokens -= len(old[0])
            self._prefix_cache[qid] = (toks, pages)
            self._cached_tokens += n

        self._run_on_loop(_write)
        self.kv_imports += 1
        self.kv_import_bytes += len(payload)
        self.last_kv_import_ms = (time.monotonic() - t0) * 1000.0

    def is_stale_update(self, version: Optional[int]) -> bool:
        """True iff update_params(version=version) would drop the update
        as stale. Lets callers skip the (potentially multi-GB) weight
        load on a retry of a version that already landed."""
        if version is None:
            return False
        with self._stage_lock:
            return version <= self._highest_pinned

    def escalate_pending_interrupt(self):
        """Interrupt running requests iff a staged update is waiting to
        apply — the allow_interrupt side of a retry whose reload was
        skipped as stale (see is_stale_update). A bare interrupt with
        nothing pending would kill running requests for nothing."""
        with self._lock:
            if self._pending_params is not None:
                self._interrupt.set()

    def update_params(self, params, allow_interrupt: bool = True,
                      version: Optional[int] = None):
        """Swap weights at the next block boundary. With allow_interrupt,
        running requests are interrupted and returned partially (the AReaL
        protocol); without it, admission pauses and the swap happens once
        running requests drain. `version` pins the new weight version to
        the trainer's published one (self-incrementing would drift when
        the trainer publishes faster than the manager flushes).

        The host->device transfer is staged HERE, on the caller's
        thread, so decoding continues while the weights stream in; the
        serve loop's swap is then just a pointer flip + sync. Peak HBM
        holds two weight copies during staging (live + staged) — same
        as the old swap-time peak, just for longer. Staging seconds
        (dispatch + transfer completion) land in last_weight_stage_s.

        Concurrent callers (manager retry after a flush timeout) are
        serialized under _stage_lock, and a pinned update that is not
        newer than the highest pinned version already staged (and not
        since cancelled) is dropped — an older staging finishing last
        must never overwrite newer weights with stale ones. Unversioned
        updates are never dropped and never consume a pinned version."""

        def build():
            if self.mesh is not None:
                from areal_tpu.parallel.sharding import shard_params

                return shard_params(params, self.mesh)
            return jax.tree_util.tree_map(jnp.asarray, params)

        self._stage_update(build, allow_interrupt, version)

    def _stage_update(self, build, allow_interrupt: bool,
                      version: Optional[int]):
        """Shared staging machinery behind update_params /
        stage_shard_leaves: version gating, pending-copy eviction, the
        host->device transfer via ``build()`` (returns the staged device
        tree), and the pending-params publish + optional interrupt."""
        with self._stage_lock:
            if version is not None and version <= self._highest_pinned:
                logger.info(
                    f"dropping stale weight update v{version} "
                    f"(highest pinned v{self._highest_pinned}, "
                    f"live v{self.version})"
                )
                # Still honor interrupt escalation: a retry of a version
                # staged with allow_interrupt=False may be the manager
                # asking to stop waiting for the drain. The helper takes
                # _lock so the pending check-and-set is atomic against
                # _apply_pending_params' pop — a bare interrupt with
                # nothing pending would kill running requests for
                # nothing.
                if allow_interrupt:
                    self.escalate_pending_interrupt()
                return
            with self._lock:
                # A faster publisher must not stack staged copies: drop
                # any not-yet-applied pending weights BEFORE staging, or
                # HBM would briefly hold three copies (live + old staged
                # + new). A cancelled pinned staging never went live, so
                # its version must not block a later retry of the same
                # version (roll back to the last APPLIED pinned version;
                # _apply_pending_params removes pending under this same
                # lock, so a concurrently-applying update is never
                # rolled back here).
                if (
                    self._pending_params is not None
                    and self._pending_version is not None
                ):
                    self._highest_pinned = self._applied_pinned
                self._pending_params = None
                self._pending_version = None
            t0 = time.monotonic()
            staged = build()
            # Bound transfer completion (safe here: we're off the serve
            # loop).
            jax.block_until_ready(staged)
            self.last_weight_stage_s = time.monotonic() - t0
            with self._lock:
                self._pending_params = staged
                self._pending_version = version
                if version is not None:
                    self._highest_pinned = max(self._highest_pinned, version)
        if allow_interrupt:
            self._interrupt.set()

    def cutover_params(
        self,
        params,
        version: int,
        allow_interrupt: bool = True,
        timeout_s: float = 120.0,
    ) -> float:
        """Weight-plane cutover hook: swap to `params` (pinned to
        `version`) and BLOCK until the serve loop has landed it — the
        full interrupt -> device-transfer -> pointer-flip window, end to
        end. This is the number the distribution plane bounds separately
        from network transfer time: the bytes were already prefetched to
        host memory, so everything timed here is cutover cost (running
        requests interrupted via the pending-update escalation path and
        returned partial for client-side re-prefill).

        Returns seconds; recorded as ``last_weight_cutover_s``. Raises
        TimeoutError if the version never lands (serve loop dead)."""
        t0 = time.monotonic()
        self.update_params(
            params, allow_interrupt=allow_interrupt, version=int(version)
        )
        return self._await_pinned(int(version), t0, timeout_s)

    def _await_pinned(self, version: int, t0: float,
                      timeout_s: float) -> float:
        deadline = t0 + timeout_s
        while self._applied_pinned < version:
            if self.fatal_error is not None:
                raise RuntimeError(
                    f"cutover v{version}: serve loop died: "
                    f"{self.fatal_error!r}"
                ) from self.fatal_error
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"cutover v{version} did not land within {timeout_s}s "
                    f"(live v{self.version})"
                )
            time.sleep(0.002)
        self.last_weight_cutover_s = time.monotonic() - t0
        return self.last_weight_cutover_s

    # -- shard-aware cutover (the weight plane's sliced-manifest path) --

    def _addressable_axis_coords(self, axis: str) -> Dict[Any, int]:
        """{device: ``axis`` coordinate} for this PROCESS's devices.
        Under multi-host sharding each process sees only its own mesh
        slice (so it needs only its own ranks' shard leaves);
        single-process meshes see every coordinate."""
        coords: Dict[Any, int] = {}
        t_ax = list(self.mesh.axis_names).index(axis)
        local = {d.id for d in jax.local_devices()}
        for idx, dev in np.ndenumerate(self.mesh.devices):
            if dev.id in local:
                coords[dev] = int(idx[t_ax])
        return coords

    def _build_from_shard_leaves(self, leaves_by_rank, degree: int,
                                 global_shapes=None, axis: str = "tensor"):
        """Staged device tree from per-rank HOST shard leaves (flat
        {path: local ndarray} per shard rank, e.g. assemble_leaves of
        shard-manifest ChunkStores): each addressable device gets its
        rank's slab via device_put, then the global arrays form through
        jax.make_array_from_single_device_arrays under the engine's own
        NamedSharding. No model-sized host buffer and no resharding
        copy ever exists — the sliced wire bytes ARE the device shards.

        ``axis`` is the mesh axis the ranks shard: "tensor" (TP-sliced
        streams) or "fsdp" (expert-sliced streams — the EP stream ships
        each rank only its experts, with non-expert leaves replicated;
        a replicated slab that the serving mesh nonetheless shards gets
        sliced down host-side to the device's window)."""
        from jax.sharding import NamedSharding

        from areal_tpu.parallel.sharding import fitted_param_spec
        from areal_tpu.system.weight_transfer import unflatten_leaves

        mesh = self.mesh
        if mesh is None:
            raise ValueError(
                "shard-leaves cutover needs a mesh-sharded engine"
            )
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r}")
        t_size = mesh.shape.get(axis, 1)
        if degree != t_size:
            raise ValueError(
                f"shard degree {degree} != mesh {axis} size {t_size}"
            )
        for ax, size in mesh.shape.items():
            if ax != axis and size != 1:
                raise ValueError(
                    f"shard-leaves cutover supports single-axis meshes; "
                    f"axis {ax!r} has size {size}"
                )
        if axis != "tensor" and global_shapes is None:
            # TP shapes are inferrable (every fitted-tensor dim scales
            # by degree); an EP stream mixes sliced expert leaves with
            # replicated ones, so only the manifest's recorded global
            # shapes disambiguate.
            raise ValueError(
                f"shard-leaves cutover over {axis!r} needs global_shapes"
            )
        coords = self._addressable_axis_coords(axis)
        missing = sorted(
            {t for t in coords.values()} - set(leaves_by_rank)
        )
        if missing:
            raise ValueError(
                f"missing shard leaves for addressable tensor ranks "
                f"{missing}"
            )
        any_rank = next(iter(leaves_by_rank))
        paths = sorted(leaves_by_rank[any_rank])
        sizes = dict(mesh.shape)
        flat = {}
        for path in paths:
            local0 = leaves_by_rank[any_rank][path]
            if global_shapes is not None and path in global_shapes:
                # Shard manifests record each leaf's global shape —
                # authoritative (no inference edge cases on tiny dims).
                gshape = list(global_shapes[path])
            else:
                if axis != "tensor":
                    raise ValueError(
                        f"{path}: global shape required for "
                        f"{axis!r}-sharded leaves"
                    )
                # Infer: local shapes agree with the global on every dim
                # except those the fitted spec shards on 'tensor', which
                # concatenate across ranks. Fit against the local shape,
                # scale the tensor-sharded dims, then re-fit against the
                # recovered global shape.
                gshape = list(local0.shape)
                spec = fitted_param_spec(path, gshape, sizes)
                entries = list(spec) + [None] * (len(gshape) - len(spec))
                for i, entry in enumerate(entries):
                    names = (
                        entry if isinstance(entry, tuple)
                        else (entry,) if entry else ()
                    )
                    if "tensor" in names:
                        gshape[i] *= t_size
            spec = fitted_param_spec(path, gshape, sizes)
            sharding = NamedSharding(mesh, spec)
            idx_map = sharding.devices_indices_map(tuple(gshape))
            shards = []
            for dev, t in coords.items():
                local = leaves_by_rank[t][path]
                want = tuple(
                    (sl.stop if sl.stop is not None else dim)
                    - (sl.start or 0)
                    for sl, dim in zip(idx_map[dev], gshape)
                )
                if tuple(local.shape) != want:
                    if tuple(local.shape) == tuple(gshape):
                        # The stream replicated this leaf (e.g. an EP
                        # stream's attention weights) but the serving
                        # mesh shards it: take the device's window.
                        local = local[idx_map[dev]]
                    else:
                        raise ValueError(
                            f"{path}: rank-{t} shard shape {local.shape}"
                            f" != device shard {want} "
                            f"(global {tuple(gshape)})"
                        )
                shards.append(jax.device_put(local, dev))
            flat[path] = jax.make_array_from_single_device_arrays(
                tuple(gshape), sharding, shards
            )
        return unflatten_leaves(flat)

    def stage_shard_leaves(self, leaves_by_rank, degree: int,
                           version: Optional[int] = None,
                           allow_interrupt: bool = True,
                           global_shapes=None, axis: str = "tensor"):
        """update_params for pre-sliced host shards (see
        _build_from_shard_leaves)."""
        self._stage_update(
            lambda: self._build_from_shard_leaves(
                leaves_by_rank, degree, global_shapes, axis=axis
            ),
            allow_interrupt, version,
        )

    def cutover_shard_leaves(
        self, leaves_by_rank, degree: int, version: int,
        allow_interrupt: bool = True, timeout_s: float = 120.0,
        global_shapes=None, axis: str = "tensor",
    ) -> float:
        """cutover_params for pre-sliced host shards: stage each rank's
        slabs straight onto its devices, then block until the serve
        loop lands the version. ``axis="fsdp"`` lands expert-sliced
        (EP) streams on an expert-parallel serving mesh."""
        t0 = time.monotonic()
        self.stage_shard_leaves(
            leaves_by_rank, degree, version=int(version),
            allow_interrupt=allow_interrupt, global_shapes=global_shapes,
            axis=axis,
        )
        return self._await_pinned(int(version), t0, timeout_s)

    @property
    def queue_depth(self) -> int:
        """Requests accepted but not yet admitted to a slot. Uses the
        loop-maintained backlog-length snapshot (loop-only contract)."""
        return self._queue.qsize() + self._backlog_len

    def latency_snapshot(self, reset: bool = False) -> Dict[str, Any]:
        """Raw TTFT/ITL bucket counts (areal_tpu.base.latency edges) +
        percentiles; reset=True zeroes the histograms (the open-loop
        bench reads one snapshot per sweep point)."""
        from areal_tpu.base.latency import percentile_from_counts

        ttft = self.ttft_hist.counts(reset=reset)
        itl = self.itl_hist.counts(reset=reset)
        return {
            "ttft_counts": ttft,
            "itl_counts": itl,
            "ttft_p50_ms": percentile_from_counts(ttft, 50.0),
            "ttft_p99_ms": percentile_from_counts(ttft, 99.0),
            "itl_p50_ms": percentile_from_counts(itl, 50.0),
            "itl_p99_ms": percentile_from_counts(itl, 99.0),
        }

    def metrics(self) -> Dict[str, float]:
        return {
            "num_running_reqs": float(self.n_running),
            "num_used_tokens": float(self.n_used_tokens),
            "total_generated": float(self.total_generated),
            "queue_depth": float(self.queue_depth),
            "queued_prompt_tokens": float(self.queued_prompt_tokens),
            "ttft_p50_ms": self.ttft_hist.percentile(50.0),
            "ttft_p99_ms": self.ttft_hist.percentile(99.0),
            "itl_p50_ms": self.itl_hist.percentile(50.0),
            "itl_p99_ms": self.itl_hist.percentile(99.0),
            "ttft_count": float(self.ttft_hist.total()),
            "itl_count": float(self.itl_hist.total()),
            "kv_pages_free": float(self._kv_pages_free),
            "kv_pages_total": float(self.n_pages - 1),
            # Decode-dispatch H2D accounting (device-resident decode
            # state, docs/perf_notes.md Round 15): stagings + bytes on
            # the admit/decode hot path, and the decode-block count they
            # amortize over.
            "h2d_transfers_total": float(self.h2d_transfers),
            "h2d_bytes_total": float(self.h2d_bytes),
            "decode_blocks_total": float(self.decode_blocks),
            "h2d_per_decode_block": float(self.h2d_transfers)
            / max(1.0, float(self.decode_blocks)),
            "decode_resident": 1.0 if self.decode_resident else 0.0,
            "moe_drop_rate": float(self.moe_drop_rate),
            "moe_router_entropy": float(self.moe_router_entropy),
            "num_preempted_reqs": float(self.n_preempted),
            "last_weight_swap_s": float(self.last_weight_swap_s),
            "last_weight_stage_s": float(self.last_weight_stage_s),
            "last_weight_cutover_s": float(self.last_weight_cutover_s),
            "prefix_cache_hits": float(self.prefix_cache_hits),
            "prefix_tokens_reused": float(self.prefix_tokens_reused),
            "prefix_cached_tokens": float(self._cached_tokens),
            "total_requests": float(self.total_requests),
            # Disaggregated-serving KV handoff (export on prefill-role
            # engines, import on decode-role ones).
            "kv_export_total": float(self.kv_exports),
            "kv_export_bytes": float(self.kv_export_bytes),
            "last_kv_export_ms": float(self.last_kv_export_ms),
            "kv_import_total": float(self.kv_imports),
            "kv_import_bytes": float(self.kv_import_bytes),
            "last_kv_import_ms": float(self.last_kv_import_ms),
            # Tiered KV plane: spill/restore counters + per-tier store
            # telemetry (zeros when the tier is disabled).
            "kv_spill_total": float(self.kv_spills),
            "kv_spill_bytes": float(self.kv_spill_bytes),
            "kv_spill_tokens": float(self.kv_spill_tokens),
            "kv_restore_total": float(self.kv_restores),
            "kv_restore_host": float(self.kv_restore_host),
            "kv_restore_disk": float(self.kv_restore_disk),
            "kv_restore_tokens": float(self.kv_restore_tokens),
            "kv_prefix_lost_total": float(
                self._kv_lost_evict + self._kv_lost_spill
            ),
            **{
                f"kv_tier_{k}": v
                for k, v in (
                    self.kv_tier.stats() if self.kv_tier is not None
                    else {}
                ).items()
            },
            # Speculative decoding yield: emitted tokens per decode STEP
            # across slots that were active (1.0 = no speculation value;
            # the ceiling is 1 + draft_len). The number that decides
            # whether AREAL_SPEC_DRAFT stays on.
            "spec_tokens_per_step": float(
                self._spec_emitted / self._spec_steps
            ) if self._spec_steps else 0.0,
            # Raw numerator/denominator for fleet-level aggregation.
            "spec_emitted_tokens": float(self._spec_emitted),
            "spec_active_steps": float(self._spec_steps),
        }

    # ------------------------------------------------------------------
    # Engine loop
    # ------------------------------------------------------------------

    def _refresh_qparams(self):
        """(Re)build the int8 decode-weight tree from the live params —
        at init and after every weight swap."""
        if self.decode_weight_dtype is None:
            return
        from areal_tpu.ops.wquant import maybe_quantize_decode_weights

        self._qparams = maybe_quantize_decode_weights(
            self.params, self.cfg.tied_embeddings, self.decode_weight_dtype
        )

    @property
    def _decode_params(self):
        """Param tree the DECODE blocks run on (quantized when
        decode_weight_dtype is set); prefill always uses self.params."""
        return self._qparams if self._qparams is not None else self.params

    def _ensure_pool(self):
        if self._k_pages is not None:
            return
        c = self.cfg
        cdt = jnp.dtype(c.compute_dtype)
        shape = (c.n_layers, c.n_kv_heads, self.n_pages, self.page_size,
                 c.head_dim)

        def fresh_pool():
            if self.kv_cache_dtype == "int8":
                # Scales squeezed to [L, Hkv, N, pg]: pg is the lane dim
                # (a trailing size-1 dim would pad 128x under TPU tiled
                # layouts — see paged.py "int8 KV pools").
                return (jnp.zeros(shape, jnp.int8),
                        jnp.zeros(shape[:-1], jnp.float32))
            return jnp.zeros(shape, cdt)

        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            tensor = self.mesh.shape.get("tensor", 1)
            if c.n_kv_heads % tensor == 0:
                spec_d = P(None, "tensor", None, None, None)
                spec_s = P(None, "tensor", None, None)  # squeezed scales
            else:
                spec_d = spec_s = P()

            def put(pool):
                if isinstance(pool, tuple):
                    return (
                        jax.device_put(
                            pool[0], NamedSharding(self.mesh, spec_d)),
                        jax.device_put(
                            pool[1], NamedSharding(self.mesh, spec_s)),
                    )
                return jax.device_put(pool, NamedSharding(self.mesh, spec_d))

            self._k_pages = put(fresh_pool())
            self._v_pages = put(fresh_pool())
        else:
            self._k_pages = fresh_pool()
            self._v_pages = fresh_pool()

    def _free_slots(self) -> List[int]:
        return [i for i in range(self.B) if self._slot_req[i] is None]

    def _drain_queue(self):
        try:
            while True:
                self._backlog.append(self._queue.get_nowait())
        except queue.Empty:
            pass
        # Keep the off-thread snapshot near-live across the queue ->
        # backlog move, so queue_depth doesn't under-report for a lap.
        self._backlog_len = len(self._backlog)

    def _pop_backlog(self, idx: int = 0) -> GenRequest:
        req = self._backlog.pop(idx)
        self._backlog_len = len(self._backlog)
        with self._fatal_lock:
            self.queued_prompt_tokens = max(
                0, self.queued_prompt_tokens - len(req.input_ids)
            )
            n = self._queued_qids.get(req.qid, 0)
            if n > 1:
                self._queued_qids[req.qid] = n - 1
            else:
                self._queued_qids.pop(req.qid, None)
        return req

    # Admission rounds a class-1 request may be passed over before it
    # is promoted to class 0. With more live sessions than slots the
    # continuation stream never dries up, so without aging a fresh
    # request could wait forever behind promoted continuations.
    STARVATION_ROUNDS = 16

    def _effective_priority(self, req: GenRequest) -> int:
        if req.starved_rounds >= self.STARVATION_ROUNDS:
            return 0
        # A parked prefix marks a session continuation regardless of the
        # caller-declared class: its KV is already paid for.
        if req.qid in self._prefix_cache:
            return 0
        return req.priority

    def _order_backlog(self):
        """Priority-aware admission order: continuations / interrupted
        re-prefills (class 0) ahead of fresh requests; FIFO within a
        class (sort is stable). Fresh requests age (counter bumped in
        _admit_impl for requests passed over by an admitting round):
        after STARVATION_ROUNDS they join class 0, so a sustained
        continuation stream cannot starve them."""
        if any(self._effective_priority(r) != 0 for r in self._backlog):
            self._backlog.sort(key=self._effective_priority)

    def _h2d(self, arr) -> jnp.ndarray:
        """jnp.asarray with decode-dispatch H2D accounting (engine
        thread only): every staging on the admit/decode hot path goes
        through here so the per-block transfer counts the decode-state
        A/B banks are measured, not estimated."""
        a = jnp.asarray(arr)
        self.h2d_transfers += 1
        self.h2d_bytes += int(a.nbytes)
        return a

    def _chunked_prefill_one(
        self, input_ids: List[int], pages: List[int], start: int = 0
    ):
        """Prefill one prompt chunk-by-chunk into its allocated pages,
        beginning at position `start` (nonzero for prefix-cache hits:
        positions below `start` already hold valid KV in `pages`).
        Returns the device [V] logits row of the final token (for
        first-token sampling). One compiled program total — chunk size,
        page-table width, and pool shapes are all static. Resident mode
        fuses each chunk's (tokens, start, valid) control into ONE
        staged array; legacy mode keeps the three separate transfers."""
        # Cache-hit deltas run even when chunked prefill is not
        # configured; the prompt bucket doubles as the chunk size then.
        C = self.prefill_chunk or self.prompt_bucket
        self._ensure_pool()
        prow = np.full((self.max_pages,), TRASH_PAGE, np.int32)
        prow[: len(pages)] = pages
        prow_dev = self._h2d(prow)
        last = None
        for s0 in range(start, len(input_ids), C):
            seg = input_ids[s0 : s0 + C]
            valid = len(seg)
            if self.decode_resident:
                ctl = np.zeros((C + 2,), np.int32)
                ctl[:valid] = seg
                ctl[C] = s0
                ctl[C + 1] = valid
                last, self._k_pages, self._v_pages = (
                    paged_chunk_prefill_packed(
                        self.params, self.cfg, self._h2d(ctl),
                        self._k_pages, self._v_pages, prow_dev,
                        attn_impl=self.attn_impl, mesh=self.mesh,
                    )
                )
                continue
            toks = np.zeros((C,), np.int32)
            toks[:valid] = seg
            last, self._k_pages, self._v_pages = paged_chunk_prefill(
                self.params, self.cfg, self._h2d(toks), self._k_pages,
                self._v_pages, prow_dev,
                self._h2d(np.int32(s0)), self._h2d(np.int32(valid)),
                attn_impl=self.attn_impl,
                mesh=self.mesh,
            )
        return last

    def _takes_chunked_path(
        self, req: "GenRequest", plen: int,
        cached_use: Optional[int] = None,
    ) -> bool:
        """Single source of truth for which prompts run the one-at-a-time
        chunked prefill (vs the batched bucketed path): cache hits always
        (only the delta past cached_use needs compute), fresh prompts when
        longer than the configured chunk. With cached_use=None this is the
        pre-validation PREDICTION used by the per-lap admission cap — any
        parked cache entry counts, conservatively, since prefix validation
        happens later; a mispredicted entry just defers to the next lap."""
        if cached_use is None:
            hit = req.qid in self._prefix_cache
        else:
            hit = cached_use > 0
        return hit or bool(self.prefill_chunk and plen > self.prefill_chunk)

    def _admit(self):
        """Fill free slots from the backlog with ONE batched prefill and
        ONE fused device state update. Thin wrapper: the in-flight admit
        batch lives on the engine so _fail_all can reach requests that a
        mid-admit prefill failure (e.g. an XLA compile error) would
        otherwise strand in a dead stack frame."""
        batch = self._admit_inflight
        batch.clear()
        t0 = tracing.now_ns() if tracing.enabled() else 0
        self._admit_impl(batch)
        if batch and tracing.enabled():
            # Generation-busy evidence for the merged RL timeline (the
            # overlap score unions these with decode blocks).
            tracing.record_span(
                "server.prefill", t0, n_prompts=len(batch),
            )
        batch.clear()  # normal completion: requests now live in _slot_req

    def _admit_impl(self, batch):
        # Drain semantics for non-interrupting weight updates: stop
        # admitting so running requests finish and the swap can land.
        # (Before the counter reset: a pending swap must not consume the
        # interleave window — admission retries the lap after it lands.)
        if self._pending_params is not None:
            return
        self._blocks_since_admit = 0
        self._drain_queue()
        self._order_backlog()
        free = self._free_slots()
        # Chunked / cache-hit prefills run one prompt at a time on the
        # serve loop; admitting many long prompts in one lap would stall
        # decode for every running slot for the full sequential prefill.
        # Cap them per lap (the rest stay in the backlog for the next
        # lap, after a decode block has run).
        n_chunked = 0
        # Per-round prefill-token budget (token-budget continuous
        # batching): estimated from the parked prefix BEFORE validation
        # — a misprediction only shifts a prompt to the next round.
        tok_budget = self.prefill_token_budget
        while free and self._backlog and len(batch) < self.prefill_max_batch:
            req = self._backlog[0]
            plen = len(req.input_ids)
            if (
                self._takes_chunked_path(req, plen)
                and n_chunked >= self.chunked_prefill_per_lap
            ):
                break
            est_new = plen
            if tok_budget is not None:
                ent = self._prefix_cache.get(req.qid)
                if ent is not None:
                    est_new = plen - min(len(ent[0]), plen - 1)
                est_new = max(1, est_new)
                # The first admission of a round always proceeds: a
                # single over-budget prompt must not starve forever.
                if batch and est_new > tok_budget:
                    break
            if plen + req.max_new_tokens > self.S:
                req.max_new_tokens = max(0, self.S - plen)
            if plen >= self.S or req.max_new_tokens == 0:
                self._pop_backlog()
                self._finish_host(req, [], [], no_eos=True, interrupted=False,
                                  vstart=self.version)
                continue
            n_need = pages_needed(plen, self.page_size)
            if n_need > self.n_pages - 1:
                # The prompt alone exceeds the ENTIRE pool: no amount of
                # waiting frees enough pages. Reject now — blocking here
                # would stall this request forever and head-of-line-block
                # everything behind it. (Reachable via partial-rollout
                # resubmission growing the prefix past pool capacity.)
                self._pop_backlog()
                logger.warning(
                    f"rejecting {req.qid}: prompt needs {n_need} pages, "
                    f"pool has {self.n_pages - 1}"
                )
                self._finish_host(req, [], [], no_eos=True, interrupted=False,
                                  vstart=self.version)
                continue
            # Reserve through the first decode block, not just the prompt:
            # a prompt-only reservation can be preempted by _ensure_pages
            # before producing a single block, cycling admit -> preempt ->
            # resubmit with a full batched prefill each lap.
            n_reserve = pages_needed(plen + self.block_steps, self.page_size)
            n_reserve = min(n_reserve, self.max_pages, self.n_pages - 1)
            # Prefix-cache lookup: a resubmission whose prompt extends
            # the cached tokens keeps those pages and prefills only the
            # delta (positions cached_use..plen-1).
            pages = None
            cached_use = 0
            ent = self._prefix_cache.pop(req.qid, None)
            if ent is not None:
                ctoks, cpages = ent
                self._cached_tokens -= len(ctoks)
                use = min(len(ctoks), plen - 1)
                if (
                    use >= self.page_size
                    and ctoks[:use] == req.input_ids[:use]
                ):
                    if len(cpages) < n_reserve:
                        got = self._alloc_pages(n_reserve - len(cpages))
                        if got is None:
                            # Pool pressure mid-extension: re-park the
                            # entry and stop admitting.
                            self._prefix_cache[req.qid] = ent
                            self._cached_tokens += len(ctoks)
                            break
                        cpages = cpages + got
                    pages = cpages
                    cached_use = use
                    self.prefix_cache_hits += 1
                    self.prefix_tokens_reused += use
                else:
                    self._allocator.free(cpages)
            if pages is None:
                pages = self._alloc_pages(n_reserve)
                if pages is None:
                    break  # pool pressure: wait for frees
            self._pop_backlog()
            batch.append((free.pop(0), req, plen, pages, cached_use))
            if tok_budget is not None:
                tok_budget = max(0, tok_budget - est_new)
            if self._takes_chunked_path(req, plen, cached_use):
                n_chunked += 1
        if batch:
            # Starvation aging: only requests genuinely PASSED OVER age —
            # someone else admitted ahead of them this round. Rounds with
            # no admission capacity (all slots busy, pool dry) age no one,
            # so sustained saturation can't promote the whole backlog.
            for r in self._backlog:
                r.starved_rounds += 1
        if not batch:
            return
        # Long prompts go through the fixed-shape chunked prefill (one
        # compiled program regardless of length); short ones keep the
        # batched bucketed path. Chunked entries first so logits rows
        # stay aligned with `batch` order.
        def _is_chunked(e):
            return self._takes_chunked_path(e[1], e[2], e[4])

        long = [e for e in batch if _is_chunked(e)]
        short = [e for e in batch if not _is_chunked(e)]
        batch[:] = long + short  # in place: _admit_inflight keeps tracking
        logits_rows = [
            self._chunked_prefill_one(req.input_ids, pages, start=cu)
            for _, req, _, pages, cu in long
        ]
        if short:
            pad = _round_up(max(p for _, _, p, _, _ in short), self.prompt_bucket)
            pad = _round_up(min(pad, self.S), self.page_size)
            n_s = _pow2_at_least(len(short), self.prefill_max_batch)
            ids = np.zeros((n_s, pad), np.int32)
            lens = np.ones((n_s,), np.int32)  # dummy rows: 1-token prompts
            for i, (_, req, plen, _, _) in enumerate(short):
                ids[i, :plen] = req.input_ids
                lens[i] = plen
            short_logits, k_pref, v_pref = _prefill_batch(
                self.params, self.cfg, self._h2d(ids), self._h2d(lens),
                pad_len=pad, mesh=self.mesh,
            )
            # Scatter prefill KV into the pool. Chunks past a row's
            # allocation (prompt-bucket padding) and dummy rows land on
            # the trash page.
            n_chunks = pad // self.page_size
            flat = np.full((n_s, n_chunks), TRASH_PAGE, np.int32)
            for i, (_, _, plen_i, pages, _) in enumerate(short):
                # Only the prompt's chunks carry prefill KV; pages
                # reserved beyond the prompt (first-decode-block
                # headroom) receive decode writes later.
                n_p = pages_needed(plen_i, self.page_size)
                flat[i, :n_p] = pages[:n_p]
            self._ensure_pool()
            self._k_pages, self._v_pages = scatter_prefill(
                self._k_pages, self._v_pages, k_pref, v_pref,
                self._h2d(flat.reshape(-1)),
            )
            if long:
                # Only the mixed case pays for per-row slicing; the
                # all-short fast path below uses short_logits whole.
                logits_rows.extend(
                    short_logits[i] for i in range(len(short))
                )
        n_b = _pow2_at_least(len(batch), self.prefill_max_batch)
        if not long:
            last_logits = short_logits  # already [n_b, V]: fast path
        else:
            last_logits = jnp.stack(
                logits_rows
                + [jnp.zeros_like(logits_rows[0])] * (n_b - len(batch))
            )
        # Sample each row's first token (same warp as the decode block).
        self._rng, sub = jax.random.split(self._rng)
        eos_rows = np.stack(
            [self._eos_mask_np(req) for _, req, *_ in batch]
            + [self._eos_mask_np(None)] * (n_b - len(batch))
        )

        def col(fn, dtype, fill):
            return np.asarray(
                [fn(r) for _, r, *_ in batch]
                + [fill] * (n_b - len(batch)), dtype,
            )

        temps = col(lambda r: r.temperature, np.float32, 1.0)
        tps = col(lambda r: r.top_p, np.float32, 1.0)
        tks = col(lambda r: r.top_k, np.int32, -1)
        greedy = col(lambda r: r.greedy, bool, False)
        packed = np.asarray(_first_sample_packed(
            last_logits, sub, self._h2d(temps), self._h2d(tps),
            self._h2d(tks), self._h2d(greedy),
            self._h2d(col(lambda r: r.min_new_tokens > 0, bool, False)),
            self._h2d(eos_rows),
        ))  # one fetch: [n_b, 2]
        # First token is on host: TTFT = submit -> now (queue wait +
        # prefill + first sample, the SLO number the openloop bench
        # sweeps).
        t_first = time.monotonic()
        for slot_i, req_i, *_ in batch:
            self.ttft_hist.add((t_first - req_i.submit_time) * 1000.0)
            # ITL for this slot measures from its first token's arrival.
            self._slot_emit_t[slot_i] = t_first

        # Host bookkeeping + one fused device admit.
        adm_slots, adm_valid = [], []
        adm_plens, adm_toks, adm_budget, adm_minr = [], [], [], []
        adm_t, adm_tp, adm_tk, adm_g = [], [], [], []
        for i, (slot, req, plen, pages, _) in enumerate(batch):
            tok_i, lp_f = int(packed[i, 0]), float(packed[i, 1])
            # A stale deactivation from this slot's PREVIOUS request must
            # not clobber the fresh activation (apply_admits fully
            # overwrites the slot's device state anyway).
            self._pending_deact[slot] = False
            self._slot_req[slot] = req
            self._slot_out[slot] = [tok_i]
            self._slot_lp[slot] = [lp_f]
            self._slot_vstart[slot] = self.version
            self._slot_pages[slot] = pages
            self._page_table[slot, :] = TRASH_PAGE
            self._page_table[slot, : len(pages)] = pages
            self._pt_dirty = True
            self._pt_dirty_slots.add(slot)
            is_eos = tok_i in self._eos_set(req)
            budget_left = req.max_new_tokens - 1
            if (is_eos and req.min_new_tokens <= 1) or budget_left <= 0:
                # The prompt's KV is fully in the pool even though no
                # decode step ran; record it so _finish_slot can park
                # the pages for a same-qid extension instead of freeing
                # a fresh (possibly 16-32k-token) prefill.
                self._len[slot] = plen
                self._finish_slot(slot, hit_eos=is_eos)
                continue
            # `self._len` counts cache fill EXCLUDING the pending
            # next_input token: the first decode step writes the sampled
            # first token's k/v at position plen, then advances.
            self._len[slot] = plen
            adm_slots.append(slot)
            adm_valid.append(True)
            adm_plens.append(plen)
            adm_toks.append(tok_i)
            adm_budget.append(budget_left)
            adm_minr.append(max(0, req.min_new_tokens - 1))
            adm_t.append(req.temperature)
            adm_tp.append(req.top_p)
            adm_tk.append(req.top_k)
            adm_g.append(req.greedy)
        if not adm_slots:
            return
        m = _pow2_at_least(len(adm_slots), self.prefill_max_batch)
        pad_n = m - len(adm_slots)
        self._dstate = apply_admits(
            self._dstate,
            self._h2d(np.asarray(adm_slots + [0] * pad_n, np.int32)),
            self._h2d(np.asarray(adm_valid + [False] * pad_n)),
            self._h2d(np.asarray(adm_plens + [0] * pad_n, np.int32)),
            self._h2d(np.asarray(adm_toks + [0] * pad_n, np.int32)),
            self._h2d(np.asarray(adm_budget + [0] * pad_n, np.int32)),
            self._h2d(np.asarray(adm_minr + [0] * pad_n, np.int32)),
            self._h2d(np.asarray(adm_t + [1.0] * pad_n, np.float32)),
            self._h2d(np.asarray(adm_tp + [1.0] * pad_n, np.float32)),
            self._h2d(np.asarray(adm_tk + [-1] * pad_n, np.int32)),
            self._h2d(np.asarray(adm_g + [False] * pad_n)),
            n_slots=self.B,
        )
        if self._history is not None:
            from areal_tpu.engine.spec_decode import set_history

            rows = np.zeros((m, self.S + 1), np.int32)
            for i, slot in enumerate(adm_slots):
                req = self._slot_req[slot]
                plen = min(len(req.input_ids), self.S)
                rows[i, :plen] = req.input_ids[:plen]
                rows[i, plen] = self._slot_out[slot][0]
            self._history = set_history(
                self._history,
                self._h2d(np.asarray(adm_slots + [0] * pad_n, np.int32)),
                self._h2d(np.asarray(adm_valid + [False] * pad_n)),
                self._h2d(rows),
            )

    def _evict_one_prefix(self, pinned: Optional[set] = None,
                          spill: bool = True) -> bool:
        """Evict the least-recently-used cached prefix's pages — but
        SPILL the KV to the host tier first when one is configured
        (handoff wire format; the gather dispatches here on the loop,
        the device fetch + pack run on the spill thread), so eviction
        demotes the prefix instead of destroying it. Entries whose qid
        is in `pinned` (a request for them is already queued — a
        KV-handoff import or a continuation about to admit) are
        skipped: evicting them turns a one-token delta prefill into a
        full re-prefill ON the serve loop, stalling every running decode
        stream. Returns False when nothing (unpinned) is evictable.
        ``spill=False`` is the weight-swap flush: that KV is stale the
        moment the swap lands, so spilling it would only poison the
        tier."""
        if not self._prefix_cache:
            return False
        qid = None
        if pinned:
            for q in self._prefix_cache:  # oldest-first iteration
                if q not in pinned:
                    qid = q
                    break
            if qid is None:
                return False
            toks, pages = self._prefix_cache.pop(qid)
        else:
            qid, (toks, pages) = self._prefix_cache.popitem(last=False)
        self._spill_or_lose(qid, toks, pages, spill)
        self._allocator.free(pages)
        self._cached_tokens -= len(toks)
        return True

    def _spill_or_lose(self, qid: str, toks: List[int], pages: List[int],
                       spill: bool):
        """Loop-thread half of a spill: dispatch the token-major gather
        while the pages are still allocated (the results are fresh
        arrays, safe to device_get off-loop), then hand the rest to the
        spill thread. Anything that prevents the spill while the KV was
        still valid counts as a TRUE prefix loss (kv_prefix_lost_total
        on /metrics — the residual the tier exists to eliminate)."""
        if not spill:
            return  # weight-swap flush: the KV is stale, not lost
        if self.kv_tier is None:
            self._kv_lost_evict += 1
            return
        from areal_tpu.engine.paged import gather_kv_tokens

        n = len(toks)
        n_pg = pages_needed(n, self.page_size)
        k = gather_kv_tokens(self._k_pages, pages[:n_pg], n)
        v = gather_kv_tokens(self._v_pages, pages[:n_pg], n)
        try:
            self._spill_q.put_nowait(
                (qid, list(toks), self.version, k, v)
            )
        except queue.Full:
            # Dropping here (not blocking) keeps the serve loop's
            # latency bounded; the continuation pays a re-prefill.
            self._kv_lost_evict += 1

    def _pack_kv_wire(self, k, v, compress: Optional[str]):
        """(arrays, wire) for a gathered (possibly int8-pool) KV pair —
        shared by the handoff export and the spill worker. int8 pools
        ship their (data, scales) form unchanged; float pools
        optionally quantize on the wire (``compress='int8'`` or the
        e4m3 ``compress='fp8'`` — same 1-byte wire footprint, floating
        mantissa)."""
        if isinstance(k, tuple):  # int8 pool: (data, scales)
            arrays = [
                ("k_data", np.asarray(k[0])),
                ("k_scales", np.asarray(k[1], np.float32)),
                ("v_data", np.asarray(v[0])),
                ("v_scales", np.asarray(v[1], np.float32)),
            ]
            return arrays, "int8"
        if compress == "int8":
            kw, ks = quantize_kv(k)
            vw, vs = quantize_kv(v)
            arrays = [
                ("k_data", np.asarray(kw)),
                ("k_scales", np.asarray(ks[..., 0], np.float32)),
                ("v_data", np.asarray(vw)),
                ("v_scales", np.asarray(vs[..., 0], np.float32)),
            ]
            return arrays, "int8"
        if compress == "fp8":
            from areal_tpu.engine import kv_handoff as kvh

            kw, ks = kvh.quantize_kv_fp8(np.asarray(k))
            vw, vs = kvh.quantize_kv_fp8(np.asarray(v))
            arrays = [
                ("k_data", kw),
                ("k_scales", ks),
                ("v_data", vw),
                ("v_scales", vs),
            ]
            return arrays, "fp8"
        kh, vh = np.asarray(k), np.asarray(v)
        return [("k", kh), ("v", vh)], kh.dtype.name

    def _spill_worker(self):
        """Dedicated spill thread: device fetch (np.asarray of the
        fresh gathered arrays), optional int8 quantize, chunk hashing,
        and the tier insert — all the blocking work the serve loop must
        never pay (PR 10 discipline). One failure loses one prefix
        (counted), never the thread."""
        from areal_tpu.engine import kv_handoff as kvh

        while not self._stop.is_set():
            if self._tier_clear.is_set():
                # Weight swap landed: every tiered prefix is stale.
                # Cleared HERE (disk unlinks, store lock) so the serve
                # loop's swap window never pays for it.
                self._tier_clear.clear()
                self.kv_tier.clear()
            try:
                item = self._spill_q.get(timeout=0.2)
            except queue.Empty:
                continue
            if item is None:
                continue
            qid, toks, version, k, v = item
            if version != self.version:
                # Spilled under weights that are no longer live (a swap
                # landed while the item queued): restoring it would be
                # version-rejected anyway — stale, not lost. Dropping
                # here also keeps post-clear re-population impossible.
                continue
            t0 = tracing.now_ns() if tracing.enabled() else 0
            try:
                faults.maybe_fail("engine.kv_spill")
                arrays, wire = self._pack_kv_wire(
                    k, v, self.kv_spill_dtype
                )
                segments, chunks, payload = kvh.pack_arrays(arrays)
                meta = kvh.build_meta(
                    qid, version, toks, wire, self.cfg, segments, chunks
                )
                self.kv_tier.put(qid, meta, payload)
                self.kv_spills += 1
                self.kv_spill_bytes += len(payload)
                self.kv_spill_tokens += len(toks)
                if tracing.enabled():
                    tracing.record_span(
                        "server.kv_spill", t0, qid=qid,
                        n_tokens=len(toks), bytes=len(payload), wire=wire,
                    )
            except Exception:
                self._kv_lost_spill += 1
                logger.warning(f"kv spill failed for {qid!r}",
                               exc_info=True)

    def restore_from_tier(self, qid: str,
                          prompt_ids: Optional[List[int]] = None) -> int:
        """Pull a spilled prefix back from the tier into the paged pool
        (import scatter path) and park it, so the continuation about to
        be submitted admits as a delta prefill. Returns the restored
        token count, 0 on a miss/mismatch. Runs on server executor
        threads — never the event loop, never the serve loop directly
        (import_kv_handoff takes the loop door itself).

        A version-mismatched entry (spilled under older weights) is
        dropped; a prompt that does not extend the spilled tokens leaves
        the entry in place (another turn may still match)."""
        from areal_tpu.engine import kv_handoff as kvh

        if self.kv_tier is None:
            return 0
        # Validate against the META first (always host-resident): a
        # rejected probe must not pay a disk read/promotion nor count a
        # tier hit — that would churn the LRU and overstate the tier's
        # effectiveness vs kv_restore_total.
        meta0 = self.kv_tier.peek_meta(qid, count_miss=True)
        if meta0 is None:
            return 0
        if int(meta0.get("version", -1)) != self.version:
            self.kv_tier.discard(qid)  # stale forever under new weights
            return 0
        if prompt_ids is not None:
            toks = [int(t) for t in meta0["tokens"]]
            use = min(len(toks), len(prompt_ids) - 1)
            if use < self.page_size or toks[:use] != [
                int(t) for t in prompt_ids[:use]
            ]:
                return 0
        got = self.kv_tier.get(qid)
        if got is None:
            return 0  # raced an LRU ageout between peek and get
        meta, payload, tier = got
        try:
            self.import_kv_handoff(meta, payload)
        except kvh.KVHandoffVersionMismatch:
            self.kv_tier.discard(qid)  # stale forever under new weights
            return 0
        except (kvh.KVHandoffError, RuntimeError, TimeoutError):
            # Pool exhaustion / transient loop trouble: keep the entry —
            # this continuation re-prefills, a later one may restore.
            return 0
        self.kv_tier.discard(qid)  # HBM owns the prefix again
        self.kv_restores += 1
        self.kv_restore_tokens += int(meta["n_tokens"])
        if tier == "disk":
            self.kv_restore_disk += 1
        else:
            self.kv_restore_host += 1
        return int(meta["n_tokens"])

    def has_parked(self, qid: str) -> bool:
        """Whether the engine holds a parked HBM prefix for qid, from
        the loop-refreshed snapshot (up to ~0.2s stale — callers use it
        to skip redundant tier probes, and admission revalidates)."""
        return qid in self._parked_qids

    def parked_qids_now(self, timeout_s: float = 30.0) -> Dict[str, int]:
        """Authoritative qid -> token-count map of parked HBM prefixes,
        read ON the loop thread via the door. The off-thread
        ``_parked_qids`` snapshot is up to ~0.2s stale — fine for index
        advertisement, NOT for a drain enumerating what it must migrate
        (a just-parked prefix missed there would silently die with the
        process)."""
        def _read():
            return {
                q: len(e[0]) for q, e in self._prefix_cache.items()
            }

        return self._run_on_loop(_read, timeout_s)

    def parked_index(self, cap: int = 8192) -> List[Dict[str, Any]]:
        """HBM-parked entries for the /kv/index surface (snapshot-fed;
        tier entries come from kv_tier.held())."""
        out = []
        for q, n in list(self._parked_qids.items()):
            if len(out) >= cap:
                break
            out.append({
                "qid": q, "tier": "hbm", "n_tokens": int(n),
                "content_hash": "", "version": int(self.version),
            })
        return out

    def stage_peer_export(self, qid: str) -> Dict[str, Any]:
        """Peer-pull staging (/kv/manifest): return the handoff meta for
        a prefix this server holds, guaranteeing its payload is servable
        from the tier. A tier entry is served as-is (kept until LRU ages
        it); an HBM park is exported (consumed — the session is moving)
        and parked in the tier so /kv/chunk can stream its bytes.
        Raises KeyError when neither tier holds qid."""
        if self.kv_tier is None:
            raise KeyError(f"no kv tier to stage peer export for {qid!r}")
        got = self.kv_tier.get(qid, count=False)
        if got is not None:
            return got[0]
        meta, payload = self.export_kv_handoff(qid)
        self.kv_tier.put(qid, meta, payload)
        return meta

    def peer_payload(self, qid: str) -> Optional[Tuple[Dict, bytes]]:
        """(meta, payload) for /kv/chunk byte serving — no hit
        accounting, no consume (the peer may pull many chunks)."""
        if self.kv_tier is None:
            return None
        got = self.kv_tier.get(qid, count=False)
        return None if got is None else (got[0], got[1])

    def _flush_prefix_cache(self):
        while self._evict_one_prefix(spill=False):
            pass

    def _pinned_qids(self) -> set:
        """Qids with a pending (accepted, not yet admitted) request —
        submit queue AND backlog: their parked KV is about to be
        consumed."""
        with self._fatal_lock:
            return set(self._queued_qids)

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """Allocate, evicting cached prefixes under pressure: speculative
        cache pages must never cost an active request its admission or
        its next decode block. Prefixes with a queued consumer go last —
        a hard pool need may still take them, but only after every
        speculative park is gone."""
        got = self._allocator.alloc(n)
        if got is not None:
            return got
        pinned = self._pinned_qids()
        while got is None and self._evict_one_prefix(pinned):
            got = self._allocator.alloc(n)
        while got is None and self._evict_one_prefix():
            got = self._allocator.alloc(n)
        return got

    def _ensure_pages(self):
        """Grow each active slot's page allocation to cover the next
        decode block; preempt (interrupt-partial) the slot itself on pool
        exhaustion — the client resubmits with the prefix once pages free
        up (vLLM/SGLang preempt-and-recompute semantics)."""
        for slot in range(self.B):
            if self._slot_req[slot] is None or self._pending_deact[slot]:
                continue
            # Cap at the page-table width: a slot at max_seq_len stops on
            # budget within the block, and overflow writes are
            # trash-routed on device, so capping is safe — not capping
            # would overrun the page-table row and kill the loop thread.
            # Speculative blocks feed 1+draft_len rows per step; every
            # fed row writes KV, so reservation covers the worst case —
            # clamped by the slot's remaining budget: the device never
            # writes past len + remaining (eff <= remaining - 1 and the
            # len+remaining sum is invariant across steps), so a
            # nearly-done slot must not over-reserve 5x and trip
            # pool-pressure preemption it doesn't need.
            block_tokens = self.block_steps * (1 + self.spec_draft_len)
            req = self._slot_req[slot]
            remaining = max(
                1, req.max_new_tokens - len(self._slot_out[slot])
            )
            need = min(
                pages_needed(
                    int(self._len[slot]) + min(block_tokens, remaining),
                    self.page_size,
                ),
                self.max_pages,
            )
            cur = len(self._slot_pages[slot])
            if need <= cur:
                continue
            got = self._alloc_pages(need - cur)
            if got is None:
                self.n_preempted += 1
                self._finish_slot(slot, hit_eos=False, interrupted=True)
                continue
            self._page_table[slot, cur:need] = got
            self._pt_dirty = True
            self._pt_dirty_slots.add(slot)
            self._slot_pages[slot].extend(got)

    def _eos_set(self, req: Optional[GenRequest]) -> set:
        s = set(req.stop_token_ids) if req is not None else set()
        if self.eos_token_id is not None:
            s.add(self.eos_token_id)
        return s

    def _eos_mask_np(self, req: Optional[GenRequest] = None) -> np.ndarray:
        """[V] bool mask of stop-token columns (empty set -> all False;
        an index-based encoding would need a pad index, and any pad value
        lands on a real vocab column)."""
        mask = np.zeros((self.cfg.vocab_size,), bool)
        for t in self._eos_set(req):
            if 0 <= t < self.cfg.vocab_size:
                mask[t] = True
        return mask

    def _finish_host(self, req, out, lps, no_eos, interrupted, vstart):
        res = GenResult(
            qid=req.qid,
            output_ids=list(out),
            output_logprobs=list(lps),
            no_eos=no_eos,
            interrupted=interrupted,
            version_start=vstart,
            version_end=self.version,
            latency=time.monotonic() - req.submit_time,
        )
        self.total_generated += len(out)
        if req.done_cb:
            req.done_cb(res)

    def _finish_slot(self, slot: int, hit_eos: bool, interrupted: bool = False):
        req = self._slot_req[slot]
        self._finish_host(
            req, self._slot_out[slot], self._slot_lp[slot],
            no_eos=not hit_eos, interrupted=interrupted,
            vstart=self._slot_vstart[slot],
        )
        pages = self._slot_pages[slot]
        if pages:
            # Park the sequence's KV for qid resubmission instead of
            # freeing (budget permitting): the covered tokens are the
            # prompt plus emitted tokens whose K/V actually landed in
            # the pool (self._len excludes the pending next-input token).
            covered = (list(req.input_ids) + self._slot_out[slot])[
                : int(self._len[slot])
            ]
            if (
                self.prefix_cache_tokens
                and len(covered) >= self.page_size
                # A pending weight swap invalidates this KV the moment it
                # lands — parking it would only churn the eviction loop
                # before _apply_pending_params flushes everything.
                and self._pending_params is None
            ):
                old = self._prefix_cache.pop(req.qid, None)
                if old is not None:
                    self._allocator.free(old[1])
                    self._cached_tokens -= len(old[0])
                self._prefix_cache[req.qid] = (covered, pages)
                self._cached_tokens += len(covered)
                # Budget trim is SOFT: entries with a queued consumer
                # are never trimmed for budget (only for hard pool
                # pressure, _alloc_pages) — under a handoff-import burst
                # the oldest parks are exactly the queued continuations.
                trim_pinned = self._pinned_qids()
                while (
                    self._cached_tokens > self.prefix_cache_tokens
                    and self._evict_one_prefix(trim_pinned)
                ):
                    pass
            else:
                self._allocator.free(pages)
        self._slot_req[slot] = None
        self._slot_out[slot] = []
        self._slot_lp[slot] = []
        self._slot_pages[slot] = []
        self._page_table[slot, :] = TRASH_PAGE
        self._pt_dirty = True
        self._pt_dirty_slots.add(slot)
        # The device active mask may still have this slot on (host-side
        # stop, preemption, interrupt): deactivate before the next block
        # so its freed pages are never written again.
        self._pending_deact[slot] = True
        self._len[slot] = 0

    def _interrupt_all(self):
        for slot in range(self.B):
            if self._slot_req[slot] is not None:
                self._finish_slot(slot, hit_eos=False, interrupted=True)

    def _apply_pending_params(self):
        with self._lock:
            pending = self._pending_params
            version = self._pending_version
            self._pending_params = None
            self._pending_version = None
            # Commit the pinned version HERE, atomically with the pop: a
            # popped update always applies, and recording it only after
            # the (multi-second) swap would let update_params' cancel
            # -rollback read a not-yet-bumped _applied_pinned and regress
            # _highest_pinned below a version that is about to go live.
            if pending is not None and version is not None:
                self._applied_pinned = max(self._applied_pinned, version)
        if pending is not None:
            # Cached prefixes hold KV computed under the OLD weights:
            # reusing them after the swap would decode against a stale
            # attention state. Flush before the new version goes live.
            self._flush_prefix_cache()
            t0 = time.monotonic()
            # Transfers were staged on the updater's thread
            # (update_params); this is a pointer flip + completion sync.
            self.params = pending
            self._refresh_qparams()
            jax.block_until_ready(self.params)
            self.last_weight_swap_s = time.monotonic() - t0
            self.version = version if version is not None else self.version + 1
            say("weight_version", pid=os.getpid(), version=self.version)
            # The spill tier holds KV from the OLD version: flag the
            # flush for the spill thread (disk unlinks + store lock are
            # its kind of work, never this loop's) AFTER the version
            # bump, so its version gate also drops any pre-swap items
            # still sitting in the spill queue. Until it runs (<0.2s),
            # restores of stale entries are version-rejected anyway.
            if self.kv_tier is not None:
                self._tier_clear.set()
            logger.info(
                f"serving engine weights updated to v{self.version} "
                f"in {self.last_weight_swap_s:.3f}s"
            )
        self._interrupt.clear()

    def _flush_device_control(self):
        """Apply pending deactivations + page-table changes (async
        dispatches, no host sync).

        Resident mode stages only the DIRTY page-table rows (donated
        scatter, paged.update_page_rows) — the full [B, max_pages]
        restage is kept for init / legacy mode / more-than-half-dirty
        laps (at that point one bulk transfer beats many row
        scatters)."""
        if self._pending_deact.any():
            (lengths, next_input, active, remaining, min_remaining,
             temps, top_ps, top_ks, greedy) = self._dstate
            active = apply_deactivations(
                active, self._h2d(self._pending_deact)
            )
            self._dstate = (lengths, next_input, active, remaining,
                            min_remaining, temps, top_ps, top_ks, greedy)
            self._pending_deact[:] = False
        dirty = self._pt_dirty_slots
        if self._pt_dev is None or (self._pt_dirty and not dirty) or (
            dirty
            and (not self.decode_resident or len(dirty) > self.B // 2)
        ):
            self._pt_dev = self._h2d(self._page_table)
        elif dirty:
            slots = sorted(dirty)
            m = _pow2_at_least(len(slots), self.B)
            packed = np.full((m, self.max_pages + 1), -1, np.int32)
            packed[: len(slots), 0] = slots
            packed[: len(slots), 1:] = self._page_table[slots]
            self._pt_dev = update_page_rows(
                self._pt_dev, self._h2d(packed), n_slots=self.B,
            )
        self._pt_dirty = False
        dirty.clear()

    def _loop(self):
        try:
            self._serve()
        except Exception as e:  # serve-loop death must not strand clients
            self.fatal_error = e
            logger.exception("serving engine loop died: %s", e)
            self._fail_all(e)

    def _fail_all(self, exc: BaseException):
        """Deliver an error GenResult to every running + queued request so
        callers blocked on done_cb unwind instead of hanging (measured
        failure mode: a chunk-prefill XLA compile error left the 16k gen
        probe waiting out its full 1800 s timeout)."""
        msg = f"{type(exc).__name__}: {exc}"
        reqs = [r for r in self._slot_req if r is not None]
        self._slot_req = [None] * len(self._slot_req)
        # _backlog holds requests _drain_queue accepted but couldn't admit
        # yet (pool pressure / per-lap caps); _admit_inflight holds the
        # batch a mid-admit prefill failure abandoned — both are engine-
        # thread-only state, and the engine thread is dead by now. Dedup
        # by identity: a request can be in _admit_inflight AND _slot_req
        # if the failure hit partway through the slotting loop.
        reqs.extend(self._backlog)
        self._backlog.clear()
        self._backlog_len = 0
        seen = {id(r) for r in reqs}
        reqs.extend(e[1] for e in self._admit_inflight
                    if id(e[1]) not in seen)
        self._admit_inflight.clear()
        with self._fatal_lock:  # no submit can interleave with the drain
            while True:
                try:
                    reqs.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            self.queued_prompt_tokens = 0
            self._queued_qids.clear()
        for req in reqs:
            if req.done_cb:
                try:
                    req.done_cb(GenResult(
                        qid=req.qid, output_ids=[], output_logprobs=[],
                        no_eos=True, interrupted=True,
                        version_start=self.version, version_end=self.version,
                        latency=time.monotonic() - req.submit_time,
                        error=msg,
                    ))
                except Exception:
                    logger.exception("done_cb failed during _fail_all")

    def _serve(self):
        self._ensure_pool()
        eos_global = jnp.asarray(self._eos_mask_np())
        # Column count of the packed block result: the spec block emits
        # up to (1 + draft_len) tokens per step.
        n = self.block_steps * (1 + self.spec_draft_len)
        while not self._stop.is_set():
            # Handoff export/import closures (engine-thread state only).
            self._drain_cmds()
            # Refresh the off-thread telemetry snapshots (see __init__).
            self._backlog_len = len(self._backlog)
            self._kv_pages_free = self._allocator.n_free
            now_lap = time.monotonic()
            if now_lap - self._parked_snap_t > 0.2:
                # Parked-prefix snapshot for off-thread consumers
                # (has_parked / the /kv/index surface): replaced
                # wholesale, like _backlog_len.
                self._parked_qids = {
                    q: len(e[0]) for q, e in self._prefix_cache.items()
                }
                self._parked_snap_t = now_lap
            if self._interrupt.is_set():
                self._interrupt_all()
                self._apply_pending_params()
            # Prefill/decode interleave: admission (which runs prefill on
            # this thread) only every decode_blocks_per_admit blocks —
            # except when idle, where admitting immediately is free.
            if (
                self._blocks_since_admit >= self.decode_blocks_per_admit
                or not any(r is not None for r in self._slot_req)
            ):
                # _admit resets the interleave counter itself, AFTER its
                # pending-weight-swap guard: a swap-blocked attempt keeps
                # the counter saturated so admission retries next lap
                # instead of waiting a fresh interleave period.
                self._admit()
            if not any(r is not None for r in self._slot_req):
                # idle: apply updates immediately, then wait for work
                if self._pending_params is not None:
                    self._apply_pending_params()
                time.sleep(0.002)
                self.n_running = 0
                continue
            self._ensure_pages()
            self._flush_device_control()
            if not any(r is not None for r in self._slot_req):
                continue
            self.n_running = sum(r is not None for r in self._slot_req)
            self.n_used_tokens = int(self._len.sum())

            (lengths, next_input, active, remaining, min_remaining,
             temps, top_ps, top_ks, greedy) = self._dstate
            decode_t0 = tracing.now_ns() if tracing.enabled() else 0
            t_blk0 = time.monotonic()
            if self.spec_draft_len > 0:
                from areal_tpu.engine.spec_decode import (
                    paged_spec_decode_block,
                )

                (packed, self._k_pages, self._v_pages, lengths,
                 next_input, active, remaining, min_remaining, self._rng,
                 self._history) = paged_spec_decode_block(
                    self._decode_params, self.cfg, self._k_pages,
                    self._v_pages,
                    self._pt_dev, lengths, next_input, active, remaining,
                    min_remaining, temps, top_ps, top_ks, greedy,
                    eos_global, self._rng, self._history,
                    n_steps=self.block_steps,
                    draft_len=self.spec_draft_len,
                    ngram=self.spec_ngram,
                    ngram_window=self.spec_window,
                    attn_impl=self.attn_impl, mesh=self.mesh,
                )
            else:
                (packed, self._k_pages, self._v_pages, lengths, next_input,
                 active, remaining, min_remaining,
                 self._rng) = paged_decode_block(
                    self._decode_params, self.cfg, self._k_pages,
                    self._v_pages,
                    self._pt_dev, lengths, next_input, active, remaining,
                    min_remaining, temps, top_ps, top_ks, greedy,
                    eos_global, self._rng,
                    n_steps=n, attn_impl=self.attn_impl, mesh=self.mesh,
                )
            self._dstate = (lengths, next_input, active, remaining,
                            min_remaining, temps, top_ps, top_ks, greedy)
            p = np.asarray(packed)  # the block's single device fetch
            self._blocks_since_admit += 1
            self.decode_blocks += 1
            if self.cfg.moe is not None and p.shape[1] >= 2 * n + 6:
                # MoE packed layout appends [moe_drop_rate,
                # moe_router_entropy] broadcast columns (paged.py).
                self.moe_drop_rate = float(p[0, 2 * n + 4])
                self.moe_router_entropy = float(p[0, 2 * n + 5])
            t_blk1 = time.monotonic()
            if tracing.enabled():
                tracing.record_span(
                    "server.decode_block", decode_t0,
                    n_running=self.n_running,
                )
            toks_h = p[:, :n]
            lps_h = p[:, n:2 * n]
            n_emitted = p[:, 2 * n].astype(np.int64)
            # Inter-token latency: wall time since the slot's PREVIOUS
            # token delivery, amortized over the tokens this block
            # emitted (uniform within the block — the device doesn't
            # timestamp individual steps). Measuring from the last
            # delivery rather than the block start charges the
            # admission-prefill stalls between blocks to the running
            # slots that actually waited through them — the decode-
            # latency interference the disaggregated fleet removes.
            for slot in range(self.B):
                k = int(n_emitted[slot])
                if k > 0 and self._slot_req[slot] is not None:
                    t_prev = self._slot_emit_t[slot] or t_blk0
                    self.itl_hist.add(
                        (t_blk1 - t_prev) * 1000.0 / k, count=k
                    )
                    self._slot_emit_t[slot] = t_blk1
            if self.spec_draft_len > 0:
                # Spec block appends a per-slot active-steps column: the
                # exact yield denominator (early-finishing slots charge
                # only the steps they actually ran).
                self._spec_emitted += int(n_emitted.sum())
                self._spec_steps += int(p[:, 2 * n + 4].sum())
            hit_eos_h = p[:, 2 * n + 1] > 0.5
            active_h = p[:, 2 * n + 2] > 0.5
            # Mirror lengths for occupied slots only: the device array is
            # never reset for freed slots, so copying it wholesale would
            # resurrect stale counts into num_used_tokens (and skew the
            # manager's least_token_usage routing).
            occupied = np.asarray(
                [r is not None for r in self._slot_req], bool
            )
            self._len = np.where(
                occupied, p[:, 2 * n + 3].astype(np.int64), 0
            )
            for slot in range(self.B):
                req = self._slot_req[slot]
                if req is None:
                    continue
                k = int(n_emitted[slot])
                if k:
                    self._slot_out[slot].extend(
                        toks_h[slot, :k].astype(np.int64).tolist()
                    )
                    self._slot_lp[slot].extend(lps_h[slot, :k].tolist())
                # Per-request extra stop tokens (beyond the global EOS set)
                # are enforced on host: trim at the first occurrence AFTER
                # the min_new_tokens floor (the device forbid mask only
                # covers the global EOS set).
                extra = set(req.stop_token_ids) - self._eos_set(None)
                if extra:
                    for j, t in enumerate(self._slot_out[slot]):
                        if j < req.min_new_tokens:
                            continue
                        if t in extra:
                            self._slot_out[slot] = self._slot_out[slot][: j + 1]
                            self._slot_lp[slot] = self._slot_lp[slot][: j + 1]
                            self._finish_slot(slot, hit_eos=True)
                            break
                    if self._slot_req[slot] is None:
                        continue
                if not active_h[slot]:
                    self._finish_slot(slot, hit_eos=bool(hit_eos_h[slot]))
        # drain on stop
        self._interrupt_all()
