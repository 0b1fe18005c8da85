"""The JAX/GSPMD train+inference+generation engine.

Counterpart of the reference's backend stack — ReaLMegatronEngine
(realhf/impl/model/backend/megatron.py:385), PipelinableInferenceEngine
(backend/inference.py:25) and the pipe runner — collapsed into one class:
on TPU there is no pipeline schedule or DDP wrapper; `train_batch` runs
micro-batch gradient accumulation and a single optimizer step, exactly
matching PipelinableEngine.train_batch semantics
(realhf/api/core/model_api.py:514). Two input paths share the math: the
fused path (one donated jitted program, lax.scan accumulation — used for
'dp' normalization and serialized-dispatch CPU meshes) and the default
overlapped path, where a bounded prefetch thread packs + device_puts
micro-batch i+1 while micro-batch i's accumulate program runs
(engine/prefetch.py), with one accumulate program a micro-batch shape
(a minibatch's first micro-batch and the rest run the same one) and one
optimizer apply — no host fetch until the single packed-stats transfer
per batch.

Loss functions are pure jit-able callables
`loss_fn(model_out, rows) -> (loss_sum, aux_dict)` where `model_out` is
the per-token next-token logprobs [R, T] (LM models; computed by the
fused chunked-vocab op so [R, T, V] logits are never materialized) or
values [R, T] (critics), and `rows` carries the packed [R, T] arrays for
every data key (token-aligned keys scattered, per-sequence scalars
broadcast across their span).
"""

from __future__ import annotations

import collections
import collections.abc
import dataclasses
import functools
import operator
import time
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import GenerationHyperparameters, TrainEngine
from areal_tpu.base import datapack, env_registry
from areal_tpu.base import logging as areal_logging
from areal_tpu.base import stats_tracker, tracing
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.generation import generate_tokens
from areal_tpu.models.packing import PackedBatch, pack_sequences
from areal_tpu.models.transformer import forward as model_forward
from areal_tpu.models.transformer import scan_stacked
from areal_tpu.ops import band_loop
from areal_tpu.ops.loss import (
    fused_next_token_logprobs,
    response_scoring_mask,
    two_on,
)
from areal_tpu.engine import train_counts
from areal_tpu.engine.optimizer import (
    OptimizerConfig,
    host_lr_schedule,
    make_optimizer,
)
from areal_tpu.parallel.mesh import single_device_mesh
from areal_tpu.parallel.sharding import batch_sharding, param_shardings

logger = areal_logging.getLogger("jax_engine")

# From this module's import on (jax is in use anyway), every program the
# process builds is a record of `tracing.builds()`: the engine's own by
# name and shape (`tracing.build_site` before each dispatch), and what is
# built beside it (a caller's jitted weights, eager ops).
tracing.watch_builds()

PackedLossFn = Callable[[jnp.ndarray, Dict[str, jnp.ndarray]], Tuple[jnp.ndarray, Dict]]
# rows -> [R, T], nonzero at the positions whose logprob the loss reads;
# on device rows inside the step and on numpy rows for the host's counts.
ScoredFn = Callable[[Dict[str, Any]], Any]


def opt_state_shardings(opt_state, params, mesh):
    """Give optimizer-state subtrees that mirror the parameter tree their
    parameters' shardings (ZeRO: Adam mu/nu shard exactly like their
    params); everything else (step counts etc.) replicates.

    Matches *structurally*: any subtree of opt_state with the same treedef
    as `params` is assumed to be a per-parameter moment tree.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    p_shardings = param_shardings(params, mesh)
    params_treedef = jax.tree_util.tree_structure(params)
    replicated = NamedSharding(mesh, P())

    def walk(node):
        if jax.tree_util.tree_structure(node) == params_treedef:
            return p_shardings
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            mapped = [walk(v) for v in node]
            if hasattr(node, "_fields"):  # NamedTuple (optax states)
                return type(node)(*mapped)
            return type(node)(mapped)
        return jax.tree_util.tree_map(lambda _: replicated, node)

    return walk(opt_state)


# Leaves of the parameter tree that are buffers, not weights: the
# forward pass reads them, no gradient reaches them, and the optimizer
# neither updates them nor keeps moments for them. `expert_bias` is the
# router's selection bias, which pre-training balances by a rule of its
# own and an RL step leaves as it is.
BUFFER_LEAVES = ("expert_bias",)


def trainable(tree):
    """`tree` (parameters, or gradients of the same structure) without
    its buffer leaves: what the optimizer sees. A tree without buffers
    comes back with the same structure."""
    if isinstance(tree, dict):
        return {k: trainable(v) for k, v in tree.items()
                if k not in BUFFER_LEAVES}
    return tree


def with_buffers(new, old):
    """`old` with every leaf that `new` has replaced by it: the updated
    weights put back beside the buffers they were taken from."""
    if isinstance(old, dict):
        return {k: with_buffers(new[k], v) if k in new else v
                for k, v in old.items()}
    return new


@dataclasses.dataclass
class EngineStats:
    """Host-side per-train_batch summary."""

    loss: float = 0.0
    grad_norm: float = 0.0
    lr: float = 0.0
    n_tokens: float = 0.0


class TrainStats(collections.abc.Mapping):
    """What `train_batch` returns: a step's stats, read from the device
    when first looked at (any key, `len`, iteration, `dict(st)`) and kept
    from then on. A Mapping and no `dict` subclass: `dict(st)` on one of
    those copies the underlying storage past every overridden method."""

    def __init__(self, resolve: Optional[Callable[[], Dict[str, float]]] = None,
                 stats: Optional[Dict[str, float]] = None):
        """`resolve` makes the read, once; `stats` is a mapping born read."""
        self._resolve, self._stats = resolve, stats

    @property
    def resolved(self) -> bool:
        return self._stats is not None

    def read(self) -> Dict[str, float]:
        """The stats, read now if no look has read them yet."""
        if self._stats is None:
            self._stats, self._resolve = self._resolve(), None
        return self._stats

    def __getitem__(self, key):
        return self.read()[key]

    def __iter__(self):
        return iter(self.read())

    def __len__(self):
        return len(self.read())

    def __repr__(self):
        return f"TrainStats({self._stats if self.resolved else 'on the device'})"


class JaxTrainEngine(TrainEngine):

    def __init__(
        self,
        model_cfg: TransformerConfig,
        params: Dict[str, Any],
        mesh=None,
        optimizer_config: Optional[OptimizerConfig] = None,
        total_train_steps: int = 1000,
        attn_impl: str = "auto",
        remat: Any = "full",  # "full" | "save_attn" | "mlp" | "none" (bools ok)
        row_len_multiple: int = 128,
        max_row_len: Optional[int] = None,
        hf_family: Optional[str] = None,
        prefetch_depth: int = 2,
        stats_fetch_interval: int = 1,
    ):
        # AREAL_MOE_DISPATCH is the trainer-side dispatch A/B hook
        # (capacity vs dropless without config plumbing), snapshotted at
        # construction like the other engine A/B knobs.
        env_dispatch = env_registry.get_str("AREAL_MOE_DISPATCH")
        if env_dispatch is not None and model_cfg.moe is not None:
            model_cfg = dataclasses.replace(
                model_cfg,
                moe=dataclasses.replace(model_cfg.moe, dispatch=env_dispatch),
            )
        self.model_cfg = model_cfg
        # What `train.dispatch` says of the stack it runs.
        self._stack_attrs: Dict[str, Any] = train_counts.stack_attrs(model_cfg)
        # The prediction module's share of a train step (models/config
        # MTPConfig): the weight of its loss, 0 = the step skips its pass,
        # and the expert layers a step then runs, its block among them.
        self._mtp_weight = (
            float(model_cfg.mtp.loss_weight) if model_cfg.mtp is not None else 0.0)
        mtp_on = self._mtp_weight > 0
        self._n_moe_layers = model_cfg.n_moe_layers + (
            mtp_on and model_cfg.kinds()[-1].mlp == "moe")
        # The indexers' share of a train step (models/config IndexerConfig):
        # the weight of their KL loss, 0 = the step skips its pass.
        self._n_indexed = model_cfg.n_indexed_layers
        self._index_weight = (
            float(model_cfg.indexer.loss_weight) if self._n_indexed else 0.0)
        # Pin AREAL_CE_CHUNK now: retraces mid-run must not mix tuning
        # settings, and bad values must fail at init.
        from areal_tpu.ops import snapshot_env_tuning

        snapshot_env_tuning()
        # HF model family ("qwen2", "llama", ...) used by interface.save
        # to pick the weight-export mapping; None = not HF-exportable.
        self.hf_family = hf_family
        self.mesh = mesh if mesh is not None else single_device_mesh()
        self.attn_impl = attn_impl
        self.remat = remat
        self.row_len_multiple = row_len_multiple
        self.max_row_len = max_row_len
        self._is_train = optimizer_config is not None
        # Overlapped input pipeline: a background thread FFD-packs,
        # pads-to-bucket and device_puts micro-batch i+1 while micro-batch
        # i runs on device (engine/prefetch.py). 0 disables (fully eager).
        # AREAL_PREFETCH_DEPTH is an A/B hook like AREAL_KV_CACHE_DTYPE,
        # snapshotted at construction so a mid-run env change cannot flip
        # the pipeline shape between steps.
        env_depth = env_registry.get_int("AREAL_PREFETCH_DEPTH")
        if env_depth is not None:
            prefetch_depth = env_depth
        if prefetch_depth < 0:
            raise ValueError(f"prefetch_depth must be >= 0, got {prefetch_depth}")
        self.prefetch_depth = prefetch_depth
        # Stats-fetch cadence: every Nth train_batch pays the packed-stats
        # device round trip; the other
        # calls return the last fetched values tagged `<loss>/stats_stale`.
        if stats_fetch_interval < 1:
            raise ValueError(
                f"stats_fetch_interval must be >= 1, got {stats_fetch_interval}"
            )
        self.stats_fetch_interval = stats_fetch_interval
        self._train_calls = 0
        self._last_train_stats: Optional[Dict[str, float]] = None
        # Telemetry of the most recent train_batch/forward input pipeline
        # (packing density of what shipped to HBM, host-blocked wait, gap
        # between dispatches, structural overlap evidence). Also recorded
        # through the stats tracker as perf/* series.
        self.last_overlap: Dict[str, float] = {
            "packing_efficiency": 0.0,
            "h2d_wait_ms": 0.0,
            "dispatch_gap_ms": 0.0,
            "overlap_events": 0.0,
        }

        # dispatch='dropless' on an expert-parallel mesh (fsdp > 1
        # dividing num_experts) routes through the shard_map EP path
        # (models/moe.py _moe_mlp_ep): per-shard ragged_dot over local
        # experts with an all-gather + psum_scatter token exchange, so
        # the expert weights are never all-gathered. The indivisible
        # case keeps sharding.py's hidden-dim ZeRO fallback (ragged_dot
        # contracts an UNsharded expert axis there — legal under GSPMD).
        # Until PR 17 this combination raised NotImplementedError.
        self._param_shardings = param_shardings(params, self.mesh)
        self.params = jax.device_put(params, self._param_shardings)
        self._batch_sharding = batch_sharding(self.mesh)
        self._n_row_multiple = int(np.prod(self.mesh.devices.shape[:2]))  # data*fsdp
        # What a train step says of itself while tracing is on: the
        # `train.*` counters and its spans' attributes (engine/train_counts.py).
        self.counts = train_counts.TrainCounts(
            model_cfg, self.mesh, attn_impl, row_len_multiple, self._n_row_multiple,
            mtp=mtp_on, n_moe_layers=self._n_moe_layers)
        # XLA's in-process CPU collectives mismatch rendezvous when two
        # collective-bearing executables are in flight (async dispatch lets
        # e.g. the next step's program overlap the previous one); serialize
        # dispatch on the CPU platform. Real TPUs order collectives per
        # device stream, no sync needed.
        self._serial_dispatch = (
            self.mesh.size > 1 and self.mesh.devices.flat[0].platform == "cpu"
        )

        self.optimizer = None
        self.opt_state = None
        self._opt_shardings = None
        self._lr_schedule = None
        # LR-schedule position when callers do not pass version_steps
        # (one optimizer step per train_batch, the pre-PR-9 behavior).
        self._lr_steps = 0
        if optimizer_config is not None:
            # The optimizer applies a UNIT learning rate; the step
            # programs scale updates by the schedule value evaluated at
            # `version_steps` (see train_batch docstring).
            self.optimizer = make_optimizer(
                optimizer_config, total_train_steps, external_lr=True
            )
            self._lr_schedule = host_lr_schedule(
                optimizer_config, total_train_steps
            )
            weights = trainable(self.params)  # no moments for buffers

            def init_state(w):
                # Adam's moments in float32 from the start, as the first
                # update leaves them whatever the parameters' dtype
                # (optax makes them zeros_like the parameters, and the
                # update promotes them): moments that change dtype once
                # make every program that takes the optimizer state
                # twice, and the second build of the first one falls
                # wherever its shape comes round again.
                return self.optimizer.init(jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32), w))

            opt_shape = jax.eval_shape(init_state, weights)
            self._opt_shardings = opt_state_shardings(opt_shape, weights, self.mesh)
            self.opt_state = jax.jit(
                init_state, out_shardings=self._opt_shardings
            )(weights)
            if self._serial_dispatch:
                jax.block_until_ready(self.opt_state)
        # jit caches keyed by (kind, loss name, row shape, extra)
        self._jit_cache: Dict[Any, Any] = {}
        self.version = 0
        self._gen_calls = 0
        self._offloaded = False
        self._host_params = None
        self._host_opt_state = None
        # The overlapped path's fp32 gradient sums, kept from one
        # minibatch to the next (`_accum_step_fn`) and dropped when the
        # engine is asked for anything but another (`forward`,
        # `generate`, `offload`, `set_params`); None until the first.
        self._grad_sums = None

    # ------------------------------------------------------------------
    # Batch building
    # ------------------------------------------------------------------

    def _build_rows(
        self, sample: SequenceSample, keys: Optional[List[str]] = None
    ) -> Tuple[PackedBatch, Dict[str, np.ndarray]]:
        """Pack the main token key into rows; scatter/broadcast other keys.

        The one rule by which this engine picks a micro-batch's (rows,
        row length), for train steps, forward passes and the interfaces'
        whole-batch prep alike: `datapack.ladder_shape`, as few rows as
        hold its tokens at a row length from a short ladder."""
        main_key = sample._main_key()
        flat_main = sample.data[main_key]
        lens_per_seq: List[int] = []
        seqs: List[np.ndarray] = []
        offset = 0
        for sl in sample.seqlens[main_key]:
            for l in sl:
                seqs.append(np.asarray(flat_main[offset : offset + l]))
                lens_per_seq.append(l)
                offset += l
        n_rows, row_len = datapack.ladder_shape(
            lens_per_seq,
            row_len_multiple=self.row_len_multiple,
            n_rows_multiple=self._n_row_multiple,
            max_row_len=self.max_row_len,
        )
        batch = pack_sequences(seqs, row_len=row_len, n_rows=n_rows)
        rows: Dict[str, np.ndarray] = {
            "input_ids": batch.input_ids,
            "segment_ids": batch.segment_ids,
            "positions": batch.positions,
        }
        total_main = sum(lens_per_seq)
        for k in keys if keys is not None else sample.keys:
            if k == main_key or sample.data.get(k) is None:
                continue
            d = np.asarray(sample.data[k])
            if d.shape[0] == total_main:
                # Token-aligned: split per sequence in main-key order.
                per_seq, off = [], 0
                for l in lens_per_seq:
                    per_seq.append(d[off : off + l])
                    off += l
                rows[k] = batch.scatter_per_token(per_seq)
            elif d.shape[0] == len(lens_per_seq):
                # Per-sequence scalar: broadcast across each span.
                per_seq = [np.full((l,), d[i]) for i, l in enumerate(lens_per_seq)]
                rows[k] = batch.scatter_per_token(per_seq)
            else:
                raise ValueError(
                    f"key {k!r} length {d.shape[0]} aligns with neither tokens "
                    f"({total_main}) nor sequences ({len(lens_per_seq)})"
                )
        return batch, rows

    def _device_rows(self, rows: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        return {
            k: jax.device_put(np.asarray(v), self._batch_sharding)
            for k, v in rows.items()
        }

    # ------------------------------------------------------------------
    # Train
    # ------------------------------------------------------------------

    def _head_weight(self, p):
        if self.model_cfg.is_critic:
            return None
        if self.model_cfg.tied_embeddings:
            return p["embedding"]["weight"].T
        return p["head"]["weight"]

    def _mb_loss_fn(self, loss_fn: PackedLossFn, scored_fn: Optional[ScoredFn]):
        """loss over one micro-batch's rows: (params, rows) -> (loss_sum, aux).

        Non-critic models run the forward to hidden states only and feed
        the loss the fused next-token logprobs; the [R, T, V] logits are
        never materialized (reference analogue: vocab-parallel fused CE,
        realhf/impl/model/parallelism/tensor_parallel/modules.py:1180).

        `scored_fn(rows)` is `train_batch`'s: the caller of `train_batch`
        names the positions whose logprob `loss_fn` reads, and the head
        computes those alone (zeros elsewhere), each shard of the rows
        over its own. None: the head runs over every valid position.
        """
        is_critic = self.model_cfg.is_critic
        mtp = self._mtp_weight > 0
        mesh = self.mesh if self.mesh.size > 1 else None
        hyper = self.model_cfg.hyper is not None
        sums = self.model_cfg.moe is not None or self._n_indexed > 0 or hyper

        def compute(p, rows):
            out = model_forward(
                p, self.model_cfg,
                rows["input_ids"], rows["segment_ids"], rows["positions"],
                attn_impl=self.attn_impl, remat=self.remat,
                output="logits" if is_critic else "hidden",
                return_aux=sums,
                mesh=mesh, mtp=mtp, index_loss=self._index_weight > 0,
                bands=self._dead_bands(rows["input_ids"].shape[-1]),
            )
            if sums:
                out, moe_aux = out
            if mtp:
                out, mtp_hidden = out
            if not is_critic:
                scored = None if scored_fn is None else scored_fn(rows)
                out = fused_next_token_logprobs(
                    out, self._head_weight(p),
                    rows["input_ids"], rows["segment_ids"],
                    scored=scored, mesh=mesh,
                )
            loss_sum, aux = loss_fn(out, rows)
            if mtp:
                # The prediction module's loss beside the caller's: the
                # head again, over the module's hidden states and the
                # tokens two on, at the positions whose such token the
                # caller's loss scores; summed here and divided by the
                # step's denominator with the rest (the caller's count of
                # scored tokens, which is the module's count of targets
                # wherever no sequence's second token is scored). The
                # head's weight is the model's and stays the caller's
                # loss's to move (models/config.MTPConfig).
                with jax.named_scope("mtp_head"):
                    keep = (jnp.ones(rows["segment_ids"].shape, jnp.float32)
                            if scored is None else two_on(scored))
                    logp, hit = fused_next_token_logprobs(
                        mtp_hidden, jax.lax.stop_gradient(self._head_weight(p)),
                        rows["input_ids"], rows["segment_ids"],
                        scored=keep, mesh=mesh, shift=2, top=True,
                    )
                aux = dict(aux, mtp_loss=-jnp.sum(logp), mtp_accept=jnp.sum(hit))
                loss_sum = loss_sum + self._mtp_weight * aux["mtp_loss"]
            if self.model_cfg.moe is not None:
                # MoE aux losses scale with token count so they
                # survive the 1/global_denom normalization applied
                # at the optimizer step.
                n_tok = jnp.sum(rows["segment_ids"] > 0).astype(jnp.float32)
                moe_cfg = self.model_cfg.moe
                loss_sum = loss_sum + n_tok * (
                    moe_cfg.aux_loss_coef * moe_aux["load_balance_loss"]
                    + moe_cfg.z_loss_coef * moe_aux["z_loss"]
                )
                aux = dict(aux)
                aux["moe_load_balance"] = n_tok * moe_aux["load_balance_loss"]
                aux["moe_z_loss"] = n_tok * moe_aux["z_loss"]
                # Per-layer-mean capacity-overflow drop rate over REAL
                # tokens (0 under dropless dispatch). "mean:" stats are
                # averaged over micro-batches at surfacing instead of
                # 1/global_denom-normalized — n_tok counts all non-pad
                # tokens while global_denom counts loss-weight (response)
                # tokens, so the n_tok scaling used by the loss-like
                # stats would inflate a fraction.
                n_layers = self._n_moe_layers
                aux["mean:moe_drop_rate"] = moe_aux["drop_rate"] / n_layers
                # Router telemetry (PR 17): layer-mean router entropy,
                # expert overload factor (E * max_e layer-mean routing
                # fraction; 1.0 = perfectly balanced), and EP-exchange
                # bytes per device per step (layer-summed; 0 off
                # expert-parallel meshes). Same "mean:" convention as
                # drop_rate — these are ratios/volumes, not loss-like
                # token-scaled sums.
                aux["mean:moe_router_entropy"] = (
                    moe_aux["router_entropy"] / n_layers
                )
                aux["mean:moe_expert_overload"] = (
                    jnp.max(moe_aux["expert_load"])
                    / n_layers
                    * moe_cfg.num_experts
                )
                aux["mean:moe_a2a_bytes"] = moe_aux["a2a_bytes"]
                if "pairs_held" in moe_aux:
                    # A share of the experts (MoEConfig.experts_held):
                    # counts summed over expert layers and micro-batches,
                    # for the counters train.moe_pairs_held / train.moe_rows
                    # / train.moe_chunks.
                    aux["sum:moe_pairs_held"] = moe_aux["pairs_held"]
                    aux["sum:moe_rows"] = moe_aux["rows_run"]
                    aux["sum:moe_chunks"] = moe_aux["chunks_run"]
            if self._n_indexed:
                # The indexers' KL beside the caller's loss: the layers'
                # mean of a sum over this micro-batch's real tokens. Its
                # denominator is the step's count of real tokens, not the
                # caller's of scored ones, and only the indexers'
                # parameters have a gradient in it: `_optimizer_apply`
                # divides their gradients by that count and the rest by
                # the caller's (`inv_denom` holds both), and
                # `_fetch_train_stats` reports the loss the same way.
                kl = moe_aux["index_kl"] / self._n_indexed
                aux = dict(aux)
                aux["num:indexer_kl"] = kl
                aux["den:indexer_kl"] = jnp.sum(
                    rows["segment_ids"] > 0).astype(jnp.float32)
                aux["num:indexer_selected"] = moe_aux["index_chosen"]
                aux["den:indexer_selected"] = moe_aux["index_cells"]
                loss_sum = loss_sum + self._index_weight * kl
            if hyper:
                # What Sinkhorn left undone of H_res (the largest distance of
                # a row or column sum from 1), a mean over the step's real
                # tokens and the stack's sublayers: 0 where it converged.
                aux = dict(aux)
                aux["num:mhc_res_err"] = moe_aux["mhc_res_err"]
                aux["den:mhc_res_err"] = 2.0 * self.model_cfg.n_layers * jnp.sum(
                    rows["segment_ids"] > 0).astype(jnp.float32)
            return loss_sum, aux

        return compute

    def _optimizer_apply(self, params, opt_state, grads, inv_denom, lr):
        """The tail both step programs share, traced under one scope:
        1/global_denom normalization, grad norm, optimizer update at a
        unit LR, `p + lr * u` and the norm of what survived rounding."""
        with jax.named_scope("optimizer_apply"):
            weights = trainable(params)
            if inv_denom.ndim:
                # (the caller's loss's, the indexers' KL's): the two sets
                # of parameters are disjoint (`_mb_loss_fn`)
                grads = jax.tree_util.tree_map_with_path(
                    lambda path, g: g * inv_denom[int(any(
                        getattr(k, "key", None) == "indexer" for k in path))],
                    trainable(grads))
            else:
                grads = jax.tree_util.tree_map(
                    lambda g: g * inv_denom, trainable(grads))
            gnorm = optax_global_norm(grads)
            updates, opt_state = self.optimizer.update(grads, opt_state, weights)
            weights, unorm = apply_updates(weights, updates, lr)
        return with_buffers(weights, params), opt_state, gnorm, unorm

    def _train_step_fn(self, loss_name: str, loss_fn: PackedLossFn,
                       row_keys: Tuple[str, ...], n_mbs: int,
                       scored_fn: Optional[ScoredFn] = None):
        """One fused jitted program for the whole train step: micro-batch
        gradient accumulation (a lax.scan over each stack of equal-shaped
        micro-batches, `_stack_mb_rows`), global-denom
        normalization, grad norm, optimizer update — with params and
        optimizer state donated.

        One executable per step (vs the reference's per-microbatch
        fwd/bwd launches + separate optimizer step) keeps XLA free to
        overlap collectives and avoids any host round-trip inside a step.
        """
        key = ("train", loss_name, row_keys, n_mbs > 1, scored_fn is not None)
        if key in self._jit_cache:
            return self._jit_cache[key]

        mb_loss = self._mb_loss_fn(loss_fn, scored_fn)

        def step(params, opt_state, rows, inv_denom, lr):
            if n_mbs > 1:
                # rows: [n, R, T] stacks, one a micro-batch shape;
                # accumulate grads in fp32.
                def body(grads_acc, mb_rows):
                    (loss, aux), g = jax.value_and_grad(mb_loss, has_aux=True)(
                        params, mb_rows
                    )
                    with jax.named_scope("grad_accum"):
                        grads_acc = jax.tree_util.tree_map(
                            lambda a, b: a + b.astype(jnp.float32), grads_acc, g
                        )
                    return grads_acc, (loss, aux)

                grads = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                sums = []
                for stack in rows:
                    grads, per_mb = jax.lax.scan(body, grads, stack)
                    sums.append(jax.tree_util.tree_map(jnp.sum, per_mb))
                loss_sum, aux = jax.tree_util.tree_map(
                    lambda *x: functools.reduce(operator.add, x), *sums)
            else:
                (loss_sum, aux), grads = jax.value_and_grad(mb_loss, has_aux=True)(
                    params, rows
                )
                with jax.named_scope("grad_accum"):
                    grads = jax.tree_util.tree_map(
                        lambda g: g.astype(jnp.float32), grads
                    )

            # The optimizer runs with a unit LR; `_optimizer_apply` scales
            # by the schedule value for this version (multiplication
            # commutes bitwise, so the math equals an internal-schedule
            # adamw at this lr).
            params, opt_state, gnorm, unorm = self._optimizer_apply(
                params, opt_state, grads, inv_denom, lr
            )
            params = jax.lax.with_sharding_constraint(params, self._param_shardings)
            opt_state = jax.lax.with_sharding_constraint(
                opt_state, self._opt_shardings
            )
            # Pack every scalar stat into ONE f32 vector: the host then
            # needs a single device fetch per step (per-leaf fetches are
            # serial round trips). The
            # raw aux pytree is also returned — never fetched — purely so
            # the host can read its key structure.
            aux_leaves = jax.tree_util.tree_leaves(aux)
            packed = jnp.stack(
                [loss_sum.astype(jnp.float32), gnorm.astype(jnp.float32), unorm]
                + [a.astype(jnp.float32) for a in aux_leaves]
            )
            return params, opt_state, packed, aux

        return self._jit_cache.setdefault(key, jax.jit(step, donate_argnums=(0, 1)))

    def _accum_step_fn(self, loss_name: str, loss_fn: PackedLossFn,
                       row_keys: Tuple[str, ...],
                       scored_fn: Optional[ScoredFn] = None):
        """The one program a micro-batch shape of the pipelined
        accumulation path: `(params, g_acc, rows, first) -> (g_acc,
        (loss_sum, aux))`, one micro-batch's gradient added into the
        donated fp32 sums, or, where `first` (a value of the run, not of
        the trace) says so, put in their place whatever they held. A
        minibatch's first micro-batch and every later one run this same
        program, so a shape is traced, lowered and compiled once. Same
        per-mb math and left-to-right fp32 addition order as the fused
        scan body — the step's numerics must not depend on which path
        ran (see tests/engine/test_prefetch.py equivalence).

        How `first` is obeyed is decided a leaf, by where its gradient
        comes from (`transformer.scan_stacked`; PERF.md section 6, PR 49,
        each form measured on the other's leaves and read in the
        compiled text). A leaf whose layers all run in a scan has its
        gradient written as a stack when the scan ends and added by a
        fusion of its own: those leaves go through one `lax.cond`
        between converting and adding, which costs nothing, and the
        first branch reads no sum. Any other weight's gradient is a
        product the compiler fuses with the add into its sum, or reaches
        the add in another layout than the sum's; a branch wants its
        operands whole and in one layout, so it would part the product
        from the add or copy the gradient (either way written and read
        once more, 4 bytes a parameter a micro-batch or more). There the
        add stays unconditional over `where(first, 0, sum)`, and a
        minibatch's first micro-batch reads a sum it discards (4 bytes a
        parameter a minibatch, however many micro-batches follow)."""
        key = ("accum", loss_name, row_keys, scored_fn is not None)
        if key in self._jit_cache:
            return self._jit_cache[key]

        mb_loss = self._mb_loss_fn(loss_fn, scored_fn)
        # the leaves (in the tree's flat order) that a scan stacks
        stacked = [i for i, s in enumerate(jax.tree_util.tree_leaves(
            scan_stacked(self.model_cfg, self.params))) if s]

        def to_f32(tree):
            return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)

        def mb_accum(params, g_acc, rows, first):
            (loss, aux), g = jax.value_and_grad(mb_loss, has_aux=True)(
                params, rows
            )
            with jax.named_scope("grad_accum"):
                sums, treedef = jax.tree_util.tree_flatten(g_acc)
                g = treedef.flatten_up_to(g)
                branch = dict(zip(stacked, jax.lax.cond(
                    first,
                    lambda acc, g: to_f32(g),
                    lambda acc, g: [a + b.astype(jnp.float32)
                                    for a, b in zip(acc, g)],
                    [sums[i] for i in stacked], [g[i] for i in stacked],
                ))) if stacked else {}
                out = [branch[i] if i in branch
                       else jnp.where(first, 0.0, a) + b.astype(jnp.float32)
                       for i, (a, b) in enumerate(zip(sums, g))]
                g_acc = treedef.unflatten(out)
                stats = to_f32((loss, aux))
            # the donated sums come back where they were
            g_acc = jax.lax.with_sharding_constraint(g_acc, self._param_shardings)
            return g_acc, stats

        return self._jit_cache.setdefault(key, jax.jit(mb_accum, donate_argnums=(1,)))

    def _accum_sum_fns(self):
        """Two programs beside `_accum_step_fn`'s that see no row and no
        model, one build an engine: `zero_sums`, the fp32 sums' buffers
        where the engine holds none (later minibatches take the last
        one's, whose content `first` discards: a fill a minibatch was
        3-6 ms of a step in `q15d12-train-short`, PERF.md section 6, PR
        49, call F), and `sum_add`, a later micro-batch's `(loss_sum,
        aux)` scalars added to the minibatch's, left to right."""
        key = ("accum_sums",)
        if key in self._jit_cache:
            return self._jit_cache[key]

        def zero_sums(params):
            return jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )

        def sum_add(stats, mb_stats):
            with jax.named_scope("grad_accum"):
                return jax.tree_util.tree_map(operator.add, stats, mb_stats)

        return self._jit_cache.setdefault(key, (
            jax.jit(zero_sums, out_shardings=self._param_shardings),
            jax.jit(sum_add),
        ))

    def _apply_step_fn(self, loss_name: str):
        """Optimizer apply for the pipelined path: `_optimizer_apply`,
        sharding constraints and the single packed stats vector — the
        tail of the fused train program."""
        key = ("apply", loss_name)
        if key in self._jit_cache:
            return self._jit_cache[key]

        def apply(params, opt_state, carry, inv_denom, lr):
            grads, loss_sum, aux = carry
            params, opt_state, gnorm, unorm = self._optimizer_apply(
                params, opt_state, grads, inv_denom, lr
            )
            params = jax.lax.with_sharding_constraint(
                params, self._param_shardings
            )
            opt_state = jax.lax.with_sharding_constraint(
                opt_state, self._opt_shardings
            )
            aux_leaves = jax.tree_util.tree_leaves(aux)
            packed = jnp.stack(
                [loss_sum.astype(jnp.float32), gnorm.astype(jnp.float32), unorm]
                + [a.astype(jnp.float32) for a in aux_leaves]
            )
            return params, opt_state, packed, aux

        # the sums are not donated: the engine hands their buffers to the
        # next minibatch (`_train_batch_overlapped`)
        return self._jit_cache.setdefault(key, jax.jit(apply, donate_argnums=(0, 1)))

    @staticmethod
    def _stack_mb_rows(
        mbs_rows: List[Dict[str, np.ndarray]]
    ) -> List[Dict[str, np.ndarray]]:
        """The fused step's input: the micro-batches' row dicts stacked
        into one [n, R, T] dict a shape, in the order the shapes first
        appear. The step scans each stack, so no micro-batch is padded
        to another's shape (the packer gives a large micro-batch one row
        of 16,384 and a small one beside it one of 1,024)."""
        by_shape: Dict[Tuple[int, int], List[Dict[str, np.ndarray]]] = {}
        for r in mbs_rows:
            by_shape.setdefault(r["input_ids"].shape, []).append(r)
        return [{k: np.stack([r[k] for r in same]) for k in same[0]}
                for same in by_shape.values()]

    @staticmethod
    def _dp_token_weights(rows_np: Dict[str, np.ndarray]) -> np.ndarray:
        """Host-side per-token loss weights used to build the per-shard
        denominators for 'dp' normalization. Mirrors what the standard
        losses weight by: the shifted response mask for SFT/PPO batches
        (ops/loss.response_scoring_mask), or an explicit loss_mask."""
        pm = rows_np.get("prompt_mask")
        if pm is not None:
            return response_scoring_mask(
                np.asarray(rows_np["segment_ids"]), np.asarray(pm))
        lm = rows_np.get("loss_mask")
        if lm is None:
            raise ValueError(
                "token_normalize_scope='dp' needs per-token loss weights: "
                "rows must carry 'prompt_mask' or 'loss_mask', or pass "
                "dp_token_weights_fn to train_batch"
            )
        return np.asarray(lm, np.float32)

    def _apply_dp_token_scale(
        self,
        stacks: List[Dict[str, np.ndarray]],
        global_denom: float,
        dp_token_weights_fn=None,
    ) -> List[Dict[str, np.ndarray]]:
        """Inject a 'dp_loss_scale' rows key so global normalization equals
        per-dp-shard normalization (see train_batch docstring). `stacks`
        hold the step's micro-batches, [R, T] or [n, R, T] arrays each.
        Rows are sharded over (data, fsdp) in contiguous chunks; shard
        s's denominator D_s sums its loss weights across every
        micro-batch (the reference's per-rank denominator spans the
        rank's whole step). Losses multiply this scale into their token
        mask."""
        n = self._n_row_multiple
        if n <= 1:
            return stacks  # one shard: 'dp' == 'global'
        weights_fn = dp_token_weights_fn or self._dp_token_weights

        def by_shard(a):  # [.., R, T] -> [.., n, R // n, T]
            return a.reshape(a.shape[:-2] + (n, a.shape[-2] // n, a.shape[-1]))

        ws = [by_shard(weights_fn(rows).astype(np.float32)) for rows in stacks]
        # D_s: sum over everything except the shard axis.
        d_s = np.maximum(sum(
            w.sum(axis=tuple(i for i in range(w.ndim) if i != w.ndim - 3))
            for w in ws), 1.0)  # [n]
        scale = (global_denom / (n * d_s)).astype(np.float32)
        out = []
        for rows, w in zip(stacks, ws):
            rows = dict(rows)
            rows["dp_loss_scale"] = np.ascontiguousarray(np.broadcast_to(
                scale[:, None, None], w.shape
            ).reshape(rows["input_ids"].shape))
            out.append(rows)
        return out

    def train_batch(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_fn: PackedLossFn,
        loss_weight_fn: Callable[[SequenceSample], float],
        token_normalize_scope: str = "global",
        version_steps: Optional[int] = None,
        loss_name: str = "loss",
        dp_token_weights_fn=None,
        scored_fn: Optional[ScoredFn] = None,
    ) -> TrainStats:
        """Forward+backward over micro-batches, one optimizer step, no
        host sync at all: the stats come back as a mapping that makes the
        single packed-stats fetch when first looked at (`TrainStats`). Two
        equivalent input paths: the default overlapped pipeline (one
        accumulate program a micro-batch shape; pack+H2D of mb i+1 hidden
        behind mb i's compute — _train_batch_overlapped) and the fused path (one
        donated jitted program, lax.scan accumulation), which 'dp'
        normalization and serialized-dispatch CPU meshes use.

        `version_steps` is HONORED as the LR-schedule position (it was
        previously accepted and silently ignored): the schedule value at
        `version_steps` scales this step's updates, so e.g. every PPO
        minibatch update of one version trains at that version's LR —
        the reference's scheduler semantics — and a recovery restart
        resumes the schedule at the restored version. Adam's bias
        correction still counts actual optimizer updates. `None` (the
        default) falls back to the engine's own train_batch count — the
        pre-honoring behavior for callers that never pass it, resumed
        at the restored version on checkpoint load
        (engine/checkpoint.py) — and the applied value is reported as
        `<loss_name>/lr`.

        `token_normalize_scope='dp'` reproduces the reference's per-rank
        normalization (mean over dp ranks of grad_r / tokens_r,
        realhf/impl/model/interface/ppo_interface.py:253) under GSPMD:
        there is one global program, so instead of per-rank programs the
        engine injects a `dp_loss_scale` rows key — a token in row-shard
        s gets scale D_global / (n_shards * D_s) — which loss_fns
        multiply into their per-token mask; the global 1/D_global
        normalization then equals mean_s(grad_s / D_s) exactly (valid
        because every loss is linear in its per-token weights). D_s comes
        from `dp_token_weights_fn(rows)` when given, else from the
        standard response mask / loss_mask (_dp_token_weights).

        `scored_fn(rows) -> [R, T]` (nonzero = read) is how the caller,
        who wrote `loss_fn`, names the positions whose logprob it reads;
        the loss head then computes those alone and hands `loss_fn`
        zeros at the others, which it must give weight 0 (the PPO and
        SFT interfaces pass `ops/loss.response_positions`, the mask
        their losses multiply by). It is called on device rows inside
        the step and on numpy rows for the counters
        `train.scored_cells` / `train.head_cells`. None (the critic, any
        caller that says nothing): every valid position, the program
        this engine built before the argument existed.
        """
        assert self.optimizer is not None, "engine built without optimizer"
        self._ensure_loaded()
        if token_normalize_scope not in ("global", "dp"):
            raise ValueError(
                f"unknown token_normalize_scope {token_normalize_scope!r}"
            )
        with tracing.span("train.batch"):
            # The host's work before the first micro-batch is asked for: a
            # leaf of its own, ended where the path's input begins.
            begin = tracing.start_span("train.begin")
            lr_pos = self._lr_steps if version_steps is None else int(version_steps)
            self._lr_steps += 1
            lr = self._lr_schedule(lr_pos)  # a host number: nothing touches the device
            # The overlapped pipeline needs per-micro-batch programs; the
            # fused path keeps the single donated executable. 'dp' scope stays
            # fused (its per-shard denominators need every micro-batch's loss
            # weights before the first dispatch) and so do serialized-dispatch
            # CPU meshes (two collective-bearing executables must never be in
            # flight there).
            use_overlap = (
                self.prefetch_depth > 0
                and not self._serial_dispatch
                and token_normalize_scope == "global"
            )
            if use_overlap:
                mb_iter, groups, _, _ = input_.split_lazy(mb_spec)
                if len(groups) > 1:
                    return self._train_batch_overlapped(
                        mb_iter, len(groups), loss_fn, loss_weight_fn, loss_name,
                        lr, scored_fn, begin,
                    )
                # One micro-batch: nothing to pipeline against; run eagerly.
                mbs = list(mb_iter)
            else:
                mbs, _, _ = input_.split(mb_spec)
            global_denom = float(sum(loss_weight_fn(mb) for mb in mbs))
            global_denom = max(global_denom, 1.0)
            if begin is not None:
                begin.end()

            t_prep = time.monotonic_ns()
            with tracing.span("train.pack"):
                built = [self._build_rows(mb) for mb in mbs]
                n_tok = sum(b.total_tokens for b, _ in built)
                all_rows = [r for _, r in built]
                if len(mbs) > 1:
                    stacks = self._stack_mb_rows(all_rows)
                    sharding = jax.sharding.NamedSharding(
                        self.mesh,
                        jax.sharding.PartitionSpec(None, ("data", "fsdp"), "seq"),
                    )
                else:
                    stacks = all_rows
                    sharding = self._batch_sharding
                if token_normalize_scope == "dp":
                    stacks = self._apply_dp_token_scale(
                        stacks, global_denom, dp_token_weights_fn
                    )
            with tracing.span("train.h2d"):
                rows_dev = [
                    {k: jax.device_put(np.asarray(v), sharding)
                     for k, v in rows.items()}
                    for rows in stacks
                ]
            prep_ms = (time.monotonic_ns() - t_prep) / 1e6
            n_cells = sum(b.n_rows * b.row_len for b, _ in built)
            # Eager-path telemetry: the whole pack+stack+H2D cost blocks the
            # host before the single dispatch, so h2d_wait == dispatch gap ==
            # the prep time (nothing is hidden).
            self.last_overlap = {
                "packing_efficiency": n_tok / max(n_cells, 1),
                "h2d_wait_ms": prep_ms,
                "dispatch_gap_ms": prep_ms,
                "overlap_events": 0.0,
            }
            self._record_overlap_stats()
            # One program for all the micro-batches: the span says the
            # shape of the largest.
            big = max(range(len(stacks)),
                      key=lambda i: np.prod(stacks[i]["input_ids"].shape[-2:]))
            rows, row_len = stacks[big]["input_ids"].shape[-2:]
            attn_attrs = {}
            if tracing.enabled():  # host passes whose only readers are spans and counters
                tok_of = collections.Counter()  # a stack's real tokens, by its shape
                for b, r in built:
                    tok_of[r["input_ids"].shape] += b.total_tokens
                said = [self.counts.of(r, tok_of[r["input_ids"].shape[-2:]], scored_fn)
                        for r in stacks]
                counts = collections.Counter()
                for c, _ in said:
                    counts.update(c)
                self._count_batch("fused", len(mbs), sum(b.n_rows == 1 for b, _ in built),
                                  n_tok, n_cells, counts)
                attn_attrs = said[big][1]

            step = self._train_step_fn(
                loss_name, loss_fn, tuple(sorted(stacks[0].keys())), len(mbs),
                scored_fn,
            )
            tracing.build_site("fused_step", step, rows, row_len)
            with tracing.span(
                "train.dispatch", kind="fused", rows=rows, row_len=row_len,
                **attn_attrs, **self._stack_attrs,
            ):
                self.params, self.opt_state, packed, aux = step(
                    self.params, self.opt_state,
                    rows_dev if len(mbs) > 1 else rows_dev[0],
                    self._inv_denom(global_denom, n_tok),
                    np.float32(lr),
                )
                tracing.fed("fused_step")
            if self._serial_dispatch:
                jax.block_until_ready(self.params)
            return self._fetch_train_stats(
                packed, aux, loss_name, global_denom, len(mbs), lr
            )

    def _train_batch_overlapped(
        self,
        mb_iter: Iterable[SequenceSample],
        n_mbs: int,
        loss_fn: PackedLossFn,
        loss_weight_fn: Callable[[SequenceSample], float],
        loss_name: str,
        lr: float,
        scored_fn: Optional[ScoredFn] = None,
        begin: Optional[tracing.ManualSpan] = None,
    ) -> TrainStats:
        """Pipelined gradient accumulation: a background thread FFD-packs,
        pads-to-bucket and `device_put`s micro-batch i+1 while micro-batch
        i's accumulate program runs on device (engine/prefetch.py).
        Dispatch is non-blocking — no fetch or block_until_ready inside
        the loop or after it; the single packed-stats fetch is the
        caller's first look at what comes back (`_fetch_train_stats`).
        The global denominator accumulates as micro-batches stream
        through (it is only needed at the apply)."""
        from areal_tpu.engine.prefetch import HostPrefetcher

        # The stage runs on the prefetcher's thread: hand it the batch's
        # span so its spans stay in the step's trace.
        parent = tracing.current()

        def stage(mb):
            with tracing.span("train.stage", ctx=parent):
                with tracing.span("train.pack"):
                    batch, rows = self._build_rows(mb)
                denom = float(loss_weight_fn(mb))
                with tracing.span("train.h2d"):
                    rows_dev = {
                        k: jax.device_put(np.asarray(v), self._batch_sharding)
                        for k, v in rows.items()
                    }
                cells = batch.n_rows * batch.row_len
                tracing.set_attrs(tokens=batch.total_tokens, cells=cells)
                counts, attn_attrs = None, {}
                if tracing.enabled():  # their only readers are spans and counters
                    counts, attn_attrs = self.counts.of(rows, batch.total_tokens, scored_fn)
            return (rows_dev, denom, batch.total_tokens, cells, attn_attrs, counts)

        pf = HostPrefetcher(
            mb_iter, stage, depth=self.prefetch_depth, name=f"train/{loss_name}",
            wait_span="train.wait_input",
        )
        mb_accum = None
        zero_sums, sum_add = self._accum_sum_fns()
        # The fp32 gradient sums: the last minibatch's buffers (donated
        # from micro-batch to micro-batch, so nothing else holds them
        # while a minibatch runs), or zeros where the engine holds none.
        g_acc, self._grad_sums = self._grad_sums, None
        if g_acc is None:
            tracing.build_site("accum_zeros", zero_sums)
            g_acc = zero_sums(self.params)
        stats = None
        denom_sum, n_tok, n_cells, n_one_row = 0.0, 0, 0, 0
        # what the micro-batches said of themselves, summed as they come
        # while tracing is on (`n_counted` of them)
        n_counts, n_counted = collections.Counter(), 0
        gaps_ms: List[float] = []
        if begin is not None:  # `train.begin`: up to the first `train.wait_input`
            begin.end()
        mark = time.monotonic_ns()
        try:
            for rows_dev, denom, tok, cells, attn_attrs, counts in pf:
                gaps_ms.append((time.monotonic_ns() - mark) / 1e6)
                denom_sum += denom
                n_tok += tok
                n_cells += cells
                rows, row_len = rows_dev["input_ids"].shape
                n_one_row += int(rows == 1)
                if counts is not None:
                    n_counted += 1
                    n_counts.update(counts)
                if mb_accum is None:
                    mb_accum = self._accum_step_fn(
                        loss_name, loss_fn, tuple(sorted(rows_dev.keys())),
                        scored_fn,
                    )
                tracing.build_site("accum_step", mb_accum, rows, row_len)
                # `kind`: whether the program starts the sums (`first`) or
                # adds into them (`next`); one program either way
                with tracing.span("train.dispatch",
                                  kind="first" if stats is None else "next",
                                  rows=rows, row_len=row_len,
                                  **attn_attrs, **self._stack_attrs):
                    g_acc, mb_stats = mb_accum(
                        self.params, g_acc, rows_dev, np.asarray(stats is None)
                    )
                    tracing.fed("accum_step")
                if stats is None:
                    stats = mb_stats
                else:
                    tracing.build_site("accum_stats", sum_add)
                    stats = sum_add(stats, mb_stats)
                mark = time.monotonic_ns()
        finally:
            pf.close()
        global_denom = max(denom_sum, 1.0)
        apply = self._apply_step_fn(loss_name)
        tracing.build_site("apply", apply)
        with tracing.span("train.apply"):
            self.params, self.opt_state, packed, aux = apply(
                self.params, self.opt_state, (g_acc, *stats),
                self._inv_denom(global_denom, n_tok),
                np.float32(lr),
            )
            tracing.fed("apply")
        self._grad_sums = g_acc  # the next minibatch's buffers
        if n_counts and n_counted == n_mbs:  # tracing was on for the whole batch
            self._count_batch("overlapped", n_mbs, n_one_row, n_tok, n_cells, n_counts)
        self.last_overlap = {
            "packing_efficiency": n_tok / max(n_cells, 1),
            "h2d_wait_ms": pf.wait_ms,
            "dispatch_gap_ms": float(np.mean(gaps_ms)) if gaps_ms else 0.0,
            "overlap_events": float(pf.overlap_count()),
        }
        self._record_overlap_stats()
        return self._fetch_train_stats(
            packed, aux, loss_name, global_denom, n_mbs, lr
        )

    def _inv_denom(self, global_denom: float, n_tok: int):
        """What a step's gradient sums are divided by: one over the
        caller's count of loss-weighted tokens, and, where indexers train
        beside the caller's loss, one over the step's real tokens for
        their parameters' (`_optimizer_apply`). A numpy value, as the
        learning rate beside it: it goes to the device with the step's
        own arguments (`jnp.asarray` of a Python number is a program of
        its own on the device's queue)."""
        if self._index_weight > 0:
            return np.asarray([1.0 / global_denom, 1.0 / max(n_tok, 1)], np.float32)
        return np.float32(1.0 / global_denom)

    def _dead_bands(self, row_len: int) -> bool:
        """Whether a row of `row_len` cells, as `_build_rows` packs it, may
        hold a band no token is in: what `forward(bands=)` is told
        (`ops/band_loop.dead_bands`)."""
        return band_loop.dead_bands(row_len, self.row_len_multiple)

    @staticmethod
    def _count_batch(path: str, n_mbs: int, n_one_row: int, n_tok: int, n_cells: int,
                     counts: Mapping[str, int]):
        """What one train_batch did, on its `train.batch` span and in the
        recorder's counters: the micro-batches and how many of them the
        packer made one row (what lets attention skip the block pairs
        between sequences, from 2048 cells up: ops/attention._rows_skip),
        real tokens, the cells (rows x row length) they were padded to,
        and what the micro-batches said of themselves
        (`engine/train_counts.TrainCounts.of`, summed)."""
        tracing.set_attrs(path=path, n_mbs=n_mbs, tokens=n_tok, cells=n_cells)
        tracing.count("train.batches")
        tracing.count("train.micro_batches", n_mbs)
        tracing.count("train.one_row_batches", n_one_row)
        tracing.count("train.tokens", n_tok)
        tracing.count("train.cells", n_cells)
        for name, n in counts.items():
            tracing.count(name, n)

    def _record_overlap_stats(self):
        """Ship the last pipeline's telemetry through the stats tracker so
        model workers export it per MFC (`perf/*` keys reach the master's
        perf history).
        h2d_wait/dispatch_gap merge as MAX across DP workers — the step
        blocks on the slowest worker, so averaging would understate it."""
        ov = self.last_overlap
        stats_tracker.scalar(
            **{"perf/packing_efficiency": ov["packing_efficiency"]}
        )
        stats_tracker.scalar(
            reduce_type=stats_tracker.ReduceType.MAX,
            **{
                "perf/h2d_wait_ms": ov["h2d_wait_ms"],
                "perf/dispatch_gap_ms": ov["dispatch_gap_ms"],
            },
        )
        # SUM so multi-step windows accumulate.
        stats_tracker.scalar(
            reduce_type=stats_tracker.ReduceType.SUM,
            **{"perf/overlap_events": ov["overlap_events"]},
        )

    def _record_moe_stats(self, stats: Dict[str, float], loss_name: str):
        """Ship router telemetry through the stats tracker so model
        workers export it per MFC (perf/moe_* keys reach the master's
        perf_summary). No-op for dense
        models — keyed off the moe aux stats the loss fetch surfaced."""
        if f"{loss_name}/moe_drop_rate" not in stats:
            return
        stats_tracker.scalar(
            **{
                "perf/moe_drop_rate": stats[f"{loss_name}/moe_drop_rate"],
                "perf/moe_router_entropy":
                    stats[f"{loss_name}/moe_router_entropy"],
            }
        )
        # Overload merges as MAX across DP workers: the hottest expert
        # bounds the step, averaging would understate the imbalance.
        stats_tracker.scalar(
            reduce_type=stats_tracker.ReduceType.MAX,
            **{
                "perf/moe_expert_overload":
                    stats[f"{loss_name}/moe_expert_overload"],
            },
        )
        # Bytes SUM so multi-step windows accumulate total exchange.
        stats_tracker.scalar(
            reduce_type=stats_tracker.ReduceType.SUM,
            **{"perf/moe_a2a_bytes": stats[f"{loss_name}/moe_a2a_bytes"]},
        )

    def _fetch_train_stats(
        self, packed, aux, loss_name: str, global_denom: float, n_mbs: int,
        lr: float = 0.0,
    ) -> TrainStats:
        """A step's stats as a mapping that reads them when first looked
        at: ONE host transfer for all scalars (each float() would be its
        own device round trip), made by the caller's first access and not
        here, so a caller that keeps the mapping and enqueues its next
        `train_batch` first (the PPO loops: read under `ppo.stats`) never
        waits for the device between two minibatches. `aux` stays on
        device; only its key structure is read. A caller that reads at
        once waits here as it always did. Where `_serial_dispatch` holds
        the mapping comes back read: nothing may be in flight when the
        next program is enqueued.

        What the read leaves in the trace: the span `train.fetch_stats`
        with `behind`, the `train_batch` calls enqueued after the one
        read (3, 2, 1, 0 where a step of four is read at its end), and the
        counter `train.stats_deferred` of the reads with `behind` > 0.
        Only a read with nothing behind it has emptied the device's queue
        and may tell `tracing.drained`. (A second engine on the same chip
        that trains between two minibatches of this one now finds this
        engine's work still queued; no launcher co-locates two.)

        Honors `stats_fetch_interval`: when > 1, only every Nth
        train_batch pays the round trip; the other calls return the last
        values read (stats feed logging only) tagged
        `<loss>/stats_stale` = 1 with host-side fields kept exact."""
        self._train_calls += 1
        if (
            self.stats_fetch_interval > 1
            and self._train_calls % self.stats_fetch_interval != 0
            and self._last_train_stats is not None
            # An engine driving several losses must not serve one loss's
            # cached values under another's keys.
            and f"{loss_name}/loss" in self._last_train_stats
        ):
            stats = dict(self._last_train_stats)
            stats[f"{loss_name}/n_tokens"] = global_denom
            stats[f"{loss_name}/n_mbs"] = float(n_mbs)
            stats[f"{loss_name}/lr"] = lr  # host-side: exact even when stale
            stats[f"{loss_name}/stats_stale"] = 1.0
            self._record_moe_stats(stats, loss_name)
            tracing.event("train.fetch_stats", stale=True)
            return TrainStats(stats=stats)
        aux_treedef = jax.tree_util.tree_structure(aux)
        # holds the packed vector and the host's fields, nothing else of
        # the step; `_train_calls` now, so that the read counts the calls since
        st = TrainStats(functools.partial(
            self._read_train_stats, packed, aux_treedef, loss_name, global_denom,
            n_mbs, lr, self._train_calls))
        if self._serial_dispatch:
            st.read()
        return st

    def _read_train_stats(
        self, packed, aux_treedef, loss_name: str, global_denom: float,
        n_mbs: int, lr: float, call: int,
    ) -> Dict[str, float]:
        """The read `_fetch_train_stats`' mapping makes, once."""
        behind = self._train_calls - call
        # Where the host waits for the device: the step's one fetch.
        with tracing.span("train.fetch_stats", stale=False, behind=behind):
            p = np.asarray(packed)
        if behind > 0:
            tracing.count("train.stats_deferred")
        else:
            tracing.drained("train.fetch_stats")
        loss_sum, gnorm, unorm = float(p[0]), float(p[1]), float(p[2])
        aux_vals = jax.tree_util.tree_unflatten(aux_treedef, p[3:].tolist())
        stats = {
            f"{loss_name}/loss": loss_sum / global_denom,
            f"{loss_name}/grad_norm": gnorm,
            f"{loss_name}/update_norm": unorm,
            f"{loss_name}/n_tokens": global_denom,
            f"{loss_name}/n_mbs": float(n_mbs),
            f"{loss_name}/lr": lr,
        }
        for k, v in aux_vals.items():
            if k.startswith("mean:"):
                # Micro-batch-mean stats (fractions/rates): aux values
                # sum across the accumulation scan, so dividing by the
                # micro-batch count recovers the mean.
                stats[f"{loss_name}/{k[len('mean:'):]}"] = float(v) / n_mbs
            elif k.startswith("sum:"):
                # Counts over the step's micro-batches, as they are: also
                # the recorder's counter `train.<name>`.
                name = k[len("sum:"):]
                stats[f"{loss_name}/{name}"] = float(v)
                tracing.count(f"train.{name}", float(v))
            elif k.startswith("num:"):
                # A ratio of two sums over the step's micro-batches.
                name = k[len("num:"):]
                stats[f"{loss_name}/{name}"] = float(v) / max(
                    float(aux_vals[f"den:{name}"]), 1.0)
            elif not k.startswith("den:"):
                stats[f"{loss_name}/{k}"] = float(v) / global_denom
        if self._index_weight > 0:
            # The loss sum holds the indexers' KL sum, whose denominator
            # is its own (`_mb_loss_fn`).
            stats[f"{loss_name}/loss"] = (
                (loss_sum - self._index_weight * float(aux_vals["num:indexer_kl"]))
                / global_denom
                + self._index_weight * stats[f"{loss_name}/indexer_kl"])
        if self.stats_fetch_interval > 1:
            stats[f"{loss_name}/stats_stale"] = 0.0
        self._last_train_stats = dict(stats)
        self._record_moe_stats(stats, loss_name)
        return stats

    # ------------------------------------------------------------------
    # Inference
    # ------------------------------------------------------------------

    def _forward_fn(self, output: str):
        key = ("fwd", output)
        if key not in self._jit_cache:

            def fwd(params, rows):
                # "logprobs" uses the fused chunked-vocab path over hidden
                # states; values/raw logits come straight from the model.
                fuse = output == "logprobs" and not self.model_cfg.is_critic
                out = model_forward(
                    params, self.model_cfg,
                    rows["input_ids"], rows["segment_ids"], rows["positions"],
                    attn_impl=self.attn_impl,
                    output="hidden" if fuse else "logits",
                    mesh=self.mesh if self.mesh.size > 1 else None,
                    bands=self._dead_bands(rows["input_ids"].shape[-1]),
                )
                if fuse:
                    return fused_next_token_logprobs(
                        out, self._head_weight(params),
                        rows["input_ids"], rows["segment_ids"],
                    )
                return out  # [R, T] values or [R, T, V] logits

            self._jit_cache[key] = jax.jit(fwd)
        return self._jit_cache[key]

    def forward(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        output_key: str = "logprobs",
        output: Optional[str] = None,
        post_hook: Optional[Callable] = None,
    ) -> SequenceSample:
        """Gradient-free forward; returns a SequenceSample keyed
        `output_key` with per-token arrays aligned to the main key.

        With `prefetch_depth > 0` the per-micro-batch pack + H2D runs on
        the prefetch thread while the previous micro-batch computes, and
        the per-mb output fetch is deferred: every program is dispatched
        non-blocking, then ONE `jax.device_get` drains all outputs —
        the packed-stats single-fetch discipline applied to forward."""
        output = output or ("values" if self.model_cfg.is_critic else "logprobs")
        self._ensure_loaded()
        self._grad_sums = None  # a train step's, not a forward phase's to hold
        main_key = input_._main_key()
        fn = self._forward_fn(output)
        per_mb_flat: List[np.ndarray] = []
        mb_seqlens: List[List[int]] = []
        n_tok = n_cells = 0
        with tracing.span("fwd.batch"):
            if self.prefetch_depth > 0 and not self._serial_dispatch:
                from areal_tpu.engine.prefetch import HostPrefetcher

                mb_iter, _, _, bwd_indices = input_.split_lazy(mb_spec)

                def stage(mb):
                    batch, rows = self._build_rows(mb, keys=[main_key])
                    return batch, self._device_rows(rows), mb.seqlens_of()

                pf = HostPrefetcher(
                    mb_iter, stage, depth=self.prefetch_depth, name="forward",
                    wait_span="fwd.wait_input",
                )
                batches, outs = [], []
                gaps_ms: List[float] = []
                mark = time.monotonic_ns()
                try:
                    for batch, rows_dev, sl in pf:
                        gaps_ms.append((time.monotonic_ns() - mark) / 1e6)
                        tracing.build_site("forward", fn, batch.n_rows, batch.row_len)
                        with tracing.span("fwd.dispatch", rows=batch.n_rows,
                                          row_len=batch.row_len):
                            outs.append(fn(self.params, rows_dev))  # not fetched
                            tracing.fed("forward")
                        batches.append(batch)
                        mb_seqlens.append(sl)
                        n_tok += batch.total_tokens
                        n_cells += batch.n_rows * batch.row_len
                        mark = time.monotonic_ns()
                finally:
                    pf.close()
                with tracing.span("fwd.fetch"):
                    fetched = jax.device_get(outs)  # one blocking drain per batch
                tracing.drained("fwd.fetch")
                per_mb_flat = [
                    b.gather_flat(np.asarray(o, np.float32))
                    for b, o in zip(batches, fetched)
                ]
                self.last_overlap = {
                    "packing_efficiency": n_tok / max(n_cells, 1),
                    "h2d_wait_ms": pf.wait_ms,
                    "dispatch_gap_ms": float(np.mean(gaps_ms)) if gaps_ms else 0.0,
                    "overlap_events": float(pf.overlap_count()),
                }
                self._record_overlap_stats()
            else:
                mbs, _, bwd_indices = input_.split(mb_spec)
                for mb in mbs:
                    batch, rows = self._build_rows(mb, keys=[main_key])
                    rows_dev = self._device_rows(rows)
                    tracing.build_site("forward", fn, batch.n_rows, batch.row_len)
                    with tracing.span("fwd.dispatch", rows=batch.n_rows,
                                      row_len=batch.row_len):
                        out_dev = fn(self.params, rows_dev)
                        tracing.fed("forward")
                    with tracing.span("fwd.fetch"):
                        out_rows = np.asarray(out_dev, np.float32)
                    tracing.drained("fwd.fetch")
                    per_mb_flat.append(batch.gather_flat(out_rows))
                    mb_seqlens.append(mb.seqlens_of())
                    n_tok += batch.total_tokens
                    n_cells += batch.n_rows * batch.row_len
            tracing.set_attrs(n_mbs=len(mb_seqlens), tokens=n_tok, cells=n_cells)
        merged = SequenceSample.reorder_output(
            np.concatenate(per_mb_flat, axis=0),
            mb_seqlens,
            bwd_indices,
        )
        out = SequenceSample(
            ids=list(input_.ids),
            keys={output_key},
            data={output_key: merged},
            seqlens={output_key: [list(sl) for sl in input_.seqlens[main_key]]},
        )
        if post_hook is not None:
            out = post_hook(out)
        return out

    # ------------------------------------------------------------------
    # Generation
    # ------------------------------------------------------------------

    def generate(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        tokenizer: Any,
        gconfig: GenerationHyperparameters,
        rng: Optional[jax.Array] = None,
    ) -> List[Dict[str, Any]]:
        """Generate for each prompt (replicated `gconfig.n` times).

        Returns the raw per-sequence dicts; the PPO interface assembles
        them into a SequenceSample (grouping semantics live there).
        """
        main_key = input_._main_key()
        flat = np.asarray(input_.data[main_key])
        prompts: List[List[int]] = []
        offset = 0
        for sl in input_.seqlens[main_key]:
            for l in sl:
                prompts.append(flat[offset : offset + l].astype(np.int32).tolist())
                offset += l
        expanded = [p for p in prompts for _ in range(gconfig.n)]
        # Default RNG: fold in a per-call counter so repeated generate
        # calls draw independent sampling streams.
        self._gen_calls += 1
        self._ensure_loaded()
        self._grad_sums = None  # the KV cache's room
        rng = rng if rng is not None else jax.random.PRNGKey(self._gen_calls)
        eos = getattr(tokenizer, "eos_token_id", None) if tokenizer is not None else None
        with jax.sharding.set_mesh(self.mesh):
            return generate_tokens(
                self.params, self.model_cfg, expanded, gconfig, rng, eos_token_id=eos
            )

    # ------------------------------------------------------------------
    # State
    # ------------------------------------------------------------------

    def offload(self):
        """Move params + optimizer state to host memory, freeing HBM for
        other models colocated on this worker (reference
        ReaLModel.async_offload, real_llm_api.py:307 — pinned-memory +
        side-stream there; here a host fetch, restored lazily by the
        next engine call)."""
        if self._offloaded:
            return
        leaves = jax.tree_util.tree_leaves(self.params)
        if leaves and not leaves[0].is_fully_addressable:
            # Multi-host GSPMD arrays can't be fetched from one process;
            # offload would need a per-shard protocol. Stay resident.
            logger.warning(
                "offload skipped: params span multiple hosts "
                "(not fully addressable)"
            )
            return
        self._host_params = jax.device_get(self.params)
        self._host_opt_state = (
            jax.device_get(self.opt_state) if self.opt_state is not None else None
        )
        self.params = None
        self.opt_state = None
        self._grad_sums = None
        self._offloaded = True
        logger.info("engine params offloaded to host")

    def _ensure_loaded(self):
        if not getattr(self, "_offloaded", False):
            return
        self.params = jax.device_put(self._host_params, self._param_shardings)
        if self._host_opt_state is not None:
            self.opt_state = jax.device_put(
                self._host_opt_state, self._opt_shardings
            )
        self._host_params = None
        self._host_opt_state = None
        self._offloaded = False
        logger.info("engine params restored to device")

    def get_params(self):
        """Current params; while offloaded, the HOST copy is returned
        directly — every caller (checkpoint dump, HF export, weight
        transfer) copies to host anyway, and restoring to HBM here could
        OOM the colocated model the offload made room for."""
        if self._offloaded:
            return self._host_params
        return self.params

    def get_opt_state(self):
        """Optimizer state under the same offload-transparency contract
        as get_params."""
        if self._offloaded:
            return self._host_opt_state
        return self.opt_state

    def drop_offloaded_state(self):
        """Discard offloaded host copies WITHOUT restoring them — for
        callers about to overwrite both params and optimizer state
        (checkpoint load), where restoring first would double-occupy HBM."""
        self._offloaded = False
        self._host_params = None
        self._host_opt_state = None

    def rng_state(self) -> dict:
        """Checkpointable RNG/counter state: the call counters every
        engine-derived PRNGKey folds in (generate's default key is
        PRNGKey(_gen_calls)), so a restored engine continues the exact
        sampling stream an uninterrupted run would have produced."""
        return {
            "gen_calls": int(self._gen_calls),
            "train_calls": int(self._train_calls),
            "lr_steps": int(self._lr_steps),
        }

    def load_rng_state(self, state: dict):
        self._gen_calls = int(state.get("gen_calls", 0))
        self._train_calls = int(state.get("train_calls", 0))
        self._lr_steps = int(state.get("lr_steps", self._lr_steps))

    def set_params(self, params):
        if self._offloaded and self._host_opt_state is not None:
            # Param realloc swaps weights but 'optimizer state stays
            # local' (model_worker._param_realloc): the offloaded moments
            # must come back, not be dropped.
            self.opt_state = jax.device_put(
                self._host_opt_state, self._opt_shardings
            )
        self.drop_offloaded_state()
        self._grad_sums = None
        self.params = jax.device_put(params, param_shardings(params, self.mesh))


def apply_updates(params, updates, lr):
    """`p + lr * u` in each parameter's dtype, and the global norm of the
    change that survived the rounding: 0 says the step changed nothing
    (bf16 parameters under a learning rate below their spacing)."""
    new = jax.tree_util.tree_map(
        lambda p, u: p + (u * lr).astype(p.dtype), params, updates
    )
    delta = jax.tree_util.tree_map(
        lambda n, p: n.astype(jnp.float32) - p.astype(jnp.float32), new, params
    )
    return new, optax_global_norm(delta)


def optax_global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in leaves))
