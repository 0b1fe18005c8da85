"""Model worker: hosts model shards + datasets, executes MFCs.

Counterpart of the reference's ModelWorker
(realhf/system/model_worker.py:101-1610). One model worker drives one
jax mesh (its local TPU devices) and acts as one DP rank of every model
it hosts. Request handlers:

- "spec": dataset size + readiness handshake
- "fetch": next dataloader batch -> DataManager, reply metadata
- "mfc": execute pre-hooks (data_transfer pulls, param_realloc, ...),
  assemble the input batch, run the interface method under
  `constants.model_scope`, store outputs, reply meta + stats
- "save"/"ckpt"/"evaluate"/"restore": persistence + recovery
- "clear_data_cache": per-step sample GC
- "exit": leave the poll loop
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from areal_tpu.api import data_api
from areal_tpu.api.config import ModelName
from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.base import monitor
from areal_tpu.utils import jaxenv, profiling
from areal_tpu.api.model_api import (
    FinetuneSpec,
    Model,
    make_backend,
    make_interface,
    make_model,
)
from areal_tpu.api.system_api import ModelWorkerConfig
from areal_tpu.base import constants, env_registry, logging, metrics_registry, name_resolve, names, recover, seeding, stats_tracker, timeutil, tracing
from areal_tpu.system import eval_scores
from areal_tpu.system import request_reply_stream as rrs
from areal_tpu.system.data_manager import DataManager
from areal_tpu.system.redistributor import RedistribStep
from areal_tpu.system.worker_base import PollResult, Worker

logger = logging.getLogger("model_worker")


class ModelWorker(Worker):
    def _configure(self, config: ModelWorkerConfig):
        self.cfg = config
        constants.set_experiment_trial_names(
            config.experiment_name, config.trial_name
        )
        seeding.set_random_seed(config.seed, config.worker_name)
        # Import factories/interfaces so registries are populated.
        import areal_tpu.engine.factories  # noqa: F401
        import areal_tpu.interfaces  # noqa: F401
        import areal_tpu.datasets  # noqa: F401

        self.stream = rrs.make_worker_stream(
            config.experiment_name, config.trial_name, config.worker_name
        )
        self.data_manager = DataManager(
            config.experiment_name, config.trial_name, config.worker_name
        )

        # Multi-host sharded training: join the train partition's host
        # group BEFORE any model (or device) is touched — jax.distributed
        # must initialize before the first backend acquires devices, and
        # every host must rendezvous or the global mesh never forms.
        self._train_group = None
        if int(getattr(config, "train_n_hosts", 1) or 1) > 1:
            from areal_tpu.parallel.distributed import setup_host_group

            self._train_group = setup_host_group(
                config.experiment_name,
                config.trial_name,
                "train",
                config.train_host_rank,
                config.train_n_hosts,
            )
            logger.info(
                f"{config.worker_name}: joined train host group as "
                f"{config.train_host_rank}/{config.train_n_hosts} "
                f"(coordinator {self._train_group.coordinator_address})"
            )

        # Datasets (only on data-hosting workers).
        self.dataloader = None
        self._dataset = None
        if config.stream_dataset:
            from areal_tpu.system.stream_dataset import PullerStreamDataset

            self._dataset = PullerStreamDataset(
                config.experiment_name,
                config.trial_name,
                puller_index=config.dataset_dp_rank,
            )
            self.dataloader = None
        elif config.datasets:
            tokenizer = (
                data_api.load_hf_tokenizer(config.tokenizer_path)
                if config.tokenizer_path
                else None
            )
            util = data_api.DatasetUtility(
                seed=config.seed,
                dp_rank=config.dataset_dp_rank,
                world_size=config.dataset_dp_size,
                tokenizer=tokenizer,
            )
            datasets = [
                data_api.make_dataset(d, util) for d in config.datasets
            ]
            self._dataset = (
                datasets[0]
                if len(datasets) == 1
                else data_api.ConcatDataset(datasets)
                if hasattr(data_api, "ConcatDataset")
                else datasets[0]
            )
            self.dataloader = data_api.PackedDataLoader(
                self._dataset,
                batch_size=max(
                    1, config.train_batch_size // config.dataset_dp_size
                ),
                shuffle=config.shuffle_dataset,
                seed=config.seed,
            )

        # Models.
        self.models: Dict[str, Model] = {}
        self.interfaces: Dict[str, Any] = {}
        self.backends: Dict[str, Any] = {}
        dataset_size = len(self._dataset) * config.dataset_dp_size if self._dataset is not None else 0
        self._host_rank: Dict[str, int] = {}
        for shard in config.shards:
            mn = shard.id.model_name
            self._host_rank[str(mn)] = shard.id.host_rank
            ft_spec = FinetuneSpec(
                total_train_epochs=config.total_train_epochs,
                dataset_size=dataset_size,
                train_batch_size=config.train_batch_size,
            )
            model = make_model(shard.model, name=mn)
            backend = make_backend(shard.backend)
            model = backend.initialize(model, ft_spec)
            # Startup verification that this process hosts exactly its
            # slice of every multi-device train mesh (the training-side
            # mirror of the serving fleet's weight-shard check): a
            # misconfigured host must fail HERE with an actionable
            # message, not deep inside the first collective.
            mesh = getattr(model.module, "mesh", None)
            if mesh is not None and mesh.size > 1:
                from areal_tpu.parallel.distributed import (
                    verify_host_mesh_slice,
                )

                info = verify_host_mesh_slice(
                    mesh,
                    getattr(config, "train_host_rank", 0),
                    int(getattr(config, "train_n_hosts", 1) or 1),
                )
                logger.info(
                    f"{config.worker_name}: {mn} mesh "
                    f"{dict(mesh.shape)} verified — hosts "
                    f"{info['local_devices']}/{info['mesh_devices']} "
                    f"devices as slice {info['host_rank']}/"
                    f"{info['n_hosts']}"
                )
            self.models[str(mn)] = model
            self.backends[str(mn)] = backend
            self.interfaces[str(mn)] = make_interface(shard.interface)
        jaxenv.report_devices(config.worker_name)
        logger.info(
            f"{config.worker_name} configured: models={list(self.models)}, "
            f"dataset_size={dataset_size}"
        )

    # ------------------------------------------------------------------
    # Handlers
    # ------------------------------------------------------------------

    def _handle_spec(self, req):
        # LOCAL size only; the master sums across data hosts.
        local = len(self._dataset) if self._dataset is not None else 0
        return {"dataset_size": local, "models": list(self.models)}

    def _handle_fetch(self, req):
        if self.dataloader is None and self._dataset is None:
            return {"meta": None, "epoch_done": False}
        if self.dataloader is not None:
            batch, epoch_done = self.dataloader.next_batch()
            if epoch_done:
                # Curriculum step at the epoch boundary (reference
                # model_worker.py:576-618 filters on dataloader
                # StopIteration): drop prompts the policy already solves;
                # the dataloader detects the size change and reshuffles.
                eval_scores.apply_filter(
                    self._dataset,
                    self.cfg.experiment_name,
                    self.cfg.trial_name,
                    tag=f"data{self.cfg.worker_index}",
                    # Floor at the per-rank fetch batch: dropping below it
                    # would starve the master's batch assembly forever.
                    min_size=self.dataloader.batch_size,
                )
        else:
            batch = self._dataset.poll_batch()
            epoch_done = False
            if batch is None:
                return {"meta": None, "epoch_done": False}
        self.data_manager.store(batch)
        return {"meta": batch.meta(), "epoch_done": epoch_done}

    def _exec_hook(self, hook: Dict, model_name: str, step: int = 0):
        htype = hook.get("type")
        if htype == "data_transfer":
            steps = [RedistribStep(**s) for s in hook["plan"]]
            self.data_manager.redistribute(steps)
        elif htype == "save":
            self._save_model(model_name)
        elif htype == "evaluate":
            self._evaluate_model(model_name)
        elif htype == "offload":
            model = self.models.get(model_name)
            if model is not None and hasattr(model.module, "offload"):
                # Free the idle model's HBM; the engine restores lazily
                # on its next call (jax_engine.offload).
                model.module.offload()
            else:
                logger.debug("offload hook: engine has no offload; no-op")
        elif htype == "param_realloc":
            self._param_realloc(hook, step)
        else:
            raise ValueError(f"unknown hook {hook!r}")

    def _handle_mfc(self, req) -> Dict:
        d = req.data
        model_name = d["model_name"]
        model = self.models[model_name]
        interface = self.interfaces[model_name]

        step = int(d.get("step_info", {}).get("global_step", 0))
        # Pre-hooks: data transfer plan is embedded in the request.
        if d.get("plan"):
            self.data_manager.redistribute(
                [RedistribStep(**s) for s in d["plan"]]
            )
        for hook in req.pre_hooks:
            self._exec_hook(hook, model_name, step)

        input_ = self.data_manager.gather(d["ids"], d["input_keys"])
        if d.get("input_key_remap"):
            input_.remap_keys_(d["input_key_remap"])
        mb_spec = MicroBatchSpec(**d["mb_spec"])

        itype = d["interface_type"]
        mn = ModelName.parse(model_name)
        t0 = time.monotonic()
        # Worker-side MFC execution span, parented under the master's
        # MFC span (trace_ctx rides the request payload). The train-step
        # spans are the "training busy" track of the merged timeline's
        # overlap score.
        with constants.model_scope(mn), tracing.span(
            f"mfc.{d.get('mfc_name', itype)}",
            ctx=tracing.extract(req.trace_ctx),
            itype=itype,
            model=model_name,
            step=step,
            n_seqs=len(d["ids"]),
        ), profiling.maybe_profile(
            d.get("mfc_name", itype), step
        ):
            if itype == "generate":
                out = interface.generate(model, input_, mb_spec)
                stats = {}
            elif itype == "inference":
                out = interface.inference(model, input_, mb_spec)
                stats = {}
            elif itype == "train_step":
                res = interface.train_step(model, input_, mb_spec)
                out = None
                stats = res[-1] if isinstance(res, list) else res
            else:
                raise ValueError(f"bad interface_type {itype!r}")
        # Per-MFC perf accounting shipped back to the master (counterpart
        # of the reference's FlopsCounter + time_record,
        # realhf/system/flops_counter.py, model_function_call.py:460-472).
        # Worker-side because only the worker knows the model config and
        # the true packed shapes.
        stats = dict(stats or {})
        # Stats recorded through the tracker during the interface call ship
        # with their declared reduce types so the master merges MIN/MAX/SUM
        # stats correctly across DP workers (merge_worker_stats).
        tracked, ttypes = stats_tracker.export(return_types=True)
        stats.update(tracked)
        if ttypes:
            stats["__reduce_types__"] = ttypes
        stats["perf/sec"] = time.monotonic() - t0
        # HBM telemetry + OOM guard after every MFC (reference
        # model_worker.py:1507-1610 GPU-memory watch + kill threshold):
        # zeros on backends without memory_stats, so always logged.
        mem = monitor.device_memory_stats()
        # Regression note: this used to f-string-build `perf/{k}` keys,
        # invisible to the metrics registry — a renamed monitor stat
        # would ship an undeclared key downstream consumers silently
        # drop. perf_mem_stats validates every key against the
        # registry (metrics-registry lint checker).
        stats.update(metrics_registry.perf_mem_stats(mem))
        monitor.check_memory_kill_threshold(mem)
        jaxenv.report_usage(self.config.worker_name)
        cfg = getattr(model.module, "model_cfg", None)
        if cfg is not None:
            in_lens = [
                l for sl in input_.seqlens[input_._main_key()] for l in sl
            ]
            # Packing-density accounting for train/inference MFCs: the
            # engine records the REALIZED density of what it shipped to
            # HBM (tracked export above); when it did not run a packed
            # path (mock engines, custom interfaces) fall back to the
            # estimate of the engine's own rule over this MFC's input
            # lengths (`datapack.ladder_density`).
            # Generate MFCs are deliberately excluded — the serving
            # engine admits prompts into a paged pool, so a row-pack
            # density over its inputs would be a made-up number.
            row_mult = getattr(model.module, "row_len_multiple", None)
            if (
                itype in ("train_step", "inference")
                and in_lens
                and row_mult
                and "perf/packing_efficiency" not in stats
            ):
                from areal_tpu.base import datapack

                stats["perf/packing_efficiency"] = datapack.ladder_density(
                    in_lens,
                    row_len_multiple=row_mult,
                    n_rows_multiple=getattr(model.module, "_n_row_multiple", 1),
                    max_row_len=getattr(model.module, "max_row_len", None),
                )
            out_lens = None
            if out is not None and itype == "generate":
                try:
                    ok = out._main_key()
                    out_lens = [l for sl in out.seqlens[ok] for l in sl]
                except Exception:
                    out_lens = None
            stats["perf/flops"] = float(
                monitor.mfc_flops(cfg, itype, in_lens, out_lens)
            )
            if itype == "generate" and out_lens:
                # Group sampling replicates each prompt gconfig.n times in
                # the output, so subtract each prompt once per replica.
                group = (
                    len(out_lens) // len(in_lens)
                    if in_lens and len(out_lens) % len(in_lens) == 0
                    else 1
                )
                stats["perf/gen_tokens"] = float(
                    sum(out_lens) - group * sum(in_lens)
                )

        output_meta = None
        if out is not None:
            # Per-prompt eval scores from the reward MFC feed the dataset
            # curriculum filter (reference model_worker.py:956-994; the
            # all-gather is replaced by a locked file merge). Popped so
            # scores don't ride along into downstream MFC inputs. EVERY
            # worker writes: DP ranks hold disjoint id slices, so skipping
            # non-zero ranks would leave their prompts unscorable.
            scores = out.metadata.pop("scores", None)
            if scores:
                eval_scores.merge_scores(
                    self.cfg.experiment_name,
                    self.cfg.trial_name,
                    dict(zip(out.ids, scores)),
                )
            if d.get("output_key_remap"):
                out.remap_keys_(d["output_key_remap"])
            self.data_manager.store(out)
            output_meta = out.meta()

        for hook in req.post_hooks:
            self._exec_hook(hook, model_name, step)

        if itype == "train_step" and self._host_rank.get(model_name, 0) == 0:
            # Publish AFTER post-hooks: the param-realloc dump the gserver
            # manager fans out must be on disk before the version appears,
            # or servers would load the previous step's weights under the
            # new version number. Only DP rank 0 publishes (and dumps).
            self._publish_version(mn)

        return {"stats": stats, "output_meta": output_meta}

    def _publish_version(self, model_name: ModelName):
        model = self.models[str(model_name)]
        name_resolve.add(
            names.model_version(
                self.cfg.experiment_name, self.cfg.trial_name, model_name.role
            ),
            str(model.version),
            replace=True,
        )

    def _save_model(self, model_name: Optional[str] = None):
        for mn, model in self.models.items():
            if model_name is not None and mn != model_name:
                continue
            iface = self.interfaces[mn]
            save_dir = os.path.join(
                constants.get_save_path(
                    self.cfg.experiment_name, self.cfg.trial_name
                ),
                ModelName.parse(mn).role,
                f"step{model.version}",
                f"dp{self.cfg.worker_index}",
            )
            iface.save(model, save_dir)

    def _ckpt_dir(self, mn: str) -> str:
        return os.path.join(
            constants.get_recover_path(
                self.cfg.experiment_name, self.cfg.trial_name
            ),
            ModelName.parse(mn).role,
            f"dp{self.cfg.worker_index}",
        )

    def _handle_ckpt(self, req):
        for mn, model in self.models.items():
            self.backends[mn].save(model, self._ckpt_dir(mn))
        if self.dataloader is not None:
            import json

            state_path = os.path.join(
                constants.get_recover_path(
                    self.cfg.experiment_name, self.cfg.trial_name
                ),
                f"dataloader_{self.cfg.worker_index}.json",
            )
            os.makedirs(os.path.dirname(state_path), exist_ok=True)
            # Atomic like every other recovery artifact: a kill
            # mid-write must leave the previous cursor, not a torn file.
            tmp = state_path + f".tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self.dataloader.state_dict(), f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, state_path)
        self._compact_stream_wal()
        return {"ok": True}

    def _compact_stream_wal(self):
        """Checkpoint-barrier WAL truncation, one barrier behind: drop
        journaled rollouts whose seqs the PREVIOUS durable recover
        record already marked consumed. (The record for THIS barrier is
        written by the master after this handler returns; compacting
        against the previous one keeps truncation strictly behind the
        durable ledger — it is GC, safe to lag, never safe to lead.)"""
        dataset = self._dataset
        if dataset is None or not hasattr(dataset, "compact_wal"):
            return
        try:
            info = recover.load(self.cfg.experiment_name, self.cfg.trial_name)
        except (FileNotFoundError, ValueError):
            return
        from areal_tpu.system.wal import SeqLedger

        snapshot = getattr(info, "consumed_seqs", None)
        if not snapshot:
            return
        try:
            dropped = dataset.compact_wal(SeqLedger.from_dict(snapshot))
            if dropped:
                logger.info("WAL compaction dropped %d consumed record(s)",
                            dropped)
        except Exception:
            logger.exception("WAL compaction failed (journal kept as-is)")

    def _handle_restore(self, req):
        from areal_tpu.engine.checkpoint import has_engine_state

        for mn, model in self.models.items():
            d = self._ckpt_dir(mn)
            if has_engine_state(d):
                self.backends[mn].load(model, d)
        if self.dataloader is not None:
            import json

            # Curriculum state first: the dataloader snapshot records the
            # FILTERED dataset size, so indices must be restored before
            # load_state_dict's size check (reference
            # model_worker.py:368-385 does the same at model setup).
            eval_scores.restore_indices(
                self._dataset,
                self.cfg.experiment_name,
                self.cfg.trial_name,
                tag=f"data{self.cfg.worker_index}",
            )
            state_path = os.path.join(
                constants.get_recover_path(
                    self.cfg.experiment_name, self.cfg.trial_name
                ),
                f"dataloader_{self.cfg.worker_index}.json",
            )
            if os.path.exists(state_path):
                with open(state_path) as f:
                    self.dataloader.load_state_dict(json.load(f))
                self.dataloader.restart_epoch()
        return {"ok": True}

    def _evaluate_model(self, model_name: Optional[str] = None):
        stats = {}
        for mn, model in self.models.items():
            if model_name is not None and mn != model_name:
                continue
            iface = self.interfaces[mn]
            stats[mn] = iface.evaluate(model, None)
        return stats

    # ------------------------------------------------------------------

    def _poll(self) -> Optional[PollResult]:
        try:
            req = self.stream.poll(block=True, timeout_ms=50)
        except rrs.NoMessage:
            return PollResult(batch_count=0)
        try:
            h = req.handle_name
            if h == "spec":
                resp = self._handle_spec(req)
            elif h == "fetch":
                resp = self._handle_fetch(req)
            elif h == "mfc":
                resp = self._handle_mfc(req)
            elif h == "save":
                self._save_model()
                resp = {"ok": True}
            elif h == "ckpt":
                resp = self._handle_ckpt(req)
            elif h == "restore":
                resp = self._handle_restore(req)
            elif h == "evaluate":
                resp = self._evaluate_model()
            elif h == "clear_data_cache":
                self.data_manager.clear(req.data)
                resp = {"ok": True}
            elif h == "exit":
                self.stream.reply_to(req, {"ok": True})
                self.exit()
                return PollResult(batch_count=1)
            else:
                resp = {"error": f"unknown handle {h!r}"}
        except Exception as e:
            logger.exception(f"error handling {req.handle_name}")
            resp = {"error": repr(e)}
        self.stream.reply_to(req, resp)
        return PollResult(batch_count=1)

    def _exit_hook(self):
        try:
            # A clean exit must not abandon an in-flight async
            # checkpoint write (the daemon writer dies with the process).
            from areal_tpu.engine.checkpoint import wait_pending_writes

            wait_pending_writes(timeout=60)
        except Exception:
            logger.exception("pending checkpoint writes not drained on exit")
        try:
            for src in getattr(self, "_wp_sources", {}).values():
                src.close()
            self.stream.close()
            self.data_manager.close()
            if self._dataset is not None and hasattr(self._dataset, "close"):
                self._dataset.close()
        except Exception:
            pass

    def _ensure_weight_plane_source(self, role: str, dump_dir: str):
        """Start (once per role) the trainer-side origin of the weight
        plane and register its URL for manager discovery."""
        sources = getattr(self, "_wp_sources", None)
        if sources is None:
            sources = self._wp_sources = {}
        if role in sources:
            return
        from areal_tpu.base import network
        from areal_tpu.system.weight_plane import WeightPlaneSource

        src = WeightPlaneSource(
            dump_dir,
            chunk_bytes=getattr(self.cfg, "weight_chunk_bytes", 8 << 20),
            host=network.gethostip(),
        ).start()
        src.register(self.cfg.experiment_name, self.cfg.trial_name, role)
        sources[role] = src
        logger.info(
            f"weight-plane source for {role} at {src.address} over {dump_dir}"
        )

    def _param_realloc(self, hook: Dict, step: int = 0):
        """Disk-mediated weight sync between model replicas (reference
        __param_realloc, model_worker.py:1046; DISK impl is the reference
        default). The source stamps the dump with the global step; the
        target WAITS for a stamp >= its step, so a cross-worker load can
        never silently pick up stale (or missing) weights."""
        import time as _time

        src, dst = hook.get("source"), hook.get("target")
        realloc_root = constants.get_param_realloc_path(
            self.cfg.experiment_name, self.cfg.trial_name
        )
        src_model = self.models.get(src) if src is not None else None
        multi_proc = False
        mesh_size = 1
        if src_model is not None:
            import jax

            mesh = getattr(src_model.module, "mesh", None)
            mesh_size = int(getattr(mesh, "size", 1) or 1)
            multi_proc = any(
                isinstance(l, jax.Array) and not l.is_fully_addressable
                for l in jax.tree_util.tree_leaves(
                    src_model.module.get_params()
                )
            )
        # Single writer per shard: DP replicas hold identical logical
        # params, so only rank 0 dumps — EXCEPT on a multi-process
        # (jax.distributed) train mesh, where every process must write
        # its own slab of the shard-local dump (rank 0 alone cannot even
        # address the other hosts' shards).
        if src_model is not None and (
            self._host_rank.get(src, 0) == 0 or multi_proc
        ):
            model = src_model
            role = ModelName.parse(src).role
            d = os.path.join(realloc_root, role)
            from areal_tpu.engine.checkpoint import save_engine_state
            from areal_tpu.system.weight_transfer import (
                LAST_DUMP_STATS, dump_raw_params, dump_raw_params_sharded,
                mirror_dump_version, shm_transfer_dir,
            )

            import jax

            sharded = mesh_size > 1 or multi_proc
            is_rank0 = (
                self._host_rank.get(src, 0) == 0
                and (not multi_proc or jax.process_index() == 0)
            )
            if is_rank0 and not sharded:
                # The realloc dump is a TRANSFER format, not a recover
                # checkpoint: the destination reads engine_state.pkl
                # directly (below) — an orbax (collective, shard-wise)
                # save here would deadlock multi-host and break the
                # reader. Sharded engines skip the pickle entirely: it
                # would host-gather the full model (the exact cost the
                # shard-local dump removes); a dst model falls back to
                # assembling the raw dump (below).
                save_engine_state(model.module, d, backend="pickle")
            # Stamp the dump with model.version — the exact value
            # _publish_version later announces — NOT the global step:
            # the two counters differ (step counts MFC dispatches from
            # 0; version increments inside train_step), and the
            # generation server now VERIFIES the loaded dump matches
            # the requested version (WeightVersionMismatch otherwise).
            # Match the sidecar's chunk size to the plane's knob so the
            # source serves the dump-time index instead of re-hashing.
            cb = getattr(self.cfg, "weight_chunk_bytes", 8 << 20)
            # Quantized wire: the dump pass also publishes the int8
            # companion bin the plane serves at ~half the bytes per
            # version (weight_wire_dtype knob; servers dequantize).
            wire = getattr(self.cfg, "weight_wire_dtype", None)
            shm = shm_transfer_dir(
                self.cfg.experiment_name, self.cfg.trial_name, role
            )
            if multi_proc:
                # The tmpfs fast path is a SAME-HOST optimization; a
                # multi-host dump's slabs would land on N different
                # hosts' /dev/shm and no single origin could ever
                # assemble the stream. Every reader (origin included)
                # uses the shared disk dir instead.
                shm = None
            if sharded:
                # Shard-local dump: each process writes only its
                # addressable shard slabs — no whole-model host gather,
                # host high-water ~1/mesh_size of the full payload; the
                # weight-plane origin reassembles the identical byte
                # stream from the slabs (weight_transfer.py).
                raw = model.module.get_params()
                pi = jax.process_index() if multi_proc else 0
                pn = jax.process_count() if multi_proc else 1
                dump_s = dump_raw_params_sharded(
                    raw, d, version=model.version, chunk_bytes=cb,
                    process_index=pi, n_processes=pn, wire_dtype=wire,
                )
                if is_rank0:
                    # A pre-sharding run may have left engine_state.pkl
                    # in this dir; the dst realloc branch prefers it, so
                    # a stale pickle would silently shadow every fresh
                    # sharded dump after a mixed-mode restart.
                    try:
                        os.unlink(os.path.join(d, "engine_state.pkl"))
                    except OSError:
                        pass
                if shm is not None:
                    # Mirror the finished artifacts at the FILE level
                    # (page-cache reads) — a second dump call would
                    # re-materialize every shard off the device.
                    dump_s += mirror_dump_version(d, shm, model.version)
            else:
                # Raw mmap-able dumps for the generation servers: tmpfs
                # same-host fast path + disk fallback.
                params = jax.tree_util.tree_map(
                    lambda x: np.asarray(x), model.module.get_params()
                )
                dump_s = dump_raw_params(
                    params, d, version=model.version, chunk_bytes=cb,
                    wire_dtype=wire,
                )
                if shm is not None:
                    dump_s += dump_raw_params(
                        params, shm, version=model.version, chunk_bytes=cb,
                        wire_dtype=wire,
                    )
            hw = LAST_DUMP_STATS.get("high_water_bytes", 0)
            logger.info(
                f"param_realloc dump for {role} step {step}: "
                f"{'shard-local ' if sharded else ''}raw dump "
                f"v{model.version} {dump_s:.3f}s host-high-water "
                f"{hw / float(1 << 20):.1f}MiB "
                f"(shm={'yes' if shm is not None else 'no'})"
            )
            # Streaming weight-distribution plane: the dump rank exposes
            # this role's raw-bin dumps over chunked HTTP so the gserver
            # manager can fan the bytes out through a peer tree instead
            # of every generation server re-reading the checkpoint from
            # NFS. The source serves the tmpfs copy when one exists
            # (page-cache-hot either way); armed by the experiment's
            # gen_weight_plane knob or the AREAL_WEIGHT_PLANE env gate,
            # so legacy deployments keep zero extra listeners.
            if is_rank0 and (
                getattr(self.cfg, "weight_plane", False)
                or env_registry.get_bool("AREAL_WEIGHT_PLANE")
            ):
                self._ensure_weight_plane_source(role, shm or d)
            if is_rank0:
                # One stamp writer: non-zero slab ranks of a multi-host
                # mesh dumped above but must not publish step.txt (a
                # reader could race a stamp ahead of missing slabs; the
                # slab-completeness check in DumpStreamReader is the
                # backstop either way).
                tmp = os.path.join(d, "step.txt.tmp")
                with open(tmp, "w") as f:
                    f.write(str(step))
                os.replace(tmp, os.path.join(d, "step.txt"))
        if dst is not None and dst in self.models:
            model = self.models[dst]
            role = ModelName.parse(dst).role
            # The source role's dump is what we load from.
            src_role = ModelName.parse(src).role if src else role
            d = os.path.join(realloc_root, src_role)
            stamp = os.path.join(d, "step.txt")
            deadline = _time.monotonic() + 300
            while True:
                try:
                    with open(stamp) as f:
                        if int(f.read().strip() or -1) >= step:
                            break
                except (FileNotFoundError, ValueError):
                    pass
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"param_realloc: no fresh dump for {src_role} "
                        f"(step {step}) within 300s"
                    )
                _time.sleep(0.05)
            # Only params move; optimizer state stays local.
            import pickle

            pkl = os.path.join(d, "engine_state.pkl")
            if os.path.exists(pkl):
                with open(pkl, "rb") as f:
                    state = pickle.load(f)
                model.module.set_params(state["params"])
            else:
                # Sharded trainer source: no pickle was written (it
                # would host-gather the full model). Assemble the full
                # tree from the shard-local raw dump instead — with a
                # bounded retry: the step.txt stamp only proves rank 0
                # dumped, while peer hosts' slabs can still be landing
                # on shared storage (load_raw_params reads a
                # slab-incomplete dump as absent by design).
                from areal_tpu.system.weight_transfer import load_raw_params

                got = None
                fallback_deadline = _time.monotonic() + 60
                while got is None:
                    got = load_raw_params(d)
                    if got is not None:
                        break
                    if _time.monotonic() > fallback_deadline:
                        raise FileNotFoundError(
                            f"param_realloc: neither engine_state.pkl "
                            f"nor a complete raw dump in {d} within 60s"
                        )
                    _time.sleep(0.25)
                model.module.set_params(got[0])
