"""PPO actor/critic algorithm interfaces.

Counterpart of realhf/impl/model/interface/ppo_interface.py
(PPOActorInterface:210, PPOCriticInterface:984): generate -> rollout
sample assembly; inference -> proximal/ref logprob recompute; train_step ->
rewards (KL penalty + clipped task score) -> GAE -> advantage
normalization (global or per-group GRPO-style) -> minibatched decoupled-PPO
updates through the engine.

Data-layout conventions (all token-aligned keys live in the *shifted*
frame used by next_token_logprobs: position t scores token t+1):
- packed_input_ids: prompt + response tokens, grouped per prompt id
- prompt_mask: 1 on prompt token positions
- packed_logprobs: behavior logprobs from generation
- logprobs: proximal logprobs recomputed at train time (decoupled PPO)
- ref_logprobs: reference-model logprobs
- values: critic values (absent in group-reward / GRPO mode)
- rewards: per-sequence task scores; seq_no_eos_mask: per-sequence
- version_start / version_end: per-sequence weight versions (staleness)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    GenerationHyperparameters,
    Model,
    ModelInterface,
    register_interface,
)
from areal_tpu.base import logging as areal_logging
from areal_tpu.base import stats_tracker, tracing
from areal_tpu.interfaces import functional as F
from areal_tpu.base import env_registry
from areal_tpu.ops.gae import packed_gae
from areal_tpu.ops.loss import (
    masked_normalization,
    response_positions,
    response_scoring_mask,
)

logger = areal_logging.getLogger("ppo")


def last_response_position_mask(resp_mask):
    """[R, T] 1.0 at the final scoring position of each segment."""
    nxt = jnp.concatenate([resp_mask[:, 1:], jnp.zeros_like(resp_mask[:, :1])], axis=1)
    return resp_mask * (1.0 - nxt)


@dataclasses.dataclass
class PPOActorInterface(ModelInterface):
    n_minibatches: int = 4
    # 'global' | 'dp' — per-dp-shard gradient normalization (reference
    # ppo_interface.py:253; engine implements it via loss_mask reweight).
    token_normalize_scope: str = "global"
    eps_clip: float = 0.2
    c_clip: Optional[float] = None
    kl_ctl: float = 0.1
    adaptive_kl_ctl: bool = False
    adaptive_kl_target: float = 6.0
    adaptive_kl_horizon: float = 10000.0
    discount: float = 1.0
    gae_lambda: float = 1.0
    max_reward_clip: float = 20.0
    reward_output_scaling: float = 1.0
    reward_output_bias: float = 0.0
    adv_norm: bool = True
    group_adv_norm: bool = False
    mask_no_eos_with_zero: bool = False
    use_decoupled_loss: bool = False
    behav_imp_weight_cap: Optional[float] = None
    temperature: float = 1.0
    # Best-of-k: sample `generation_size` responses per prompt, verify
    # them, and keep only the top `gconfig.n` (by score, longer-first on
    # ties) for training (reference ppo_interface.py:376-408).
    generation_size: Optional[int] = None
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters
    )

    def __post_init__(self):
        if isinstance(self.gconfig, dict):
            self.gconfig = GenerationHyperparameters(**self.gconfig)
        if self.adaptive_kl_ctl:
            self.kl_controller = F.AdaptiveKLController(
                self.kl_ctl, self.adaptive_kl_target, self.adaptive_kl_horizon
            )
        else:
            self.kl_controller = F.FixedKLController(self.kl_ctl)

    # ------------------------------------------------------------------
    # Generate (sync PPO path; async uses the rollout workers instead)
    # ------------------------------------------------------------------

    def _best_of_k(
        self, model: Model, input_: SequenceSample, outs: List[Dict], k: int
    ) -> List[Dict]:
        """Sample-then-select (reference ppo_interface.py:376-408 get_score
        + topk): verify all `generation_size` candidates per prompt and
        keep the k best, scores descending with longer generations
        breaking ties. The reference looks answers up in a global id2info
        table; here they ride in the sample metadata ('solutions').
        Verification goes through verify_all (thread pool / remote batch
        verifier) — bs * generation_size gradings would crawl serially."""
        from areal_tpu.interfaces.reward import verify_all

        g = self.generation_size
        tasks = input_.metadata.get("tasks") or ["math"] * input_.bs
        answers = input_.metadata.get("solutions") or input_.metadata.get(
            "answers"
        )
        if answers is None:
            raise ValueError(
                "generation_size > gconfig.n needs 'solutions'/'answers' "
                "metadata to score candidates"
            )
        jobs = [
            (
                tasks[pi],
                model.tokenizer.decode(outs[pi * g + ci]["output_ids"]),
                answers[pi],
            )
            for pi in range(input_.bs)
            for ci in range(g)
        ]
        oks = verify_all(jobs)
        selected: List[Dict] = []
        for pi in range(input_.bs):
            cand = outs[pi * g : (pi + 1) * g]
            scored = [
                (1.0 if oks[pi * g + ci] else 0.0, len(o["output_ids"]), ci)
                for ci, o in enumerate(cand)
            ]
            scored.sort(key=lambda t: (t[0], t[1]), reverse=True)
            selected.extend(cand[ci] for _, _, ci in scored[:k])
        return selected

    def generate(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        engine = model.module
        n = self.gconfig.n
        if self.generation_size is not None and self.generation_size > n:
            gcfg = dataclasses.replace(self.gconfig, n=self.generation_size)
            outs = engine.generate(input_, mb_spec, model.tokenizer, gcfg)
            outs = self._best_of_k(model, input_, outs, n)
        else:
            outs = engine.generate(
                input_, mb_spec, model.tokenizer, self.gconfig
            )
        prompt_key = "packed_prompts" if "packed_prompts" in input_.keys else input_._main_key()
        flat_prompts = np.asarray(input_.data[prompt_key])
        plens = [sum(sl) for sl in input_.seqlens[prompt_key]]
        offsets = np.concatenate([[0], np.cumsum(plens)])

        seqs, pmask, blogp, no_eos = [], [], [], []
        group_lens: List[List[int]] = []
        for pi in range(input_.bs):
            prompt = flat_prompts[offsets[pi] : offsets[pi + 1]].astype(np.int64)
            lens = []
            for gi in range(n):
                o = outs[pi * n + gi]
                out_ids = np.asarray(o["output_ids"], np.int64)
                full = np.concatenate([prompt, out_ids])
                lens.append(len(full))
                seqs.append(full)
                pm = np.zeros(len(full), np.int64)
                pm[: len(prompt)] = 1
                pmask.append(pm)
                # Shifted frame: gen token i (abs pos len(prompt)+i) is
                # scored at abs pos len(prompt)+i-1.
                lp = np.zeros(len(full), np.float32)
                lp[len(prompt) - 1 : len(full) - 1] = o["output_logprobs"]
                blogp.append(lp)
                no_eos.append(1.0 if o["no_eos"] else 0.0)
            group_lens.append(lens)

        n_seqs_per_prompt = [[1] * n for _ in range(input_.bs)]
        res = SequenceSample(
            ids=list(input_.ids),
            keys={
                "packed_input_ids", "prompt_mask", "packed_logprobs",
                "seq_no_eos_mask",
            },
            data={
                "packed_input_ids": np.concatenate(seqs),
                "prompt_mask": np.concatenate(pmask),
                "packed_logprobs": np.concatenate(blogp),
                "seq_no_eos_mask": np.asarray(no_eos, np.float32),
            },
            seqlens={
                "packed_input_ids": group_lens,
                "prompt_mask": group_lens,
                "packed_logprobs": group_lens,
                "seq_no_eos_mask": n_seqs_per_prompt,
            },
            metadata={
                "version_start": [model.version] * input_.bs,
                "version_end": [model.version] * input_.bs,
            },
        )
        return res

    # ------------------------------------------------------------------
    # Inference: recompute logprobs under the current (proximal) policy
    # ------------------------------------------------------------------

    def inference(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        engine = model.module
        return engine.forward(input_, mb_spec, output_key="logprobs")

    # ------------------------------------------------------------------
    # Train
    # ------------------------------------------------------------------

    def _prep_fn(self, engine):
        if not hasattr(self, "_jit_prep"):
            # GAE impl pinned when the prep program is first built (the
            # AREAL_CE_CHUNK snapshot discipline: a mid-run retrace must
            # not silently switch kernels). 'auto' resolves per shape at
            # trace time (ops/gae.resolve_gae_impl — the associative
            # scan; the serial lax.scan stays the oracle + explicit
            # fallback, the Pallas kernel the measured opt-in).
            gae_impl = env_registry.get_str("AREAL_GAE_IMPL")

            @jax.named_scope("ppo_prep")
            def prep(rows, kl_coef):
                resp_mask = response_scoring_mask(
                    rows["segment_ids"], rows["prompt_mask"]
                )
                last_mask = last_response_position_mask(resp_mask)
                values = rows.get("values")
                has_critic = values is not None
                if values is None:
                    values = jnp.zeros_like(resp_mask)
                no_eos = rows["seq_no_eos_mask"]
                rewards = F.packed_rewards(
                    kl_coef=kl_coef,
                    clip_reward_value=self.max_reward_clip,
                    score=rows["rewards"] * self.reward_output_scaling
                    + self.reward_output_bias,
                    logprobs=rows["packed_logprobs"],
                    ref_logprobs=rows.get("ref_logprobs", jnp.zeros_like(resp_mask)),
                    response_mask=resp_mask,
                    last_response_mask=last_mask,
                    mask_no_eos_with_zero=self.mask_no_eos_with_zero,
                    no_eos_mask=no_eos,
                )
                # GAE runs over the *scoring* region only: restricting the
                # segment ids to scoring positions makes each segment end at
                # its last scoring position, which is exactly where the
                # bootstrap value V(s_T) must enter the recursion for
                # truncated (no-EOS) sequences.
                score_seg = rows["segment_ids"] * resp_mask.astype(
                    rows["segment_ids"].dtype
                )
                # Bootstrap for truncated (no-EOS) sequences: V(s_{T+1}),
                # the critic value at the *final token* position — one to
                # the right of the last scoring position (values are
                # token-aligned, so shift left to read position t+1 at t).
                values_next = jnp.concatenate(
                    [values[:, 1:], jnp.zeros_like(values[:, :1])], axis=1
                )
                bootstrap = (
                    values_next * last_mask * no_eos
                    if has_critic
                    else jnp.zeros_like(resp_mask)
                )
                masked_values = values * resp_mask
                adv, ret = packed_gae(
                    rewards * resp_mask,
                    masked_values,
                    score_seg,
                    bootstrap,
                    gamma=self.discount,
                    lam=self.gae_lambda,
                    impl=gae_impl,
                )
                adv = adv * resp_mask
                ret = ret * resp_mask
                kl_sum = jnp.sum(
                    (rows["packed_logprobs"] - rows.get(
                        "ref_logprobs", jnp.zeros_like(resp_mask))) * resp_mask
                )
                if self.adv_norm and not self.group_adv_norm:
                    adv = masked_normalization(adv, resp_mask)
                return adv, ret, resp_mask, kl_sum

            self._jit_prep = jax.jit(prep)
        return self._jit_prep

    def train_step(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict:
        engine = model.module
        with tracing.span("ppo.train_step", version=model.version):
            kl_coef = self.kl_controller.value

            # 1) Whole-batch advantage computation on device.
            with tracing.span("ppo.prep"):
                batch, (adv_rows, ret_rows, resp_rows, kl_sum) = _run_prep(
                    engine, self._prep_fn(engine), input_, kl_coef)
                # What is left of `ppo.prep` outside its children: the
                # step's first wait for the device.
                adv_rows = np.asarray(adv_rows)
                tracing.drained("ppo.prep")
                with tracing.span("ppo.prep.gather"):
                    adv_flat = batch.gather_flat(adv_rows)
                    ret_flat = batch.gather_flat(np.asarray(ret_rows))
                    resp_flat = batch.gather_flat(np.asarray(resp_rows))
            tracing.set_attrs(
                tokens=batch.total_tokens, sequences=len(batch.seq_lens)
            )

            with tracing.span("ppo.advantages"):
                # 2) Optional group normalization (GRPO): per prompt-group over
                #    response positions.
                if self.adv_norm and self.group_adv_norm:
                    adv_flat = adv_flat.copy()
                    offset = 0
                    for sl in input_.seqlens["packed_input_ids"]:
                        glen = sum(sl)
                        idx = np.arange(offset, offset + glen)[resp_flat[offset : offset + glen] > 0]
                        if idx.size > 1:
                            vals = adv_flat[idx]
                            adv_flat[idx] = (vals - vals.mean()) / (vals.std() + 1e-5)
                        offset += glen
                train_sample = input_
                train_sample.update_(
                    SequenceSample(
                        ids=list(input_.ids),
                        keys={"advantages"},
                        data={"advantages": adv_flat.astype(np.float32)},
                        seqlens={
                            "advantages": [list(sl) for sl in input_.seqlens["packed_input_ids"]]
                        },
                    )
                )

                # 3) Minibatched PPO updates.
                mb_inputs, *_ = train_sample.split(
                    MicroBatchSpec(n_mbs=self.n_minibatches)
                )
            use_decoupled = self.use_decoupled_loss and "logprobs" in train_sample.keys

            def actor_loss(lp, rows):
                # `lp` is the fused next-token logprobs [R, T] computed by the
                # engine (logits never materialized).
                mask = response_scoring_mask(rows["segment_ids"], rows["prompt_mask"])
                # Engine-injected per-shard normalization scale applies to the
                # LOSS weighting only (monitoring stats keep the raw mask).
                loss_w = (
                    mask * rows["dp_loss_scale"] if "dp_loss_scale" in rows else mask
                )
                prox = rows["logprobs"] if use_decoupled else None
                loss_sum, st = F.actor_loss_fn(
                    logprobs=lp,
                    old_logprobs=rows["packed_logprobs"],
                    advantages=rows["advantages"],
                    eps_clip=self.eps_clip,
                    loss_mask=loss_w,
                    c_clip=self.c_clip,
                    proximal_logprobs=prox,
                    behav_imp_weight_cap=self.behav_imp_weight_cap if use_decoupled else None,
                    stats_mask=mask,
                )
                # Approx KL(new || behavior) for monitoring.
                st["approx_kl"] = jnp.sum((rows["packed_logprobs"] - lp) * mask)
                return loss_sum, st

            def weight_fn(mb):
                return _n_response_tokens(mb)

            all_stats = []
            for i, mb in enumerate(mb_inputs):
                with tracing.span("ppo.minibatch", index=i,
                                  tokens=mb.total_seqlen()):
                    st = engine.train_batch(
                        mb, MicroBatchSpec(n_mbs=1, max_tokens_per_mb=mb_spec.max_tokens_per_mb),
                        loss_fn=actor_loss, loss_weight_fn=weight_fn,
                        token_normalize_scope=self.token_normalize_scope,
                        version_steps=model.version, loss_name="ppo_actor",
                        scored_fn=response_positions,
                    )
                # kept unread: the first look at `st` waits for the device,
                # and the next minibatch is enqueued first (`ppo.stats` reads)
                all_stats.append(st)
            with tracing.span("ppo.stats"):
                model.inc_version()

                n_resp = float(np.sum(resp_flat))
                mean_kl = float(kl_sum) / max(n_resp, 1.0)
                self.kl_controller.update(mean_kl, int(n_resp))

                agg = {k: float(np.mean([s[k] for s in all_stats])) for k in all_stats[0]}
                agg.update(
                    {
                        "ppo_actor/kl": mean_kl,
                        "ppo_actor/kl_coef": kl_coef,
                        "ppo_actor/adv_mean": float(
                            np.sum(adv_flat * resp_flat) / max(n_resp, 1.0)
                        ),
                        "ppo_actor/ret_mean": float(
                            np.sum(ret_flat * resp_flat) / max(n_resp, 1.0)
                        ),
                        "ppo_actor/reward_mean": float(np.mean(input_.data["rewards"]))
                        if input_.data.get("rewards") is not None else 0.0,
                        "ppo_actor/n_tokens": float(batch.total_tokens),
                    }
                )
                # Staleness accounting (reference: ppo_interface.py:752-762).
                vs = input_.metadata.get("version_start")
                ve = input_.metadata.get("version_end")
                if vs:
                    agg["ppo_actor/head_offpolicyness"] = float(model.version - 1 - np.min(vs))
                    agg["ppo_actor/tail_offpolicyness"] = float(model.version - 1 - np.max(ve))
                stats_tracker.scalar(**agg)
            return agg

    def save(self, model: Model, save_dir: str):
        from areal_tpu.interfaces.sft import SFTInterface

        SFTInterface.save(self, model, save_dir)  # same HF export path


def _run_prep(engine, prep, input_: SequenceSample, kl_coef: float):
    """The host's side of `ppo.prep` up to the enqueue, a leaf span each
    part: pack the whole batch into rows, put them on the device, call the
    prep program. Returns the packed batch and the program's outputs,
    still on the device."""
    with tracing.span("ppo.prep.pack"):
        batch, rows = engine._build_rows(input_)
    tracing.set_attrs(rows=batch.n_rows, row_len=batch.row_len)
    with tracing.span("ppo.prep.h2d"):
        rows_dev = engine._device_rows(rows)
    tracing.build_site("ppo_prep", prep, batch.n_rows, batch.row_len)
    with tracing.span("ppo.prep.dispatch"):
        out = prep(rows_dev, np.float32(kl_coef))
        tracing.fed("ppo_prep")
    return batch, out


def _n_response_tokens(mb: SequenceSample) -> float:
    pm = np.asarray(mb.data["prompt_mask"])
    total, offset = 0, 0
    for sl in mb.seqlens["prompt_mask"]:
        for l in sl:
            total += int(np.sum(pm[offset + 1 : offset + l] == 0))
            offset += l
    return float(total)


@dataclasses.dataclass
class PPOCriticInterface(ModelInterface):
    n_minibatches: int = 4
    token_normalize_scope: str = "global"
    value_eps_clip: float = 0.2
    kl_ctl: float = 0.1
    adaptive_kl_ctl: bool = False
    adaptive_kl_target: float = 6.0
    adaptive_kl_horizon: float = 10000.0
    discount: float = 1.0
    gae_lambda: float = 1.0
    max_reward_clip: float = 20.0
    reward_output_scaling: float = 1.0
    reward_output_bias: float = 0.0
    value_norm: bool = True
    mask_no_eos_with_zero: bool = False

    def __post_init__(self):
        self.rms = F.RunningMeanStd()
        # Mirrors the actor's controller so returns use the same (possibly
        # drifting) KL coefficient: both controllers see the same per-step
        # observed KL and so stay in lockstep (reference keeps separate but
        # identically-updated adapters on actor and critic interfaces).
        if self.adaptive_kl_ctl:
            self.kl_controller = F.AdaptiveKLController(
                self.kl_ctl, self.adaptive_kl_target, self.adaptive_kl_horizon
            )
        else:
            self.kl_controller = F.FixedKLController(self.kl_ctl)
        # Returns must be computed with the SAME reward transform as the
        # actor's advantages; the helper is cached so its jitted prep
        # program survives across train steps.
        self._helper = PPOActorInterface(
            discount=self.discount, gae_lambda=self.gae_lambda,
            kl_ctl=self.kl_ctl, max_reward_clip=self.max_reward_clip,
            reward_output_scaling=self.reward_output_scaling,
            reward_output_bias=self.reward_output_bias,
            adv_norm=False, mask_no_eos_with_zero=self.mask_no_eos_with_zero,
        )

    def inference(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> SequenceSample:
        engine = model.module
        out = engine.forward(input_, mb_spec, output_key="values", output="values")
        if self.value_norm:
            out.data["values"] = self.rms.denormalize(out.data["values"])
        return out

    def train_step(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict:
        engine = model.module
        with tracing.span("ppo.train_step", version=model.version):
            # Returns are recomputed exactly like the actor does.
            with tracing.span("ppo.prep"):
                batch, (_, ret_rows, resp_rows, kl_sum) = _run_prep(
                    engine, self._helper._prep_fn(engine), input_,
                    self.kl_controller.value)
                ret_rows = np.asarray(ret_rows)  # the step's first wait for the device
                tracing.drained("ppo.prep")
                with tracing.span("ppo.prep.gather"):
                    ret_flat = batch.gather_flat(ret_rows)
                    resp_flat = batch.gather_flat(np.asarray(resp_rows))
            tracing.set_attrs(
                tokens=batch.total_tokens, sequences=len(batch.seq_lens)
            )
            with tracing.span("ppo.advantages"):
                if self.value_norm:
                    self.rms.update(ret_flat, mask=resp_flat > 0)
                    norm_ret = np.where(resp_flat > 0, self.rms.normalize(ret_flat), 0.0)
                    old_values = np.where(
                        resp_flat > 0,
                        self.rms.normalize(np.asarray(input_.data["values"])),
                        0.0,
                    )
                else:
                    norm_ret = ret_flat
                    old_values = np.asarray(input_.data["values"])

                sl = [list(s) for s in input_.seqlens["packed_input_ids"]]
                input_.update_(
                    SequenceSample(
                        ids=list(input_.ids), keys={"returns", "old_values_norm"},
                        data={
                            "returns": norm_ret.astype(np.float32),
                            "old_values_norm": old_values.astype(np.float32),
                        },
                        seqlens={"returns": sl, "old_values_norm": sl},
                    )
                )
                mb_inputs, *_ = input_.split(MicroBatchSpec(n_mbs=self.n_minibatches))

            def critic_loss(values, rows):
                mask = response_scoring_mask(rows["segment_ids"], rows["prompt_mask"])
                loss_w = (
                    mask * rows["dp_loss_scale"] if "dp_loss_scale" in rows else mask
                )
                loss_sum, st = F.critic_loss_fn(
                    value=values,
                    old_value=rows["old_values_norm"],
                    target_value=rows["returns"],
                    value_eps_clip=self.value_eps_clip,
                    loss_mask=loss_w,
                    stats_mask=mask,
                )
                return loss_sum, st

            all_stats = []
            for i, mb in enumerate(mb_inputs):
                with tracing.span("ppo.minibatch", index=i,
                                  tokens=mb.total_seqlen()):
                    st = engine.train_batch(
                        mb, MicroBatchSpec(n_mbs=1, max_tokens_per_mb=mb_spec.max_tokens_per_mb),
                        loss_fn=critic_loss, loss_weight_fn=_n_response_tokens,
                        token_normalize_scope=self.token_normalize_scope,
                        version_steps=model.version, loss_name="ppo_critic",
                    )
                all_stats.append(st)  # unread until `ppo.stats`, as the actor's
            with tracing.span("ppo.stats"):
                model.inc_version()
                n_resp = float(np.sum(resp_flat))
                self.kl_controller.update(float(kl_sum) / max(n_resp, 1.0), int(n_resp))
                agg = {k: float(np.mean([s[k] for s in all_stats])) for k in all_stats[0]}
                stats_tracker.scalar(**agg)
            return agg


register_interface("ppo_actor", PPOActorInterface)
register_interface("ppo_critic", PPOCriticInterface)
