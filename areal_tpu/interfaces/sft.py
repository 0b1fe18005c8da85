"""SFT algorithm interface (reference: realhf/impl/model/interface/sft_interface.py)."""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax.numpy as jnp
import numpy as np

from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import Model, ModelInterface, register_interface
from areal_tpu.base import stats_tracker
from areal_tpu.ops.loss import response_positions


def sft_row_loss(lp, rows):
    """Next-token CE over response tokens (prompt_mask == 1 marks prompts).

    `lp` is the engine-supplied fused next-token logprobs [R, T]."""
    mask = response_positions(rows)
    n_tokens = jnp.sum(mask)
    if "dp_loss_scale" in rows:
        # Engine-injected per-shard normalization scale
        # (token_normalize_scope='dp', jax_engine._apply_dp_token_scale).
        mask = mask * rows["dp_loss_scale"]
    loss_sum = -jnp.sum(lp * mask)
    return loss_sum, {"n_response_tokens": n_tokens}


def sft_loss_weight(mb: SequenceSample) -> float:
    """Number of loss (response) tokens in a micro-batch."""
    pm = np.asarray(mb.data["prompt_mask"])
    total = 0
    offset = 0
    for sl in mb.seqlens["prompt_mask"]:
        for l in sl:
            seq_pm = pm[offset : offset + l]
            # mask[t] = next token is response (same shifted frame as the loss)
            total += int(np.sum(seq_pm[1:] == 0))
            offset += l
    return float(total)


@dataclasses.dataclass
class SFTInterface(ModelInterface):
    token_normalize_scope: str = "global"

    def train_step(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict:
        engine = model.module
        # read at once: one update a step, nothing to enqueue behind it
        stats = dict(engine.train_batch(
            input_,
            mb_spec,
            loss_fn=sft_row_loss,
            loss_weight_fn=sft_loss_weight,
            token_normalize_scope=self.token_normalize_scope,
            version_steps=model.version,
            loss_name="sft",
            scored_fn=response_positions,
        ))
        model.inc_version()
        stats_tracker.scalar(**stats)
        return stats

    def evaluate(self, model: Model, eval_dataloader) -> Dict:
        engine = model.module
        total_loss, total_tokens = 0.0, 0.0
        for batch in eval_dataloader:
            out = engine.forward(batch, MicroBatchSpec(), output_key="logprobs")
            pm = np.asarray(batch.data["prompt_mask"]).astype(bool)
            lp = np.asarray(out.data["logprobs"])
            # Shifted frame: position t scores token t+1.
            offset = 0
            for sl in batch.seqlens["prompt_mask"]:
                for l in sl:
                    seq_pm = pm[offset : offset + l]
                    seq_lp = lp[offset : offset + l]
                    resp_next = ~seq_pm[1:]
                    total_loss += float(-np.sum(seq_lp[:-1][resp_next]))
                    total_tokens += float(resp_next.sum())
                    offset += l
        return {
            "eval_loss": total_loss / max(total_tokens, 1.0),
            "eval_n_tokens": total_tokens,
        }

    def save(self, model: Model, save_dir: str):
        from areal_tpu.models.hf import save_hf_model

        engine = model.module
        family = getattr(engine, "hf_family", None)
        if family is None:
            raise ValueError(
                "engine has no hf_family set; pass hf_family= when building "
                "the JaxTrainEngine so save() knows which HF weight mapping "
                "to use (silently guessing would corrupt the checkpoint)"
            )
        import jax

        save_hf_model(
            save_dir,
            engine.model_cfg,
            jax.device_get(engine.get_params()),
            family,
            tokenizer=model.tokenizer,
        )


register_interface("sft", SFTInterface)
