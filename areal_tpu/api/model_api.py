"""Model, engine, interface and backend contracts + registries.

Counterpart of the reference's model API (realhf/api/core/model_api.py).
The central engine abstraction (`TrainEngine`, mirroring the reference's
`PipelinableEngine:514`) is what algorithm interfaces program against:
`train_batch` / `forward` / `generate` over packed `SequenceSample`s with
micro-batch specs. On TPU an engine owns a pytree of sharded params on a
`jax.sharding.Mesh` and jitted step functions — there is no per-rank
pipelining object; GSPMD replaces the reference's pipe runner.
"""

from __future__ import annotations

import abc
import dataclasses
import keyword
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from areal_tpu.api.config import (
    ModelAbstraction,
    ModelBackendAbstraction,
    ModelFamily,
    ModelInterfaceAbstraction,
    ModelName,
    Registry,
)
from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample


@dataclasses.dataclass
class GenerationHyperparameters:
    """Sampling configuration (mirrors reference GenerationHyperparameters)."""

    n: int = 1  # group size: samples per prompt
    max_new_tokens: int = 256
    min_new_tokens: int = 0
    greedy: bool = False
    top_p: float = 1.0
    top_k: int = -1
    temperature: float = 1.0
    stop_token_ids: List[int] = dataclasses.field(default_factory=list)

    def new(self, **kwargs) -> "GenerationHyperparameters":
        d = dataclasses.asdict(self)
        d.update(kwargs)
        return GenerationHyperparameters(**d)


@dataclasses.dataclass
class FinetuneSpec:
    total_train_epochs: int = 1
    dataset_size: int = 0
    train_batch_size: int = 1

    @property
    def steps_per_epoch(self) -> int:
        return max(1, self.dataset_size // max(1, self.train_batch_size))

    @property
    def total_train_steps(self) -> int:
        return self.total_train_epochs * self.steps_per_epoch


class TrainEngine(abc.ABC):
    """What algorithm interfaces call. All data is packed SequenceSamples.

    Implementations: `areal_tpu.engine.jax_engine.JaxTrainEngine` (optax
    train + inference + in-framework generation) and the mock engine for
    CPU system tests.
    """

    @abc.abstractmethod
    def train_batch(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        loss_fn: Any,
        loss_weight_fn: Any,
        token_normalize_scope: str = "global",
        version_steps: Optional[int] = None,
        loss_name: str = "loss",
        scored_fn: Any = None,
    ) -> Dict[str, float]:
        """Run forward+backward+update over micro-batches; returns host
        stats. `version_steps` positions the LR schedule (None = the
        engine's own step count); `scored_fn(rows)` names the positions
        whose logprob `loss_fn` reads (None = all); see
        JaxTrainEngine.train_batch."""

    @abc.abstractmethod
    def forward(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        output_key: str = "logprobs",
        post_hook: Optional[Callable] = None,
    ) -> Optional[SequenceSample]:
        """Gradient-free forward over micro-batches, gathered to host."""

    @abc.abstractmethod
    def generate(
        self,
        input_: SequenceSample,
        mb_spec: MicroBatchSpec,
        tokenizer: Any,
        gconfig: GenerationHyperparameters,
    ) -> Optional[SequenceSample]:
        """In-framework generation (sync PPO path)."""

    def eval(self):
        return self

    def train(self, mode: bool = True):
        return self


@dataclasses.dataclass
class Model:
    """A named model hosted by a model worker: engine + tokenizer + version."""

    name: ModelName
    module: Optional[TrainEngine]
    tokenizer: Any
    version: int = 0
    ft_spec: FinetuneSpec = dataclasses.field(default_factory=FinetuneSpec)

    def inc_version(self):
        self.version += 1


class ModelInterface(abc.ABC):
    """Algorithm glue executed by MFCs (ppo_actor, ppo_critic, sft, reward...).

    Mirrors reference ModelInterface (realhf/api/core/model_api.py:759).
    """

    def save(self, model: Model, save_dir: str):
        pass

    def evaluate(self, model: Model, eval_dataloader) -> Dict:
        return {}

    def inference(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Optional[SequenceSample]:
        raise NotImplementedError()

    def generate(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Optional[SequenceSample]:
        raise NotImplementedError()

    def train_step(
        self, model: Model, input_: SequenceSample, mb_spec: MicroBatchSpec
    ) -> Dict | List[Dict]:
        raise NotImplementedError()


class ModelBackend(abc.ABC):
    """Wraps a bare Model with an engine (optimizer state etc.)."""

    @abc.abstractmethod
    def initialize(self, model: Model, spec: FinetuneSpec) -> Model:
        ...

    def save(self, model: Model, save_dir: str):
        pass

    def load(self, model: Model, load_dir: str):
        pass


# ---------------------------------------------------------------------------
# Generation server API types (reference: model_api.py:46-205)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GenReqMeta:
    """What the gserver manager needs to route a request."""

    prompt_len: int = 0
    group_size: int = 1
    new_token_budget: int = 0
    predicted_new_tokens: Optional[int] = None
    previous_server_url: str = ""
    previous_version: int = -1


@dataclasses.dataclass
class APIGenerateInput:
    qid: str
    prompt_ids: List[int]
    input_ids: List[int]  # prompt + previously generated (resubmission prefix)
    gconfig: GenerationHyperparameters
    stop_token_ids: List[int] = dataclasses.field(default_factory=list)
    return_logprob: bool = True
    version_start: int = -1
    metadata: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class APIGenerateOutput:
    qid: str
    prompt_ids: List[int] = dataclasses.field(default_factory=list)
    input_ids: List[int] = dataclasses.field(default_factory=list)
    output_ids: List[int] = dataclasses.field(default_factory=list)
    output_logprobs: List[float] = dataclasses.field(default_factory=list)
    no_eos: bool = True  # True if generation stopped for a non-EOS reason
    version_start: int = -1
    version_end: int = -1
    latency: float = 0.0
    # Tokens resubmitted for prefill after interrupts/chunk boundaries —
    # the measured cost of interruptible generation (tracing + telemetry).
    reprefill_tokens: int = 0
    n_interruptions: int = 0

    @classmethod
    def from_input(cls, inp: APIGenerateInput) -> "APIGenerateOutput":
        return cls(
            qid=inp.qid,
            prompt_ids=list(inp.prompt_ids),
            input_ids=list(inp.input_ids),
            version_start=inp.version_start,
        )

    @property
    def gen_len(self) -> int:
        return len(self.output_ids)


@dataclasses.dataclass
class BundledGenerationOutputs:
    """A prompt group's finished generations, handed to the agent/trainer."""

    qid: str
    prompt_ids: List[int]
    seqs: List[List[int]]  # prompt + answer, per group member
    logprobs: List[List[float]]  # aligned with seqs (prompt positions = 0)
    no_eos: List[bool]
    version_start: List[int]
    version_end: List[int]
    reprefill_tokens: List[int] = dataclasses.field(default_factory=list)
    n_interruptions: List[int] = dataclasses.field(default_factory=list)

    @classmethod
    def from_api_outputs(
        cls, outputs: List[APIGenerateOutput]
    ) -> "BundledGenerationOutputs":
        assert len({o.qid for o in outputs}) == 1
        prompt = outputs[0].prompt_ids
        return cls(
            qid=outputs[0].qid,
            prompt_ids=list(prompt),
            seqs=[list(o.prompt_ids) + list(o.output_ids) for o in outputs],
            logprobs=[[0.0] * len(o.prompt_ids) + list(o.output_logprobs) for o in outputs],
            no_eos=[o.no_eos for o in outputs],
            version_start=[o.version_start for o in outputs],
            version_end=[o.version_end for o in outputs],
            reprefill_tokens=[o.reprefill_tokens for o in outputs],
            n_interruptions=[o.n_interruptions for o in outputs],
        )

    @property
    def prompt_len(self) -> int:
        return len(self.prompt_ids)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------

MODEL_REGISTRY = Registry("model")
INTERFACE_REGISTRY = Registry("interface")
BACKEND_REGISTRY = Registry("backend")


def register_model(name: str, factory):
    MODEL_REGISTRY.register(name, factory)


def make_model(cfg: ModelAbstraction | str, **kwargs) -> Model:
    return MODEL_REGISTRY.make(cfg, **kwargs)


def register_interface(name: str, factory):
    INTERFACE_REGISTRY.register(name, factory)


def make_interface(cfg: ModelInterfaceAbstraction | str, **kwargs) -> ModelInterface:
    return INTERFACE_REGISTRY.make(cfg, **kwargs)


def register_backend(name: str, factory):
    BACKEND_REGISTRY.register(name, factory)


def make_backend(cfg: ModelBackendAbstraction | str, **kwargs) -> ModelBackend:
    return BACKEND_REGISTRY.make(cfg, **kwargs)


# HF model family registry: family name -> conversion helpers, filled by
# areal_tpu.models.hf.
HF_FAMILY_REGISTRY: Dict[str, Any] = {}


def register_hf_family(name: str, helpers: Any):
    if name in HF_FAMILY_REGISTRY:
        raise ValueError(f"HF family {name!r} already registered")
    HF_FAMILY_REGISTRY[name] = helpers
