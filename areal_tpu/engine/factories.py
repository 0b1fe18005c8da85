"""Model and backend factories wired into the registries.

Counterpart of the reference's registered models/backends
(realhf/impl/model/__init__.py, realhf/impl/model/backend/megatron.py:761,
inference.py:230, mock_train.py:240): `make_model("tpu_transformer")`
builds params (random init or HF checkpoint), and the backends wrap them
into engines — "jax_train" (optax + GSPMD), "jax_inference"
(gradient-free), and "mock_train"/"mock_inference" (compute-free engines
for CPU control-plane tests).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from areal_tpu.api import data_api
from areal_tpu.api.config import ModelName
from areal_tpu.api.data_api import MicroBatchSpec, SequenceSample
from areal_tpu.api.model_api import (
    FinetuneSpec,
    GenerationHyperparameters,
    Model,
    ModelBackend,
    TrainEngine,
    register_backend,
    register_model,
)
from areal_tpu.base import logging, seeding
from areal_tpu.engine.jax_engine import JaxTrainEngine
from areal_tpu.engine.optimizer import OptimizerConfig
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import init_params
from areal_tpu.parallel.mesh import make_mesh, single_device_mesh
from areal_tpu.base.topology import MeshSpec

logger = logging.getLogger("factories")


def _build_mesh(mesh_spec: Optional[str], device_ids: Optional[List[int]] = None):
    devices = jax.devices()
    if device_ids is not None:
        devices = [devices[i] for i in device_ids]
    if mesh_spec is None:
        return single_device_mesh(devices[0])
    return make_mesh(MeshSpec.parse(mesh_spec), devices)


def make_transformer_model(
    name: ModelName | str = "default",
    tokenizer_path: Optional[str] = None,
    model_path: Optional[str] = None,
    config: Optional[Dict[str, Any]] = None,
    is_critic: bool = False,
    mesh_spec: Optional[str] = None,
    device_ids: Optional[List[int]] = None,
    hf_family: Optional[str] = None,
    dtype: str = "bfloat16",
    init_seed: int = 1,
) -> Model:
    """Build a Model whose raw params/config are stashed for the backend.

    Either `model_path` (HF checkpoint dir; config+weights+family inferred)
    or `config` (TransformerConfig kwargs, random init) must be given.
    """
    if isinstance(name, str):
        name = ModelName.parse(name)
    mesh = _build_mesh(mesh_spec, device_ids)
    if model_path is not None:
        from areal_tpu.models.hf import family_from_hf_config, load_hf_config, load_hf_model

        if hf_family is None:
            hf_family = family_from_hf_config(load_hf_config(model_path)).name
        cfg, params = load_hf_model(model_path, is_critic=is_critic, family=hf_family)
        tokenizer_path = tokenizer_path or model_path
    else:
        assert config is not None, "need model_path or config"
        cfg = TransformerConfig(**{**config, "is_critic": is_critic})
        rng = jax.random.fold_in(
            jax.random.PRNGKey(init_seed), seeding._hash_key(f"model_init/{name}")
        )
        params = init_params(cfg, rng)
    tokenizer = (
        data_api.load_hf_tokenizer(tokenizer_path) if tokenizer_path else None
    )
    model = Model(name=name, module=None, tokenizer=tokenizer)
    model._raw = dict(  # consumed by backends
        cfg=cfg, params=params, mesh=mesh, hf_family=hf_family, dtype=dtype
    )
    return model


register_model("tpu_transformer", make_transformer_model)


@dataclasses.dataclass
class JaxTrainBackend(ModelBackend):
    """Wraps a model into a training JaxTrainEngine (reference
    MegatronTrainBackend, backend/megatron.py:561)."""

    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    attn_impl: str = "auto"
    remat: bool = True
    row_len_multiple: int = 128
    max_row_len: Optional[int] = None
    # Overlapped input pipeline depth (0 = eager) and packed-stats fetch
    # cadence — see JaxTrainEngine.
    prefetch_depth: int = 2
    stats_fetch_interval: int = 1

    def __post_init__(self):
        if isinstance(self.optimizer, dict):
            self.optimizer = OptimizerConfig(**self.optimizer)

    def initialize(self, model: Model, spec: FinetuneSpec) -> Model:
        raw = model._raw
        model.module = JaxTrainEngine(
            model_cfg=raw["cfg"],
            # Handed over, not shared: on a mesh the engine reshards
            # them, and a reference kept here would pin the unsharded
            # originals on device 0.
            params=raw.pop("params"),
            mesh=raw["mesh"],
            optimizer_config=self.optimizer,
            total_train_steps=max(1, spec.total_train_steps),
            attn_impl=self.attn_impl,
            remat=self.remat,
            row_len_multiple=self.row_len_multiple,
            max_row_len=self.max_row_len,
            hf_family=raw.get("hf_family"),
            prefetch_depth=self.prefetch_depth,
            stats_fetch_interval=self.stats_fetch_interval,
        )
        model.ft_spec = spec
        return model

    def save(self, model: Model, save_dir: str):
        from areal_tpu.engine.checkpoint import save_engine_state

        save_engine_state(model.module, save_dir)

    def load(self, model: Model, load_dir: str):
        from areal_tpu.engine.checkpoint import load_engine_state

        load_engine_state(model.module, load_dir)


@dataclasses.dataclass
class JaxInferenceBackend(JaxTrainBackend):
    """Gradient-free engine for ref/reward models (reference
    PipelinableInferenceEngine, backend/inference.py:25)."""

    def initialize(self, model: Model, spec: FinetuneSpec) -> Model:
        raw = model._raw
        model.module = JaxTrainEngine(
            model_cfg=raw["cfg"],
            # Handed over, not shared: on a mesh the engine reshards
            # them, and a reference kept here would pin the unsharded
            # originals on device 0.
            params=raw.pop("params"),
            mesh=raw["mesh"],
            optimizer_config=None,
            attn_impl=self.attn_impl,
            remat=False,
            row_len_multiple=self.row_len_multiple,
            max_row_len=self.max_row_len,
            hf_family=raw.get("hf_family"),
            prefetch_depth=self.prefetch_depth,
            stats_fetch_interval=self.stats_fetch_interval,
        )
        model.ft_spec = spec
        return model


register_backend("jax_train", JaxTrainBackend)
register_backend("jax_inference", JaxInferenceBackend)


class MockEngine(TrainEngine):
    """Compute-free engine for control-plane tests (reference
    MockTrainEngine, backend/mock_train.py). Deterministic, shape-correct
    outputs with no device work."""

    def __init__(self, seed: int = 0, vocab_size: int = 128):
        self.seed = seed
        self.vocab_size = vocab_size
        self.version = 0
        self.n_train_calls = 0

    def train_batch(self, input_, mb_spec, loss_fn, loss_weight_fn,
                    token_normalize_scope="global", version_steps=0,
                    loss_name="loss", scored_fn=None):
        self.n_train_calls += 1
        self.version += 1
        return {
            f"{loss_name}/loss": 1.0 / self.n_train_calls,
            f"{loss_name}/n_tokens": float(input_.total_seqlen()),
        }

    def forward(self, input_, mb_spec, output_key="logprobs", post_hook=None):
        key = input_._main_key()
        seqlens = input_.seqlens[key]
        total = sum(sum(sl) for sl in seqlens)
        rng = np.random.RandomState(self.seed + total)
        data = rng.uniform(-1, 0, size=(total,)).astype(np.float32)
        return SequenceSample(
            ids=list(input_.ids),
            keys={output_key},
            data={output_key: data},
            seqlens={output_key: [list(sl) for sl in seqlens]},
        )

    def generate(self, input_, mb_spec, tokenizer, gconfig: GenerationHyperparameters):
        key = "packed_prompts" if "packed_prompts" in input_.keys else input_._main_key()
        plens = [sum(sl) for sl in input_.seqlens[key]]
        outs = []
        rng = np.random.RandomState(self.seed + sum(plens))
        for pl in plens:
            for _ in range(gconfig.n):
                glen = int(rng.randint(1, max(2, gconfig.max_new_tokens)))
                outs.append(
                    dict(
                        output_ids=rng.randint(0, self.vocab_size, size=glen).tolist(),
                        output_logprobs=(-rng.uniform(0, 1, size=glen)).astype(np.float32),
                        no_eos=bool(rng.rand() < 0.2),
                    )
                )
        return outs

    def get_params(self):
        return {}

    def set_params(self, params):
        pass


@dataclasses.dataclass
class MockTrainBackend(ModelBackend):
    seed: int = 0
    vocab_size: int = 128

    def initialize(self, model: Model, spec: FinetuneSpec) -> Model:
        model.module = MockEngine(seed=self.seed, vocab_size=self.vocab_size)
        model.ft_spec = spec
        return model


register_backend("mock_train", MockTrainBackend)
register_backend("mock_inference", MockTrainBackend)
