"""The serving engine's warm hook: compile the serving programs through
the live loop. Backs `warm_on_start` serving pods."""

import pytest

jax = pytest.importorskip("jax")

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import init_params


def _tiny_cfg():
    return TransformerConfig(
        n_layers=2, hidden_dim=64, n_q_heads=4, n_kv_heads=2, head_dim=16,
        intermediate_dim=128, vocab_size=256, compute_dtype="float32",
    )


def test_serving_warm_compiles_then_serves():
    import threading

    from areal_tpu.engine.serving import GenRequest, ServingEngine

    cfg = _tiny_cfg()
    eng = ServingEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(1)),
        max_batch_size=2, max_seq_len=128, decode_block_steps=4,
        prompt_bucket=8, page_size=8, eos_token_id=None,
        kv_pool_tokens=2 * 128,
    )
    eng.start()
    try:
        dt = eng.warm([8, 16])
        assert dt > 0.0
        done = threading.Event()
        out = []
        eng.submit(GenRequest(
            qid="q0", input_ids=[1] * 8, max_new_tokens=8, greedy=True,
            done_cb=lambda r: (out.append(r), done.set()),
        ))
        assert done.wait(60)
        assert len(out[0].output_ids) == 8
    finally:
        eng.stop()


def test_serving_warm_requires_start():
    from areal_tpu.engine.serving import ServingEngine

    cfg = _tiny_cfg()
    eng = ServingEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(1)),
        max_batch_size=2, max_seq_len=64, decode_block_steps=4,
        prompt_bucket=8, page_size=8, kv_pool_tokens=128,
    )
    with pytest.raises(AssertionError):
        eng.warm([8])
