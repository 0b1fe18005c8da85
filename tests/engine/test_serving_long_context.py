"""Long-context serving pin: a >=16k-token sequence through the paged
engine with a reduced KV pool (VERDICT r3 missing #4 — the reference's
headline workload generates ~31k-token sequences,
benchmark/verl_v0_3_0_post1_76084d3/README.md:38-44; this CPU test keeps
the >=16k path from rotting; no cell measures it on the chip yet:
PERF.md section 7)."""

import threading

import jax
import numpy as np
import pytest

from areal_tpu.engine.serving import GenRequest, ServingEngine
from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import init_params

PLEN = 16256
MAX_NEW = 64
PAGE = 128


@pytest.mark.slow
@pytest.mark.parametrize("prefill_chunk", [2048, None])
def test_serving_16k_context_reduced_pool(prefill_chunk):
    cfg = TransformerConfig(
        n_layers=1,
        hidden_dim=32,
        n_q_heads=1,
        n_kv_heads=1,
        head_dim=16,
        intermediate_dim=64,
        vocab_size=128,
        max_position_embeddings=32768,
        compute_dtype="float32",
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    # Pool sized to barely one 16k request (plus block headroom): total
    # context must run inside a REDUCED pool, exercising the token-budget
    # accounting at long-context scale rather than a B*S-sized pool.
    eng = ServingEngine(
        cfg,
        params,
        max_batch_size=2,
        max_seq_len=PLEN + MAX_NEW + PAGE,
        decode_block_steps=16,
        prompt_bucket=PAGE,
        eos_token_id=None,
        page_size=PAGE,
        kv_pool_tokens=PLEN + MAX_NEW + 2 * PAGE,
        # Both long-context paths stay pinned: fixed-shape chunked
        # prefill (the recommended one — one compile for any prompt
        # length) and the batched bucketed path (still the default).
        prefill_chunk=prefill_chunk,
    )
    eng.start()
    try:
        rng = np.random.RandomState(0)
        done = threading.Event()
        res_holder = {}

        def cb(res):
            res_holder["res"] = res
            done.set()

        eng.submit(
            GenRequest(
                qid="long0",
                input_ids=rng.randint(0, cfg.vocab_size, size=PLEN).tolist(),
                max_new_tokens=MAX_NEW,
                done_cb=cb,
            )
        )
        assert done.wait(900), "16k-context generation stalled"
        res = res_holder["res"]
        assert len(res.output_ids) == MAX_NEW
        assert PLEN + len(res.output_ids) >= 16000  # >=16k total context
    finally:
        eng.stop()
