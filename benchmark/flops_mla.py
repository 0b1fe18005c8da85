"""Operations a training step of one chip's share of a latent-attention
expert model with a prediction module requires (a stack of DeepSeek-V3's
shape), from shapes and from the program's counts of the (token, expert)
pairs it held and of the cells each run of the loss head ran.

`flops_moe.py`'s conventions: forward + backward of every matrix
multiplication the architecture requires, 2 FLOPs a multiply-add,
backward twice the forward, no recomputation, the embedding lookups free,
the element-wise work (norms, rotary, the router's sigmoid and top-k)
not counted. By part:

- `attn_proj`: latent attention's five matrices in every layer of the
  stack: hidden -> q_lora_rank -> heads x (nope + rope), hidden ->
  kv_lora_rank + rope, kv_lora_rank -> heads x (nope + v), heads x v ->
  hidden;
- `attention`: QK^T over nope + rope and PV over v, a head, over the
  cells a causal mask leaves within each sequence, in every layer of the
  stack (the materialised form: k and v a head);
- `dense_mlp` (the first `first_k_dense_replace` layers), `router` over
  all routed experts and `shared` in every expert layer of the stack;
- `experts`: a SwiGLU of `moe_intermediate_size` for every (token,
  expert) pair whose expert is held here, **the prediction module's
  expert layer included**: the program counts them together
  (`train.moe_pairs_held`);
- `mtp`: the rest of the prediction module: hidden x 2 -> hidden, one
  more layer's `attn_proj`, `attention`, `router` and `shared`;
- `head`: the vocabulary slice over **the cells each run of the head
  ran** (`train.head_cells`, and `train.mtp_head_cells` at two thirds:
  in the module's run the head's weight is a constant and only the
  hidden state's gradient is required), not once a token: since PR 31
  the head runs only chunks that hold a scored position, and what it
  skips is no work the step requires.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights a token passes through in a matmul, by part; `layer_*` are
    one layer's."""
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    nope, rope, v = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rq, rkv = hf["q_lora_rank"], hf["kv_lora_rank"]
    n_dense = hf.get("first_k_dense_replace", 0)
    n_moe = hf["num_hidden_layers"] - n_dense
    routed = hf.get("num_experts_routed", hf["n_routed_experts"])
    width = hf["moe_intermediate_size"]
    attn = (d * rq + rq * heads * (nope + rope) + d * (rkv + rope)
            + rkv * heads * (nope + v) + heads * v * d)
    shared = hf.get("n_shared_experts", 0) * 3 * d * width
    return dict(
        layer_attn=attn, layer_shared=shared, layer_router=d * routed,
        attn_proj=hf["num_hidden_layers"] * attn,
        dense_mlp=n_dense * 3 * d * hf["intermediate_size"],
        shared=n_moe * shared,
        router=n_moe * d * routed,
        head=d * hf["vocab_size"],
        pair=3 * d * width,  # one (token, expert) pair
        attn_dim=heads * (nope + rope + v),  # multiply-adds a cell, both products
        mtp=hf.get("num_nextn_predict_layers", 0),
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float,
                head_cells: float, mtp_head_cells: float = 0.0) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences;
    `pairs_held` the (token, expert) pairs of held experts summed over
    every expert layer that ran, `head_cells` and `mtp_head_cells` the
    cells the loss head ran its logits tile over for the model and for
    the prediction module; by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    cells = sum(attention_cells(l) for l in lens)  # one layer's
    out = dict(
        attn_proj=6.0 * m["attn_proj"] * tokens,
        attention=6.0 * m["attn_dim"] * hf["num_hidden_layers"] * cells,
        dense_mlp=6.0 * m["dense_mlp"] * tokens,
        shared=6.0 * m["shared"] * tokens,
        router=6.0 * m["router"] * tokens,
        experts=6.0 * m["pair"] * float(pairs_held),
        mtp=m["mtp"] * 6.0 * (
            (2 * hf["hidden_size"] ** 2 + m["layer_attn"] + m["layer_router"]
             + m["layer_shared"]) * tokens + m["attn_dim"] * cells),
        head=m["head"] * (6.0 * float(head_cells) + 4.0 * float(mtp_head_cells)),
    )
    out["total"] = sum(out.values())
    return out
