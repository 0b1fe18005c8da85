"""Operations a training step of one chip's share of a stack of delta-rule
mixers and latent attention over sigmoid-routed experts requires (Kimi
Linear's shape: `linear_attn_config` names each layer's mixer), and the
work of the delta rule itself, from shapes and from the program's counts.

`flops_moe.py`'s conventions: forward + backward of every product the
architecture requires, 2 FLOPs a multiply-add, backward twice the forward,
no recomputation, the embedding lookup free, the element-wise work (the
convolutions' taps, norms, decays, gates, the router's sigmoid and top-k)
not counted. By part:

- `kda_proj`: a delta-rule mixer's matrices: hidden -> heads x head_dim
  three times (q, k, v), the decay's and the output gate's low-rank pairs
  (hidden -> head_dim -> heads x head_dim, twice), hidden -> heads (beta),
  heads x head_dim -> hidden;
- `kda_rule`: the recurrence as published, a head a token: the decay of
  the state, `k^T S`, the rank-one update and `S^T q`, 4 K V multiply-adds.
  **The same whatever implements it**: a chunked form does more products
  (`ops/kda.py`: A and P over a chunk, an inverse, W and U), and its share
  of a roofline says so;
- `attn_proj`, `attention`: latent attention's four matrices (a full-rank
  q: `q_lora_rank` null) and QK^T over nope + rope with PV over v, a head,
  over the cells a causal mask leaves within each sequence, in the
  `full_attn_layers`;
- `dense_mlp` (the first `first_k_dense_replace` layers), `router` over
  all routed experts and `shared` in every expert layer; `experts`: a
  SwiGLU of `moe_intermediate_size` for every (token, expert) pair whose
  expert is held here (`train.moe_pairs_held`);
- `head`: the vocabulary slice over the cells the loss head ran
  (`train.head_cells`).

`kda_work` is what the rule's calls take in and give out over
`train.kda_cells` positions (the cells of the chunks it ran, summed over
the delta-rule layers): forward, q, k, v (the activations' bytes), g
(float32) and beta in, o out, and the 4 K V multiply-adds a head;
backward, those and o's cotangent in, five cotangents out, and twice the
multiply-adds.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights (for the rule: multiply-adds) a token passes through, by
    part, summed over the layers."""
    lin = hf["linear_attn_config"]
    n_layers = hf["num_hidden_layers"]
    n_kda, n_full = len(lin["kda_layers"]), len(lin["full_attn_layers"])
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    nope, rope, v = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    rkv = hf["kv_lora_rank"]
    H, K = lin["num_heads"], lin["head_dim"]
    n_dense = hf.get("first_k_dense_replace", 0)
    n_moe = n_layers - n_dense
    routed = hf.get("num_experts_routed", hf["num_experts"])
    width = hf["moe_intermediate_size"]
    kda = 3 * d * H * K + 2 * (d * K + K * H * K) + d * H + H * K * d
    attn = (d * heads * (nope + rope) + d * (rkv + rope) + rkv * heads * (nope + v)
            + heads * v * d)
    return dict(
        kda_proj=n_kda * kda,
        kda_rule=n_kda * 4 * K * K * H,
        attn_proj=n_full * attn,
        attn_dim=n_full * heads * (nope + rope + v),  # multiply-adds a cell, both products
        dense_mlp=n_dense * 3 * d * hf["intermediate_size"],
        shared=n_moe * hf.get("num_shared_experts", 0) * 3 * d * width,
        router=n_moe * d * routed,
        head=d * hf["vocab_size"],
        pair=3 * d * width,  # one (token, expert) pair
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float,
                head_cells: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences;
    `pairs_held` the (token, expert) pairs of held experts summed over the
    expert layers, `head_cells` the cells the loss head ran its logits
    tile over; by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    out = {part: 6.0 * m[part] * tokens
           for part in ("kda_proj", "kda_rule", "attn_proj", "dense_mlp", "router", "shared")}
    out["attention"] = 6.0 * m["attn_dim"] * sum(attention_cells(l) for l in lens)
    out["experts"] = 6.0 * m["pair"] * float(pairs_held)
    out["head"] = 6.0 * m["head"] * float(head_cells)
    out["total"] = sum(out.values())
    return out


def kda_work(hf: Dict, cells: float, calls: int = 1, backward: bool = False,
             act_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes of `calls` runs of the delta rule over `cells`
    positions (summed over the delta-rule layers), forward or backward."""
    lin = hf["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    macs = 4.0 * K * K * H  # a position: decay, k^T S, the update, S^T q
    # q, k, v and o at the activations' bytes, g float32 a channel, beta float32 a head
    once = H * (4 * K * act_bytes + K * 4.0 + 4.0)
    if backward:  # those and o's cotangent in, five cotangents out
        macs, once = 2.0 * macs, 2.0 * once
    return dict(flops=2.0 * macs * cells * calls, bytes=once * cells * calls)
