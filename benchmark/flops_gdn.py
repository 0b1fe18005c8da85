"""Operations a training step of one chip's share of a stack of Gated
DeltaNet mixers and gated attention over softmax-routed experts requires
(Qwen3-Next's shape: layer `i` is attention when `(i + 1) %
full_attention_interval == 0`), and the work of the delta rule itself,
from shapes and from the program's counts.

`flops_moe.py`'s conventions: forward + backward of every product the
architecture requires, 2 FLOPs a multiply-add, backward twice the forward,
no recomputation, the embedding lookup free, the element-wise work (the
convolution's taps, norms, decays, gates, the router's softmax and top-k)
not counted. By part:

- `gdn_proj`: a DeltaNet mixer's matrices: hidden -> key heads x head_dim
  twice (q, k), hidden -> value heads x head_dim twice (v and the gate z),
  hidden -> value heads twice (beta and the decay's column), value heads x
  head_dim -> hidden;
- `gdn_rule`: the recurrence as published, a value head a token: the decay
  of the state, `S^T k`, the rank-one update and `S^T q`, 4 K V
  multiply-adds. **The same whatever implements it**: a chunked form does
  more products (`ops/kda.py`: K K^T and Q K^T over a chunk, an inverse, W
  and U), and its share of a roofline says so;
- `attn_proj`, `attention`: the attention layers' matrices (q with its
  gate: 2 x heads x head_dim; k, v; the output) and QK^T with PV, a query
  head, over the cells a causal mask leaves within each sequence;
- `router` over all routed experts, `shared` (the shared SwiGLU of
  `shared_expert_intermediate_size` and its gate's column) in every layer;
  `experts`: a SwiGLU of `moe_intermediate_size` for every (token, expert)
  pair whose expert is held here (`train.moe_pairs_held`);
- `head`: the vocabulary slice over the cells the loss head ran
  (`train.head_cells`).

`gdn_work` is what the rule's calls take in and give out over
`train.kda_cells` positions (the cells of the chunks it ran, summed over
the DeltaNet layers): forward, q and k once a key head and v a value head
(the activations' bytes), g and beta (float32, one a value head) in, o
out, and the 4 K V multiply-adds a value head; backward, those and o's
cotangent in, five cotangents out, and twice the multiply-adds.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells


def layer_counts(hf: Dict):
    """(DeltaNet layers, attention layers)."""
    n = hf["num_hidden_layers"]
    n_full = sum((i + 1) % hf["full_attention_interval"] == 0 for i in range(n))
    return n - n_full, n_full


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights (for the rule: multiply-adds) a token passes through, by
    part, summed over the layers."""
    n_gdn, n_full = layer_counts(hf)
    n_layers = n_gdn + n_full
    d, heads, kv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                        hf["num_key_value_heads"], hf["head_dim"])
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    K, V = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    routed = hf.get("num_experts_routed", hf["num_experts"])
    gdn = d * (2 * Hk * K + 2 * Hv * V) + d * 2 * Hv + Hv * V * d
    attn = d * 2 * heads * hd + 2 * d * kv * hd + heads * hd * d
    return dict(
        gdn_proj=n_gdn * gdn,
        gdn_rule=n_gdn * 4 * K * V * Hv,
        attn_proj=n_full * attn,
        attn_dim=n_full * heads * 2 * hd,  # multiply-adds a cell, both products
        shared=n_layers * (3 * d * hf["shared_expert_intermediate_size"] + d),
        router=n_layers * d * routed,
        head=d * hf["vocab_size"],
        pair=3 * d * hf["moe_intermediate_size"],  # one (token, expert) pair
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float,
                head_cells: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences;
    `pairs_held` the (token, expert) pairs of held experts summed over the
    layers, `head_cells` the cells the loss head ran its logits tile over;
    by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    out = {part: 6.0 * m[part] * tokens
           for part in ("gdn_proj", "gdn_rule", "attn_proj", "router", "shared")}
    out["attention"] = 6.0 * m["attn_dim"] * sum(attention_cells(l) for l in lens)
    out["experts"] = 6.0 * m["pair"] * float(pairs_held)
    out["head"] = 6.0 * m["head"] * float(head_cells)
    out["total"] = sum(out.values())
    return out


def gdn_work(hf: Dict, cells: float, calls: int = 1, backward: bool = False,
             act_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes of `calls` runs of the delta rule over `cells`
    positions (summed over the DeltaNet layers), forward or backward."""
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    K, V = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    macs = 4.0 * K * V * Hv  # a position: decay, S^T k, the update, S^T q
    # q, k once a key head, v and o a value head, g and beta float32 a value head
    once = (2 * Hk * K + 2 * Hv * V) * act_bytes + 2 * Hv * 4.0
    if backward:  # those and o's cotangent in, five cotangents out
        macs, once = 2.0 * macs, 2.0 * once
    return dict(flops=2.0 * macs * cells * calls, bytes=once * cells * calls)
