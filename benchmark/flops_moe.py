"""Operations a training step of one chip's share of an expert model
requires, from shapes and from the program's count of the pairs it held.

`flops.py` counts a dense block repeated under a full causal mask: `3 x
hidden x intermediate_size` in every layer. A stack of layer kinds with a
share of the experts needs its own count (the same conventions: forward
+ backward of every matrix multiplication the architecture requires, 2
FLOPs a multiply-add, backward twice the forward, no recomputation, the
embedding lookup free):

- every layer: the q, k, v, output-gate and output projections;
- attention: QK^T and PV over the cells each layer's mask requires
  within a sequence: causal, and on a sliding layer only the `window`
  positions that end at the token;
- a dense layer (the first `num_dense_layers`): the SwiGLU of width
  `intermediate_size`;
- an expert layer: the router over all routed experts, the shared
  expert, and a SwiGLU of width `moe_intermediate_size` for every
  (token, expert) pair whose expert is held on this chip: a count only
  the program has (`train.moe_pairs_held`), since routing decides it;
- the head over the vocabulary slice, once.
"""

from __future__ import annotations

from typing import Dict, Iterable


def attention_cells(seq_len: int, window=None) -> float:
    """(query, key) pairs a causal mask leaves one sequence, with a
    window: each query sees itself and up to window - 1 before it."""
    l = float(seq_len)
    if window is None or l <= window:
        return l * (l + 1) / 2
    w = float(window)
    return w * (w + 1) / 2 + (l - w) * w


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights a token passes through in a matmul, by part."""
    d, heads, hd = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    qd, kvd = heads * hd, hf["num_key_value_heads"] * hd
    n_dense = hf["num_dense_layers"]
    n_moe = hf["num_hidden_layers"] - n_dense
    routed = hf.get("num_experts_routed", hf["num_experts"])
    return dict(
        attn_proj=hf["num_hidden_layers"] * (d * (2 * qd + 2 * kvd) + qd * d),
        dense_mlp=n_dense * 3 * d * hf["intermediate_size"],
        shared=n_moe * hf["num_shared_experts"] * 3 * d * hf["moe_intermediate_size"],
        router=n_moe * d * routed,
        head=d * hf["vocab_size"],
        pair=3 * d * hf["moe_intermediate_size"],  # one (token, expert) pair
        q_dim=qd,
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences,
    `pairs_held` being the (token, expert) pairs of held experts summed
    over the expert layers; by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    windows = [hf["sliding_window"] if t == "sliding_attention" else None
               for t in hf["layer_types"]]
    cells = sum(attention_cells(l, w) for w in windows for l in lens)
    out = dict(
        attn_proj=6.0 * m["attn_proj"] * tokens,
        attention=12.0 * m["q_dim"] * cells,  # 2 matmuls x 2 FLOPs x 3 passes
        dense_mlp=6.0 * m["dense_mlp"] * tokens,
        shared=6.0 * m["shared"] * tokens,
        router=6.0 * m["router"] * tokens,
        experts=6.0 * m["pair"] * float(pairs_held),
        head=6.0 * m["head"] * tokens,
    )
    out["total"] = sum(out.values())
    return out
