"""Operations a training step of one chip's share of Mellum 2 requires
(GQA attention, window layers and full layers each under a rotary table
of its own, softmax-routed experts in every layer), from shapes, from the
window's sequence lengths and from the program's counts of the (token,
expert) pairs it held and of the cells the loss head ran.

`flops_moe.py`'s conventions: forward + backward of every matrix
multiplication the architecture requires, 2 FLOPs a multiply-add,
backward twice the forward, no recomputation, the embedding lookup free,
the element-wise work (norms, the rotation by either table, softmaxes, the
router's top-k) not counted. By part:

- `attn_proj`: q, k, v and the output projection in every layer;
- `attention_window`: QK^T and PV over the cells a window layer's mask
  requires within a sequence (`flops_moe.attention_cells`: a query sees
  itself and the `sliding_window - 1` before it), `head_dim`
  multiply-adds each a cell a q head, summed over the window layers;
- `attention_full`: the same over a causal mask alone, the full layers;
- `router` over all routed experts, `experts`: a SwiGLU of
  `moe_intermediate_size` for every (token, expert) pair whose expert is
  held here (`train.moe_pairs_held`);
- `head`: the vocabulary slice over the cells the loss head ran
  (`train.head_cells`).
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells

SLIDING = "sliding_attention"


def layer_counts(hf: Dict):
    """(window layers, full layers)."""
    n_window = sum(t == SLIDING for t in hf["layer_types"])
    return n_window, len(hf["layer_types"]) - n_window


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights a token passes through in a matmul, by part, summed over
    the layers; `attn_dim`: multiply-adds a cell a layer, both products."""
    d, heads, kv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                        hf["num_key_value_heads"], hf["head_dim"])
    n = hf["num_hidden_layers"]
    routed = hf.get("num_experts_routed", hf["num_experts"])
    return dict(
        attn_proj=n * (d * (heads + 2 * kv) * hd + heads * hd * d),
        attn_dim=heads * 2 * hd,
        router=n * d * routed,
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
    n_window, n_full = layer_counts(hf)
    out = dict(
        attn_proj=6.0 * m["attn_proj"] * tokens,
        attention_window=6.0 * m["attn_dim"] * n_window * sum(
            attention_cells(l, hf["sliding_window"]) for l in lens),
        attention_full=6.0 * m["attn_dim"] * n_full * sum(attention_cells(l) for l in lens),
        router=6.0 * m["router"] * tokens,
        experts=6.0 * m["pair"] * float(pairs_held),
        head=6.0 * m["head"] * float(head_cells),
    )
    out["total"] = sum(out.values())
    return out
