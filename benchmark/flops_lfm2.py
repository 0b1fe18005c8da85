"""Operations a training step of one chip's share of LFM2-8B-A1B requires
(gated short convolutions and GQA attention layers, a dense MLP in the
leading layers, sigmoid-routed experts in the rest, the head tied to the
embedding), from shapes, from the window's sequence lengths and from the
program's counts of the (token, expert) pairs it held and of the cells the
loss head ran.

`flops_moe.py`'s conventions: forward + backward of every matrix
multiplication the architecture requires, 2 FLOPs a multiply-add, backward
twice the forward, no recomputation, the embedding lookup free, the
element-wise work (norms, the two gates and the three taps of a conv
mixer, the rotation, softmaxes, the router's top-k) not counted. The held
share's work and nothing of the absent chip's: the experts by the pairs
whose expert is here, the head over the slice. By part:

- `conv_proj`: a conv layer's `in_proj` (hidden -> 3 x hidden) and
  `out_proj`;
- `attn_proj`: q, k, v and the output projection of an attention layer;
- `attention`: QK^T and PV over the cells a causal mask requires within a
  sequence, `head_dim` multiply-adds each a cell a q head, summed over the
  attention layers;
- `dense_mlp`: the SwiGLU of `intermediate_size` in the leading layers;
- `router` over all routed experts, `experts`: a SwiGLU of
  `moe_intermediate_size` for every (token, expert) pair whose expert is
  held here (`train.moe_pairs_held`);
- `head`: the vocabulary slice over the cells the loss head ran
  (`train.head_cells`).

`conv_work` is the gated convolution alone (what a kernel in its place
would be held to): bytes in and out of HBM and element-wise operations a
cell.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells

CONV = "conv"


def layer_counts(hf: Dict):
    """(conv layers, attention layers, dense layers, expert layers)."""
    types = hf["layer_types"][: hf["num_hidden_layers"]]
    n_conv = sum(t == CONV for t in types)
    n_dense = min(hf.get("num_dense_layers", 0), len(types))
    return n_conv, len(types) - n_conv, n_dense, len(types) - n_dense


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights a token passes through in a matmul, by part, summed over
    the layers; `attn_dim`: multiply-adds a cell an attention layer, both
    products."""
    d, heads, kv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or d // heads
    n_conv, n_attn, n_dense, n_moe = layer_counts(hf)
    routed = hf.get("num_experts_routed", hf["num_experts"])
    return dict(
        conv_proj=n_conv * (d * 3 * d + d * d),
        attn_proj=n_attn * (d * (heads + 2 * kv) * hd + heads * hd * d),
        attn_dim=n_attn * heads * 2 * hd,
        dense_mlp=n_dense * 3 * d * hf["intermediate_size"],
        router=n_moe * d * routed,
        head=d * hf["vocab_size"],
        pair=3 * d * hf["moe_intermediate_size"],  # one (token, expert) pair
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float,
                head_cells: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences;
    `pairs_held` the (token, expert) pairs of held experts summed over the
    expert layers, `head_cells` the cells the loss head ran its logits tile
    over; by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    out = dict(
        conv_proj=6.0 * m["conv_proj"] * tokens,
        attn_proj=6.0 * m["attn_proj"] * tokens,
        attention=6.0 * m["attn_dim"] * sum(attention_cells(l) for l in lens),
        dense_mlp=6.0 * m["dense_mlp"] * tokens,
        router=6.0 * m["router"] * tokens,
        experts=6.0 * m["pair"] * float(pairs_held),
        head=6.0 * m["head"] * float(head_cells),
    )
    out["total"] = sum(out.values())
    return out


def conv_work(hidden: int, taps: int, cells: int, itemsize: int = 2) -> Dict[str, float]:
    """The gated convolution `C * conv(B * x)` over `cells` cells of
    `hidden` channels: the forward reads `[B | C | x]` and writes the
    product (`3 + 1` values a channel a cell), the backward reads them and
    the product's cotangent and writes `[dB | dC | dx]` (`3 + 1 + 3`; the
    taps' own gradient is `taps x hidden` values, nothing); multiplies and
    adds, forward: the first gate, `taps` multiply-adds, the second gate."""
    values = float(cells) * hidden
    return dict(
        fwd_bytes=4.0 * values * itemsize,
        bwd_bytes=7.0 * values * itemsize,
        fwd_flops=(2.0 + 2.0 * taps) * values,
        bwd_flops=(6.0 + 6.0 * taps) * values,
    )
