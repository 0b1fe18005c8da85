"""Operations a training step of one chip's share of a sparse-attention
expert model requires (Keye-VL-2.0's language model: GQA attention over
the keys a learned indexer chooses, softmax-routed experts), from shapes
and from the program's counts of the cells its indexers scored and kept,
of the (token, expert) pairs it held and of the cells the loss head ran;
and what each of the indexer's kernels must compute and move.

`flops_moe.py`'s conventions: forward + backward of every matrix
multiplication the architecture requires, 2 FLOPs a multiply-add,
backward twice the forward, no recomputation, the embedding lookups free,
the element-wise work (norms, rotary, the relu and the weighted sum over
the indexer's heads, the threshold's halvings, softmaxes, the router's
top-k) not counted. By part:

- `attn_proj`: q, k, v and the output projection in every layer;
- `index_proj`: the indexer's three projections in every layer. Their
  input is a constant (`stop_gradient`), so where the indexer's loss
  runs the backward pass is the weights' gradient alone: forward + once
  the forward, not twice; without the loss, forward alone;
- `index_scores`: `indexer_num_heads x indexer_head_dim` multiply-adds a
  scored cell (every key of a query's causal prefix) forward; where the
  loss runs, twice that a **chosen** cell backward (the gradient in q
  and in the key; outside the choice it is zero);
- `attention`: QK^T and PV, `head_dim` multiply-adds each a chosen cell
  a q head (`train.index_selected`: dense attention under the choice's
  mask requires the chosen cells and no others), forward + backward;
- `index_kl`: the q heads' scores once more over the chosen cells (the
  probabilities the KL reads, which no attention kernel writes out),
  forward only: they are constants of the loss;
- `router` over all routed experts, `experts`: a SwiGLU of
  `moe_intermediate_size` for every (token, expert) pair whose expert is
  held here (`train.moe_pairs_held`);
- `head`: the vocabulary slice over the cells the loss head ran
  (`train.head_cells`).
"""

from __future__ import annotations

from typing import Dict, Iterable


def sizes(hf: Dict) -> Dict[str, float]:
    """Multiply-adds, by part: `*_token` a token a layer, `*_cell` a cell
    a layer."""
    d, hq, hkv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                      hf["num_key_value_heads"], hf["head_dim"])
    sa = hf["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    routed = hf.get("num_experts_routed", hf["num_experts"])
    return dict(
        layers=hf["num_hidden_layers"],
        attn_proj_token=d * hq * hd * 2 + d * hkv * hd * 2,
        index_proj_token=d * (hi * di + di + hi),
        index_cell=hi * di,  # one product of the indexer over a cell
        attn_cell=hq * hd,  # one product of attention over a cell, all q heads
        router_token=d * routed,
        pair=3 * d * hf["moe_intermediate_size"],
        head=d * hf["vocab_size"],
        # bytes a token a layer: the indexer's q, key and head weights;
        # attention's q and k with its logsumexp a head
        index_token_bytes=2 * (hi * di + di) + 4 * hi,
        attn_token_bytes=2 * (hq + hkv) * hd + 4 * hq,
    )


def train_flops(hf: Dict, tokens: float, cells_scored: float, cells_chosen: float,
                pairs_held: float, head_cells: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass: `tokens` real tokens,
    `cells_scored` / `cells_chosen` the cells the indexers scored and an
    exact choice keeps, `pairs_held` the (token, expert) pairs of held
    experts, each summed over the layers; `head_cells` the cells the loss
    head ran its logits tile over; by part, and `total`."""
    s = sizes(hf)
    loss = float(hf.get("indexer_loss_weight", 1.0)) > 0
    out = dict(
        attn_proj=6.0 * s["attn_proj_token"] * s["layers"] * tokens,
        index_proj=(4.0 if loss else 2.0) * s["index_proj_token"] * s["layers"] * tokens,
        index_scores=2.0 * s["index_cell"] * (
            cells_scored + (2.0 * cells_chosen if loss else 0.0)),
        attention=6.0 * 2 * s["attn_cell"] * cells_chosen,
        index_kl=2.0 * s["attn_cell"] * cells_chosen if loss else 0.0,
        router=6.0 * s["router_token"] * s["layers"] * tokens,
        experts=6.0 * s["pair"] * float(pairs_held),
        head=6.0 * s["head"] * float(head_cells),
    )
    out["total"] = sum(out.values())
    return out


def pool_cells(seqlens: Iterable[int], topk: int):
    """(cells scored, cells an exact choice keeps) of one layer over these
    sequences: `sum L (L + 1) / 2` and `sum_t min(t + 1, topk)`."""
    scored = kept = 0.0
    for l in seqlens:
        l = int(l)
        scored += l * (l + 1) / 2
        kept += l * (l + 1) / 2 if l <= topk else topk * (topk + 1) / 2 + (l - topk) * topk
    return scored, kept


# What each of the indexer's kernels must compute and move for a training
# pass (operations on the matrix unit, bytes between HBM and the chip),
# from the program's counters, all layers: `tokens` x layers rows of
# operands, `scored` and `chosen` cells. A kernel that a step under full
# remat calls twice a layer (the forward pass and remat's) does the work
# twice: `calls`.


def index_select_work(hf, tokens, scored, chosen, calls=1):
    """Scores over every scored cell; in: the indexer's q, key and
    weights; out: a byte a scored cell (the choice) and three numbers a
    query."""
    s = sizes(hf)
    return dict(flops=calls * 2.0 * s["index_cell"] * scored,
                bytes=calls * (s["layers"] * tokens * (s["index_token_bytes"] + 12)
                               + scored))


def index_kl_fwd_work(hf, tokens, scored, chosen, calls=1):
    """The q heads' scores and the indexer's over the chosen cells; in:
    q, k, the logsumexp, the indexer's operands, a byte a chosen cell."""
    s = sizes(hf)
    return dict(flops=calls * 2.0 * (s["attn_cell"] + s["index_cell"]) * chosen,
                bytes=calls * (s["layers"] * tokens
                               * (s["attn_token_bytes"] + s["index_token_bytes"] + 4)
                               + chosen))


def index_kl_bwd_work(hf, tokens, scored, chosen, calls=1):
    """`index_kl_fwd`'s products again (nothing of them is kept) and the
    gradient's two over the chosen cells; out: float32 gradients of the
    indexer's q, key and weights."""
    s = sizes(hf)
    fwd = index_kl_fwd_work(hf, tokens, scored, chosen, calls)
    return dict(flops=fwd["flops"] + calls * 2.0 * 2 * s["index_cell"] * chosen,
                bytes=fwd["bytes"] + calls * s["layers"] * tokens
                * 2 * s["index_token_bytes"])
