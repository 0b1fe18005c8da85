"""Operations a training step of one chip's share of a hybrid stack
requires: layers of one part each, named by the letters of
`hybrid_override_pattern` (M a state-space mixer, * attention, E an
expert layer, - a dense MLP), from shapes and from the program's count of
the (token, expert) pairs it held.

`flops_moe.py`'s conventions: forward + backward of every matrix
multiplication the architecture requires, 2 FLOPs a multiply-add,
backward twice the forward, no recomputation, the embedding lookup free,
the element-wise work (convolution, decays, gates, norms, the router's
sigmoid and top-k) not counted. By part:

- `ssm_proj`: a state-space layer's two projections, hidden -> [z | xBC |
  dt] and d_in -> hidden;
- `ssm_scan`: the chunked form of its recurrence, a token: within its
  chunk, under the causal mask (a token and the (Q + 1) / 2 cells up to
  it, on average), C B^T over the groups' states (2 G N a cell) and the
  weighted sum of the inputs (2 H P a cell); between chunks, building a
  chunk's state from its tokens and reading the incoming state (2 H P N
  each);
- `attn_proj`, `attention`: the q, k, v and output projections; QK^T and
  PV over the cells a causal mask leaves within each sequence;
- `router` over all routed experts, `shared` (the shared expert, two
  matrices), `experts` (two matrices of width `moe_intermediate_size`
  for every (token, expert) pair whose expert is held here: a count only
  the program has, `train.moe_pairs_held`), `dense_mlp` (the `-` layers);
- `head` over the vocabulary slice, once a token.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights (or, for the scan, multiply-adds) a token passes through,
    by part, summed over the layers of the pattern."""
    pattern = hf["hybrid_override_pattern"]
    n = {letter: pattern.count(letter) for letter in "M*E-"}
    d = hf["hidden_size"]
    H, P, G, N = (hf["mamba_num_heads"], hf["mamba_head_dim"], hf["n_groups"],
                  hf["ssm_state_size"])
    d_in, Q = H * P, hf["chunk_size"]
    qd = hf["num_attention_heads"] * hf["head_dim"]
    kvd = hf["num_key_value_heads"] * hf["head_dim"]
    routed = hf.get("num_experts_routed", hf["n_routed_experts"])
    shared = hf.get("n_shared_experts", 0) and hf.get(
        "moe_shared_expert_intermediate_size",
        hf["moe_intermediate_size"] * hf.get("n_shared_experts", 0))
    return dict(
        ssm_proj=n["M"] * (d * (2 * d_in + 2 * G * N + H) + d_in * d),
        ssm_scan=n["M"] * ((Q + 1) / 2 * (G * N + H * P) + 2 * H * P * N),
        attn_proj=n["*"] * (d * (qd + 2 * kvd) + qd * d),
        router=n["E"] * d * routed,
        shared=n["E"] * 2 * d * shared,
        dense_mlp=n["-"] * 2 * d * hf["intermediate_size"],
        head=d * hf["vocab_size"],
        pair=2 * d * hf["moe_intermediate_size"],  # one (token, expert) pair
        q_dim=qd, attn_layers=n["*"],
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences,
    `pairs_held` being the (token, expert) pairs of held experts summed
    over the expert layers; by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    cells = m["attn_layers"] * sum(attention_cells(l) for l in lens)
    out = {part: 6.0 * m[part] * tokens
           for part in ("ssm_proj", "ssm_scan", "attn_proj", "router", "shared",
                        "dense_mlp", "head")}
    out["attention"] = 12.0 * m["q_dim"] * cells  # 2 matmuls x 2 FLOPs x 3 passes
    out["experts"] = 6.0 * m["pair"] * float(pairs_held)
    out["total"] = sum(out.values())
    return out
