"""Operations a training step of one chip's share of Olmo-Hybrid requires
(Gated DeltaNet mixers with a state of `linear_key_head_dim x
linear_value_head_dim` a head and plain attention layers, `layer_types`
saying which, every layer under a dense SwiGLU), and the work of the delta
rule's kernels, from shapes and from the program's counts.

`flops_moe.py`'s conventions: forward + backward of every product the
architecture requires, 2 FLOPs a multiply-add, backward twice the forward,
no recomputation, the embedding lookup free, the element-wise work (the
convolution's taps, norms, decays, gates) not counted. By part:

- `gdn_proj`: a DeltaNet mixer's matrices: hidden -> key heads x K twice (q,
  k), hidden -> value heads x V twice (v and the gate), hidden -> value heads
  twice (beta and the decay's column), value heads x V -> hidden;
- `gdn_rule`: the recurrence as published, a value head a token: the decay
  of the state, `S^T k`, the rank-one update and `S^T q`, 4 K V multiply-adds
  at the rectangle's own K and V. **The same whatever implements it**;
- `attn_proj`: q, k, v and the output projection of the attention layers;
  `attention`: QK^T and PV a query head over the cells a causal mask leaves
  within each sequence;
- `mlp`: the dense SwiGLU of `intermediate_size`, three matrices, every layer;
- `head`: the vocabulary slice over the cells the loss head ran
  (`train.head_cells`).

`rule_work` is what the rule's kernels take in and give out over
`train.kda_cells` positions (the cells of the chunks they ran, summed over
the DeltaNet layers): forward, q and k once a key head **at the width they
cross HBM at** (a key head of 96 stands widened to 128 lanes with zeros,
`areal_tpu/ops/kda.key_lanes`: `KEY_LANES`' rule, restated here; the zeros are
bytes the kernels read and are counted), v a value head, the decay's input
(the activations' bytes) and beta (float32) one a value head in, o out, and
the 4 K V multiply-adds at the keys' own K; backward, those and o's
cotangent in, five cotangents out, and twice the multiply-adds. `taps_work`:
the taps' kernels read an array once and write it once forward (q, k at their
widened width, and v), and backward read the array and the output's cotangent
and write the array's.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells

LINEAR = "linear_attention"


def key_lanes(K: int) -> int:
    """`areal_tpu/ops/kda.key_lanes`, restated (the benchmark imports nothing
    of the program's): the next whole tile of 128 lanes where the zeros up to
    it are at most a third more, else K."""
    full = -(-K // 128) * 128
    return full if 4 * K >= 3 * full else K


def layer_counts(hf: Dict):
    """(DeltaNet layers, attention layers)."""
    types = hf["layer_types"][: hf["num_hidden_layers"]]
    n_gdn = sum(t == LINEAR for t in types)
    return n_gdn, len(types) - n_gdn


def head_dim(hf: Dict) -> int:
    return hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Weights (for the rule: multiply-adds) a token passes through, by
    part, summed over the layers."""
    n_gdn, n_full = layer_counts(hf)
    d, heads, kv, hd = (hf["hidden_size"], hf["num_attention_heads"],
                        hf["num_key_value_heads"], head_dim(hf))
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    K, V = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    gdn = d * (2 * Hk * K + 2 * Hv * V) + d * 2 * Hv + Hv * V * d
    attn = d * (heads + 2 * kv) * hd + heads * hd * d
    return dict(
        gdn_proj=n_gdn * gdn,
        gdn_rule=n_gdn * 4 * K * V * Hv,
        attn_proj=n_full * attn,
        attn_dim=n_full * heads * 2 * hd,  # multiply-adds a cell, both products
        mlp=(n_gdn + n_full) * 3 * d * hf["intermediate_size"],
        head=d * hf["vocab_size"],
    )


def train_flops(hf: Dict, seqlens: Iterable[int], head_cells: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences;
    `head_cells` the cells the loss head ran its logits tile over; by part,
    and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    out = {part: 6.0 * m[part] * tokens for part in ("gdn_proj", "gdn_rule", "attn_proj", "mlp")}
    out["attention"] = 6.0 * m["attn_dim"] * sum(attention_cells(l) for l in lens)
    out["head"] = 6.0 * m["head"] * float(head_cells)
    out["total"] = sum(out.values())
    return out


def rule_work(hf: Dict, cells: float, calls: int = 1, backward: bool = False,
              act_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes of `calls` runs of the delta rule's kernel over `cells`
    positions (summed over the DeltaNet layers), forward or backward."""
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    K, V = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    macs = 4.0 * K * V * Hv  # a position: decay, S^T k, the update, S^T q
    # q, k a key head as they cross HBM, v and o a value head, the decay's input
    # (an activation) and beta (float32) one a value head
    once = (2 * Hk * key_lanes(K) + 2 * Hv * V + Hv) * act_bytes + Hv * 4.0
    if backward:  # those and o's cotangent in, five cotangents out
        macs, once = 2.0 * macs, 2.0 * once
    return dict(flops=2.0 * macs * cells * calls, bytes=once * cells * calls)


def taps_work(hf: Dict, cells: float, calls: int = 1, backward: bool = False,
              act_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes of `calls` runs of the taps' kernels over `cells`
    positions (rows x row length x DeltaNet layers: q's, k's and v's calls
    together): a multiply-add a tap a channel; an array in and out forward,
    the array and the cotangent in and the array's cotangent out backward."""
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    width = 2 * Hk * key_lanes(hf["linear_key_head_dim"]) + Hv * hf["linear_value_head_dim"]
    taps = hf["linear_conv_kernel_dim"]
    macs, moved = width * taps, 2 * width * act_bytes
    if backward:  # the taps again, the input's cotangent and the weights' sums
        macs, moved = 3.0 * macs, 3 * width * act_bytes
    return dict(flops=2.0 * macs * cells * calls, bytes=float(moved) * cells * calls)
