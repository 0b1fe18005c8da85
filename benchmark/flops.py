"""Operations a training step requires, from shapes alone.

The yardstick's own arithmetic (the idea is `areal_tpu/bench/workloads.py
::train_step_flops`; that one counts the embedding table as a matmul and
takes a parameter count from outside). Model FLOPs, as a utilisation is
defined: forward + backward of every matrix multiplication the
architecture requires, 2 FLOPs a multiply-add, backward twice the
forward. The output head counts once as a matmul (tied or not: the
lookup at the input is a gather and costs none). Attention counts what a
causal mask requires within each sequence, not what a kernel executes
over a packed row. Recomputation (remat) is not counted.
"""

from __future__ import annotations

from typing import Dict, Iterable


def matmul_params(hf: Dict) -> Dict[str, int]:
    """Weights that take part in a matmul, per layer and in the head."""
    d, f, v = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    heads = hf["num_attention_heads"]
    hd = hf.get("head_dim") or d // heads
    qd = heads * hd
    kvd = hf.get("num_key_value_heads", heads) * hd
    per_layer = d * (qd + 2 * kvd) + qd * d + 3 * d * f
    return dict(per_layer=per_layer, head=d * v, q_dim=qd,
                layers=hf["num_hidden_layers"])


def train_flops(hf: Dict, seqlens: Iterable[int]) -> float:
    """Forward + backward model FLOPs of one pass over these sequences."""
    lens = [float(l) for l in seqlens]
    return train_flops_from_sums(hf, sum(lens), sum(l * l for l in lens))


def train_flops_from_sums(hf: Dict, tokens: float, sum_len_sq: float) -> float:
    """The same, from sum(l) and sum(l^2) over the sequences trained."""
    m = matmul_params(hf)
    dense = m["layers"] * m["per_layer"] + m["head"]
    # Attention: QK^T and AV are 2 matmuls x 2 FLOPs x l^2 x q_dim, half of
    # it under a causal mask; x3 for forward + backward -> 6 L q_dim l^2.
    return 6.0 * dense * tokens + 6.0 * m["layers"] * m["q_dim"] * sum_len_sq
