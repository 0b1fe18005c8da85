"""Operations a training step of a SambaY stack (`model_type` phi4flash)
requires, and the bytes its selective scan must move, from shapes alone.

`flops_hybrid.py`'s conventions: forward + backward of every
multiply-add the architecture requires, 2 FLOPs each, backward twice the
forward, no recomputation, the embedding lookup free, the element-wise
work (convolution, softplus, gates, norms, the differential combine) not
counted. The layers follow from `num_hidden_layers` by the published
rule (`layer_letters`): M a Mamba-1 scan, S / F differential attention
over a window / the whole sequence, G a gated memory unit, X
differential cross-attention. By part:

- `ssm_proj`: a scan layer's four projections: hidden -> [x | z], d_in ->
  [r | B | C], rank -> d_in, d_in -> hidden;
- `ssm_scan`: the recurrence, 3 multiply-adds a state element a token
  (discretise dt (x) A, update the state, read it with C): 3 d_in N;
- `gmu`: a memory unit's two matrices;
- `attn_proj`: Wqkv and the output projection (a cross layer: q and the
  output alone);
- `attention`: both softmaxes, QK^T at the head size and PV at twice it,
  over the cells a causal mask leaves within each sequence, cut at
  `sliding_window` positions in the S layers: (2 hd + 2 x 2 hd) a cell a
  pair of q heads = 3 q_dim a cell;
- `mlp`: three matrices a layer; `head` over the vocabulary slice, once
  a token.

`sscan_bytes`: what the scan must read and write, whatever implements
it: forward x, dt, B, C in and y out; backward those in again with dy,
and dx, ddt, dB, dC out; at the compute dtype, nothing recomputed.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark.flops_moe import attention_cells


def layer_letters(n_layers: int) -> str:
    half = n_layers // 2
    out = []
    for i in range(n_layers):
        if i % 2 == 0:
            out.append("M" if i <= half else "G")
        else:
            out.append("S" if i < half else "F" if i == half + 1 else "X")
    return "".join(out)


def scan_sizes(hf: Dict) -> Dict[str, int]:
    """The scan layer's sizes, Mamba-1's defaults where the config is silent."""
    d = hf["hidden_size"]
    return dict(d_in=int(hf.get("mamba_expand", 2)) * d,
                n=int(hf.get("mamba_d_state", 16)),
                rank=int(hf.get("mamba_dt_rank") or -(-d // 16)))


def matmul_params(hf: Dict) -> Dict[str, float]:
    """Multiply-adds a token passes through, by part, summed over the
    layers of the rule."""
    letters = layer_letters(hf["num_hidden_layers"])
    n = {c: letters.count(c) for c in "MSFGX"}
    d = hf["hidden_size"]
    s = scan_sizes(hf)
    d_in, N, rank = s["d_in"], s["n"], s["rank"]
    hd = d // hf["num_attention_heads"]
    qd, kvd = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    return dict(
        ssm_proj=n["M"] * (d * 2 * d_in + d_in * (rank + 2 * N) + rank * d_in + d_in * d),
        ssm_scan=n["M"] * 3 * d_in * N,
        gmu=n["G"] * 2 * d * d_in,
        attn_proj=(n["S"] + n["F"]) * (d * (qd + 2 * kvd) + qd * d) + n["X"] * 2 * d * qd,
        mlp=len(letters) * 3 * d * hf["intermediate_size"],
        head=d * hf["vocab_size"],
        q_dim=qd, window_layers=n["S"], full_layers=n["F"] + n["X"],
    )


def train_flops(hf: Dict, seqlens: Iterable[int]) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences;
    by part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    m = matmul_params(hf)
    cells = sum(m["window_layers"] * attention_cells(l, hf.get("sliding_window"))
                + m["full_layers"] * attention_cells(l) for l in lens)
    out = {part: 6.0 * m[part] * tokens
           for part in ("ssm_proj", "ssm_scan", "gmu", "attn_proj", "mlp", "head")}
    # a cell a pair of q heads: QK^T twice at hd, PV twice at 2 hd = 3 q_dim
    # multiply-adds over the pairs; x 2 FLOPs x 3 passes
    out["attention"] = 18.0 * m["q_dim"] * cells
    out["total"] = sum(out.values())
    return out


def sscan_bytes(hf: Dict, tokens: float, dtype_bytes: int = 2) -> float:
    """Bytes the scan layers of a training pass over `tokens` positions
    must move between HBM and the chip: a position's x, dt (d_in each), B
    and C (N each) in and y out forward; x, dt, B, C, dy in and dx, ddt,
    dB, dC out backward."""
    s = scan_sizes(hf)
    n_scan = layer_letters(hf["num_hidden_layers"]).count("M")
    a_position = (3 * s["d_in"] + 2 * s["n"]) + (5 * s["d_in"] + 4 * s["n"])
    return float(n_scan * a_position * dtype_bytes) * float(tokens)
