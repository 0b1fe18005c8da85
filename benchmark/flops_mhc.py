"""Operations a training step of one chip's share of a latent-attention
expert model over several residual streams requires (a stack of
DeepSeek-V3's shape under manifold-constrained hyper-connections), and
the work of the two stream kernels, from shapes and from the program's
counts.

`flops_mla.py`'s conventions and its parts but `mtp` (`attn_proj`,
`attention`, `dense_mlp`, `router`, `shared`, `experts` from
`train.moe_pairs_held`, `head` over `train.head_cells`): forward +
backward of every product the architecture requires, 2 FLOPs a
multiply-add, backward twice the forward, no recomputation, the
element-wise work (norms, rotary, sigmoids, the Sinkhorn iterations over
n^2 floats a token) not counted. And, a sublayer (two a layer: the mixer's
and the MLP's):

- `mhc_proj`: the coefficients' product, `n hidden x (n^2 + 2 n)`
  weights a token;
- `mhc_mix`: the read (`H_pre X`: n multiply-adds a feature) and the
  write (`H_res X + H_post^T y`: n^2 + n), `n^2 + 2 n` multiply-adds a
  feature of `hidden`; their backward is the same count to the input and
  the same to the coefficients, so the factor of 6 holds.

The kernels' work (`mhc_mix_work`, `mhc_coef_grad_work`) is what the
calls of a train step under full remat take in and give out, from the
program's counters: `train.mhc_cells` (the cells the stream steps run,
summed over sublayers and layers) and `train.mhc_loop_cells` (those of
them inside a layer that walks its row band by band).

- `mhc_mix`, a layer (its two sublayers), in stream-rows of `hidden` a
  cell: the read (n streams in, one out) of each sublayer and the mixer's
  write (n + 1 in, n out) twice forward (full remat runs the layer's
  forward again), the MLP's write once (it makes the layer's output, which
  nothing in the backward reads), and each one's backward to its input
  once (one in, n out; n in, n + 1 out): 6 (n + 1) + 5 (2 n + 1), 75 at n
  = 4, what the step requires. **And a term of its own, `again`**: a layer
  that walks bands makes a band's forward a third time in its backward
  loop (`ops/band_loop.py` keeps nothing of a band), both reads and the
  mixer's write, 2 (n + 1) + (2 n + 1) more, 19 at n = 4, over
  `train.mhc_loop_cells`. The float32 coefficients beside them.
- `mhc_coef_grad`: one contraction a read (1 + n stream-rows in) and one
  a write (n + n + 1 in), the float32 gradients out.

**These are bytes in and out of calls, not bytes through HBM.** The
compiled accumulate step of `xing4-d5e8-train-ppo-8k` (PERF.md section 6,
PR 47, after review) keeps a band's operands in VMEM between ops wherever
a loop's body fits: of the 25 `mhc_mix` calls of the program, the three of
a band's first forward and the ten over a whole row of 8,192 read and
write HBM; of the nine in the re-forward and the backward loop of a band
only two operands (a band of the kept input in, a band of its cotangent
out) do. So `bytes / hbm_bytes_per_s` is not the least time such a call
can take, a share of the HBM roofline by it reads over 100 (108 in that
cell's traced pass), and neither share is listed in `BENCHMARK.json`.
"""

from __future__ import annotations

from typing import Dict, Iterable

from benchmark import flops_mla
from benchmark.flops_moe import attention_cells


def sizes(hf: Dict) -> Dict[str, float]:
    n, d = hf["hc_mult"], hf["hidden_size"]
    return dict(
        n=n, coefs=n * (n + 2), sublayers=2 * hf["num_hidden_layers"],
        proj_token=n * d * n * (n + 2),  # weights a token passes, a sublayer
        mix_token=n * (n + 2) * d,  # multiply-adds a token, a sublayer
    )


def train_flops(hf: Dict, seqlens: Iterable[int], pairs_held: float,
                head_cells: float) -> Dict[str, float]:
    """Forward + backward model FLOPs of one pass over these sequences, by
    part, and `total`."""
    lens = [int(l) for l in seqlens]
    tokens = float(sum(lens))
    out = flops_mla.train_flops(dict(hf, num_nextn_predict_layers=0), lens, pairs_held,
                                head_cells)
    del out["total"], out["mtp"]
    s = sizes(hf)
    out["mhc_proj"] = 6.0 * s["proj_token"] * s["sublayers"] * tokens
    out["mhc_mix"] = 6.0 * s["mix_token"] * s["sublayers"] * tokens
    out["total"] = sum(out.values())
    return out


def mhc_mix_work(hf: Dict, counters: Dict[str, float], act_bytes: int = 2) -> Dict[str, float]:
    """What a train step's `mhc_mix` calls take in and give out over
    `train.mhc_cells` cells (sublayers x cells): FLOPs and bytes, of them
    `again` the bytes of the bands' third forward over
    `train.mhc_loop_cells`."""
    n, d = hf["hc_mult"], hf["hidden_size"]
    read, write = n + 1, 2 * n + 1  # stream-rows in and out, a call
    coef_read, coef_write = n, n * (n + 1)  # float32 coefficients, a call

    def layer_cell(reads, writes):
        return ((reads * read + writes * write) * d * act_bytes
                + (reads * coef_read + writes * coef_write) * 4.0)

    # a layer's two sublayers: reads 2 x (2 forward + 1 backward), the
    # mixer's write 2 + 1, the MLP's write 1 + 1; a band again: 2 reads, 1 write
    cells = counters["train.mhc_cells"] / 2.0
    loop_cells = counters.get("train.mhc_loop_cells", 0.0) / 2.0
    again = layer_cell(2, 1) * loop_cells
    macs = (6 * coef_read + 5 * coef_write) * d * cells + (
        2 * coef_read + coef_write) * d * loop_cells
    return dict(flops=2.0 * macs, bytes=layer_cell(6, 5) * cells + again, again=again)


def mhc_coef_grad_work(hf: Dict, counters: Dict[str, float],
                       act_bytes: int = 2) -> Dict[str, float]:
    """What a train step's `mhc_coef_grad` calls take in and give out over
    `train.mhc_cells` cells: one contraction a read and one a write."""
    n, d = hf["hc_mult"], hf["hidden_size"]
    cells = counters["train.mhc_cells"]
    rows = (1 + n) + (n + n + 1)
    coefs = n + n * (n + 1)
    return dict(flops=2.0 * n * (n + 2) * d * cells,
                bytes=(rows * d * act_bytes + coefs * 4.0) * cells)
