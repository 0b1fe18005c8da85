"""What a train step says of itself: the `train.*` counters of a
micro-batch and the attributes of its spans.

`JaxTrainEngine` builds one `TrainCounts` and asks it, a micro-batch at a
time while tracing is on, what the device will do with the packed rows;
it adds the mappings of a batch's micro-batches and emits the sums
(`tracing.count`), so a counter's name and its value are written in one
place. Every rule is the device's own and lives with its kernel
(`ops/attention.attn_block_cells`, `ops/ssm.chunk_counts`, ...): a part
here adds the layer count, the gate on the configuration and the name.
Every value is linear in a micro-batch, which is what lets the two input
paths add them in any grouping; `width` is a maximum and `attn_row_len` a
length, so they are span attributes and not counters.

A new family, span or kernel adds a part (and its names) here, and
`of` one line: `jax_engine.py` is not opened.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from areal_tpu.models.config import TransformerConfig
from areal_tpu.models.transformer import looping_layers
from areal_tpu.ops import band_loop, kda
from areal_tpu.ops import ssm as ssm_ops
from areal_tpu.ops.attention import (
    attn_block_cells,
    attn_grid_steps,
    attn_in_place,
    attn_run_len,
)
from areal_tpu.ops.indexer import index_counts
from areal_tpu.ops.loss import head_cells_run, two_on

Rows = Dict[str, np.ndarray]
Counts = Dict[str, int]


def kinds_label(cfg: TransformerConfig) -> str:
    """The stack's layer kinds in order, runs of equal kinds folded:
    `dense.w2048.rope,moe.w2048.rope x2,moe.full.nope` for transformer
    blocks (named by their MLP); a layer of one part is `ssm`, `moe`,
    `dense` or `attn.full.nope`. Differential attention says `diff.`, a
    layer that reads layer n's tensor `<n`, one that keeps its own `^`:
    `ssm+dense^`, `dense.diff.full.nope<5`, `gmu+dense<4`; latent
    attention says `latent.`, a gated short convolution beside an MLP `conv.`
    and its taps (`moe.conv.k3`), a delta-rule mixer beside an MLP `kda.` and
    its chunk (`moe.kda.c64`; with one decay a head and h key heads
    `moe.kda.head.k16.c64`; with keys and values of two widths and a
    doubled beta `dense.kda.head.k30.96x192.b2.c64`), attention over the
    keys an indexer chooses
    `indexed.`, a layer over four residual streams starts with `hc4.`, and
    a prediction module after the stack ends the label with `+mtp`. A
    layer that rotates by a named set of the stack's (`rotary_sets`) says
    which: `moe.w1024.rope[sliding_attention] x3,moe.full.rope[full_attention]`."""
    streams = f"hc{cfg.hyper.n}." if cfg.hyper is not None else ""

    def name(k):
        attn = (f"{'diff.' if k.diff else ''}{'latent.' if k.latent else ''}"
                f"{'indexed.' if k.indexed else ''}"
                f"{'full' if k.window is None else 'w%d' % k.window}."
                f"{'rope' if k.rotary else 'nope'}"
                f"{'[%s]' % k.rotary_set if k.rotary_set else ''}")
        keeps, reads = "^" if k.keeps else "", "" if k.reads is None else f"<{k.reads}"
        if k.block:
            return f"{streams}{k.mlp}.{attn}{keeps}{reads}"
        if k.mixer == "conv" and k.mlp is not None:
            return f"{k.mlp}.conv.k{cfg.conv.kernel}"
        if k.mixer == "kda" and k.mlp is not None:
            form = "" if cfg.kda.decay == "channel" else f"head.k{cfg.kda.key_heads}."
            if cfg.kda.value_dim != cfg.kda.head_dim or cfg.kda.neg_eigval:
                form += (f"{cfg.kda.head_dim}x{cfg.kda.value_dim}."
                         f"{'b2.' if cfg.kda.neg_eigval else ''}")
            return f"{k.mlp}.kda.{form}c{cfg.kda.chunk_size}"
        return (f"attn.{attn}{keeps}" if k.mixer == "attention" else k.parts) + reads

    names = [name(k) for k in cfg.kinds()]
    out = []
    for n in names:
        if out and out[-1][0] == n:
            out[-1][1] += 1
        else:
            out.append([n, 1])
    return ",".join(n if c == 1 else f"{n} x{c}" for n, c in out) + (
        "+mtp" if cfg.mtp is not None else "")


def stack_attrs(cfg: TransformerConfig) -> Dict[str, Any]:
    """What `train.dispatch` says of the stack it runs (its narrowest
    window and `kinds_label`): nothing for a stack of one plain kind."""
    if (cfg.layer_kinds is None and cfg.mla is None
            and cfg.indexer is None and cfg.hyper is None):
        return {}
    windows = sorted({k.window for k in cfg.kinds() if k.window is not None})
    return dict(window=windows[0] if windows else None, kinds=kinds_label(cfg))


@dataclasses.dataclass
class TrainCounts:
    """The counters of one engine's train steps. `mtp`: whether a step
    runs the prediction module's pass (its loss has a weight);
    `n_moe_layers`: the expert layers a step runs, the module's block
    among them (the engine's count, which its loss reads too);
    `n_row_multiple`: the mesh's data x fsdp, the groups of rows the loss
    head lays its chunks out over."""

    cfg: TransformerConfig
    mesh: Any
    attn_impl: str
    row_len_multiple: int
    n_row_multiple: int
    mtp: bool
    n_moe_layers: int

    def __post_init__(self):
        # the layers a step runs: the stack's, and the module's block, one
        # of the stack's last kind
        kinds = self.cfg.kinds() + self.cfg.kinds()[-1:] * self.mtp
        self.n_step_layers = len(kinds)
        # a window an attention layer a step runs
        attention = [k for k in kinds if k.mixer == "attention"]
        self.windows: List[Optional[int]] = [k.window for k in attention]
        # q's and k's head size an attention layer a step runs; None where
        # the kernels take an indexer's choice, a mask operand, head-first
        self.qk_dims: List[Optional[int]] = [
            None if k.indexed else self.cfg.mla.qk_dim if k.latent else self.cfg.head_dim
            for k in attention]

    def of(self, rows: Rows, n_tokens: int,
           scored_fn: Optional[Callable[[Rows], np.ndarray]] = None,
           ) -> Tuple[Counts, Dict[str, int]]:
        """One micro-batch's numpy `rows` ([R, T] arrays) or a stack of
        several ([n, R, T]), on the host before the transfer, with its
        count of real tokens and `train_batch`'s `scored_fn`: (counter
        name -> what the device will run, the attributes `attn_row_len`
        and `width` of the `train.dispatch` that runs it). A part with
        nothing to say of this configuration has no entry."""
        seg = np.asarray(rows["segment_ids"])
        mbs = seg.reshape((-1,) + seg.shape[-2:])
        scored = None
        if scored_fn is not None and not self.cfg.is_critic:
            scored = np.asarray(scored_fn(rows)).reshape(mbs.shape)
        stretch = self._stretch_cells(mbs)
        attn, attrs = self._attention(mbs)
        return {
            **self._bands(stretch), **self._streams(stretch), **attn,
            **self._head(mbs, scored), **self._mtp_head(mbs, scored),
            **self._experts(n_tokens), **self._ssm(mbs), **self._kda(mbs),
            **self._conv(mbs), **self._indexers(rows),
        }, attrs

    # -- the layers' token-wise stretches -------------------------------

    def _stretch_cells(self, mbs: np.ndarray) -> List[Tuple[int, int]]:
        """The cells the layers' token-wise steps run a micro-batch,
        summed over the layers a step runs, as (those of the layers that
        walk bands, those of the layers that run the row whole):
        `ops/band_loop.band_cells_run` for the first; a layer whose kind
        keeps the whole row (`models/transformer.looping_layers`) counts
        every cell, and so does every layer of a row the packer fills to
        the last band (`ops/band_loop.dead_bands`)."""
        n, loop = self.n_step_layers, self._looping(mbs)
        return [(loop * band_loop.band_cells_run(mb), (n - loop) * mb.size) for mb in mbs]

    def _looping(self, mbs: np.ndarray, mixer: Optional[str] = None) -> int:
        """The layers a step runs that walk the live bands of micro-batches
        of this shape (`models/transformer.looping_layers`; of one `mixer`
        where given): none where the packer fills every band
        (`ops/band_loop.dead_bands`)."""
        if not band_loop.dead_bands(mbs.shape[2], self.row_len_multiple):
            return 0
        return looping_layers(self.cfg, *mbs.shape[1:], sharded=self.mesh.size > 1,
                              mtp=self.mtp, mixer=mixer)

    def _bands(self, stretch) -> Counts:
        """The cells the stretches run, a mean over the layers."""
        return {"train.band_cells": sum(
            (bands + whole) // self.n_step_layers for bands, whole in stretch)}

    def _streams(self, stretch) -> Counts:
        """The cells the stream steps of hyper-connections run
        (`models/transformer._hc_read`, `_hc_write`), over the two
        sublayers of every layer, and those of them inside a layer that
        walks bands, whose backward loop makes a band's forward once more
        (`ops/band_loop.py`)."""
        if self.cfg.hyper is None:
            return {}
        return {"train.mhc_cells": 2 * sum(bands + whole for bands, whole in stretch),
                "train.mhc_loop_cells": 2 * sum(bands for bands, _ in stretch)}

    # -- attention -------------------------------------------------------

    def _attention(self, mbs: np.ndarray) -> Tuple[Counts, Dict[str, int]]:
        """What the attention kernels do with packed rows: rows x the
        length they run a row at (`ops/attention.attn_run_len`: splash
        pads a row to a length whose blocks are large); the cells of the
        block pairs they run, summed over rows and layers, by the rows'
        own segment ids (`attn_block_cells`), the window layers' part of
        them and the full layers' (the rest), and the cells a causal mask
        alone would make them run; the grid steps the forward and
        backward kernels walk, those whose pair runs and those the
        backward walks, summed over rows, q heads and layers
        (`attn_grid_steps`); of `train.attn_cells`, a mean over the
        attention layers, those whose kernels read q, k and v where the
        projections left them (`attn_in_place`). The attributes: that
        length, the widest forward grid, kv steps a q block, of any layer
        and row, and the attention layers that read in place."""
        cfg = self.cfg
        n, rows, row_len = mbs.shape
        shape = dict(
            impl=self.attn_impl, hq=cfg.n_q_heads, hkv=cfg.n_kv_heads,
            mesh=self.mesh if self.mesh.size > 1 else None,
        )
        run_len = attn_run_len(t=row_len, r=rows, **shape)
        # One count a window, not one a layer: (cells run, causal cells,
        # steps walked, live steps, width, the backward's steps) a
        # micro-batch.
        per = {w: [attn_block_cells(segment_ids=mb, window=w, **shape)
                   + attn_grid_steps(segment_ids=mb, window=w, **shape)
                   for mb in mbs]
               for w in set(self.windows)}
        total = lambda i: int(sum(mb[i] for w in self.windows for mb in per[w]))
        active = total(0)
        in_place = sum(hd is not None and attn_in_place(t=row_len, r=rows, hd=hd, **shape)
                       for hd in self.qk_dims)
        window = int(sum(mb[0] for w in self.windows if w is not None for mb in per[w]))
        return {
            "train.attn_cells": n * rows * run_len,
            "train.attn_cells_in_place":
                n * rows * run_len * in_place // max(len(self.qk_dims), 1),
            "train.attn_active_cells": active,
            "train.attn_window_cells": window,
            "train.attn_full_cells": active - window,
            "train.attn_causal_cells": total(1),
            "train.attn_grid_steps": cfg.n_q_heads * total(2),
            "train.attn_live_steps": cfg.n_q_heads * total(3),
            "train.attn_bwd_steps": cfg.n_q_heads * total(5),
        }, dict(attn_row_len=run_len,
                width=max(mb[4] for counts in per.values() for mb in counts),
                in_place=in_place)

    # -- the loss head ---------------------------------------------------

    def _head_cells(self, mbs, scored, shift: int) -> Tuple[int, int]:
        """(the positions whose logprob a loss reads, the cells of the
        chunks the head runs its logits tile over) by the device's own
        rule (`ops/loss.head_cells_run`); `shift`: how many tokens on the
        labels lie."""
        counts = [head_cells_run(mb, s, self.cfg.vocab_size, self.n_row_multiple, shift)
                  for mb, s in zip(mbs, [None] * len(mbs) if scored is None else scored)]
        return tuple(int(x) for x in np.sum(counts, axis=0))

    def _head(self, mbs, scored) -> Counts:
        """The caller's loss's run of the head; a critic has no such head."""
        if self.cfg.is_critic:
            return {}
        n_scored, cells = self._head_cells(mbs, scored, 1)
        return {"train.scored_cells": n_scored, "train.head_cells": cells}

    def _mtp_head(self, mbs, scored) -> Counts:
        """The prediction module's run of the head, over the tokens two
        on that the caller's loss scores (`jax_engine._mb_loss_fn`)."""
        if not self.mtp:
            return {}
        reads = np.ones(mbs.shape, np.float32) if scored is None else two_on(scored)
        targets, cells = self._head_cells(mbs, reads, 2)
        return {"train.mtp_targets": targets, "train.mtp_head_cells": cells}

    # -- experts, mixers, indexers ---------------------------------------

    def _experts(self, n_tokens: int) -> Counts:
        """The (token, expert) pairs the routers of the expert layers make."""
        moe = self.cfg.moe
        if moe is None:
            return {}
        return {"train.moe_pairs": moe.top_k * n_tokens * self.n_moe_layers}

    def _ssm(self, mbs) -> Counts:
        """What the state-space layers' chunked scan does with packed
        rows (`ops/ssm.chunk_counts`; for the selective scan a chunk is
        the kernel's block of time), summed over those layers: the chunks
        it runs (the selective scan's also as positions the kernel
        walks), those that hold a token, those that hold a sequence start
        after their first cell, sequence starts. A layer that walks its
        row's live bands (`_looping`: the Mamba-2 form alone in its layer)
        runs the chunks of those bands and no others."""
        n = self.cfg.n_ssm_layers
        if not n:
            return {}
        ssm = self.cfg.ssm
        chunks, live, mixed, resets = (
            n * c for c in ssm_ops.chunk_counts(mbs, ssm.chunk_size))
        loop = self._looping(mbs, "ssm")
        if loop:
            chunks -= loop * (chunks // n - ssm_ops.chunk_counts(
                mbs, ssm.chunk_size, band_loop._BAND)[0])
        walked = {"train.sscan_cells": chunks * ssm.chunk_size} if ssm.form == "mamba1" else {}
        return {**walked, "train.ssm_chunks": chunks, "train.ssm_chunks_live": live,
                "train.ssm_chunks_mixed": mixed, "train.ssm_resets": resets}

    def _kda(self, mbs) -> Counts:
        """What the delta-rule layers do with packed rows, summed over
        those layers: the positions their chunked rule walks
        (`ops/kda.chunk_counts`) and, of them, those whose forward and
        whose backward the kernels run (all where the rule takes its
        kernels, `ops/kda.use_kernel`, none where it takes the plain
        form), its chunks, those that hold a token and the sequence
        starts; the cells their convolutions are asked for (a call's
        R x T a layer: q's, k's and v's counted once) and those that go
        through the taps' kernels: all where the rule takes its own and
        the row's length and the widths fit (`ops/kda.taps_in_kernel`)."""
        n = self.cfg.n_kda_layers
        if not n:
            return {}
        cfg = self.cfg.kda
        cells, chunks, live, resets = (n * c for c in kda.chunk_counts(mbs, cfg.chunk_size))
        kernel = kda.use_kernel(cfg, self.mesh)
        taps = n * mbs.size
        return {
            "train.kda_cells": cells,
            "train.kda_fwd_kernel_cells": cells if kernel else 0,
            "train.kda_bwd_kernel_cells": cells if kernel else 0,
            "train.kda_chunks": chunks,
            "train.kda_chunks_live": live,
            "train.kda_resets": resets,
            "train.kda_taps_cells": taps,
            "train.kda_taps_kernel_cells":
                taps if kda.taps_in_kernel(cfg, mbs.shape[-1], kernel) else 0,
        }

    def _conv(self, mbs) -> Counts:
        """The cells the gated short-convolution mixers run, summed over
        those layers (a layer that walks its row's live bands, `_looping`,
        runs the bands up to the row's last token, any other every cell),
        those of them that hold a token, and those that go through the
        convolution's kernels: the whole rows', where they take them
        (`ops/ssm.conv_in_kernel`)."""
        n = self.cfg.n_conv_layers
        if not n:
            return {}
        cfg, loop = self.cfg, self._looping(mbs, "conv")
        whole = (n - loop) * mbs.size
        kernel = ssm_ops.conv_in_kernel(
            mbs.shape[2], cfg.hidden_dim, cfg.conv.kernel, cfg.conv.bias, False,
            False if self.mesh.size > 1 else None)
        return {
            "train.conv_cells": whole + sum(loop * band_loop.band_cells_run(mb) for mb in mbs),
            "train.conv_live_cells": n * int((mbs > 0).sum()),
            "train.conv_kernel_cells": whole if kernel else 0,
        }

    def _indexers(self, rows: Rows) -> Counts:
        """What the indexers do with packed rows, summed over indexed
        layers: the cells they score, those an exact choice keeps and the
        queries with more keys than `top_k`
        (`ops/indexer.index_counts`)."""
        n = self.cfg.n_indexed_layers
        if not n:
            return {}
        cells, selected, choosing = (n * int(c) for c in index_counts(
            np.asarray(rows["positions"]).astype(np.int64),
            np.asarray(rows["segment_ids"]), self.cfg.indexer.top_k))
        return {"train.index_cells": cells, "train.index_selected": selected,
                "train.index_queries_choosing": choosing}
