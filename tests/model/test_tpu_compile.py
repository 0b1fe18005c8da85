"""The new kernels' call sites compiled for a v5e that is described, not
attached, at the widths `trinity-d5e16-train-ppo-long` runs them (the
on-chip-measurement guide's third rehearsal, kept as tests): what the
chip's compiler would refuse, it refuses here. Nothing runs, so nothing
here says a result or a time. All such compiles live in this one file:
only one process at a time may load the TPU's library."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _no_partial_of_dq(text, hq, t, hd):
    """No array of the compiled program `text` has an axis (of pairs, of
    kv blocks) in front of dq's `[heads, t, hd]`, as the backward kernel
    sums it (`[heads, hd, t]`) or as the model takes it: the sum of a q
    block lives in one place."""
    import math
    import re

    for dims in set(re.findall(r"\b(?:bf16|f32)\[([\d,]+)\]", text)):
        dims = [int(d) for d in dims.split(",")]
        if tuple(dims[-3:]) in ((hq, t, hd), (hq, hd, t)):
            assert math.prod(dims[:-3]) == 1, f"a partial of dq: {dims}"


def _reads_in_place(text, t, heads=32, qk=192, v=128):
    """The pair kernels of the compiled step `text` take latent
    attention's operands where XLA's products leave them: their custom
    calls' q, k, v, output and gradients are `[heads, hd, t]`, and nothing
    relays a `[1, t, heads, 192]` array (q, k, dq, dk: the parent copied
    each, 201 MB at 16,384, twice forward and once backward a layer) nor
    transposes one in a fusion; of the `[1, t, heads, 128]` ones (v, the
    output, `do`, dv: the parent's four copies a layer) the one copy left
    is `do` on its way out of the `attn_out` stretch's backward loop, whose
    product writes its buffer head-minor whatever reads it: at most one an
    attention call in the backward (the output itself goes into that
    stretch sequence-minor, `[1, heads, 128, t]`: `band_loop.stretch`'s
    `minor`)."""
    import re

    calls = [(line.split(" custom-call(")[0], name) for line, name in re.findall(
        r"^(.* custom-call\(.*/splash_pairs_(fwd|bwd)/pallas_call.*)$", text, re.M)]
    assert calls and {name for _, name in calls} == {"fwd", "bwd"}
    for outs, name in calls:
        assert f"bf16[{heads},{v if name == 'fwd' else qk},{t}]" in outs, (name, outs[:200])
        assert f"[{heads},{t}," not in outs
    relaid = lambda hd: re.findall(
        rf"= bf16\[(?:1,{t},{heads},{hd}|1,{heads},{hd},{t}|{heads},{t},{hd}|{heads},{hd},{t})\]\S* "
        r"(?:copy|transpose)\(", text)
    backward_calls = sum(name == "bwd" for _, name in calls)
    assert not relaid(qk) and len(relaid(v)) <= backward_calls, (relaid(qk), relaid(v))
    assert not re.search(rf"%transpose\S* = bf16\[[\d,]*{t}[\d,]*{qk}[\d,]*\]", text)


def _accumulate_step(one_chip, monkeypatch, config, t):
    """(the configuration, the compiled forward-backward micro-batch of
    its model at one row of `t`: full remat, the masked loss head, the
    stretches over the live bands, as the engine's accumulate step runs
    it)."""
    import json

    from areal_tpu.models.transformer import forward, init_params
    from areal_tpu.ops.loss import fused_next_token_logprobs
    from benchmark.model import transformer_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels, not interpret mode
    with open(f"benchmark/configs/{config}.json") as f:
        hf = {k: v for k, v in json.load(f).items() if k != "benchmark"}
    cfg = transformer_config(hf, "bfloat16")
    params = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    ids = _shape((1, t), jnp.int32, one_chip)

    def loss(p, input_ids, seg, pos):
        hidden, _ = forward(p, cfg, input_ids, seg, pos, attn_impl="splash", remat=True,
                            output="hidden", return_aux=True, bands=True)
        head = p["head"]["weight"] if "head" in p else p["embedding"]["weight"].T
        return fused_next_token_logprobs(hidden, head, input_ids, seg, scored=seg > 0).sum()

    return cfg, jax.jit(jax.value_and_grad(loss)).lower(params, ids, ids, ids).compile()


@pytest.mark.parametrize("rows", [1, 3, "vmap"], ids=["row", "rows", "rows_vmap"])
@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_splash_row_of_16k_at_32_over_4_heads_compiles_forward_and_backward(one_chip, window, rows):
    """The segment ids are arguments, so the list of block pairs a long
    row alone walks (`ops/attention._pair_lists`) and its length are
    values of the run: scalar-prefetch operands that are traced, and a
    grid dimension that is dynamic, in the repo's own forward and
    backward kernels (`ops/pallas/splash_pairs.py`): two custom calls, no
    branch over widths, no `[kv blocks, heads, t, hd]` partials of dq
    (537 MB here) and no sum over them: dq is one float32 `[heads, hd,
    t]` that the backward kernel sums in place. Three rows in one call keep
    splash's static kernels and the fused backward; a caller's `vmap`
    over rows each given alone is a list a row: pallas's own loop over
    the kernel calls."""
    from areal_tpu.ops.attention import splash_packed_attention

    t, hq, hkv, hd = 8192, 32, 4, 128  # half the longest row: a quicker compile
    lead = (1,) if rows == 1 else (3,)
    q = _shape((*lead, t, hq, hd), jnp.bfloat16, one_chip)
    kv = _shape((*lead, t, hkv, hd), jnp.bfloat16, one_chip)
    ids = _shape((*lead, t), jnp.int32, one_chip)

    def attend(q, k, v, seg, pos):
        return splash_packed_attention(q, k, v, seg, pos, window=window, interpret=False)

    def loss(q, k, v, seg, pos):
        if rows == "vmap":
            out = jax.vmap(lambda *a: attend(*(x[None] for x in a))[0])(q, k, v, seg, pos)
        else:
            out = attend(q, k, v, seg, pos)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv, ids, ids).compile()
    text = compiled.as_text()
    # the forward and the fused backward kernel, or the forward and the
    # backward over the row's lists
    assert text.count("tpu_custom_call") == 2 and "splash_pairs_dq" not in text
    assert ("splash_pairs_bwd" in text) == (rows != 3) == ("splash_mqa" not in text)
    assert (" while(" in text) == (rows == "vmap")
    assert " conditional(" not in text  # no widths: the grid is as long as the list
    if rows == 1:
        assert f"bf16[{hkv},8,{hq // hkv},{t},{hd}]" not in text  # dq a kv block
        assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9  # PR 34's: 0.61e9


@pytest.mark.parametrize("hq,hkv,hd,hd_v,window", [
    (32, 32, 192, 128, None), (16, 1, 128, 128, 2048), (12, 2, 128, 128, None),
    (16, 2, 256, 256, None)],
    ids=["joyai_192_128", "group_16_window", "group_6", "qwen3next_256_group_8"])
def test_pair_kernels_compile_at_a_row_of_16k(one_chip, hq, hkv, hd, hd_v, window):
    """The list-walking kernels at the longest row, 16,384 at the blocks
    it runs at (512 x 1024): q and k of 192 against v of 128 (the joyai
    cell's call), a group of 16 under a window, a group of 6, and q, k and
    v of 256 in a group of 8 (the qwen3next cell's attention layer)."""
    from areal_tpu.ops.attention import splash_packed_attention

    t = 16384
    q = _shape((1, t, hq, hd), jnp.bfloat16, one_chip)
    k = _shape((1, t, hkv, hd), jnp.bfloat16, one_chip)
    v = _shape((1, t, hkv, hd_v), jnp.bfloat16, one_chip)
    ids = _shape((1, t), jnp.int32, one_chip)

    def loss(q, k, v, seg, pos):
        out = splash_packed_attention(q, k, v, seg, pos, window=window, interpret=False)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and " conditional(" not in text
    assert all(f"splash_pairs_{name}" in text for name in ("fwd", "bwd"))
    assert not any(f"splash_pairs_{name}" in text for name in ("dq", "dkv"))


def test_the_indexers_kernels_compile_at_a_row_of_16k(one_chip):
    """The keye cell's attention at its longest row: `index_select` (8 MB
    of scores in VMEM a step, 32 halvings), the pair kernels under the
    int8 mask operand, `index_kl_bwd` (the heads innermost, a transposed
    product into a block that stays in VMEM); the backward pass needs no
    `index_kl_fwd` (the KL's value) and the compiler drops it."""
    from areal_tpu.ops.indexer import indexed_attention

    t, hq, hkv, hd, hi, d = 16384, 32, 4, 128, 16, 64
    bf = jnp.bfloat16
    args = (_shape((1, t, hq, hd), bf, one_chip), _shape((1, t, hkv, hd), bf, one_chip),
            _shape((1, t, hkv, hd), bf, one_chip), _shape((1, t, hi, d), bf, one_chip),
            _shape((1, t, d), bf, one_chip), _shape((1, t, hi), jnp.float32, one_chip))
    ids = _shape((1, t), jnp.int32, one_chip)

    def loss(q, k, v, iq, ik, iw, seg, pos):
        out, sums = indexed_attention(q, k, v, iq, ik, iw, seg, pos, 2048, "splash", True,
                                      interpret=False)
        return out.astype(jnp.float32).sum() + sums["index_kl"]

    text = jax.jit(jax.grad(loss, tuple(range(6)))).lower(*args, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 4 and " conditional(" not in text
    for name in ("index_select", "splash_pairs_fwd", "splash_pairs_bwd", "index_kl_bwd"):
        assert name in text, name
    assert "index_kl_fwd" not in text and "splash_pairs_dq" not in text
    text = jax.jit(loss).lower(*args, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 3 and "index_kl_fwd" in text


def test_a_train_step_of_one_row_holds_no_partial_of_dq(one_chip, monkeypatch):
    """A forward-backward micro-batch of `q15d12-train-ppo`'s model (12
    layers, 12 / 2 heads of 128, full remat, the masked loss head) at
    one row of 8,192, as the engine's accumulate step runs it: three
    kernels (forward, remat's forward and the one backward over the
    row's list of pairs: twelve before PR 41, three widths each; four
    before PR 51, dq in a kernel of its own), no branch over widths, and
    nowhere the fused backward's `[2, 8, 6, 8192, 128]` partials of dq,
    nor any array with an axis of pairs or kv blocks in front of dq's
    `[heads, hd, t]` (`_no_partial_of_dq`), nor the sum over them that
    followed the kernel."""
    import json
    import re

    from areal_tpu.models.transformer import forward, init_params
    from areal_tpu.ops.loss import fused_next_token_logprobs
    from benchmark.model import transformer_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels, not interpret mode
    with open("benchmark/configs/qwen2.5-1.5b-d12.json") as f:
        cfg = transformer_config(json.load(f), "bfloat16")
    assert (cfg.n_layers, cfg.n_q_heads, cfg.n_kv_heads) == (12, 12, 2)
    params = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    ids = _shape((1, 8192), jnp.int32, one_chip)

    def loss(p, input_ids, seg, pos):
        hidden = forward(p, cfg, input_ids, seg, pos, attn_impl="splash", remat=True,
                         output="hidden")
        return fused_next_token_logprobs(hidden, p["embedding"]["weight"].T, input_ids, seg,
                                         scored=seg > 0).sum()

    text = jax.jit(jax.value_and_grad(loss)).lower(params, ids, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 3 and "splash_pairs_bwd" in text
    assert "splash_pairs_dq" not in text and "splash_pairs_dkv" not in text
    assert "splash_mqa" not in text and " conditional(" not in text
    assert "bf16[2,8,6,8192,128]" not in text
    _no_partial_of_dq(text, cfg.n_q_heads, 8192, cfg.head_dim)
    assert not re.search(r"attn_kernel/[^\n\"]*_splash_attention[^\n\"]*/reduce_sum", text)


@pytest.mark.parametrize("config,loops", [
    ("trinity-mini-d5-e16", 5), ("nemotron-3-nano-d9-e8", 4)])
def test_an_accumulate_step_of_16k_compiles_with_its_stretches_looped(
        one_chip, monkeypatch, config, loops):
    """A forward-backward micro-batch of the trinity and the nemotron cells'
    models at their one shape `(1, 16384)`, full remat, the masked loss
    head. The trinity stack's leading dense layer and its four scanned
    expert layers run their two token-wise stretches as loops whose trip count is read from the segment ids
    (`ops/band_loop.py`): a known forward, remat's and a backward one a
    stretch a kind of layer, beside the held experts' and the head's own;
    the nemotron stack's four Mamba-2 layers run whole as one carried loop
    each (`band_loop.carried`: the state and the taps' last cells handed
    from band to band), its expert and attention layers keep the whole
    row. The chip's compiler takes both, and the nemotron step's
    temporaries are no more than the whole-row program's."""
    import json
    import re

    from areal_tpu.models.transformer import forward, init_params, looping_layers
    from areal_tpu.ops.loss import fused_next_token_logprobs
    from benchmark.model import transformer_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels, not interpret mode
    with open(f"benchmark/configs/{config}.json") as f:
        cfg = transformer_config(json.load(f), "bfloat16")
    assert looping_layers(cfg, 1, 16384) == loops
    params = jax.tree_util.tree_map(
        lambda a: _shape(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    ids = _shape((1, 16384), jnp.int32, one_chip)

    def loss(p, input_ids, seg, pos, bands=True):
        hidden, _ = forward(p, cfg, input_ids, seg, pos, attn_impl="splash", remat=True,
                            output="hidden", return_aux=True, bands=bands)
        return fused_next_token_logprobs(hidden, p["head"]["weight"], input_ids, seg,
                                         scored=seg > 0).sum()

    lowered = jax.jit(jax.value_and_grad(loss)).lower(params, ids, ids, ids)
    # the jitted loop is a function of the module where a layer loops
    carried = config.startswith("nemotron")
    assert ("@_carried" in lowered.as_text()) == carried
    assert ("@_stretch" in lowered.as_text()) == (not carried)
    compiled = lowered.compile()
    whiles = len(re.findall(r" while\(", compiled.as_text()))
    assert whiles >= 16
    if carried:
        whole = jax.jit(jax.value_and_grad(lambda *a: loss(*a, bands=False))).lower(
            params, ids, ids, ids).compile()
        # Arguments and results are the same bytes in both, so the peak's
        # difference is the temporaries': 7.02 against 7.46 GB, as the buffer
        # assignment's report reads (4.49 against 4.93 GB of temporaries).
        # `temp_size_in_bytes`, which this file's other tests read, says
        # 6.14 against 5.41 of this pair and less than the assigned buffers
        # of some smaller programs: not what is assigned (PERF.md section 7).
        peak = lambda c: c.memory_analysis().peak_memory_in_bytes
        assert peak(compiled) <= peak(whole), (peak(compiled), peak(whole))


def _held_experts_compile(one_chip, T, D, F, n_held, k, act, mats, kernels=0):
    """`_held_experts` forward and backward for a described v5e, and what
    the held part must look like there: each way a loop over chunks
    around a loop over a chunk's tiles, their trip counts values of the
    run, no conditional and no grouped matmul, and in no loop body a
    `broadcast` as large as a tile's rows, the tokens or a weight stack
    (a block of zeros for what did not run). `kernels`: the custom calls
    the program holds (none where `_add_rows` is the scatter-add, as it is
    unless a test steers `jax.default_backend`). Returns the compiler's
    temporaries in bytes."""
    import math
    import re

    from areal_tpu.models import moe as moe_lib
    from areal_tpu.models.config import MoEConfig

    moe = MoEConfig(num_experts=128, top_k=k, dispatch="dropless", score_func="sigmoid",
                    experts_held=(0, n_held))
    mp = {m: _shape((n_held, F, D) if m == mats[-1] else (n_held, D, F), jnp.bfloat16, one_chip)
          for m in mats}
    x = _shape((T, D), jnp.bfloat16, one_chip)
    gate = _shape((k * T,), jnp.float32, one_chip)
    choice = _shape((k * T,), jnp.int32, one_chip)
    mask = _shape((T,), jnp.bool_, one_chip)

    def loss(x, mp, gate, choice, mask):
        y, pairs, rows, _ = moe_lib._held_experts(
            x, mp, moe, act, jnp.bfloat16, choice, gate, mask, mats)
        # a cotangent that is an array: a constant one would be made in the loop
        return (y.astype(jnp.float32) * x).sum() + pairs + rows

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        x, mp, gate, choice, mask).compile()
    text = compiled.as_text()
    assert " conditional(" not in text and "ragged-dot" not in text
    assert text.count("tpu_custom_call") == kernels  # dense products, no kernel of their own
    bodies = set(re.findall(r" while\(.*?body=(%[\w.\-]+)", text))
    assert len(bodies) == 4  # chunks and a chunk's tiles, forward and backward
    comps = {m.group(1): c for c in text.split("\n\n")
             if (m := re.match(r"\n*(?:ENTRY )?(%[\w.\-]+) \(", c))}
    least = moe_lib._HELD_ROW_TILE * min(D, F)
    for body in bodies:
        for shape in re.findall(r"= \w+\[([\d,]+)\][^=]* broadcast\(", comps[body]):
            size = math.prod(int(d) for d in shape.split(","))
            assert size < least, f"a broadcast of [{shape}] inside {body}"
    return compiled.memory_analysis().temp_size_in_bytes


def test_held_experts_tiles_compile_at_the_published_widths(one_chip):
    """16 of 128 gated experts of width 1024 over 16,384 tokens top-8, as
    `trinity-d5e16-train-ppo-long` runs them: the sort, then the loops
    over tiles of the held pairs each way, float32 sums of the tokens'
    rows and of the three gradient stacks. 0.81 GB of temporaries by the
    compiler's count where the parent's passes over a 20,480-row buffer
    took 1.61."""
    assert _held_experts_compile(one_chip, 16384, 2048, 1024, 16, 8, jax.nn.silu,
                                 ("w_gate", "w_up", "w_down")) < 1.0e9


def test_state_space_mixer_and_plain_experts_compile_at_the_published_widths(one_chip):
    """What `nemotron3n-d9e8-train-ppo-long` adds to a step, forward and
    backward at a row of 8,192 (half the cell's: a quicker compile): the
    state-space mixer at 64 heads of 64, state 128, 8 groups, chunks of
    128 (einsums and one scan, no kernel), and the held experts' tiles
    over 8 of 128 plain squared-ReLU experts of 1856 top-6."""
    from areal_tpu.models import moe as moe_lib
    from areal_tpu.models.config import SSMConfig
    from areal_tpu.ops import ssm as ssm_lib

    T, D = 8192, 2688
    ssm = SSMConfig(n_heads=64, head_dim=64, n_groups=8, state_dim=128, chunk_size=128)
    sp = jax.eval_shape(lambda k: jax.tree_util.tree_map(
        lambda a: a[0], ssm_lib.init_ssm_params(
            ssm, D, lambda k, s, scale=None: jnp.zeros(s, jnp.bfloat16), k, 1, jnp.bfloat16)),
        jax.random.PRNGKey(0))
    sp = jax.tree_util.tree_map(lambda a: _shape(a.shape, a.dtype, one_chip), sp)
    h, seg = _shape((1, T, D), jnp.bfloat16, one_chip), _shape((1, T), jnp.int32, one_chip)

    def mixer_loss(h, sp, seg):
        return ssm_lib.ssm_mixer(h, sp, ssm, seg, jnp.bfloat16, 1e-5).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(mixer_loss, (0, 1))).lower(h, sp, seg).compile()
    text = compiled.as_text()
    assert " while(" in text and "tpu_custom_call" not in text  # the scan over chunk states; no kernel
    # the float32 decay block is [64 chunks, 64 heads, 128, 128] = 268 MB: a few of them, not a row's worth a head
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9

    # 8 of 128 plain experts of 1856 top-6 at the cell's own row of 16,384:
    # 0.85 GB where the parent's passes over a 7,680-row buffer took 0.93
    assert _held_experts_compile(one_chip, 16384, D, 1856, 8, 6, moe_lib.activation_fn("relu2"),
                                 ("w_in", "w_out")) < 0.9e9


@pytest.mark.parametrize("d", [2048, 2688])
def test_the_chunk_rows_kernel_compiles_at_the_published_widths(one_chip, d, monkeypatch):
    """`_add_rows` as the chip runs it (`ops/pallas/segment_add.py`): a
    chunk's rows in bf16 added into `[16384, d]` float32 at the chunk the
    program runs, `d` of 16 and of 21 lane tiles (the trinity, joyai and
    keye cells' and the nemotron cell's): one sort, one kernel, no
    scatter; and float32 rows (an engine run in float32), whose block and
    `Precision.HIGHEST` product must fit VMEM too."""
    from areal_tpu.models import moe as moe_lib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernel, not the scatter-add
    b = moe_lib._HELD_CHUNK_ROWS
    for dtype in (jnp.bfloat16, jnp.float32):
        text = jax.jit(lambda *args: moe_lib._add_rows(*args)).lower(
            _shape((16384, d), jnp.float32, one_chip), _shape((b, d), dtype, one_chip),
            _shape((b,), jnp.int32, one_chip), _shape((), jnp.int32, one_chip)).compile().as_text()
        assert text.count("tpu_custom_call") == 1 and "moe_rows_add" in text
        assert " scatter(" not in text and text.count(" sort(") == 1
    if d == 2048:
        # the held part whole, as the trinity cell runs it on the chip: the
        # kernel once in the forward's loop over chunks and once in the
        # backward's, the float32 sums aliased through both (no copy of them)
        _held_experts_compile(one_chip, 16384, d, 1024, 16, 8, jax.nn.silu,
                              ("w_gate", "w_up", "w_down"), kernels=2)


def test_selective_scan_kernels_compile_at_the_published_widths(one_chip):
    """What `phi4flash-d8-train-ppo-8k` adds to a step: the selective
    scan's forward and backward kernels over a packed row of 8,192 at
    5,120 channels of 16 states in bf16 (blocks of 128 positions x 512
    channels, the state and a chunk's 129 states in VMEM), and nothing of
    [T, d_in, N] outside them."""
    from areal_tpu.ops import selective_scan as ss

    T, Dn, N = 8192, 5120, 16
    x, dt = _shape((1, T, Dn), jnp.bfloat16, one_chip), _shape((1, T, Dn), jnp.float32, one_chip)
    A, bc = _shape((Dn, N), jnp.float32, one_chip), _shape((1, T, N), jnp.bfloat16, one_chip)
    seg = _shape((1, T), jnp.int32, one_chip)

    def loss(x, dt, A, B, C, seg):
        return ss.kernel_scan(x, dt, A, B, C, seg, 128, interpret=False).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(x, dt, A, bc, bc, seg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "sscan_fwd" in text and "sscan_bwd" in text
    # x, dt and their gradients are 0.08-0.17 GB each; [T, d_in, N] float32 would be 2.7 GB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_splash_takes_q_and_k_at_64_against_v_at_128(one_chip, window):
    """Differential attention's one call: 40 q heads and 20 k heads of 64
    against 20 v heads of 128 (`head_dim_v`), a row of 8,192 alone in
    its call, so it walks its list of pairs in the repo's own kernels.
    Mosaic takes the head size of 64 as it is: nothing is padded."""
    from areal_tpu.ops.attention import splash_packed_attention

    t = 8192
    q = _shape((1, t, 40, 64), jnp.bfloat16, one_chip)
    k = _shape((1, t, 20, 64), jnp.bfloat16, one_chip)
    v = _shape((1, t, 20, 128), jnp.bfloat16, one_chip)
    ids = _shape((1, t), jnp.int32, one_chip)

    def loss(q, k, v, seg, pos):
        out = splash_packed_attention(q, k, v, seg, pos, window=window, interpret=False)
        assert out.shape == (1, t, 40, 128)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and "splash_pairs_bwd" in text


def test_splash_takes_q_and_k_at_192_against_v_at_128_and_a_group_of_one(one_chip):
    """Latent attention's call (PR 40): 32 q and 32 kv heads, q and k of
    128 + 64 = 192 (no multiple of the 128 lanes) against v of 128, a row
    of 8,192 alone in its call. Mosaic takes 192 as it is: no operand is
    padded to 256 (dq's float32 sums are `[heads, 192, t]`, the 192 along
    sublanes; only the blocks of dq the kernel copies out itself are 256
    wide, sliced after it), and the backward is one kernel."""
    import re

    from areal_tpu.ops.attention import splash_packed_attention

    t = 8192
    qk = _shape((1, t, 32, 192), jnp.bfloat16, one_chip)
    v = _shape((1, t, 32, 128), jnp.bfloat16, one_chip)
    ids = _shape((1, t), jnp.int32, one_chip)

    def loss(q, k, v, seg, pos):
        out = splash_packed_attention(q, k, v, seg, pos, interpret=False)
        assert out.shape == (1, t, 32, 128)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(qk, qk, v, ids, ids).compile().as_text()
    assert text.count("tpu_custom_call") == 2 and "splash_pairs_bwd" in text
    assert not re.search(r"= bf16\[32,8192,256\]\S* pad\(", text)  # no padded copy of q
    assert "f32[32,192,8192]" in text and "f32[32,8192,256]" not in text


@pytest.mark.parametrize("use", ["read", "write"])
def test_the_stream_kernels_compile_at_the_published_widths(one_chip, use):
    """`xing4-d5e8-train-ppo-8k`'s two stream steps over a band of 1,024
    tokens of four streams of 3,584 (`ops/pallas/stream_mix.py`): the mix
    forward, and in its backward the mix under the transposed
    coefficients and the coefficients' gradient: three custom calls, the
    streams never copied (`[X; y]` is two operands, not a concatenation)."""
    from areal_tpu.ops.pallas import stream_mix

    t, n, d = 1024, 4, 3584
    x = _shape((1, t, n * d), jnp.bfloat16, one_chip)
    if use == "read":
        a, ins = _shape((1, t, 1, n), jnp.float32, one_chip), (x,)
    else:
        a, ins = (_shape((1, t, n, n + 1), jnp.float32, one_chip),
                  (x, _shape((1, t, d), jnp.bfloat16, one_chip)))

    def loss(a, ins):
        out = stream_mix.mhc_mix(a, ins, True)
        assert out.shape == (1, t, a.shape[-2] * d) and out.dtype == jnp.bfloat16
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, (0, 1))).lower(a, ins).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert "mhc_mix" in text and "mhc_coef_grad" in text
    assert f"bf16[{t},{(n + 1) * d}]" not in text and f"bf16[1,{t},{(n + 1) * d}]" not in text


def test_the_sinkhorn_kernels_compile_for_a_band_of_tokens(one_chip):
    """Twenty iterations over a band's 1,024 four-by-four matrices: one
    kernel forward and one backward (`ops/pallas/sinkhorn.py`), where the
    plain form is forty reductions and forty divisions each way."""
    from areal_tpu.ops import hyper_conn

    m = _shape((1, 1024, 4, 4), jnp.float32, one_chip)
    loss = lambda m: (hyper_conn.sinkhorn(m, 20, 1e-6, kernel=True) ** 2).sum()
    text = jax.jit(jax.value_and_grad(loss)).lower(m).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "mhc_sinkhorn_bwd" in text and " reduce(" not in text.split("ENTRY")[1]


def test_an_accumulate_step_of_the_four_stream_stack_compiles_at_8k(one_chip, monkeypatch):
    """A forward-backward micro-batch of `xing4-d5e8-train-ppo-8k`'s model
    at its one shape `(1, 8192)`, full remat, the masked loss head: the
    four scanned expert layers walk their live bands with the streams
    `[1, T, 4 x 3584]` among a stretch's inputs, every stream step a
    kernel (`mhc_mix`, `mhc_coef_grad`, the Sinkhorn pair) beside the pair
    kernels and the experts' row adds. The compiler's temporaries: 6.6 GB
    beside 10.63 GB of weights, gradient sums and moments (more than the
    allocator's 15.75 by this count, which the chip runs all the same:
    PERF.md section 7, S4)."""
    from areal_tpu.models.transformer import looping_layers

    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "xing4.0-d5-e8", 8192)
    assert looping_layers(cfg, 1, 8192) == 4  # the leading dense layer, alone among streams, does not
    text = compiled.as_text()
    for name in ("mhc_mix", "mhc_coef_grad", "mhc_sinkhorn", "mhc_sinkhorn_bwd",
                 "splash_pairs_bwd", "moe_rows_add"):
        assert name in text, name
    assert compiled.memory_analysis().temp_size_in_bytes < 7.0e9
    _reads_in_place(text, 8192)


def test_the_delta_rules_kernels_compile_at_the_published_widths(one_chip):
    """`kimilinear-d5e8-train-ppo-long`'s delta rule over a row of 16,384
    at 32 heads of 128 x 128, bf16 (`ops/kda.py`): forward and backward are
    one custom call each over the whole row, `kda_fwd_rule` (decay, `intra`
    and the walk, a chunk of eight heads a grid step, the row's live chunks
    a scalar the index maps read) and `kda_bwd_rule` (a group's states
    again, then its chunks backwards: `intra` again, the walk's transpose
    and `intra`'s pullback, nothing of a chunk but the gradients in HBM).
    No loop of XLA's is left in the rule, and no kernel of the walk alone."""
    from areal_tpu.ops import kda

    t, h, k = 16384, 32, 128
    q = _shape((1, t, h, k), jnp.bfloat16, one_chip)
    b = _shape((1, t, h), jnp.float32, one_chip)
    a, bias = _shape((h,), jnp.float32, one_chip), _shape((h, k), jnp.float32, one_chip)
    seg = _shape((1, t), jnp.int32, one_chip)

    def loss(q, k_, v, f, b, a, bias, seg):
        return kda.delta_rule(q, k_, v, f, b, a, bias, seg, 64, True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, tuple(range(7)))).lower(
        q, q, q, q, b, a, bias, seg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "kda_fwd_rule" in text and "kda_bwd_rule" in text
    assert "kda_fwd_states" not in text and "kda_bwd_states" not in text
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


# The accumulate step's temporaries at the parent of PR 55 (the rule's backward
# a loop of XLA's over groups of chunks with two kernels of the walk in it),
# bytes, by this file's own compile of that tree: the backward's kernel holds
# no more.
_TEMPORARIES_WITH_THE_BACKWARD_LOOP = {
    "kimi-linear-d5-e8": 5_221_000_704, "qwen3-next-d4-e32": 3_309_406_208}


def _rule_in_two_kernels(compiled, config):
    text = compiled.as_text()
    assert "kda_fwd_rule" in text and "kda_bwd_rule" in text
    assert "kda_fwd_states" not in text and "kda_bwd_states" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= _TEMPORARIES_WITH_THE_BACKWARD_LOOP[config], temp


def _taps_in_two_kernels(compiled):
    """The mixers' way into the rule is the taps' kernels (`ops/pallas/
    kda_taps.py`): both are in the program, and XLA's masked, shifted
    products (`select_n` under the scope `kda_taps`) are not."""
    import re

    text = compiled.as_text()
    assert "kda_taps_fwd" in text and "kda_taps_bwd" in text
    under = re.findall(r'op_name="[^"]*kda_taps/[^"]*select_n[^"]*"', text)
    assert not under, under[:3]


# The step's temporaries at PR 50 (two kernels in the backward, dq bf16
# `[heads, T, hd]` out of scratch), bytes, by this file's own compile:
# the two cells with the least room beside their state.
_TEMPORARIES_WITH_TWO_BACKWARD_KERNELS = {
    "phi-4-mini-flash-d8": 2_161_756_672, "kimi-linear-d5-e8": 5_229_632_512}


def _holds_dq_once(cfg, compiled, config, t):
    """The one backward kernel's float32 sums of dq `[hq, hd, t]` are
    there, no array has an axis in front of them or of dq, and the step's
    temporaries are the parent's or less, or more by no more than that
    one array."""
    hq, hd = cfg.n_q_heads, cfg.head_dim
    text = compiled.as_text()
    assert "splash_pairs_bwd" in text and "splash_pairs_dq" not in text
    assert f"f32[{hq},{hd},{t}]" in text
    _no_partial_of_dq(text, hq, t, hd)
    assert compiled.memory_analysis().temp_size_in_bytes <= (
        _TEMPORARIES_WITH_TWO_BACKWARD_KERNELS[config] + 4 * hq * hd * t)


def test_an_accumulate_step_of_the_sambay_stack_holds_dq_once(one_chip, monkeypatch):
    """`phi4flash-d8-train-ppo-8k`'s forward-backward micro-batch at its
    one shape `(1, 8192)` (2.16 GB of temporaries beside 11.9 GB of
    state: the cell with the least room): differential attention's one
    call is 40 q heads of 64 over the rearranged heads, its backward one
    kernel that sums dq in one float32 `[40, 64, 8192]`."""
    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "phi-4-mini-flash-d8", 8192)
    _holds_dq_once(cfg, compiled, "phi-4-mini-flash-d8", 8192)


def test_an_accumulate_step_of_the_latent_stack_reads_q_k_and_v_in_place(one_chip, monkeypatch):
    """A forward-backward micro-batch of `joyai-d6e16-train-ppo-long`'s model
    at its one shape `(1, 16384)`, full remat, the masked loss head: the
    pair kernels of its latent layers (the leading dense layer alone, the
    five scanned expert layers) take q, k and v sequence-minor, `[32, hd,
    T]`, and hand back the output, dq, dk and dv likewise
    (`ops/attention._rows_in_place`), so that of the parent's twelve
    relayouts a layer (PERF.md section 6, PR 62) no `copy` or `transpose`
    of q, k, dq or dk is left and of v's, the output's, `do`'s and dv's
    one, `do`'s, in the backward; and the compiler's peak and temporaries
    are no higher than with the head-first kernels (5.71 and 3.78 GB
    there, 5.44 and 3.57 here)."""
    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "joyai-llm-flash-d6-e16", 16384)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") > 4 and "moe_rows_add" in text
    _reads_in_place(text, 16384)
    _no_partial_of_dq(text, cfg.n_q_heads, 16384, cfg.mla.qk_dim)
    memory = compiled.memory_analysis()
    assert memory.peak_memory_in_bytes <= 5.71e9 and memory.temp_size_in_bytes <= 3.78e9


def test_an_accumulate_step_of_the_delta_rule_stack_compiles_at_16k(one_chip, monkeypatch):
    """A forward-backward micro-batch of `kimilinear-d5e8-train-ppo-long`'s
    model at its one shape `(1, 16384)`, full remat, the masked loss head:
    four delta-rule layers (one alone, a scan of two, one alone) and the
    latent layer between them, every layer's token-wise stretches over the
    row's live bands, the rule's two kernels (`kda_fwd_rule` in the
    forward and in remat's, `kda_bwd_rule` in the backward) beside the pair
    kernels at 192 against 128 and the experts' row adds. The compiler's
    temporaries: 5.20 GB beside 8.43 GB of weights, gradient sums and
    moments, not above the 5.22 of the backward as a loop over groups
    (10.7 GB with the rule's parts and decays held a row at a time and the
    stretches over the whole row: PERF.md section 6, PR 50)."""
    from areal_tpu.models.transformer import looping_layers

    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "kimi-linear-d5-e8", 16384)
    assert looping_layers(cfg, 1, 16384) == 5
    text = compiled.as_text()
    assert "splash_pairs_bwd" in text and "moe_rows_add" in text
    _rule_in_two_kernels(compiled, "kimi-linear-d5-e8")
    _taps_in_two_kernels(compiled)
    _holds_dq_once(cfg, compiled, "kimi-linear-d5-e8", 16384)
    _reads_in_place(text, 16384)


def test_the_delta_rule_with_a_decay_a_head_compiles_at_the_published_widths(one_chip):
    """`qwen3next-d4e32-train-ppo-long`'s delta rule over a row of 16,384:
    16 key heads under 32 value heads of 128 x 128, one decay a value head,
    bf16. The same two custom calls as the channel form's; q and k stand
    `[T, 16, 128]` (no array of the program repeats them to 32 heads a row:
    the kernels read a key head through the block's index, and the
    backward's sums a key head's gradients inside its step) and the decay
    `[T, 32]`: no float32 `[., 32, 128]` of the row's length holds it a
    channel."""
    from areal_tpu.ops import kda

    t, hk, h, k = 16384, 16, 32, 128
    q = _shape((1, t, hk, k), jnp.bfloat16, one_chip)
    v = _shape((1, t, h, k), jnp.bfloat16, one_chip)
    f = _shape((1, t, h), jnp.bfloat16, one_chip)
    b = _shape((1, t, h), jnp.float32, one_chip)
    a = _shape((h,), jnp.float32, one_chip)
    seg = _shape((1, t), jnp.int32, one_chip)

    def loss(q, k_, v, f, b, a, bias, seg):
        return kda.delta_rule(q, k_, v, f, b, a, bias, seg, 64, True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, tuple(range(7)))).lower(
        q, q, v, f, b, a, a, seg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "kda_fwd_rule" in text and "kda_bwd_rule" in text and " while(" not in text
    assert f"f32[1,{t},{h},{k}]" not in text and f"f32[{t},{h},{k}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_an_accumulate_step_of_the_gated_deltanet_stack_compiles_at_16k(one_chip, monkeypatch):
    """A forward-backward micro-batch of `qwen3next-d4e32-train-ppo-long`'s
    model at its one shape `(1, 16384)`, full remat, the masked loss head:
    a scan of three Gated DeltaNet layers and the gated attention layer
    after them, every layer's token-wise stretches over the row's live
    bands, the rule's two kernels beside the pair kernels at heads of 256
    and the experts' row adds. The compiler's temporaries, 3.31 GB beside
    8.76 GB of weights, gradient sums and moments, are not above those of
    the backward as a loop over groups."""
    from areal_tpu.models.transformer import looping_layers

    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "qwen3-next-d4-e32", 16384)
    assert looping_layers(cfg, 1, 16384) == 4
    text = compiled.as_text()
    for name in ("splash_pairs_fwd", "splash_pairs_bwd", "moe_rows_add"):
        assert name in text, name
    _rule_in_two_kernels(compiled, "qwen3-next-d4-e32")
    _taps_in_two_kernels(compiled)


def test_an_accumulate_step_of_the_two_table_stack_compiles_at_16k(one_chip, monkeypatch):
    """A forward-backward micro-batch of `mellum2-d4e16-train-ppo-long`'s
    model at its one shape `(1, 16384)`, full remat, the masked loss head:
    one scan of four layers, three through a window of 1,024 and one over
    the whole sequence, whose body takes the layer's rotary table (plain,
    or YaRN's with its attention factor) by its variant index before the
    first stretch rotates q and k band by band, and whose switch picks the
    mask alone; experts of 896 (seven lanes of 128) through the held
    experts' tiles and the row adds. The compiler's temporaries stay under
    5 GB beside 8.33 GB of weights, gradient sums and moments."""
    from areal_tpu.models.transformer import looping_layers

    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "mellum2-d4-e16", 16384)
    assert looping_layers(cfg, 1, 16384) == 4
    assert [seg.repeats for seg in cfg.segments()] == [4]
    text = compiled.as_text()
    for name in ("splash_pairs_fwd", "splash_pairs_bwd", "moe_rows_add"):
        assert name in text, name
    assert "conditional" in text  # the window / full switch around the attention call
    # both tables are built once, [1, 16384, 64] float32 each, and stacked for the scan
    assert "rotary_table.sliding_attention" in text and "rotary_table.full_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 5.0e9


def test_the_delta_rule_at_a_rectangle_compiles_at_the_published_widths(one_chip):
    """`olmohybrid-d4-train-ppo-8k`'s delta rule over a row of 8,192: 30 heads
    with a state of 96 x 192, one decay a head, bf16, `(I + A)^-1` by doubling
    blocks. The keys stand widened to 128 lanes (`ops/kda.key_lanes`;
    `RuleForm.key_dim` 96), a grid step takes 6 heads (a block of v nine lane
    tiles): the same two custom calls as the square forms', no loop of XLA's.
    As they stand at 96 the keys' blocks are no whole lane tiles at any count
    of heads that divides 30 but all of them, and the compiler says so."""
    from areal_tpu.ops import kda
    from areal_tpu.ops.pallas import kda_fwd

    t, h, k, v = 8192, 30, 96, 192
    assert kda_fwd.step_heads(h, h, kda.key_lanes(k), v) == (6, True)
    q = _shape((1, t, h, kda.key_lanes(k)), jnp.bfloat16, one_chip)
    narrow = _shape((1, t, h, k), jnp.bfloat16, one_chip)
    val = _shape((1, t, h, v), jnp.bfloat16, one_chip)
    f = _shape((1, t, h), jnp.bfloat16, one_chip)
    b = _shape((1, t, h), jnp.float32, one_chip)
    a = _shape((h,), jnp.float32, one_chip)
    seg = _shape((1, t), jnp.int32, one_chip)

    def loss(form):
        return lambda q, k_, v, f, b, a, bias, seg: kda.delta_rule(
            q, k_, v, f, b, a, bias, seg, 64, True, form).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss(kda.RuleForm(k, True)), tuple(range(7)))).lower(
        q, q, val, f, b, a, a, seg).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "kda_fwd_rule" in text and "kda_bwd_rule" in text and " while(" not in text
    assert f"f32[1,{t},{h},{v}]" not in text  # no float32 copy of a row's values
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
    with pytest.raises(Exception, match="(?i)block|divisible|tile"):
        jax.jit(jax.grad(loss(kda.RuleForm(None, True)), tuple(range(7)))).lower(
            narrow, narrow, val, f, b, a, a, seg).compile()


def test_an_accumulate_step_of_the_rectangle_stack_compiles_at_8k(one_chip, monkeypatch):
    """A forward-backward micro-batch of `olmohybrid-d4-train-ppo-8k`'s model
    at its one shape `(1, 8192)`, full remat, the masked loss head: a scan of
    three Gated DeltaNet layers at 96 x 192 and the plain 30-head attention
    layer after them, every layer's token-wise stretches (dense MLPs of 11,008
    at hidden 3840 behind output norms) over the row's live bands, the rule's
    two kernels and the taps' pair (v's 5,760 columns: the backward asks for
    more VMEM than a kernel gets unasked, `kda_taps.WIDE_VMEM_BYTES`) beside
    the pair kernels at 30 heads, group 1. The compiler's temporaries: 2.77 GB
    beside 13.00 GB of weights, gradient sums and moments, of a chip that
    gives a program 16.9."""
    from areal_tpu.models.transformer import looping_layers

    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "olmo-hybrid-d4", 8192)
    assert looping_layers(cfg, 1, 8192) == 4
    text = compiled.as_text()
    for name in ("splash_pairs_fwd", "splash_pairs_bwd", "kda_fwd_rule", "kda_bwd_rule"):
        assert name in text, name
    _taps_in_two_kernels(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.0e9


def test_an_accumulate_step_of_the_short_convolution_stack_compiles_at_8k(one_chip, monkeypatch):
    """A forward-backward micro-batch of `lfm2-d5e16-train-ppo-8k`'s model at
    its one shape `(1, 8192)`, full remat, the masked loss head over the tied
    embedding: a dense gated short-convolution layer and an attention layer
    over held experts, each alone, then a scan of three convolution layers
    over held experts, which keep the whole row; the dense convolution layer
    one carried loop over the row's live bands (`band_loop.carried` around
    `transformer._conv_layer`: the taps' last two gated inputs handed from
    band to band), the attention layer its two stretches, the pair kernels at
    heads of 64, group 4. What
    the step holds besides: 893.7 M parameters at 14 bytes, 12.51 GB, of
    which the program's arguments are the bf16 weights; its peak, with the
    float32 gradient sums and the two moments it does not see, stands under
    the 15.75 GB a chip's allocator gives."""
    from areal_tpu.models.transformer import looping_layers

    cfg, compiled = _accumulate_step(one_chip, monkeypatch, "lfm2-8b-a1b-d5-e16", 8192)
    assert looping_layers(cfg, 1, 8192) == 2
    assert looping_layers(cfg, 1, 8192, mixer="conv") == 1
    text = compiled.as_text()
    for name in ("splash_pairs_fwd", "splash_pairs_bwd", "moe_rows_add", "conv_gate_fwd",
                 "conv_gate_bwd"):  # the whole rows' convolutions in their kernels
        assert name in text, name
    m = compiled.memory_analysis()
    n_params = m.argument_size_in_bytes // 2  # bf16 weights; the three int rows are nothing
    assert abs(n_params - 893.7e6) < 0.1e6, n_params
    # the weights' bf16 gradients are this program's results, where the engine's
    # step adds into its float32 sums: the sums (4 bytes a parameter) stand in
    # their place, beside the two moments (8)
    held = m.peak_memory_in_bytes - m.output_size_in_bytes + 12 * n_params
    assert 12.51e9 < held < 15.75e9, (held, m.peak_memory_in_bytes, m.temp_size_in_bytes)


def test_the_gated_convolutions_kernels_compile_at_the_published_widths(one_chip):
    """`ops/pallas/conv_gate.py` over `lfm2-d5e16-train-ppo-8k`'s row: `[B | C |
    x]` `[1, 8192, 6144]` bf16 under three taps, forward and transpose: one
    custom call each, no loop of XLA's and no array of the row's size beside
    the operands and results."""
    from areal_tpu.ops.pallas import conv_gate

    t, d, k = 8192, 2048, 3
    bcx = _shape((1, t, 3 * d), jnp.bfloat16, one_chip)
    w = _shape((k, d), jnp.bfloat16, one_chip)
    dy = _shape((1, t, d), jnp.bfloat16, one_chip)
    seg = _shape((1, t), jnp.int32, one_chip)
    fwd = jax.jit(lambda bcx, w, seg: conv_gate.conv_gate(bcx, w, seg)).lower(
        bcx, w, seg).compile()
    bwd = jax.jit(lambda bcx, w, dy, seg: jax.vjp(
        lambda a, b: conv_gate.conv_gate(a, b, seg), bcx, w)[1](dy)).lower(
        bcx, w, dy, seg).compile()
    for compiled, name in ((fwd, "conv_gate_fwd"), (bwd, "conv_gate_bwd")):
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and name in text and " while(" not in text
        # the cells' numbers `[1, t, 128]` int32 and nothing else of a row's size
        assert compiled.memory_analysis().temp_size_in_bytes < 8.0e6
