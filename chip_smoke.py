#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the main path once through the entry points a user would call, at
the published widths of Qwen2.5-1.5B (hidden 1536, 12 query / 2 KV heads
of 128, MLP 8960, QKV bias, tied embeddings, vocabulary 151,936, bf16)
with seeded random weights and a tokenizer and prompts generated from a
seed. Legs, one after another, each in processes of its own that are
gone before the next starts (a chip belongs to one process; this parent
never initialises a jax backend):

  kernels  every Pallas kernel the repo selects, compiled natively at
           this model's head shapes and checked against its oracle
  serve    a GenerationServer worker (all 28 layers) started through
           areal_tpu.system.worker_main as the controller starts it,
           answering /generate requests over HTTP; then its logprobs
           are checked against the plain reference forward
  train    `python training/main_sync_ppo.py` for three steps: generate,
           reward, GAE, logprob recompute, PPO actor update (depth cut
           to what fits one chip beside Adam's state; printed)
  async    `python training/main_async_ppo.py` for three steps on a
           decoupled allocation, all 28 layers under fsdp-2 (needs four
           chips; on fewer it prints `not run`)

    python chip_smoke.py                   # on the chip: the only pass
    python chip_smoke.py --rehearse-on-cpu # toy sizes on the CPU; proves
                                           # nothing about the chip

Exit 0 and a last stdout line {"ok": true, "device": {...}} only when jax
reports a TPU and every leg passed: no worker restarted, no `auto`
dispatch resolved to `reference` or `xla`, every device a leg used
reported its peak HBM, the native host ops were in use.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import json
import os
import pickle
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request
import uuid
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Qwen2.5-1.5B's config.json as published (Qwen/Qwen2.5-1.5B-Instruct),
# the keys that shape the model. Nothing here is cut: depth is cut only
# for the one-chip trainer, in `train_depth`.
QWEN25_1P5B_HF = {
    "model_type": "qwen2",
    "hidden_size": 1536,
    "intermediate_size": 8960,
    "num_hidden_layers": 28,
    "num_attention_heads": 12,
    "num_key_value_heads": 2,
    "vocab_size": 151936,
    "max_position_embeddings": 32768,
    "hidden_act": "silu",
    "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0,
    "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
}
TOY_HF = dict(
    QWEN25_1P5B_HF, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
    num_attention_heads=2, num_key_value_heads=1, vocab_size=128,
    max_position_embeddings=2048,
)
SEED = 20260926


def log(*a):
    print(*a, flush=True)


def model_config(hf: dict, dtype: str) -> dict:
    """TransformerConfig kwargs through the repo's own qwen2 family."""
    from areal_tpu.models.hf import family_from_hf_config

    cfg = family_from_hf_config(hf).config_from_hf(dict(hf))
    cfg.param_dtype = cfg.compute_dtype = dtype
    return dataclasses.asdict(cfg)


def train_depth(cfg: dict) -> int:
    """Layers the one-chip trainer holds: bf16 weights + bf16 gradients
    + fp32 Adam moments are 12 bytes a parameter, and the static state
    gets 10 GB of the chip's 16 (the rest is activations, the in-mesh
    generation cache and the fp32 gradient accumulators of a step)."""
    d, f, v = cfg["hidden_dim"], cfg["intermediate_dim"], cfg["vocab_size"]
    qd = cfg["n_q_heads"] * cfg["head_dim"]
    kvd = cfg["n_kv_heads"] * cfg["head_dim"]
    per_layer = d * (qd + 2 * kvd) + qd * d + 3 * d * f
    embedding = v * d  # tied: counted once
    layers = int((10e9 / 12 - embedding) // per_layer)
    return max(1, min(cfg["n_layers"], layers))


def make_workload(root: str):
    """Tokenizer + \\boxed math prompts from a seed (no network on the
    machine): a WordPiece tokenizer trained on the prompts themselves,
    small enough for the toy model's vocabulary."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordPiece
    from tokenizers.pre_tokenizers import Whitespace
    from tokenizers.trainers import WordPieceTrainer
    from transformers import PreTrainedTokenizerFast

    rng = random.Random(SEED)
    words = [
        "prove", "that", "the", "sum", "of", "two", "odd", "numbers",
        "is", "even", "find", "x", "such", "integral", "matrix", "prime",
        "graph", "vertex", "angle", "triangle", "circle", "radius",
    ]
    rows = []
    texts = []
    for _ in range(64):
        prompt = " ".join(rng.choice(words) for _ in range(rng.randint(6, 14)))
        rows.append(
            dict(
                query_id=str(uuid.uuid4()),
                task="math",
                prompt=prompt,
                solutions=["\\boxed{42}"],
            )
        )
        texts.append(prompt)

    tok = Tokenizer(WordPiece(unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    trainer = WordPieceTrainer(
        vocab_size=TOY_HF["vocab_size"] - 2,
        min_frequency=0,
        special_tokens=["[UNK]", "[EOS]"],
    )
    tok.train_from_iterator(texts, trainer)
    tok_file = os.path.join(root, "tokenizer.json")
    tok.save(tok_file)
    tok_dir = os.path.join(root, "tokenizer")
    PreTrainedTokenizerFast(
        tokenizer_file=tok_file, eos_token="[EOS]", pad_token="[EOS]",
        unk_token="[UNK]",
    ).save_pretrained(tok_dir)

    data_path = os.path.join(root, "math.jsonl")
    with open(data_path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return tok_dir, data_path


# ----------------------------------------------------------------------
# Process plumbing: every process a leg starts is in a group this parent
# kills when the leg ends, whatever happened.
# ----------------------------------------------------------------------

_groups = []


def spawn(cmd, env, log_path):
    f = open(log_path, "w")
    p = subprocess.Popen(
        cmd, env=env, cwd=REPO, stdout=f, stderr=subprocess.STDOUT,
        start_new_session=True,
    )
    _groups.append(p)
    return p


def reap(p):
    """The process and everything it started are gone on return."""
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    p.wait()


def read(path):
    with open(path, errors="replace") as f:
        return f.read()


def tail(path, n=40):
    return "\n".join(read(path).splitlines()[-n:])


def run_child(name, ctx, payload, timeout_s):
    """Run one of this file's chip-holding children; returns its result
    dict (the JSON after CHILD_RESULT)."""
    inp = os.path.join(ctx["work"], f"{name}.in.json")
    with open(inp, "w") as f:
        json.dump(payload, f)
    log_path = os.path.join(ctx["work"], f"{name}.log")
    p = spawn([sys.executable, __file__, "--child", name, "--input", inp],
              ctx["env"], log_path)
    try:
        p.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        reap(p)
        return {"ok": False, "error": f"timeout after {timeout_s}s"}
    reap(p)
    m = re.findall(r"^CHILD_RESULT (.*)$", read(log_path), re.M)
    if p.returncode != 0 or not m:
        return {"ok": False, "error": f"rc={p.returncode}\n{tail(log_path)}"}
    return json.loads(m[-1])


# ----------------------------------------------------------------------
# What the workers said about themselves (utils/jaxenv.say)
# ----------------------------------------------------------------------


def judge_ran(text, ctx, want_attn, want_decode, roles):
    """Check the `areal-ran` facts in a leg's log. Returns (summary,
    problems). `roles`: worker-name prefixes that must have reported."""
    from areal_tpu.utils.jaxenv import parse_ran

    facts = parse_ran(text)
    problems = []
    devices = {f["worker"]: f for f in facts if f["kind"] == "devices"}
    usage = {f["worker"]: f for f in facts if f["kind"] == "usage"}  # last wins
    for role in roles:
        if not any(w.startswith(role) for w in devices):
            problems.append(f"no devices report from {role}")
    owned = {}
    for w, d in devices.items():
        if d["platform"] != ctx["platform"]:
            problems.append(f"{w} ran on {d['platform']}, not {ctx['platform']}")
        chips = (
            [int(c) for c in d["visible_chips"].split(",")]
            if d["visible_chips"] else list(range(d["count"]))
        )
        if len(chips) != d["count"]:
            problems.append(f"{w} was given chips {chips} but saw {d['count']} devices")
        owned[w] = chips
        if d["native_host_ops"] is not True:
            problems.append(f"{w}: native host ops not in use")
        u = usage.get(w)
        if u is None:
            problems.append(f"{w} reported no memory usage")
        elif not ctx["rehearsal"] and (
            len(u["peak_hbm_bytes"]) < d["count"] or min(u["peak_hbm_bytes"]) <= 0
        ):
            problems.append(f"{w}: peak HBM {u['peak_hbm_bytes']} for {d['count']} devices")
    if not ctx["rehearsal"]:
        seen = {}
        for w, chips in owned.items():
            for c in chips:
                if c in seen:
                    problems.append(f"chip {c} owned by both {seen[c]} and {w}")
                seen[c] = w
    attn = sorted({f["ran"] for f in facts
                   if f["kind"] == "attn_impl" and f["requested"] == "auto"})
    decode = sorted({f["ran"] for f in facts
                     if f["kind"] == "paged_decode_impl" and f["requested"] == "auto"})
    if not ctx["rehearsal"]:
        if want_attn and attn != [want_attn]:
            problems.append(f"attention auto resolved to {attn}, want [{want_attn}]")
        if want_decode and decode != [want_decode]:
            problems.append(f"paged decode auto resolved to {decode}, want [{want_decode}]")
    summary = {
        "owned_chips": owned,
        "attn_auto": attn,
        "decode_auto": decode,
        "peak_hbm_gb": {w: [round(b / 1e9, 2) for b in u["peak_hbm_bytes"]]
                        for w, u in usage.items()},
        "compile_s": {w: u["compile_s"] for w, u in usage.items()},
        "native_host_ops": all(d["native_host_ops"] is True for d in devices.values()),
        "weight_versions": {
            str(f["pid"]): f["version"] for f in facts if f["kind"] == "weight_version"
        },
        "pids": {w: d["pid"] for w, d in devices.items()},
    }
    return summary, problems


def print_leg(name, res):
    log(f"--- leg {name}: {'ok' if res['ok'] else 'FAILED'}")
    for k, v in res.items():
        if k not in ("ok", "problems"):
            log(f"    {k}: {json.dumps(v, default=str)}")
    for p in res.get("problems", []):
        log(f"    PROBLEM: {p}")


# ----------------------------------------------------------------------
# Leg: kernels (child)
# ----------------------------------------------------------------------


def child_kernels(payload):
    """Compile each Pallas kernel natively (never interpreted on a TPU)
    at this model's head shapes and compare with its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.engine import paged
    from areal_tpu.ops import attention as A
    from areal_tpu.ops import gae as G

    hq, hkv, hd = payload["hq"], payload["hkv"], payload["hd"]
    t, pg = payload["t"], payload["page"]
    rng = np.random.RandomState(SEED % 2**31)
    bf = jnp.bfloat16
    out = {}

    def packed_inputs():
        # Three packed segments and a padded tail.
        bounds = [0, t // 4, t // 2 + 8, t - t // 8]
        seg = np.zeros((t,), np.int32)
        pos = np.zeros((t,), np.int32)
        for i in range(3):
            a, b = bounds[i], bounds[i + 1]
            seg[a:b] = i + 1
            pos[a:b] = np.arange(b - a)
        q = jnp.asarray(rng.randn(t, hq, hd), bf)
        k = jnp.asarray(rng.randn(t, hkv, hd), bf)
        v = jnp.asarray(rng.randn(t, hkv, hd), bf)
        return q, k, v, jnp.asarray(seg), jnp.asarray(pos)

    def attn_case(fn, tol):
        q, k, v, seg, pos = packed_inputs()
        valid = (seg > 0)[:, None, None]

        def loss(f, q, k, v):
            o = f(q, k, v, seg, pos)
            return jnp.sum(jnp.where(valid, o.astype(jnp.float32), 0.0) ** 2), o

        (_, o), g = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(fn, q, k, v), argnums=(0, 1, 2), has_aux=True
        ))(q, k, v)
        (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
            lambda q, k, v: loss(A.reference_packed_attention, q, k, v),
            argnums=(0, 1, 2), has_aux=True,
        ))(q, k, v)
        err = float(jnp.max(jnp.abs(jnp.where(valid, o - o_ref, 0).astype(jnp.float32))))
        gerr = max(
            float(jnp.max(jnp.abs((a - b).astype(jnp.float32)))
                  / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-6))
            for a, b in zip(g, g_ref)
        )
        return dict(shape=f"T={t} Hq={hq} Hkv={hkv} hd={hd} bf16",
                    max_abs_err=err, grad_max_rel_err=gerr, tol=tol,
                    ok=bool(err <= tol and gerr <= tol))

    def paged_case(quantized, impl):
        B, P = 8, 4
        N = B * P + 1
        kf = jnp.asarray(rng.randn(hkv, N, pg, hd), jnp.float32)
        vf = jnp.asarray(rng.randn(hkv, N, pg, hd), jnp.float32)
        if quantized:
            (kd, ks), (vd, vs) = paged.quantize_kv(kf), paged.quantize_kv(vf)
            kp, vp = (kd, ks[..., 0]), (vd, vs[..., 0])
        else:
            kp, vp = kf.astype(bf), vf.astype(bf)
        q = jnp.asarray(rng.randn(B, hq, hd), bf)
        lengths = jnp.asarray(rng.randint(1, P * pg, size=B), jnp.int32)
        pidx = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
        f = lambda impl: jax.jit(lambda *a: paged.paged_decode_attention(
            *a, impl=impl))(q, kp, vp, lengths, pidx)
        got, want = f(impl), f("xla")
        err = float(jnp.max(jnp.abs((got - want).astype(jnp.float32))))
        tol = 0.03
        return dict(shape=f"B={B} P={P} page={pg} Hq={hq} Hkv={hkv} hd={hd}",
                    max_abs_err=err, tol=tol, ok=bool(err <= tol))

    def gae_case():
        R, T = 8, max(128, t)
        rew = jnp.asarray(rng.randn(R, T), jnp.float32)
        val = jnp.asarray(rng.randn(R, T), jnp.float32)
        seg = np.zeros((R, T), np.int32)
        for r in range(R):
            cuts = np.sort(rng.choice(np.arange(1, T), 3, replace=False))
            for i, (a, b) in enumerate(zip([0, *cuts[:-1]], cuts)):
                seg[r, a:b] = i + 1
        boot = jnp.asarray(rng.randn(R, T), jnp.float32)
        args = (rew, val, jnp.asarray(seg), boot)
        kw = dict(gamma=0.97, lam=0.95)
        got = jax.jit(lambda *a: G.gae_rows_pallas(*a, **kw))(*args)
        want = jax.jit(lambda *a: G.gae_rows(*a, **kw))(*args)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
        tol = 1e-3
        return dict(shape=f"R={R} T={T} f32", max_abs_err=err, tol=tol,
                    ok=bool(err <= tol))

    def save_attn_case():
        """Two full-width layers, forward + backward: the `save_attn`
        remat policy (pin the splash residuals, rerun nothing of the
        kernel in the backward) against no remat at all. bf16 programs
        that fuse differently round differently, so the plain `full`
        remat's distance from no remat is printed beside it as the
        noise floor."""
        import warnings

        from areal_tpu.models.config import TransformerConfig
        from areal_tpu.models.transformer import forward, init_params

        cfg = TransformerConfig(**{**payload["config"], "n_layers": 2})
        params = init_params(cfg, jax.random.PRNGKey(SEED % 2**31))
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(2, t)), jnp.int32)
        seg = jnp.ones((2, t), jnp.int32)
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (2, t))

        def grads(remat):
            def loss(p):
                h = forward(p, cfg, ids, seg, pos, output="hidden", remat=remat)
                return jnp.mean(h.astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss))(params)

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            got = grads("save_attn")
        fell_back = [str(w.message) for w in caught if "save_attn" in str(w.message)]
        want = grads("none")

        def dist(tree):  # worst leaf, relative L2
            f32 = lambda x: x.astype(jnp.float32)
            return max(
                float(jnp.linalg.norm(f32(a) - f32(b)) / (jnp.linalg.norm(f32(b)) + 1e-12))
                for a, b in zip(jax.tree_util.tree_leaves(tree),
                                jax.tree_util.tree_leaves(want))
            )

        err, floor = dist(got), dist(grads("full"))
        tol = max(0.05, 2 * floor)
        engaged = not fell_back or jax.default_backend() != "tpu"
        return dict(shape=f"2 layers at full width, rows 2x{t}",
                    grad_rel_l2_err=err, full_remat_rel_l2_err=floor, tol=tol,
                    policy_engaged=not fell_back,
                    ok=bool(err <= tol and engaged))

    cases = {
        "splash (training/prefill attention)": lambda: attn_case(
            lambda *a: A.packed_attention(*a, impl="splash"), 0.05),
        "splash under remat=save_attn": save_attn_case,
        "paged_attention (decode, jax stock kernel)": lambda: paged_case(False, "kernel"),
        "paged_decode_int8": lambda: paged_case(True, "int8_kernel"),
        "gae_scan": gae_case,
    }
    if jax.default_backend() != "tpu":
        # The stock kernel has no interpreted form to rehearse.
        del cases["paged_attention (decode, jax stock kernel)"]
    for name, fn in cases.items():
        t0 = time.time()
        try:
            res = fn()
        except Exception as e:  # the compiler's reason is the finding
            res = dict(ok=False, error=f"{type(e).__name__}: {e}"[:4000])
        res["seconds"] = round(time.time() - t0, 1)
        out[name] = res
        print(f"kernel {name}: {json.dumps(res)}", flush=True)
    return dict(ok=all(r["ok"] for r in out.values()), kernels=out,
                platform=jax.default_backend())


def leg_kernels(ctx):
    cfg = ctx["serve_cfg"]
    payload = dict(hq=cfg["n_q_heads"], hkv=cfg["n_kv_heads"], hd=cfg["head_dim"],
                   t=256 if ctx["rehearsal"] else 1024,
                   page=ctx["page"], config=cfg)
    if ctx["rehearsal"]:
        # Interpreted Pallas on the CPU: splash gates head_dim at 128.
        payload.update(hq=4, hkv=2, hd=128)
    res = run_child("kernels", ctx, payload, 600)
    if res.get("platform", ctx["platform"]) != ctx["platform"]:
        res = dict(res, ok=False, error=f"ran on {res['platform']}")
    return res


# ----------------------------------------------------------------------
# Leg: serve
# ----------------------------------------------------------------------


def http_json(url, body=None, timeout=900):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read().decode()
    return raw if body is None else json.loads(raw)


def metrics(url):
    out = {}
    for line in http_json(f"{url}/metrics").splitlines():
        k, _, v = line.partition(" ")
        try:
            out[k] = float(v)
        except ValueError:
            out[k] = v
    return out


def leg_serve(ctx):
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.system_api import ExperimentConfig, GenerationServerConfig
    from areal_tpu.base import name_resolve, names
    from areal_tpu.system.controller import host_tpu_chips, plan_worker_envs

    exp, trial = "chip-smoke", "serve"
    nr = os.path.join(ctx["work"], "nr-serve")
    cfg = ctx["serve_cfg"]
    toy = ctx["rehearsal"]
    bucket, chunk, block = (16, 32, 4) if toy else (128, 256, 16)
    scfg = GenerationServerConfig(
        experiment_name=exp, trial_name=trial, server_index=0,
        chips=[0],
        model=ModelAbstraction("tpu_transformer", args=dict(config=cfg)),
        max_concurrent_requests=8,
        max_seq_len=16 * bucket,
        decode_block_steps=block,
        kv_page_size=ctx["page"],
        prompt_bucket=bucket,
        prefill_chunk=chunk,
        prefix_cache_tokens=8 * 16 * bucket,
        warm_on_start=True,
        seed=SEED,
    )
    cfg_path = os.path.join(ctx["work"], "gserver.pkl")
    with open(cfg_path, "wb") as f:
        pickle.dump(scfg, f)
    nr_cfg = {"backend": "nfs", "record_root": nr}
    env = dict(ctx["env"])
    env.update(plan_worker_envs(
        ExperimentConfig(generation_servers=[scfg]), {}, host_tpu_chips(env)
    )[scfg.worker_name])
    log_path = os.path.join(ctx["work"], "serve.log")
    t0 = time.time()
    p = spawn(
        [sys.executable, "-m", "areal_tpu.system.worker_main",
         "--worker-type", "generation_server", "--config", cfg_path,
         "--name-resolve", json.dumps(nr_cfg)],
        env, log_path,
    )
    res = {"ok": False, "layers": cfg["n_layers"], "problems": []}
    try:
        name_resolve.reconfigure(**nr_cfg)
        key = names.gen_server_url(exp, trial, "0")
        url = None
        while time.time() - t0 < 900 and p.poll() is None:
            try:
                url = name_resolve.get(key)
                break
            except name_resolve.NameEntryNotFoundError:
                time.sleep(1.0)
        if url is None:
            res["problems"].append(f"server never registered:\n{tail(log_path)}")
            return res
        res["start_s"] = round(time.time() - t0, 1)  # init + warm compile

        rng = random.Random(SEED)
        vocab = cfg["vocab_size"]
        prompt = lambda n: [rng.randrange(vocab) for _ in range(n)]
        new = 2 * block + 3  # more than one decode block
        g = dict(max_new_tokens=new, min_new_tokens=new, temperature=1.0)

        def gen(qid, ids, **over):
            return http_json(f"{url}/generate", dict(
                qid=qid, input_ids=ids, gconfig={**g, **over}))

        t1 = time.time()
        # Both sides of a prompt bucket and of a prefill chunk.
        plan = {
            "under-bucket": prompt(bucket - 5),
            "over-bucket": prompt(bucket + 40 if not toy else bucket + 5),
            "over-chunk": prompt(chunk + 45 if not toy else chunk + 5),
        }
        outs = {q: gen(q, ids, greedy=True) for q, ids in plan.items()}
        # One group of n > 1 samples of one prompt, in flight together.
        gp = prompt(bucket - 9)
        with ThreadPoolExecutor(4) as ex:
            group = list(ex.map(lambda i: gen(f"group/{i}", gp), range(4)))
        # One request resubmitted with its qid: prompt + what it produced
        # (how a partial rollout continues) must reuse the parked prefix.
        before = metrics(url)
        first = outs["under-bucket"]
        again = gen("under-bucket", plan["under-bucket"] + first["output_ids"],
                    greedy=True)
        after = metrics(url)
        res["requests_s"] = round(time.time() - t1, 1)

        everything = list(outs.values()) + group + [again]
        for o in everything:
            ids, lps = o.get("output_ids"), o.get("output_logprobs")
            if "error" in o or not ids or len(ids) != new:
                res["problems"].append(f"{o.get('qid')}: bad output {str(o)[:300]}")
                continue
            if not all(0 <= t < vocab for t in ids):
                res["problems"].append(f"{o['qid']}: token out of range")
            if len(lps) != len(ids) or not all(
                lp == lp and -1e4 < lp <= 0 for lp in lps
            ):
                res["problems"].append(f"{o['qid']}: logprobs not finite/<=0")
            if o.get("version_start") != 0 or o.get("version_end") != 0:
                res["problems"].append(f"{o['qid']}: version stamps {o.get('version_start')}/{o.get('version_end')}")
        if len({tuple(o["output_ids"]) for o in group if o.get("output_ids")}) < 2:
            res["problems"].append("group samples are all identical")
        reused = after["areal:prefix_tokens_reused"] - before["areal:prefix_tokens_reused"]
        res["prefix_tokens_reused"] = reused
        if after["areal:prefix_cache_hits"] <= before["areal:prefix_cache_hits"] or reused <= 0:
            res["problems"].append("resubmitted qid did not reuse its prefix")
        res["n_requests"] = len(everything)
        ctx["serve_samples"] = [
            dict(qid=q, input_ids=plan[q], output_ids=outs[q]["output_ids"],
                 output_logprobs=outs[q]["output_logprobs"])
            for q in plan if outs[q].get("output_ids")
        ]
        # Stop it the way an experiment ends; the server then says its
        # peak memory on the way out.
        name_resolve.add(names.experiment_status(exp, trial), "COMPLETE",
                         replace=True)
        try:
            p.wait(timeout=60)
        except subprocess.TimeoutExpired:
            res["problems"].append("server did not exit on COMPLETE")
    finally:
        reap(p)
    summary, problems = judge_ran(
        read(log_path), ctx, "splash", "kernel", ["generation_server"])
    res.update(summary)
    res["problems"] += problems
    if p.returncode != 0:
        res["problems"].append(f"server exit code {p.returncode}:\n{tail(log_path, 15)}")

    # The same seeded weights through the plain reference forward.
    if not res["problems"]:
        chk = run_child("serve_check", ctx, dict(
            config=cfg, samples=ctx["serve_samples"], seed=SEED), 900)
        res["reference_check"] = {k: v for k, v in chk.items() if k != "ok"}
        if not chk["ok"]:
            res["problems"].append(f"reference check failed: {chk.get('error', '')}")
    res["ok"] = not res["problems"]
    return res


def child_serve_check(payload):
    """Teacher-force the server's own outputs through the reference
    forward (einsum attention, no cache, no kernels) on the same seeded
    weights and compare the logprobs of the tokens it sampled."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import areal_tpu.engine.factories  # noqa: F401  (registry)
    from areal_tpu.api.config import ModelAbstraction
    from areal_tpu.api.model_api import make_model
    from areal_tpu.models.transformer import forward

    # The generation server's own construction: same name, same seed,
    # same weights.
    model = make_model(
        ModelAbstraction("tpu_transformer", args=dict(config=payload["config"])),
        name="gserver",
    )
    cfg, params = model._raw["cfg"], model._raw["params"]
    worst, rows = 0.0, []
    for s in payload["samples"]:
        ids = s["input_ids"] + s["output_ids"]
        n = -(-len(ids) // 128) * 128
        arr = np.zeros((1, n), np.int32)
        arr[0, : len(ids)] = ids
        seg = (np.arange(n) < len(ids)).astype(np.int32)[None]
        pos = np.where(seg > 0, np.arange(n)[None], 0).astype(np.int32)
        logits = jax.jit(
            lambda p, a, b, c: forward(p, cfg, a, b, c, attn_impl="reference")
        )(params, jnp.asarray(arr), jnp.asarray(seg), jnp.asarray(pos))
        logp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        plen = len(s["input_ids"])
        idx = np.arange(plen - 1, len(ids) - 1)
        want = np.asarray(logp[idx, np.asarray(s["output_ids"])])
        got = np.asarray(s["output_logprobs"], np.float32)
        err = float(np.max(np.abs(want - got)))
        worst = max(worst, err)
        rows.append(dict(qid=s["qid"], prompt=plen, new=len(s["output_ids"]),
                         max_abs_logprob_err=round(err, 4)))
    # bf16 compute on both sides, different kernels and summation order:
    # a few 1e-2. A wrong page, mask or position moves logprobs by ~1
    # (logits have a standard deviation of ~0.8 under these weights).
    tol = 0.15
    return dict(ok=bool(worst <= tol), tol=tol, samples=rows,
                platform=jax.default_backend(),
                error="" if worst <= tol else f"max logprob error {worst:.3f} > {tol}")


# ----------------------------------------------------------------------
# Legs: train (sync PPO) and async (async PPO), through the launchers
# ----------------------------------------------------------------------

_STEP = re.compile(r"step (\d+) \(epoch [\d.]+\) e2e=([\d.]+)s stats=(\{.*\})\s*$")


def run_launcher(ctx, name, script, overrides, timeout_s):
    log_path = os.path.join(ctx["work"], f"{name}.log")
    env = dict(ctx["env"])
    env["AREAL_FILEROOT"] = os.path.join(ctx["work"], f"fileroot-{name}")
    common = [
        "experiment_name=chip-smoke", f"trial_name={name}",
        f"tokenizer_path={ctx['tok_dir']}", f"dataset.path={ctx['data_path']}",
        "actor.init_from_scratch=true", "actor.optimizer.lr=1e-4",
        "actor.optimizer.warmup_steps_proportion=0.0",
        # Fail, never absorb: no relaunch, no in-place worker restart.
        "recover_mode=disabled", "worker_restarts=0",
        "exp_ctrl.benchmark_steps=3",
        f"name_resolve_root={os.path.join(ctx['work'], 'nr-' + name)}",
        f"seed={SEED % 10000}",
    ]
    t0 = time.time()
    p = spawn([sys.executable, script, *common, *overrides], env, log_path)
    try:
        p.wait(timeout=timeout_s)
        timed_out = False
    except subprocess.TimeoutExpired:
        timed_out = True
    reap(p)
    text = read(log_path)
    res = {"ok": False, "wall_s": round(time.time() - t0, 1), "problems": []}
    if timed_out:
        res["problems"].append(f"timeout after {timeout_s}s:\n{tail(log_path)}")
    elif p.returncode != 0:
        res["problems"].append(f"exit code {p.returncode}:\n{tail(log_path)}")
    if re.search(r"restarting \S+ \(|relaunching with recovery", text):
        res["problems"].append("a worker was restarted or the run relaunched")
    steps = []
    for line in text.splitlines():
        m = _STEP.search(line)
        if m:
            stats = ast.literal_eval(m.group(3))
            steps.append(dict(step=int(m.group(1)), e2e_s=float(m.group(2)),
                              **stats.get("actor_train", {})))
    if len(steps) != 3:
        res["problems"].append(f"{len(steps)} steps logged, want 3")
    for s in steps:
        for k in ("ppo_actor/loss", "ppo_actor/grad_norm", "ppo_actor/update_norm"):
            v = s.get(k)
            if v is None or v != v or abs(v) == float("inf"):
                res["problems"].append(f"step {s['step']}: {k}={v}")
        if not s.get("ppo_actor/grad_norm", 0) > 0:
            res["problems"].append(f"step {s['step']}: zero gradient")
        if not s.get("ppo_actor/update_norm", 0) > 0:
            res["problems"].append(f"step {s['step']}: parameters did not change")
    res["steps"] = [
        {k.split("/")[-1]: v for k, v in s.items()
         if k in ("step", "e2e_s", "ppo_actor/loss", "ppo_actor/grad_norm",
                  "ppo_actor/update_norm", "ppo_actor/n_tokens",
                  "ppo_actor/tail_offpolicyness")}
        for s in steps
    ]
    return res, text, steps


def leg_train(ctx):
    cfg = dict(ctx["serve_cfg"])
    depth = cfg["n_layers"] if ctx["rehearsal"] else train_depth(cfg)
    cfg["n_layers"] = depth
    toy = ctx["rehearsal"]
    res, text, _ = run_launcher(ctx, "train", "training/main_sync_ppo.py", [
        f"actor.config={json.dumps(cfg)}",
        "allocation_mode=d1",
        f"train_batch_size={4 if toy else 8}",
        f"group_size={2 if toy else 4}",
        f"ppo.gconfig.max_new_tokens={16 if toy else 128}",
        "ppo.ppo_n_minibatches=2",
    ], 900)
    res["depth"] = f"{depth} of {ctx['serve_cfg']['n_layers']} layers"
    summary, problems = judge_ran(text, ctx, "splash", None, ["model_worker"])
    res.update(summary)
    res["problems"] += problems
    res["ok"] = not res["problems"]
    return res


def leg_async(ctx):
    if ctx["count"] < 4:
        return {"ok": True, "not_run": f"{ctx['count']} chip(s); needs 4"}
    cfg = dict(ctx["serve_cfg"])
    toy = ctx["rehearsal"]
    bucket = 16 if toy else 128
    res, text, steps = run_launcher(ctx, "async", "training/main_async_ppo.py", [
        f"actor.config={json.dumps(cfg)}",
        # Two one-chip generation servers and an fsdp-2 trainer.
        "allocation_mode=gen.d2t1+d1f2",
        "n_generation_servers=2", "n_rollout_workers=2",
        f"train_batch_size={4 if toy else 8}",
        f"group_size={2 if toy else 4}",
        f"ppo.gconfig.max_new_tokens={16 if toy else 128}",
        "ppo.ppo_n_minibatches=2",
        # Batch k+1 may be generated one version behind at most, so the
        # third batch needs a published update to have been cut over.
        "ppo.max_head_offpolicyness=1",
        "ppo.max_concurrent_rollouts=16",
        "gen_max_concurrent_requests=16",
        f"gen_max_seq_len={16 * bucket}",
        f"gen_prompt_bucket={bucket}",
        f"gen_kv_page_size={ctx['page']}",
        f"gen_decode_block_steps={4 if toy else 16}",
    ], 1500)
    res["depth"] = f"{cfg['n_layers']} of {cfg['n_layers']} layers (fsdp-2)"
    summary, problems = judge_ran(
        text, ctx, "splash", "kernel", ["model_worker", "generation_server"])
    res.update(summary)
    res["problems"] += problems
    if not ctx["rehearsal"]:
        want = {"generation_server/0": [0], "generation_server/1": [1],
                "model_worker/0": [2, 3]}
        if summary["owned_chips"] != want:
            res["problems"].append(
                f"owned chips {summary['owned_chips']} are not the allocation {want}")
    # Every server cut over to a published update ...
    by_pid = {str(pid): w for w, pid in summary["pids"].items()}
    versions = {by_pid.get(pid, pid): v for pid, v in summary["weight_versions"].items()}
    res["server_weight_version"] = versions
    for i in range(2):
        if versions.get(f"generation_server/{i}", 0) < 1:
            res["problems"].append(f"generation_server/{i} never cut over to a new version")
    # ... and later samples carry it: a batch trained at step s has
    # max(version_end) = s - 1 - tail_offpolicyness.
    carried = [s["step"] - 1 - s.get("ppo_actor/tail_offpolicyness", 1e9) for s in steps]
    res["max_sample_version_by_step"] = carried
    if not carried or max(carried) < 1:
        res["problems"].append("no trained sample carried a version > 0")
    res["ok"] = not res["problems"]
    return res


# ----------------------------------------------------------------------


CHILDREN = {"kernels": child_kernels, "serve_check": child_serve_check}
LEGS = {"kernels": leg_kernels, "serve": leg_serve, "train": leg_train,
        "async": leg_async}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy sizes on the CPU platform; proves nothing "
                         "about the chip and never reports ok")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="comma list of legs to run (default: all)")
    ap.add_argument("--keep-logs", metavar="DIR",
                    help="copy every process's log there before cleaning up")
    ap.add_argument("--child", choices=list(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--input", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child:
        with open(args.input) as f:
            payload = json.load(f)
        print("CHILD_RESULT " + json.dumps(CHILDREN[args.child](payload)), flush=True)
        return 0

    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        log("REHEARSAL on the CPU at toy size: this run proves nothing about "
            "the chip, whatever it prints.")

    from areal_tpu.bench.devices import probe_devices

    t_start = time.time()
    try:
        dev = probe_devices()
    except RuntimeError as e:
        print(f"chip_smoke: no device: {e}", file=sys.stderr)
        return 2
    if not rehearsal and dev["platform"] != "tpu":
        print(f"chip_smoke: jax found no accelerator (platform={dev['platform']}); "
              f"there is no CPU path — see --rehearse-on-cpu", file=sys.stderr)
        return 2
    log(f"platform={dev['platform']} device_kind={dev['kind']!r} count={dev['count']}")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # One persistent compilation cache for every process of every leg.
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(work, "xla_cache")
    tok_dir, data_path = make_workload(work)
    ctx = dict(
        work=work, env=env, rehearsal=rehearsal, platform=dev["platform"],
        count=dev["count"], tok_dir=tok_dir, data_path=data_path,
        page=8 if rehearsal else 128,
        serve_cfg=model_config(
            TOY_HF if rehearsal else QWEN25_1P5B_HF,
            "float32" if rehearsal else "bfloat16"),
    )
    c = ctx["serve_cfg"]
    log(f"model: hidden={c['hidden_dim']} heads={c['n_q_heads']}/{c['n_kv_heads']}"
        f"x{c['head_dim']} mlp={c['intermediate_dim']} vocab={c['vocab_size']} "
        f"layers={c['n_layers']} dtype={c['param_dtype']} seed={SEED}")

    results = {}
    try:
        for name in [n.strip() for n in args.legs.split(",") if n.strip()]:
            t0 = time.time()
            try:
                results[name] = LEGS[name](ctx)
            except Exception as e:
                import traceback

                results[name] = {"ok": False, "problems": [traceback.format_exc()]}
            results[name]["leg_wall_s"] = round(time.time() - t0, 1)
            print_leg(name, results[name])
    finally:
        for p in _groups:
            reap(p)
        if args.keep_logs:
            os.makedirs(args.keep_logs, exist_ok=True)
            for f in os.listdir(work):
                if f.endswith(".log"):
                    shutil.copy(os.path.join(work, f), args.keep_logs)
        shutil.rmtree(work, ignore_errors=True)

    ok = bool(results) and all(r["ok"] for r in results.values())
    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]}
    log("summary: " + json.dumps({
        "legs": {n: ("not run: " + r["not_run"]) if "not_run" in r
                 else ("ok" if r["ok"] else "FAILED") for n, r in results.items()},
        "wall_s": round(time.time() - t_start, 1),
        "device": device,
        "rehearsal": rehearsal,
        "claim": None,
    }))
    if rehearsal:
        log(json.dumps({"ok": False, "rehearsal_legs_ok": ok, "device": device,
                        "note": "CPU rehearsal: proves nothing about the chip"}))
        return 0 if ok else 1
    if not ok:
        return 1
    log(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
