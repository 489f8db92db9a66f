#!/usr/bin/env python3
"""Chip smoke: the trainer, the serving stack and the RL loop on one TPU chip
at Qwen2.5-1.5B widths, through the entry points a user would call.

    python chip_smoke.py            # one chip: device, train, serve, rl
    python chip_smoke.py --chips 4  # four chips: sharded sft + async-ppo world

The quickest proof that the system still starts on the chip. It claims no
speed: every time it prints is a smoke observation, not a benchmark result.

Contract (the builder's instructions, "The chip check"):

- the parent never imports JAX; each phase that needs the chip is ONE child
  process, run in turn (a chip belongs to one process at a time). All of
  them share one persistent compile cache, placed by the program's own
  ``areal_tpu.base.compile_cache.configure()``;
- weights and data come from ``--seed``; nothing needs the network;
- every phase prints one JSON object; the LAST line of stdout is
  ``{"ok": ..., "device": {"platform", "kind", "count"}}`` and nothing more;
- no CPU fallback: anything but a TPU fails the first phase, and any failed
  phase makes the exit code non-zero with ``"ok": false``;
- kernels are checked from OUTSIDE the program: the children run under JAX's
  own ``JAX_DUMP_IR_TO`` (the lowered program of every jit, written whether
  or not the compile cache hits) and ``JAX_LOG_COMPILES`` (compile seconds).
  A train step or decode chunk whose program has no ``tpu_custom_call``
  carrying the kernel's name ran the XLA path or the interpreter, and fails;
- a child that dumps its programs builds every one (the program store,
  ``areal_tpu/base/program_store.py``, leaves it on ``jax.jit``'s own
  path), so the serve phase runs one engine twice more WITHOUT the dump: the
  second start must load its programs built and serve what the first did.

``--rehearse`` runs the same control flow at a toy size on whatever device
JAX finds (the CPU in the sandbox), skips the device and kernel checks, and
ALWAYS exits non-zero with ``"ok": false``: it debugs this script, it proves
nothing about the chip.
"""

import argparse
import concurrent.futures
import functools
import glob
import hashlib
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".smoke_work")  # git-ignored scratch of this script

# R1-Distill-Qwen-1.5B / Qwen2.5-1.5B widths, as the benchmark's cells
# have them (benchmark/configs/r1d-qwen-1p5b.json)
ARCH_1P5B = dict(
    n_layers=28, n_q_heads=12, n_kv_heads=2, head_dim=128, hidden_dim=1536,
    intermediate_dim=8960, vocab_size=151936, use_attention_bias=True,
    dtype="bfloat16",
)
ARCH_TOY = dict(
    n_layers=2, n_q_heads=4, n_kv_heads=2, head_dim=8, hidden_dim=32,
    intermediate_dim=64, vocab_size=256, use_attention_bias=True,
    dtype="float32",
)
# Training memory on a 16 GB chip: bf16 params + bf16 Adam moments are
# 9.9 GiB at full depth. With remat_policy="dots_attn" the step's
# compiler-reported need is 18.6 GiB at 28 layers (refused) and fits only
# from 20 layers down; whole-layer remat ("full") peaks at 15.0 GiB of
# 15.75 (AOT compile for a described v5e, PR 21). Depth is kept, the remat
# policy gives way.
TRAIN_OVERRIDES = dict(
    remat_policy="full", loss_chunk_size=2048, attn_max_seqlen=512
)
# Served logprobs (bf16, paged, incremental) are held to a float32 dense
# recompute within twice what bf16 alone costs the dense forward on the
# same sequences, plus this floor (nats). A fixed 0.05 was the first guess;
# the chip showed 0.09 between two correct bf16 paths at 28 layers.
LOGPROB_FLOOR = 0.02


class PhaseFailed(Exception):
    def __init__(self, msg, device=None):
        super().__init__(msg)
        self.device = device  # what JAX found, when the device phase failed


def emit(obj):
    print(json.dumps(obj), flush=True)


def require(cond, msg, device=None):
    if not cond:
        raise PhaseFailed(msg, device)


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(phase_dir, extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_DUMP_IR_TO"] = os.path.join(phase_dir, "ir")
    env["JAX_LOG_COMPILES"] = "1"
    env["TPU_STDERR_LOG_LEVEL"] = env.get("TPU_STDERR_LOG_LEVEL", "2")
    env.update(extra or {})
    return env


def run_child(phase_dir, name, argv, timeout, extra_env=None):
    """Run one child to its end; stdout/stderr go to files of the phase."""
    os.makedirs(phase_dir, exist_ok=True)
    out_p = os.path.join(phase_dir, f"{name}.out")
    err_p = os.path.join(phase_dir, f"{name}.err")
    t0 = time.time()
    with open(out_p, "w") as out, open(err_p, "w") as err:
        try:
            rc = subprocess.run(
                argv, cwd=ROOT, env=child_env(phase_dir, extra_env),
                stdout=out, stderr=err, timeout=timeout,
            ).returncode
        except subprocess.TimeoutExpired:
            rc = 124
    return rc, out_p, err_p, time.time() - t0


def tail(path, n=25):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def last_json_line(path):
    with open(path) as f:
        lines = [l for l in f.read().splitlines() if l.startswith("{")]
    require(lines, f"no JSON line in {path}")
    return json.loads(lines[-1])


def helper(phase_dir, name, payload, timeout=900, run=None, extra_env=None):
    """Run one of this file's own `--child` bodies in a fresh process
    (``run``: this run's name, where a body runs more than once)."""
    os.makedirs(phase_dir, exist_ok=True)
    run = run or name
    arg = os.path.join(phase_dir, f"{run}.in.json")
    with open(arg, "w") as f:
        json.dump(payload, f)
    rc, out_p, err_p, secs = run_child(
        phase_dir, run,
        [sys.executable, os.path.abspath(__file__), "--child", name, arg],
        timeout, extra_env,
    )
    require(rc == 0, f"{run} child rc={rc}: {tail(err_p)}")
    return last_json_line(out_p), secs


_COMPILE_RE = re.compile(
    r"Finished XLA compilation of (jit\([^)]*\)) in ([0-9.eE+-]+) sec"
)


def compile_seconds(*err_paths):
    """Sum of JAX's own per-program compile (or cache-load) seconds."""
    seen = set()
    for p in err_paths:
        try:
            with open(p, errors="replace") as f:
                seen.update(_COMPILE_RE.findall(f.read()))
        except OSError:
            pass
    return round(sum(float(s) for _, s in seen), 2), len(seen)


def ir_programs(phase_dir, jit_name):
    return sorted(glob.glob(
        os.path.join(phase_dir, "ir", f"*_jit_{jit_name}_compile.mlir")
    ))


# the decode chunk's paged kernel by model, by the start of its name: K and
# V pages (raw or int8; a window layer's own program of it is
# ``paged_decode_window``), or the latent pool of a latent-attention family
# (``joyai_llm_flash``)
DECODE_KERNELS = ("paged_decode", "mla_decode")
# the kernel that puts the step's fresh K/V into the page pool
# (ops/pallas/kv_page_write.py), in the decode chunk and in admission's
# write program (``jit_kv_write``)
KV_WRITE_KERNEL = "kv_page_write"
# the kernel a decode step ends in (ops/pallas/fused_sample.py): the head
# streamed over vocabulary blocks and sampled from inside the pass
FUSED_SAMPLE_KERNEL = "fused_sample"
# the routed experts as one grouped matmul over the stacked weights
# (ops/pallas/moe_grouped.py); the smoke's model has no router, so the
# kernel runs alone, beside the einsums (``child_moegrouped``)
MOE_GROUPED_KERNEL = "moe_grouped"
# the decode step's delta-rule update of the per-slot matrix state
# (ops/pallas/kda_decode.py); no model of the smoke has such a layer, so the
# kernel runs alone, beside ``ops/kda.py:step_update`` (``child_kdadecode``)
KDA_DECODE_KERNEL = "kda_decode"
# the decode step's update of the per-slot recurrent state
# (ops/pallas/ssm_decode.py); the smoke's own model has no state-space
# layer, so a helper child drives a two-layer model of layer KINDS (one
# state-space, one attention layer) through the engine (``child_statespace``)
SSM_DECODE_KERNEL = "ssm_decode"
# (another helper child, ``child_zaya``, drives a two-layer model whose
# attention runs inside a convolved latent behind a top-1 expert layer: its
# decode chunk holds the paged kernels above and ``moe_grouped``)


def kernels_in(paths):
    """Pallas kernels lowered for the TPU compiler in these programs, by
    the ``name=`` of their ``pallas_call`` site (``flash_fwd*``,
    ``flash_bwd*``, ``paged_decode*``, ``mla_decode``): an interpreted
    kernel or an XLA-path dispatch leaves none."""
    names = set()
    for p in paths:
        with open(p, errors="replace") as f:
            text = f.read()
        if "tpu_custom_call" in text:
            names.update(re.findall(r'kernel_name = "([^"]+)"', text))
    return sorted(names)


def read_metrics(fileroot, exp, trial="t0"):
    p = os.path.join(fileroot, "logs", exp, trial, "metrics.jsonl")
    require(os.path.exists(p), f"no metrics at {p}")
    with open(p) as f:
        return [json.loads(l) for l in f if l.strip()]


def step_seconds(lines):
    ts = [l["time"] for l in lines]
    return [round(b - a, 3) for a, b in zip(ts, ts[1:])]


def save_failure_logs():
    """The tails of every child's stdout/stderr, where the chip tool brings
    them back from (``chiprun_out/`` is all that survives its machine)."""
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "chip_smoke_failure.txt"), "w") as f:
        for p in sorted(glob.glob(os.path.join(WORK, "**", "*.err"), recursive=True)
                        + glob.glob(os.path.join(WORK, "**", "*.out"), recursive=True)):
            f.write(f"\n===== {os.path.relpath(p, WORK)} =====\n{tail(p, 150)}")


def overrides_json(d):
    return json.dumps(d, separators=(",", ":"))


# --------------------------------------------------------------------------- #
# sizes
# --------------------------------------------------------------------------- #


def sizes(rehearse):
    if rehearse:
        return dict(
            arch=ARCH_TOY, param_dtype="float32", seq=32, n_seq=8,
            prompt=8, train_steps=5, lr=1e-3,
            train_overrides=dict(remat_policy="full", loss_chunk_size=16),
            slots=8, serve_seqlen=128, n_requests=8, serve_prompt=8,
            new_tokens=16,
            rl_prompts=2, rl_group=2, rl_prompt=8, rl_new=8, rl_layers=2,
            world_layers=2,
        )
    return dict(
        arch=ARCH_1P5B, param_dtype="bfloat16", seq=512, n_seq=8,
        prompt=128, train_steps=5, lr=1e-4,
        train_overrides=TRAIN_OVERRIDES,
        slots=16, serve_seqlen=512, n_requests=16, serve_prompt=32,
        new_tokens=64,
        rl_prompts=4, rl_group=4, rl_prompt=64, rl_new=64, rl_layers=28,
        world_layers=8,
    )


def rng_ids(seed, n, vocab):
    """Seeded token ids without numpy's import cost mattering: a small LCG
    is enough for synthetic prompts (ids in [1, vocab))."""
    x = (seed * 6364136223846793005 + 1442695040888963407) % (1 << 64)
    out = []
    for _ in range(n):
        x = (x * 6364136223846793005 + 1442695040888963407) % (1 << 64)
        out.append(1 + (x >> 33) % (vocab - 1))
    return out


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #


def phase_device(sz, args, want_chips):
    d = os.path.join(WORK, "device")
    info, secs = helper(d, "device", {"rehearse": args.rehearse})
    dev = {k: info[k] for k in ("platform", "kind", "count")}
    emit({"phase": "device", **info, "seconds": round(secs, 1)})
    if not args.rehearse:
        require(dev["platform"] == "tpu",
                f"JAX found {dev['platform']!r}, not a TPU", dev)
        require(dev["count"] >= want_chips,
                f"need {want_chips} chip(s), JAX sees {dev['count']}")
        require(info["memory_stats"],
                "device.memory_stats() reported nothing on the chip")
    require(info["packer"] == "native", "native packer did not build/load")
    return dev


def write_sft_data(path, sz, seed):
    vocab = sz["arch"]["vocab_size"]
    with open(path, "w") as f:
        for i in range(sz["n_seq"]):
            ids = rng_ids(seed * 1000 + i, sz["seq"], vocab)
            f.write(json.dumps({
                "qid": f"s{i}",
                "prompt_ids": ids[: sz["prompt"]],
                "answer_ids": ids[sz["prompt"]:],
            }) + "\n")


def sft_argv(sz, fileroot, data, exp, parallel="d1m1", arch=None):
    return [
        sys.executable, "-m", "areal_tpu.apps.main", "sft",
        f"experiment_name={exp}", "trial_name=t0", f"fileroot={fileroot}",
        f"dataset.path={data}", "dataset.name=prompt_answer",
        f"batch_size={sz['n_seq']}",
        f"max_tokens_per_mb={sz['n_seq'] * sz['seq']}",
        f"control.total_train_steps={sz['train_steps']}",
        f"model.arch={overrides_json(arch or sz['arch'])}",
        f"model.overrides={overrides_json(sz['train_overrides'])}",
        f"model.parallel={parallel}",
        f"model.param_dtype={sz['param_dtype']}",
        f"model.optimizer.lr={sz['lr']}",
    ]


def check_train_run(sz, args, d, fileroot, exp, err_p, jit_name="train_step"):
    """Shared by the one-chip train phase and the four-chip comparison."""
    lines = read_metrics(fileroot, exp)
    require(len(lines) == sz["train_steps"],
            f"{len(lines)} steps logged, wanted {sz['train_steps']}")
    losses = [l["sft/loss"] for l in lines]
    require(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    # step 1 runs at lr 0 (warm-up), so steps 1 and 2 see the same weights;
    # every step sees the same 8 sequences, so the loss has to fall
    require(lines[0]["sft/lr"] == 0.0, "first step was not the lr-0 no-op")
    require(losses[-1] < losses[0] - 1e-3,
            f"loss did not move after step 1: {losses}")
    programs = ir_programs(d, jit_name)
    require(len(programs) == 1,
            f"train step was lowered {len(programs)} times (recompile)")
    kernels = kernels_in(programs)
    if not args.rehearse:
        require(any(k.startswith("flash_fwd") for k in kernels)
                and any(k.startswith("flash_bwd") for k in kernels),
                f"compiled train step has no flash kernel: {kernels}")
        require("sft/hbm_peak_bytes_in_use" in lines[-1],
                "trainer logged no memory_stats gauges")
    err = tail(err_p, 400)
    require("native packer" not in err, "packer fell back to numpy")
    csec, n_prog = compile_seconds(err_p)
    return {
        "losses": [round(x, 4) for x in losses],
        "lr": [l["sft/lr"] for l in lines],
        "step_seconds": step_seconds(lines),
        "tflops_per_sec_logged": [
            round(l["sft/tflops_per_sec"], 2) for l in lines
        ],
        "compile_seconds": csec,
        "programs_compiled": n_prog,
        "train_step_lowerings": len(programs),
        "kernels": kernels,
        "packer": "native",
        "peak_hbm_gib": round(
            lines[-1].get("sft/hbm_peak_bytes_in_use", 0) / 2**30, 2
        ),
        "hbm_in_use_gib": round(
            lines[-1].get("sft/hbm_bytes_in_use", 0) / 2**30, 2
        ),
    }


def phase_train(sz, args):
    d = os.path.join(WORK, "train")
    os.makedirs(d, exist_ok=True)
    data = os.path.join(d, "sft.jsonl")
    write_sft_data(data, sz, args.seed)
    fileroot = os.path.join(d, "root")
    rc, _, err_p, secs = run_child(
        d, "sft", sft_argv(sz, fileroot, data, "smoke-sft"), timeout=1000
    )
    require(rc == 0, f"sft rc={rc}: {tail(err_p)}")
    out = check_train_run(sz, args, d, fileroot, "smoke-sft", err_p)
    emit({
        "phase": "train", "entry": "python -m areal_tpu.apps.main sft",
        "arch": sz["arch"], "overrides": sz["train_overrides"],
        "param_dtype": sz["param_dtype"],
        "tokens_per_step": sz["n_seq"] * sz["seq"],
        "depth_cut": None, **out, "seconds": round(secs, 1),
    })


def http_json(url, body=None, timeout=600):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def completion(base, prompt, max_tokens, stream):
    """One /v1/completions call; returns (status, text, usage|None)."""
    body = {"prompt": prompt, "max_tokens": max_tokens, "temperature": 0.0,
            "stream": stream}
    req = urllib.request.Request(
        base + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=600) as r:
        if not stream:
            d = json.loads(r.read())
            return r.status, d["choices"][0]["text"], d["usage"]
        text, done = "", False
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            frame = json.loads(line[6:])
            require("error" not in frame, f"SSE error frame: {frame}")
            text += frame["choices"][0].get("text", "")
        require(done, "SSE stream ended without [DONE]")
        return r.status, text, None


def ids_of(text):
    # the seeded tokenizer spells token i as "t<i>"
    return [int(w[1:]) for w in text.split()]


def wait_for_line(proc, path, needle, timeout):
    t0 = time.time()
    while time.time() - t0 < timeout:
        with open(path, errors="replace") as f:
            for line in f:
                if needle in line:
                    return line
        require(proc.poll() is None,
                f"server exited rc={proc.returncode} before {needle!r}")
        time.sleep(0.5)
    raise PhaseFailed(f"no {needle!r} within {timeout}s")


def stop(proc, grace=60):
    if proc.poll() is None:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(30)


def warm_start_pair(d, args):
    """A WARM START of the two-kind engine (child ``statespace``). A child
    that dumps its programs stays on ``jax.jit``'s own path
    (``base/program_store.py``), so the body runs twice WITHOUT the dump:
    the first builds its programs and stores them, the second must find
    every one BUILT, load it, and serve what the first served, token for
    token."""
    payload = {"seed": args.seed, "rehearse": args.rehearse}
    no_dump = {"JAX_DUMP_IR_TO": ""}
    built, _ = helper(d, "statespace", payload, run="statespace_built",
                      extra_env=no_dump)
    loaded, secs = helper(d, "statespace", payload, run="statespace_loaded",
                          extra_env=no_dump)
    require(loaded["logprobs"]["correct"]
            and loaded["served_digest"] == built["served_digest"]
            # (a rehearsal held to the CPU configures no cache, so no store)
            and (args.rehearse or (sum(built["store_hits_misses"]) > 0
                                   and loaded["store_hits_misses"][0] > 0
                                   and loaded["store_hits_misses"][1] == 0)),
            f"a warm start: the programs the store handed over are not "
            f"the ones built, or it handed none: built {built}, "
            f"loaded {loaded}")
    emit({"phase": "serve", "step": "warm_start", "warm_start": {
        "built_hits_misses": built["store_hits_misses"],
        "loaded_hits_misses": loaded["store_hits_misses"],
        "kernels": loaded["kernels"], "seconds": round(secs, 1)}})


def phase_serve(sz, args):
    d = os.path.join(WORK, "serve")
    os.makedirs(d, exist_ok=True)
    ckpt = os.path.join(d, "ckpt")
    t0 = time.time()
    made, ckpt_secs = helper(
        d, "ckpt", {"arch": sz["arch"], "seed": args.seed, "path": ckpt}
    )
    port = free_port()
    out_p, err_p = os.path.join(d, "gateway.out"), os.path.join(d, "gateway.err")
    vocab = sz["arch"]["vocab_size"]
    n_pairs = sz["n_requests"] // 2
    prompts = [
        rng_ids(args.seed * 77 + i, sz["serve_prompt"], vocab)
        for i in range(n_pairs)
    ]
    with open(out_p, "w") as out, open(err_p, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "areal_tpu.gateway", "--model-path", ckpt,
             "--port", str(port), "--slots", str(sz["slots"]),
             "--max-seqlen", str(sz["serve_seqlen"])],
            cwd=ROOT, env=child_env(d), stdout=out, stderr=err,
        )
    try:
        line = wait_for_line(proc, out_p, "gateway listening on", 900)
        ready_secs = time.time() - t0 - ckpt_secs
        backend = re.search(r"\(backend (http://[^)]+)\)", line).group(1)
        base = f"http://127.0.0.1:{port}"
        def wave():
            """Every prompt twice at once: buffered and SSE, all greedy."""
            t = time.time()
            with concurrent.futures.ThreadPoolExecutor(sz["n_requests"]) as ex:
                futs = [
                    ex.submit(completion, base, p, sz["new_tokens"], stream)
                    for p in prompts for stream in (False, True)
                ]
                return [f.result() for f in futs], time.time() - t

        results, first_wave = wave()     # carries the compiles
        again, second_wave = wave()
        require(all(r[0] == 200 for r in results + again), "a request != 200")
        outs = [ids_of(r[1]) for r in results]
        require(all(len(o) == sz["new_tokens"] for o in outs),
                f"token counts {[len(o) for o in outs]} != {sz['new_tokens']}")
        for r in results[0::2]:
            require(r[2]["completion_tokens"] == sz["new_tokens"]
                    and r[2]["prompt_tokens"] == sz["serve_prompt"],
                    f"usage wrong: {r[2]}")
        # Token-exact agreement between two greedy runs of one prompt is an
        # observation, not a check: at bf16 a random-weight model's top
        # logits are near-ties, and the two runs differ in admit bucket,
        # slot and prefix-cache state. What is CHECKED, below, is that every
        # greedy token is the dense reference's argmax to within tolerance.
        pairs_equal = sum(outs[2 * i] == outs[2 * i + 1] for i in range(n_pairs))
        waves_equal = sum(ids_of(r[1]) == o for r, o in zip(again, outs))
        # the gateway's OpenAI surface returns no logprobs ("logprobs":
        # null); the gen server's own /generate — what RL rollout workers
        # call — does. One sampled request (greedy logprobs are those of a
        # temperature-0 distribution, i.e. ~0) for the recompute below.
        st, sampled = http_json(backend + "/generate", {
            "rid": "smoke-lp", "input_ids": prompts[0],
            "sampling_params": {"max_new_tokens": sz["new_tokens"],
                                "temperature": 1.0},
        })
        require(st == 200 and len(sampled["output_ids"]) == sz["new_tokens"],
                f"/generate: {st} {len(sampled.get('output_ids', []))} tokens")
        _, metrics = http_json(backend + "/metrics_json")
    finally:
        stop(proc)
    require(proc.returncode == 0, f"gateway exit rc={proc.returncode}")
    chunks = ir_programs(d, "chunk")
    kernels = kernels_in(chunks)
    # admission's write program: ONE, whatever the buckets and table widths
    kv_writes = ir_programs(d, "kv_write")
    write_kernels = kernels_in(kv_writes)
    if not args.rehearse:
        require(any(k.startswith(DECODE_KERNELS) for k in kernels),
                f"decode chunk has no Pallas paged kernel: {kernels}")
        require(KV_WRITE_KERNEL in kernels and KV_WRITE_KERNEL in write_kernels,
                f"fresh K/V reaches the pool by the XLA scatter: chunk "
                f"{kernels}, admission's write {write_kernels}")
        require(len(kv_writes) == 1,
                f"admission lowered {len(kv_writes)} write programs, not one")
        require(metrics.get("engine_kv_write_tiles", 0) > 0,
                "engine_kv_write_tiles is 0 on /metrics_json")
        require("hbm_peak_bytes_in_use" in metrics,
                "gen server reported no memory_stats gauges")
        # the step ends in the fused head-and-sample kernel, by the
        # engine's own rule (one TPU device, a head in the serving dtype)
        require(FUSED_SAMPLE_KERNEL in kernels
                and metrics.get("fused_sample") is True,
                f"decode chunk samples from materialised logits: {kernels}, "
                f"fused_sample {metrics.get('fused_sample')}")
        require(metrics.get("engine_fused_rows", 0) > 0
                and metrics.get("engine_sampler_fallback_rows") == 0,
                "engine_fused_rows / engine_sampler_fallback_rows: "
                f"{metrics.get('engine_fused_rows')} / "
                f"{metrics.get('engine_sampler_fallback_rows')}")
    # the server has given the chip back: the KV write alone, kernel and
    # scatter into two copies of a small real pool, compared bit for bit
    kv_write, _ = helper(d, "kvwrite", {
        "arch": sz["arch"], "seed": args.seed, "rehearse": args.rehearse,
    })
    require(all(c["bit_equal"] and c["rows_changed"] == c["rows_written"]
                for c in kv_write["cases"]),
            f"kv_page_write and the XLA scatter leave different pools: "
            f"{kv_write['cases']}")
    # ... the paged-decode kernel alone against the gather path, on rows
    # whose prefetch chain crosses empty blocks, sorted and in slot order
    paged, _ = helper(d, "pageddecode", {
        "arch": sz["arch"], "seed": args.seed, "rehearse": args.rehearse,
    })
    require(all(c["max_abs_diff_vs_gather"] <= paged["tolerance"]
                for c in paged["cases"])
            and paged["sorted_rows_bit_equal_to_shuffled"],
            f"paged_decode disagrees with the gather path, or with itself "
            f"on the same rows in another order: {paged}")
    # ... and the decode epilogue alone: the fused kernel (compiled on the
    # chip, interpreted off it) against the head on the same hidden states
    fused, _ = helper(d, "fusedsample", {
        "arch": sz["arch"], "seed": args.seed, "slots": sz["slots"],
        "steps": 16, "draw_calls": 40 if args.rehearse else 200,
    })
    require(fused["logprob_max_abs_diff_vs_f32_head"] <= 1e-3
            and fused["greedy_row_is_the_argmax"],
            f"fused_sample disagrees with the head it streams: {fused}")
    # the chi-square's quantile at p ~ 1e-6 (z = 4.75) for the degrees of
    # freedom this run has (Wilson-Hilferty)
    df, v = fused["chi2_df"], 2 / (9 * fused["chi2_df"])
    require(fused["chi2"] <= df * (1 - v + 4.75 * v ** 0.5) ** 3,
            f"fused_sample's draws are not its softmax's: {fused}")
    # ... and the routed experts alone: the grouped-matmul kernel over one
    # layer of a stack against the einsums over that layer's slice
    grouped, _ = helper(d, "moegrouped", {
        "seed": args.seed, "rehearse": args.rehearse,
    })
    require(grouped["max_abs_diff_vs_einsums"] <= grouped["tolerance"]
            and (args.rehearse or grouped["kernel"] == MOE_GROUPED_KERNEL),
            f"moe_grouped disagrees with the einsums: {grouped}")
    # ... and the delta-rule update alone: the kernel over one layer of a
    # stacked state, in place, against the plain step on that layer's slice
    delta, _ = helper(d, "kdadecode", {
        "seed": args.seed, "rehearse": args.rehearse,
    })
    require(max(delta["max_abs_diff_out"], delta["max_abs_diff_state"])
            <= delta["tolerance"] and delta["other_layers_untouched"]
            and delta["inactive_row_untouched"]
            and (args.rehearse or delta["kernel"] == KDA_DECODE_KERNEL),
            f"kda_decode disagrees with the plain step: {delta}")
    # ... and a model of layer KINDS through the engine: one state-space
    # layer beside one attention layer (the served model above has one
    # kind), its log-probs against the token-by-token reference, its decode
    # chunk searched for the state update's kernel, the paged kernels at a
    # head of 64 and, its head being the embedding with the logits divided
    # by 8, the fused epilogue over the ``[V, E]`` array as stored
    hybrid, _ = helper(d, "statespace", {
        "seed": args.seed, "rehearse": args.rehearse,
    })
    require(hybrid["logprobs"]["correct"]
            and hybrid["state_snapshot_hits"] == 3
            and hybrid["prefix_hit_tokens"][1:] == hybrid["prefix_hit_tokens"][1:2] * 3
            and hybrid["prefix_hit_tokens"][1] > 0,
            f"state-space layers beside attention: served log-probs or the "
            f"snapshot path are off: {hybrid}")
    require(args.rehearse or all(
                any(k.startswith(want) for k in hybrid["kernels"])
                for want in (SSM_DECODE_KERNEL, "paged_decode",
                             KV_WRITE_KERNEL, FUSED_SAMPLE_KERNEL))
            and hybrid["fused_rows"] == hybrid["state_slots"]
            and hybrid["sampler_fallback_rows"] == 0,
            f"the two-kind model's decode chunk lacks a kernel, or its tied "
            f"head's rows did not end in the fused one: {hybrid['kernels']}, "
            f"fused_rows {hybrid['fused_rows']} of {hybrid['state_slots']}")
    warm_start_pair(d, args)
    # ... and a model of THREE SEGMENTS through the engine (family
    # ``phi4flash``, 8 layers at the published widths): Mamba-1 layers beside
    # window layers, one full layer whose K/V a cross layer shares, a gated
    # memory unit; prompts past the window, so both programs of the paged
    # kernel run and window pages go back while the full layer's stay
    yoco, _ = helper(d, "yoco", {
        "seed": args.seed, "rehearse": args.rehearse,
    })
    require(yoco["logprobs"]["correct"]
            and yoco["state_snapshot_hits"] == 3
            and yoco["prefix_hit_tokens"][1:] == yoco["prefix_hit_tokens"][1:2] * 3
            and yoco["prefix_hit_tokens"][1] > 0
            and yoco["window_pages_released"] > 0,
            f"a decoder-hybrid-decoder: served log-probs, the snapshot path "
            f"or the window's release are off: {yoco}")
    require(args.rehearse or all(
                any(k == want or k.startswith(want + ".")
                    for k in yoco["kernels"])
                for want in ("paged_decode", "paged_decode_window",
                             KV_WRITE_KERNEL, FUSED_SAMPLE_KERNEL)),
            f"the three-segment model's decode chunk lacks a kernel: "
            f"{yoco['kernels']}")
    # ... and a model whose attention runs inside a convolved latent behind
    # a top-1 expert layer with a skip (family ``zaya``): the per-slot carry
    # beside the page pool, its snapshot in the prefix cache, the router's
    # state through the layer scan
    latent, _ = helper(d, "zaya", {
        "seed": args.seed, "rehearse": args.rehearse,
    })
    require(latent["logprobs"]["correct"]
            and latent["state_snapshot_hits"] == 3
            and latent["prefix_hit_tokens"][1:] == latent["prefix_hit_tokens"][1:2] * 3
            and latent["prefix_hit_tokens"][1] > 0
            and latent["router_agreement_given_earlier_choices"] >= 0.9,
            f"attention in a convolved latent: served log-probs, the "
            f"router or the snapshot path are off: {latent}")
    require(args.rehearse or all(
                any(k.startswith(want) for k in latent["kernels"])
                for want in ("paged_decode", KV_WRITE_KERNEL,
                             FUSED_SAMPLE_KERNEL)
                + ((MOE_GROUPED_KERNEL,) if latent["moe_grouped"] else ())),
            f"the zaya model's decode chunk lacks a kernel: "
            f"{latent['kernels']}")
    # ... and a model with WINDOW kinds through the engine (family
    # ``afmoe``, one dense + three expert layers at Trinity-Mini's widths,
    # kinds S, S, S, F, a gate on attention's output): a period that
    # crosses the dense and the expert stack, prompts past the window so
    # both programs of the paged kernel run and window pages go back
    trinity, _ = helper(d, "trinity", {
        "seed": args.seed, "rehearse": args.rehearse,
    })
    require(trinity["logprobs"]["correct"]
            and not trinity["control_full_attention_correct"]
            and trinity["prefix_hit_tokens"][1:] == trinity["prefix_hit_tokens"][1:2] * 3
            and trinity["prefix_hit_tokens"][1] > 0
            and trinity["window_pages_released"] > 0,
            f"window and full layers across two stacks: served log-probs, "
            f"the prefix hits or the window's release are off: {trinity}")
    require(args.rehearse or all(
                any(k == want or k.startswith(want + ".")
                    for k in trinity["kernels"])
                for want in ("paged_decode", "paged_decode_window",
                             KV_WRITE_KERNEL)),
            f"the afmoe model's decode chunk lacks a kernel: "
            f"{trinity['kernels']}")
    # the server has given the chip back: dense recompute in its own child
    ref, ref_secs = helper(d, "recompute", {
        "ckpt": ckpt,
        "seqs": [{"tokens": prompts[0] + sampled["output_ids"]}] + [
            {"tokens": prompts[i // 2] + o} for i, o in enumerate(outs)
        ],
        "n_prompt": sz["serve_prompt"],
    })
    # yardstick: how far the served dtype alone moves a logprob of the
    # DENSE forward against float32, over the same sequences
    yard = max(
        abs(a - b) for s in ref["seqs"]
        for a, b in zip(s["lp_served_dtype"], s["lp"])
    )
    tol = 2 * yard + LOGPROB_FLOOR
    diffs = [
        abs(a - b)
        for a, b in zip(sampled["output_logprobs"], ref["seqs"][0]["lp"])
    ]
    lp_diff = max(diffs)
    greedy_gap = max(
        m - l for s in ref["seqs"][1:] for m, l in zip(s["lp_max"], s["lp"])
    )
    require(lp_diff <= tol,
            f"served logprobs differ from the float32 dense recompute by "
            f"{lp_diff} (mean {sum(diffs) / len(diffs)}); tolerance {tol} "
            f"= 2 x dense {sz['arch']['dtype']} error {yard} + {LOGPROB_FLOOR}")
    require(greedy_gap <= tol,
            f"a greedy token is {greedy_gap} nats below the float32 dense "
            f"argmax; tolerance {tol}")
    csec, n_prog = compile_seconds(err_p)
    emit({
        "phase": "serve", "entry": "python -m areal_tpu.gateway --model-path",
        "arch": sz["arch"], "depth_cut": None,
        "requests": 2 * sz["n_requests"] + 1,
        "concurrent": sz["n_requests"], "new_tokens": sz["new_tokens"],
        "buffered_eq_sse_pairs": f"{pairs_equal} of {n_pairs}",
        "second_wave_eq_first": f"{waves_equal} of {len(outs)}",
        "first_wave_seconds": round(first_wave, 2),
        "second_wave_seconds": round(second_wave, 2),
        "logprob_max_abs_diff_vs_dense_f32": round(lp_diff, 5),
        "logprob_mean_abs_diff_vs_dense_f32": round(
            sum(diffs) / len(diffs), 5),
        "greedy_max_gap_vs_dense_f32_argmax": round(greedy_gap, 5),
        "dense_served_dtype_vs_f32_max_abs": round(yard, 5),
        "logprob_tolerance": round(tol, 5),
        "logprob_tolerance_rule": "2 x (dense forward in the served dtype "
        f"vs float32, max abs) + {LOGPROB_FLOOR} nats",
        "decode_chunk_lowerings": len(chunks), "kernels": kernels,
        "kv_write_lowerings": len(kv_writes), "kv_write_kernels": write_kernels,
        "kv_write_tiles": metrics.get("engine_kv_write_tiles"),
        "kv_write_vs_scatter": kv_write["cases"],
        "paged_decode_vs_gather": paged,
        "kv_dtype": metrics.get("kv_dtype"),
        "fused_sample": metrics.get("fused_sample"),
        "fused_rows": metrics.get("engine_fused_rows"),
        "fused_sample_vs_head": fused,
        "moe_grouped_vs_einsums": grouped,
        "state_space_model": hybrid,
        "window_kinds_across_two_stacks": trinity,
        "peak_hbm_gib": round(
            metrics.get("hbm_peak_bytes_in_use", 0) / 2**30, 2),
        "checkpoint_gib": round(made["bytes"] / 2**30, 2),
        "checkpoint_seconds": round(ckpt_secs, 1),
        "server_ready_seconds": round(ready_secs, 1),
        "compile_seconds": csec, "programs_compiled": n_prog,
        "recompute_seconds": round(ref_secs, 1),
        "seconds": round(time.time() - t0, 1),
    })


def write_prompt_data(path, n, plen, vocab, seed):
    with open(path, "w") as f:
        for i in range(n):
            f.write(json.dumps({
                "query_id": f"q{i}",
                "prompt_ids": rng_ids(seed * 31 + i, plen, vocab),
                "task": "math", "solutions": ["\\boxed{7}"],
            }) + "\n")


def ppo_overrides(sz):
    return [
        f'gconfig={{"n": {sz["rl_group"]}, "max_new_tokens": {sz["rl_new"]}}}',
        'ppo={"ppo_n_minibatches": 1, "disable_value": true,'
        ' "group_adv_norm": true, "adv_norm": false, "kl_ctl": 0.0,'
        ' "use_decoupled_loss": true, "recompute_logprob": true,'
        f' "group_size": {sz["rl_group"]}}}',
        "use_ref_model=false",
    ]


def phase_rl(sz, args):
    d = os.path.join(WORK, "rl")
    os.makedirs(d, exist_ok=True)
    arch = dict(sz["arch"], n_layers=sz["rl_layers"])
    data = os.path.join(d, "prompts.jsonl")
    write_prompt_data(data, sz["rl_prompts"], sz["rl_prompt"],
                      arch["vocab_size"], args.seed)
    fileroot = os.path.join(d, "root")
    tok_dir = os.path.join(d, "tokenizer")
    helper(d, "tokenizer", {"path": tok_dir, "vocab_size": arch["vocab_size"]})
    rounds = 2
    overrides = {k: v for k, v in sz["train_overrides"].items()
                 if k != "attn_max_seqlen"}
    rc, _, err_p, secs = run_child(d, "sync_ppo", [
        sys.executable, "-m", "areal_tpu.apps.main", "sync-ppo",
        "experiment_name=smoke-rl", "trial_name=t0", f"fileroot={fileroot}",
        f"dataset.path={data}", f"batch_size={sz['rl_prompts']}",
        f"tokenizer_path={tok_dir}",
        "max_tokens_per_mb=4096", f"control.total_train_steps={rounds}",
        f"actor.arch={overrides_json(arch)}",
        f"actor.overrides={overrides_json(overrides)}",
        f"actor.param_dtype={sz['param_dtype']}",
        f"actor.optimizer.lr={sz['lr']}",
        *ppo_overrides(sz),
    ], timeout=1000)
    require(rc == 0, f"sync-ppo rc={rc}: {tail(err_p)}")
    lines = read_metrics(fileroot, "smoke-rl")
    require(len(lines) == rounds, f"{len(lines)} rounds logged")
    n_seqs = sz["rl_prompts"] * sz["rl_group"]
    for l in lines:
        require(math.isfinite(l["sync_ppo/actor_loss"]),
                f"actor loss not finite: {l}")
        require(l["sync_ppo/n_seqs_consumed"] == n_seqs,
                f"consumed {l['sync_ppo/n_seqs_consumed']} != {n_seqs}")
    # the verifier really graded: some rollouts right, some wrong, so the
    # group-normalised advantages are not all zero and the step has a
    # gradient
    require(any(-1.0 < l["sync_ppo/reward_mean"] < 1.0 for l in lines),
            f"rewards degenerate: {[l['sync_ppo/reward_mean'] for l in lines]}")
    require(any(l["sync_ppo/grad_norm"] > 0 for l in lines),
            "no PPO step had a gradient")
    steps = ir_programs(d, "train_step")
    require(len(steps) == 1, f"PPO step lowered {len(steps)} times")
    kernels = kernels_in(glob.glob(os.path.join(d, "ir", "*.mlir")))
    if not args.rehearse:
        require(any(k.startswith("flash_fwd") for k in kernels_in(steps)),
                f"PPO train step has no flash kernel: {kernels_in(steps)}")
    csec, n_prog = compile_seconds(err_p)
    emit({
        "phase": "rl", "entry": "python -m areal_tpu.apps.main sync-ppo",
        "arch": arch,
        "depth_cut": (None if arch["n_layers"] == sz["arch"]["n_layers"]
                      else f"{arch['n_layers']} of {sz['arch']['n_layers']}"),
        "rounds": rounds, "seqs_per_round": n_seqs,
        "new_tokens": sz["rl_new"],
        "actor_loss": [l["sync_ppo/actor_loss"] for l in lines],
        "reward_mean": [l["sync_ppo/reward_mean"] for l in lines],
        "grad_norm": [round(l["sync_ppo/grad_norm"], 4) for l in lines],
        "gen_seconds": [round(l["sync_ppo/timeperf/gen"], 2) for l in lines],
        "round_seconds": [round(l["sync_ppo/timeperf/e2e"], 2) for l in lines],
        "train_step_lowerings": len(steps), "kernels": kernels,
        "compile_seconds": csec, "programs_compiled": n_prog,
        "seconds": round(secs, 1),
    })


# --------------------------------------------------------------------------- #
# --chips 4
# --------------------------------------------------------------------------- #

# sharded vs one-chip per-step loss (bf16, another reduction order)
LOSS_TOL_REL = 0.02


def hlo_entry_param_bytes(dump_dir, module_re):
    """Per-device bytes of the compiled program's entry parameters, read
    from XLA's after-optimizations text dump; plus its collectives."""
    item = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "f16": 2,
            "s8": 1, "u8": 1, "s64": 8, "u64": 8}
    best = None
    for p in glob.glob(os.path.join(dump_dir, "*after_optimizations.txt")):
        if re.search(module_re, os.path.basename(p)):
            if best is None or os.path.getsize(p) > os.path.getsize(best):
                best = p
    require(best, f"no XLA dump matching {module_re} in {dump_dir}")
    with open(best, errors="replace") as f:
        text = f.read()
    entry = text[text.index("ENTRY"):]
    total = 0
    for dt, dims in re.findall(
        r"= (\w+)\[([\d,]*)\][^ ]* parameter\(\d+\)", entry
    ):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * item.get(dt, 4)
    coll = {
        k: len(re.findall(rf"\b{k}(?:-start)?\(", text))
        for k in ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
    }
    return total, coll


def phase_sharded_sft(sz, args):
    d = os.path.join(WORK, "sharded")
    os.makedirs(d, exist_ok=True)
    data = os.path.join(d, "sft.jsonl")
    write_sft_data(data, sz, args.seed)
    runs = {}
    for tag, par in (("four_chips", "d1f2m2"), ("one_chip", "d1m1")):
        rd = os.path.join(d, tag)
        fileroot = os.path.join(rd, "root")
        dump = os.path.join(rd, "xla")
        # the compile cache is off here so that XLA really compiles and its
        # text dump (the only place collectives show) exists
        rc, _, err_p, secs = run_child(
            rd, "sft", sft_argv(sz, fileroot, data, f"smoke-{tag}", par),
            timeout=1200,
            extra_env={
                "JAX_ENABLE_COMPILATION_CACHE": "false",
                "XLA_FLAGS": (
                    os.environ.get("XLA_FLAGS", "")
                    + f" --xla_dump_to={dump} --xla_dump_hlo_as_text"
                    " --xla_dump_hlo_module_re=.*train_step.*"
                ).strip(),
            },
        )
        require(rc == 0, f"sft {par} rc={rc}: {tail(err_p)}")
        out = check_train_run(sz, args, rd, fileroot, f"smoke-{tag}", err_p)
        out["param_bytes_per_device"], out["collectives"] = (
            hlo_entry_param_bytes(dump, "train_step")
        )
        out["seconds"] = round(secs, 1)
        runs[tag] = out
    four, one = runs["four_chips"], runs["one_chip"]
    rel = max(
        abs(a - b) / max(abs(b), 1e-9)
        for a, b in zip(four["losses"], one["losses"])
    )
    require(rel <= LOSS_TOL_REL,
            f"sharded vs one-chip losses differ by {rel}: "
            f"{four['losses']} vs {one['losses']}")
    require(sum(four["collectives"].values()) > 0,
            "four-chip step has no collectives")
    require(sum(one["collectives"].values()) == 0,
            "one-chip step has collectives")
    ratio = four["param_bytes_per_device"] / one["param_bytes_per_device"]
    require(ratio < 0.5, f"per-device argument bytes ratio {ratio}: "
            "sharded leaves are not sharded")
    if not args.rehearse:
        require(four["hbm_in_use_gib"] < 0.5 * one["hbm_in_use_gib"],
                "device 0 holds as much on four chips as on one")
    emit({
        "phase": "sharded_sft", "entry": "python -m areal_tpu.apps.main sft",
        "arch": sz["arch"], "depth_cut": None, "parallel": "d1f2m2 vs d1m1",
        "loss_max_rel_diff": round(rel, 5), "loss_tolerance_rel": LOSS_TOL_REL,
        "per_device_param_bytes_ratio": round(ratio, 3),
        "four_chips": four, "one_chip": one,
    })


def phase_async_world(sz, args):
    d = os.path.join(WORK, "world")
    os.makedirs(d, exist_ok=True)
    arch = dict(sz["arch"], n_layers=sz["world_layers"])
    data = os.path.join(d, "prompts.jsonl")
    n_prompts = 8
    write_prompt_data(data, n_prompts, sz["rl_prompt"], arch["vocab_size"],
                      args.seed)
    fileroot = os.path.join(d, "root")
    steps = 3
    overrides = {k: v for k, v in sz["train_overrides"].items()
                 if k != "attn_max_seqlen"}
    cpu = ["gen.device=cpu", "trainer_device=cpu"] if args.rehearse else []
    rc, out_p, err_p, secs = run_child(d, "async_ppo", [
        sys.executable, "-m", "areal_tpu.apps.main", "async-ppo",
        "experiment_name=smoke-world", "trial_name=t0",
        f"fileroot={fileroot}", f"dataset.path={data}",
        f"train_batch_size={sz['rl_prompts']}", "max_tokens_per_mb=4096",
        f"control.total_train_steps={steps}",
        "control.ckpt_freq_steps=null", "control.ckpt_freq_secs=null",
        f"actor.arch={overrides_json(arch)}",
        f"actor.overrides={overrides_json(overrides)}",
        "actor.parallel=d1f2m1", f"actor.param_dtype={sz['param_dtype']}",
        f"actor.optimizer.lr={sz['lr']}",
        "gen.n_servers=1", "gen.tp_size=2", f"gen.max_slots={sz['slots']}",
        f"gen.max_seqlen={sz['serve_seqlen']}",
        f"gen.max_new_tokens_cap={sz['rl_new']}",
        "rollout.n_workers=1",
        f"rollout.max_concurrent_tasks={sz['slots']}",
        f"rollout.new_tokens_per_chunk={sz['rl_new']}",
        "manager.max_head_offpolicyness=4", "recover_mode=disabled",
        *ppo_overrides(sz), *cpu,
    ], timeout=1500)
    require(rc == 0, f"async-ppo rc={rc}: {tail(err_p)}\n{tail(out_p)}")
    lines = read_metrics(fileroot, "smoke-world")
    require(len(lines) == steps, f"{len(lines)} train steps logged")
    require(all(math.isfinite(l["ppo/actor_loss"]) for l in lines),
            "actor loss not finite")
    owners = []
    with open(out_p, errors="replace") as f:
        for line in f:
            if line.startswith('{"areal_devices"'):
                owners.append(json.loads(line)["areal_devices"])
    require({o["role"] for o in owners} == {"gen_server/0", "trainer"},
            f"chip owners announced: {[o['role'] for o in owners]}")
    if not args.rehearse:
        sets = [set(o["visible_chips"] or ()) for o in owners]
        require(all(len(s) == 2 for s in sets) and not (sets[0] & sets[1]),
                f"chip sets not disjoint pairs: {owners}")
        require(all(o["platform"] == "tpu" and len(o["ids"]) == 2
                    for o in owners), f"owners do not see 2 TPU chips: {owners}")
    gs = os.path.join(fileroot, "logs", "smoke-world", "t0",
                      "gen_server_0.json")
    require(os.path.exists(gs), "gen server dumped no metrics")
    with open(gs) as f:
        g = json.load(f)
    require(g["version"] > 1,
            f"gen server weight version stayed at {g['version']}")
    emit({
        "phase": "async_ppo_world",
        "entry": "python -m areal_tpu.apps.main async-ppo",
        "arch": arch,
        "depth_cut": f"{arch['n_layers']} of {sz['arch']['n_layers']} layers",
        "layout": "gen server tp=2 on 2 chips, trainer d1f2m1 on 2 chips, "
                  "manager + rollout worker on the CPU",
        "owners": owners, "train_steps": steps,
        "actor_loss": [l["ppo/actor_loss"] for l in lines],
        "gen_server_version": g["version"],
        "n_weight_updates": g.get("n_weight_updates"),
        "gen_tokens": g.get("gen_tokens"),
        "seconds": round(secs, 1),
    })


# --------------------------------------------------------------------------- #
# children (fresh processes; the only code here that imports JAX)
# --------------------------------------------------------------------------- #


def child_device(arg):
    from areal_tpu.base import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_stats": None, "packer": None,
        "jax": jax.__version__, "compile_cache_dir": cache_dir,
    }
    if devs[0].platform == "tpu" or arg["rehearse"]:
        from areal_tpu import native
        from areal_tpu.base import hbm

        # the packer is built from packer.cpp here, never loaded from a
        # file git would not commit
        for so in glob.glob(
            os.path.join(ROOT, "areal_tpu", "native", "_packer*.so")
        ):
            os.unlink(so)
        info["packer"] = "native" if native.available() else "numpy"
        info["memory_stats"] = hbm.device_memory_stats(devs[0])
    emit(info)


def write_tokenizer(path, vocab_size, spell):
    """A seeded word-level tokenizer (no download): token i is spelled
    ``spell(i)``, and decoding joins spellings with spaces."""
    from tokenizers import Tokenizer, models, pre_tokenizers
    from transformers import PreTrainedTokenizerFast

    tok = Tokenizer(models.WordLevel(
        {spell(i): i for i in range(vocab_size)}, unk_token=spell(0)
    ))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    PreTrainedTokenizerFast(tokenizer_object=tok).save_pretrained(path)


def child_tokenizer(arg):
    # For the RL loop's verifier: every token spells a boxed answer, 7 for
    # even ids and 8 for odd ones, so a rollout is "correct" (the dataset's
    # solution is \\boxed{7}) iff its LAST token is even — about half of
    # them, which gives GRPO groups rewards that differ.
    os.makedirs(arg["path"], exist_ok=True)
    write_tokenizer(arg["path"], arg["vocab_size"],
                    lambda i: f"\\boxed{{{7 + i % 2}}}#{i}")
    emit({"path": arg["path"]})


def child_ckpt(arg):
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax

    from areal_tpu.models import hf as hf_conv
    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import ModelConfig

    cfg = ModelConfig(**arg["arch"])
    params = tfm.init_params(cfg, jax.random.key(arg["seed"]), dtype=cfg.dtype)
    shutil.rmtree(arg["path"], ignore_errors=True)
    hf_conv.save_hf_checkpoint(params, cfg, "qwen2", arg["path"])
    # so the text surface of /v1/completions carries exact token ids:
    # token i is spelled "t<i>"
    write_tokenizer(arg["path"], cfg.vocab_size, lambda i: f"t{i}")
    emit({"bytes": os.path.getsize(
        os.path.join(arg["path"], "model.safetensors"))})


def child_recompute(arg):
    """The plain reference: XLA dense attention over the whole sequence,
    once in the served dtype and once in float32 at full matmul precision.
    The second is the truth; the first says how far bf16 alone moves a
    logprob at this depth — the yardstick the served values are held to."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import dataclasses

    import jax
    import jax.numpy as jnp

    from areal_tpu.models import hf as hf_conv
    from areal_tpu.models import transformer as tfm

    cfg, host = hf_conv.load_hf_checkpoint(arg["ckpt"])
    cfg = dataclasses.replace(cfg, use_flash_attention=False)
    n_prompt = arg["n_prompt"]

    def run(dtype):
        c = dataclasses.replace(cfg, dtype=dtype)
        params = jax.tree.map(lambda x: jnp.asarray(x, dtype), host)

        @jax.jit
        def logprobs(params, ids):  # params as an ARGUMENT: closed over,
            T = ids.shape[0]        # the weights become program constants
            logits = tfm.forward_packed(
                params, c, ids, jnp.ones((T,), jnp.int32),
                jnp.arange(T, dtype=jnp.int32), remat=False,
            )
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nxt = jnp.take_along_axis(lp[:-1], ids[1:, None], axis=-1)[:, 0]
            return nxt, jnp.max(lp[:-1], axis=-1)

        out = []
        with jax.default_matmul_precision("float32"):
            for s in arg["seqs"]:
                lp, lp_max = logprobs(
                    params, jnp.asarray(s["tokens"], jnp.int32)
                )
                # position t predicts token t+1: generated tokens start at
                # n_prompt
                out.append((
                    [float(x) for x in lp[n_prompt - 1:]],
                    [float(x) for x in lp_max[n_prompt - 1:]],
                ))
        return out

    served_dtype = run(cfg.dtype)
    f32 = run("float32")
    emit({"seqs": [
        {"lp_served_dtype": a[0], "lp": b[0], "lp_max": b[1]}
        for a, b in zip(served_dtype, f32)
    ]})


def child_kvwrite(arg):
    """Fresh rows into two copies of a small pool of the model's K/V
    geometry and of a latent-shaped one (one stream of 640: no latent
    model is served here, so this is as far as the chip sees that shape),
    one through ``kv_page_write`` and one through the XLA scatter that is
    its reference: a decode step's single tokens at the edges of tiles
    and pages, then an admission wave's runs and a verify pass's."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import transformer as tfm
    from areal_tpu.ops.pallas.kv_page_write import tile_rows

    arch = arg["arch"]
    dtype = jnp.dtype(arch["dtype"])
    R = tile_rows(dtype)
    page = 8 * R
    lat_w = 16 if arg["rehearse"] else 640
    pools = {
        "kv": (arch["n_layers"], 2, arch["n_kv_heads"], arch["head_dim"]),
        "latent": (5, 1, 1, lat_w),
    }
    rng = np.random.default_rng(arg["seed"])
    B, M = 8, 3
    table = jnp.asarray(1 + np.arange(B * M).reshape(B, M), jnp.int32)
    ends = [0, R - 1, R, page - 1, page, 2 * page + 5, 7, 3 * page - 1]
    writes = [      # (chunk, start, count)
        (1, ends, [1, 1, 1, 1, 1, 1, 0, 1]),
        (8 * R, [0, page, R + 5, 3, 2 * page, page - R, 0, 40],
         [8 * R, 8 * R, 8 * R, 6 * R + 4, 3 * R + 9, R, 0, 1]),
        (5, [R - 2, page - 3, 0, 11, page, 2 * page - 1, 9, 30],
         [5, 5, 3, 0, 1, 4, 5, 2]),
    ]
    kernel = jax.jit(
        lambda c, *a: tfm._write_chunk_kv(c, *a, use_pallas=True))
    scatter = jax.jit(tfm._scatter_chunk_kv)
    cases = []
    for kind, (L, S, H, W) in pools.items():
        pool = jnp.asarray(
            rng.standard_normal((L, 1 + B * M, S, H, page, W)), dtype)
        for C, start, count in writes:
            ks = jnp.asarray(rng.standard_normal((L, B, C, H, W)), dtype)
            vs = None if S == 1 else jnp.asarray(
                rng.standard_normal((L, B, C, H, W)), dtype)
            args = (ks, vs, table, jnp.asarray(start, jnp.int32),
                    jnp.asarray(count, jnp.int32))
            got = kernel(tfm.PagedKVCache(pages=pool), *args).pages
            want = scatter(tfm.PagedKVCache(pages=pool), *args).pages
            cases.append({
                "pool": kind, "shape": list(pool.shape), "chunk": C,
                "bit_equal": bool(jnp.array_equal(got, want)),
                "rows_changed": int(jnp.sum(jnp.any(got != pool, axis=-1))),
                "rows_written": sum(count) * L * S * H,
            })
            pool = got
    emit({"cases": cases})


def child_pageddecode(arg):
    """The paged-decode kernel alone (compiled on the chip, interpreted off
    it) against the XLA gather path over one pool of the model's K/V
    geometry: a heavy-tailed batch with whole blocks of empty rows in the
    middle, handed over sorted by length (as ``decode_step_paged`` does)
    and shuffled, so the kernel's prefetch chain over reached steps starts
    past empty blocks, crosses them, and alternates its two buffers over
    blocks that reach one step and blocks that reach every one. A row's
    result may not depend on the order."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.ops import paged_attention as paged_ops
    from areal_tpu.ops.pallas import paged_attention as pl_paged

    arch = arg["arch"]
    dtype = jnp.dtype(arch["dtype"])
    Hq, Hkv, D = arch["n_q_heads"], arch["n_kv_heads"], arch["head_dim"]
    B, page, M = (48, 8, 24) if arg["rehearse"] else (64, 128, 40)
    rng = np.random.default_rng(arg["seed"])
    cap = M * page - 1
    lens = np.minimum(rng.lognormal(np.log(cap / 6), 0.9, B), cap)
    lens = lens.astype(np.int32)
    lens[rng.integers(B)] = cap
    sb, kp = pl_paged.block_plan(B, Hkv, D, page, M, dtype)
    lens[2 * sb:4 * sb] = 0            # two whole blocks, in the middle
    lens[-2 * sb:-sb] = 0
    need = -(-lens // page)
    table = np.zeros((B, M), np.int32)
    pages = rng.permutation(int(need.sum())) + 1
    for b, at in enumerate(np.cumsum(need) - need):
        table[b, :need[b]] = pages[at:at + need[b]]
    pool = jnp.asarray(rng.standard_normal(
        (1, 1 + int(need.sum()), 2, Hkv, page, D)), dtype)
    q, ks, vs = (
        jnp.asarray(rng.standard_normal((B, h, D)), dtype)
        for h in (Hq, Hkv, Hkv))

    def attend(rows, use_pallas):
        return np.asarray(jax.jit(functools.partial(
            paged_ops.paged_decode_attention, use_pallas=use_pallas,
        ))(q[rows], ks[rows], vs[rows], pool, jnp.int32(0),
           jnp.asarray(table[rows]), jnp.asarray(lens[rows]),
        ).astype(jnp.float32))

    orders = {"shuffled": np.arange(B), "sorted": np.argsort(lens)}
    span, nblk = kp * page, -(-M // kp)
    got, cases = {}, []
    for name, rows in orders.items():
        got[name] = attend(rows, True)
        active, total = pl_paged.kernel_steps(lens[rows], sb, span, nblk)
        cases.append({
            "rows": name, "steps_active": active, "steps": total,
            "steps_chained": pl_paged.kernel_steps_chained(
                lens[rows], sb, span, nblk),
            "max_abs_diff_vs_gather": float(
                np.abs(got[name] - attend(rows, False)).max()),
        })
    emit({
        "slots": B, "heads": [Hq, Hkv, D], "page": page, "table": M,
        "plan": [sb, kp], "resident_tokens": int(lens.sum()),
        "cases": cases,
        # both paths accumulate in float32 and round once to the dtype
        "tolerance": 2e-5 if dtype == jnp.float32 else 2e-2,
        "sorted_rows_bit_equal_to_shuffled": bool(np.array_equal(
            got["sorted"], got["shuffled"][orders["sorted"]])),
        "compiled": jax.devices()[0].platform == "tpu",
    })


def child_fusedsample(arg):
    """One chunk's steps of the decode epilogue alone, at the served
    model's head: the fused head-and-sample kernel's log-probs of the
    tokens it drew against the head applied to the same hidden states
    (in float32: ``apply_head`` rounds the logits to the serving dtype
    first, and how far that moves a log-prob is reported beside it), a
    greedy row's token against the arg-max, and the chi-square of the
    uniform source the kernel uses HERE (the chip's PRNG where it is
    compiled): 256 rows that are one row are 256 draws a call."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.models import transformer as tfm
    from areal_tpu.models.config import ModelConfig
    from areal_tpu.ops import fused_sample as fs

    cfg = ModelConfig(**arg["arch"])
    dtype = jnp.dtype(cfg.dtype)
    B, E, V = arg["slots"], cfg.hidden_dim, cfg.vocab_size
    kx, kw, kr, kc = jax.random.split(jax.random.key(arg["seed"]), 4)
    w = (jax.random.normal(kw, (E, V), jnp.float32) * 2 / E ** 0.5).astype(dtype)
    params = {"head": {"weight": w}}
    temp = jnp.where(jnp.arange(B) == 0, 0.0, 1.0).astype(jnp.float32)

    @jax.jit
    def step(key, x):
        out = fs.fused_sample(
            key, x, tfm.head_weight(cfg, params), temp, temp <= 0.0,
            use_pallas=True)
        exact = jax.nn.log_softmax(
            jnp.dot(x, w, preferred_element_type=jnp.float32), axis=-1)
        served = jax.nn.log_softmax(tfm.apply_head(cfg, params, x), axis=-1)
        rows = jnp.arange(B)
        tok = out["tokens"]
        # a greedy row reports the log-prob of a temperature-0
        # distribution (~0): compare the sampled rows
        lp = jnp.where(temp > 0.0, out["logprobs"], exact[rows, tok])
        return (tok, jnp.max(jnp.abs(lp - exact[rows, tok])),
                jnp.max(jnp.abs(served[rows, tok] - exact[rows, tok])),
                jnp.argmax(exact[0]))

    diffs, rounding, greedy_ok, toks = [], [], True, set()
    for i, key in enumerate(jax.random.split(kr, arg["steps"])):
        x = jax.random.normal(
            jax.random.fold_in(kx, i), (B, E), jnp.float32).astype(dtype)
        tok, d, r, am = step(key, x)
        diffs.append(float(d))
        rounding.append(float(r))
        greedy_ok &= int(tok[0]) == int(am)
        toks.update(np.asarray(tok[1:]).tolist())
    # the uniform source's marginal
    R, Vs = 256, 1024
    x1 = jax.random.normal(kc, (1, 256), jnp.float32).astype(dtype)
    ws = (jax.random.normal(kw, (256, Vs), jnp.float32) * 3 / 16).astype(dtype)
    p = np.asarray(jax.nn.softmax(
        jnp.dot(x1, ws, preferred_element_type=jnp.float32)[0]), np.float64)
    draw = jax.jit(lambda k: fs.fused_sample(
        k, jnp.tile(x1, (R, 1)), ws, jnp.ones((R,)), jnp.zeros((R,), bool),
        block_size=256, use_pallas=True)["tokens"])
    counts = np.zeros(Vs)
    for key in jax.random.split(kc, arg["draw_calls"]):
        counts += np.bincount(np.asarray(draw(key)), minlength=Vs)
    n = counts.sum()
    big = np.flatnonzero(n * p >= 20)     # the rest pooled into one bin
    obs = np.append(counts[big], n - counts[big].sum())
    exp = np.append(n * p[big], n - (n * p[big]).sum())
    emit({
        "rows": B, "hidden": E, "vocab": V, "steps": arg["steps"],
        "logprob_max_abs_diff_vs_f32_head": max(diffs),
        "serving_dtype_head_moves_a_logprob_by": max(rounding),
        "greedy_row_is_the_argmax": bool(greedy_ok),
        "distinct_sampled_tokens": len(toks),
        "chi2": float(((obs - exp) ** 2 / exp).sum()), "chi2_df": len(obs) - 1,
        "draws": int(n),
        "compiled": jax.devices()[0].platform == "tpu",
    })


def child_moegrouped(arg):
    """The grouped-matmul kernel alone (compiled on the chip, interpreted
    off it) beside the einsums it stands in for, at JoyAI-LLM-Flash's
    routed experts (256 of 2048 x 768, 8 a token, sigmoid scores) on the
    256 rows of its decode step: the kernel is handed a 4-layer STACK and
    the index of one layer, the einsums that layer's slice."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops.activations import ACT2FN
    from areal_tpu.ops.pallas import moe_grouped as mg

    T, X, k, E, F, L = (
        (24, 8, 2, 128, 128, 2) if arg["rehearse"]
        else (256, 256, 8, 2048, 768, 4))
    dtype, layer = jnp.bfloat16, L - 2
    keys = jax.random.split(jax.random.key(arg["seed"]), 5)
    one = lambda key, shape: jax.random.normal(key, shape, dtype) * 0.02
    # one layer drawn and stacked: the draw's temporaries stay a layer's
    w_gate, w_up, w_down = (
        jnp.tile(one(key, shape)[None], (L, 1, 1, 1))
        for key, shape in zip(keys, ((X, E, F), (X, E, F), (X, F, E))))
    x = jax.random.normal(keys[3], (T, E), dtype)
    scores = jax.nn.sigmoid(jax.random.normal(keys[4], (T, X)))
    top_vals, top_idx = jax.lax.top_k(scores, k)
    top_vals = 2.5 * top_vals / top_vals.sum(-1, keepdims=True)
    onehot = jax.nn.one_hot(top_idx, X, dtype=jnp.float32)
    sizes = onehot.sum((0, 1))

    def einsums(x, w_gate, w_up, w_down):
        combine = (top_vals[:, :, None] * onehot).sum(1)
        h = ACT2FN["silu"](jnp.einsum("te,xef->txf", x, w_gate)) * jnp.einsum(
            "te,xef->txf", x, w_up)
        return jnp.einsum(
            "txf,xfe->te", h * combine.astype(h.dtype)[:, :, None], w_down)

    grouped = jax.jit(lambda *a: mg.moe_grouped(*a, activation="silu"))
    args = (x, top_idx, top_vals, w_gate, w_up, w_down, jnp.int32(layer))
    names = re.findall(
        r'kernel_name = "([^"]+)"', grouped.lower(*args).as_text())
    want = jax.jit(einsums)(x, w_gate[layer], w_up[layer], w_down[layer])
    got = grouped(*args)

    def ms(f, *a):
        f(*a).block_until_ready()
        t0 = time.time()
        for _ in range(10):
            y = f(*a)
        y.block_until_ready()
        return round((time.time() - t0) * 100, 3)

    f32 = lambda a: a.astype(jnp.float32)
    emit({
        "rows": T, "experts": X, "a_token": k, "widths": [E, F],
        "stack_layers": L, "layer": layer, "row_tile": mg.row_tile(T * k, X),
        "experts_hit": int((sizes > 0).sum()),
        "max_abs_diff_vs_einsums": float(jnp.abs(f32(got) - f32(want)).max()),
        # a few roundings to bf16 apart, at the outputs' own size
        "tolerance": float(jnp.abs(f32(want)).max()) * 2 ** -6,
        "kernel": names[0] if names else None,
        "ms_grouped": ms(grouped, *args),
        "ms_einsums_on_the_slice": ms(
            jax.jit(einsums), x, w_gate[layer], w_up[layer], w_down[layer]),
        "compiled": jax.devices()[0].platform == "tpu",
    })


def child_kdadecode(arg):
    """The delta-rule decode kernel alone (compiled on the chip, interpreted
    off it) beside ``ops/kda.py:step_update``, at Solar-Open2's linear
    layers (64 heads of 128 x 128 float32; 8 under ``--rehearse``): the
    kernel is handed a 3-layer stacked state, donated, and the index of one
    layer; the plain step that layer's slice. One row comes as an inactive
    row does (decay 1, beta 0) and must keep its state bit for bit."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp

    from areal_tpu.ops import kda as kda_ops
    from areal_tpu.ops.pallas import kda_decode as kd

    L, B, H, D = (3, 3, 8, 128) if arg["rehearse"] else (3, 32, 64, 128)
    layer = 1
    ks = jax.random.split(jax.random.key(arg["seed"]), 6)
    s_all = jax.random.normal(ks[0], (L, B, H, D, D), jnp.float32)
    q = kda_ops._l2norm(jax.random.normal(ks[1], (B, H, D))) * D ** -0.5
    k = kda_ops._l2norm(jax.random.normal(ks[2], (B, H, D)))
    v = jax.random.normal(ks[3], (B, H, D))
    a = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[4], (B, H, D))))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    a, beta = a.at[1].set(1.0), beta.at[1].set(0.0)     # an inactive row
    want_o, want_s = jax.jit(kda_ops.step_update)(
        s_all[layer], q, k, v, a, beta)
    before = jax.device_get(s_all)
    kernel = jax.jit(kd.kda_decode, donate_argnums=0)
    args = (jnp.int32(layer), q, k, v, a, beta)
    names = re.findall(
        r'kernel_name = "([^"]+)"', kernel.lower(s_all, *args).as_text())
    got_o, got_s = kernel(s_all, *args)
    got_s.block_until_ready()
    t0 = time.time()
    for _ in range(10):
        got2_o, got_s2 = kernel(got_s, *args)
        got_s = got_s2
    got_s.block_until_ready()
    ms = round((time.time() - t0) * 100, 3)
    # (the timing loop ran the update ten more times: compare the first)
    first_o, first_s = kernel(jnp.asarray(before), *args)
    after = jax.device_get(first_s)
    emit({
        "layers": L, "rows": B, "heads": H, "head_dim": D, "layer": layer,
        "max_abs_diff_out": float(jnp.abs(first_o - want_o).max()),
        "max_abs_diff_state": float(abs(after[layer] - want_s).max()),
        "tolerance": 1e-4,
        "other_layers_untouched": bool(
            (after[0] == before[0]).all() and (after[2] == before[2]).all()),
        "inactive_row_untouched": bool(
            (after[layer, 1] == before[layer, 1]).all()),
        "kernel": names[0] if names else None,
        "ms_a_call": ms,
        "state_bytes_a_call": 2 * 4 * B * H * D * D,
        "compiled": jax.devices()[0].platform == "tpu",
    })


def child_statespace(arg):
    """A model of two layer KINDS through the generation engine: one
    state-space layer and one attention layer at granite-4.0-h-micro's
    widths (toy widths under ``--rehearse``), seeded as the benchmark
    seeds it. A group of four shares a prompt (the first prefills in two
    chunks and files a snapshot of the recurrent state, the rest are
    seeded from it), one request generates alone; the served log-probs
    are held to the token-by-token float32 reference, and the decode
    chunk's program is searched for the kernels it should hold. The
    model's head is granite's: the embedding, its logits divided by 8, so
    on the chip the chunk ends in ``fused_sample`` over ``[V, E]`` and
    the served log-probs are that kernel's."""
    from areal_tpu.base import compile_cache, metrics

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.gen.engine import GenerationEngine, GenRequest
    from benchmark import correct, sut, weights
    from benchmark.drivers import rollout_state_inproc as drv

    with open(os.path.join(
            ROOT, "benchmark/configs/granite-4.0-h-micro.json")) as f:
        arch = json.load(f)
    arch.update(num_hidden_layers=2, layer_types=["mamba", "attention"])
    page, prompt_len, new = 128, 300, 48
    if arg["rehearse"]:
        arch.update(hidden_size=64, intermediate_size=128,
                    num_attention_heads=4, num_key_value_heads=2,
                    vocab_size=512, serving_dtype="float32")
        arch = drv._rehearsal_arch(arch)
        page, prompt_len, new = 16, 40, 12
    cfg = sut.model_config(arch, {})
    params = drv._state_space_init(
        weights.make_weights(
            sut.weight_shapes(cfg, cfg.dtype), arg["seed"],
            jnp.dtype(cfg.dtype)),
        arg["seed"])
    eng = GenerationEngine(
        cfg, params, max_slots=8, max_seqlen=4 * page + 64,
        max_new_tokens_cap=64, page_size=page, state_snapshots=2,
        seed=arg["seed"] % (2**31 - 1))
    rng = np.random.default_rng(arg["seed"])
    shared = rng.integers(1, cfg.vocab_size, prompt_len).tolist()
    alone = rng.integers(1, cfg.vocab_size, prompt_len // 3).tolist()
    prompts = {**{f"g{i}": shared for i in range(4)}, "alone": alone}
    for rid, p in prompts.items():
        eng.submit(GenRequest(
            rid=rid, input_ids=p, max_new_tokens=new, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(decode_steps=8)}
    (key,) = [k for k in eng._jit_chunk]
    chunk = eng._chunk_fn(*key)
    names = sorted(set(re.findall(
        r'kernel_name = "([^"]+)"',
        chunk.lower(
            eng.params, eng.state,
            jnp.asarray(eng._table_arg(slice(None), key[1])),
            jnp.zeros((key[2],), jnp.int32)).as_text())))
    samples = [{
        "tokens": prompts[rid] + list(o.output_ids),
        "start": len(prompts[rid]), "logprobs": o.output_logprobs,
    } for rid, o in sorted(outs.items())]
    stats = dict(eng.stats)
    eng.state = None
    verdict = correct.check_logprobs(params, arch, cfg.dtype, samples)
    emit({
        "layer_types": arch["layer_types"], "mixers": list(cfg.mixers),
        "widths": [cfg.hidden_dim, cfg.ssm.n_heads, cfg.ssm.head_dim,
                   cfg.ssm.d_state, cfg.n_kv_heads, cfg.head_dim],
        "kv_heads_per_row": cfg.kv_heads_per_row,
        "kernels": names,
        "state_snapshots_taken": stats["state_snapshots_taken"],
        "state_snapshot_hits": stats["state_snapshot_hits"],
        "prefix_hit_tokens": [outs[f"g{i}"].prefix_hit_tokens
                              for i in range(4)],
        "state_slots": stats["state_slots"],
        "tied_head": cfg.tied_embedding, "logits_scaling": cfg.logits_scaling,
        "fused_rows": stats["fused_rows"],
        "sampler_fallback_rows": stats["sampler_fallback_rows"],
        "logprobs": {k: verdict.get(k) for k in (
            "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
            "mean_abs_diff_nats", "n_positions")},
        "compiled": jax.devices()[0].platform == "tpu",
        # what the program store did for this start, and what was served
        "store_hits_misses": [
            int(metrics.counters.get(f"compile/store_{k}"))
            for k in ("hits", "misses")],
        "served_digest": hashlib.sha256(json.dumps(
            [[s["tokens"], s["logprobs"]] for s in samples]
        ).encode()).hexdigest(),
    })


def child_yoco(arg):
    """A model of THREE SEGMENTS through the generation engine: family
    ``phi4flash`` cut to 8 layers at Phi-4-mini-flash's widths (2 x (Mamba-1,
    window 512), (Mamba-1, FULL), (gated memory unit, cross attention);
    toy widths under ``--rehearse``), seeded as the benchmark seeds it. A
    group of four shares a prompt past the window (the first prefills in
    chunks, its window layers' pages going back behind the window, and
    files a snapshot of the Mamba state; the rest are seeded from it), one
    request generates alone; the served log-probs are held to the
    token-by-token float32 reference, and the decode chunk's program is
    searched for the kernels it should hold: BOTH programs of the paged
    kernel (the window layers', the full layer's and the cross layer's),
    ``kv_page_write`` over three cache layers, ``fused_sample`` over the
    200k-row tied embedding."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.gen.engine import GenerationEngine, GenRequest
    from benchmark import correct, sut, weights
    from benchmark.drivers import rollout_yoco_inproc as drv

    with open(os.path.join(
            ROOT, "benchmark/configs/phi4-mini-flash.json")) as f:
        arch = json.load(f)
    arch.update(num_hidden_layers=8)
    page, prompt_len, new = 128, 700, 48
    if arg["rehearse"]:
        arch.update(hidden_size=64, intermediate_size=128,
                    num_attention_heads=4, num_key_value_heads=2,
                    vocab_size=512, serving_dtype="float32")
        arch = drv._rehearsal_arch(arch)
        page, prompt_len, new = 16, 56, 12
    cfg = sut.model_config(arch, {})
    params = drv._seeded_init(
        weights.make_weights(
            sut.weight_shapes(cfg, cfg.dtype), arg["seed"],
            jnp.dtype(cfg.dtype)),
        arg["seed"])
    eng = GenerationEngine(
        cfg, params, max_slots=8, max_seqlen=8 * page,
        max_new_tokens_cap=64, page_size=page, state_snapshots=2,
        seed=arg["seed"] % (2**31 - 1))
    rng = np.random.default_rng(arg["seed"])
    shared = rng.integers(1, cfg.vocab_size, prompt_len).tolist()
    alone = rng.integers(1, cfg.vocab_size, prompt_len // 3).tolist()
    prompts = {**{f"g{i}": shared for i in range(4)}, "alone": alone}
    for rid, p in prompts.items():
        eng.submit(GenRequest(
            rid=rid, input_ids=p, max_new_tokens=new, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(decode_steps=8)}
    (key,) = [k for k in eng._jit_chunk]
    chunk = eng._chunk_fn(*key)
    names = sorted(set(re.findall(
        r'kernel_name = "([^"]+)"',
        chunk.lower(
            eng.params, eng.state,
            jnp.asarray(eng._table_arg(slice(None), key[1])),
            jnp.zeros((key[2],), jnp.int32)).as_text())))
    samples = [{
        "tokens": prompts[rid] + list(o.output_ids),
        "start": len(prompts[rid]), "logprobs": o.output_logprobs,
    } for rid, o in sorted(outs.items())]
    stats = dict(eng.stats)
    eng.state = None
    verdict = correct.check_logprobs(params, arch, cfg.dtype, samples)
    emit({
        "mixers": list(cfg.mixers), "cache_layers": cfg.cache_layers,
        "widths": [cfg.hidden_dim, cfg.ssm.d_inner, cfg.ssm.d_state,
                   cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim],
        "kernels": names,
        "state_snapshots_taken": stats["state_snapshots_taken"],
        "state_snapshot_hits": stats["state_snapshot_hits"],
        "prefix_hit_tokens": [outs[f"g{i}"].prefix_hit_tokens
                              for i in range(4)],
        "window_pages_released": stats["window_pages_released"],
        "fused_rows": stats["fused_rows"],
        "logprobs": {k: verdict.get(k) for k in (
            "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
            "mean_abs_diff_nats", "n_positions")},
        "compiled": jax.devices()[0].platform == "tpu",
    })


def child_zaya(arg):
    """A two-layer model of family ``zaya`` at ZAYA1-8B's widths (toy
    widths under ``--rehearse``) through the generation engine, seeded as
    the benchmark seeds it. A group of four shares a prompt (the first
    prefills in chunks and files a snapshot of the convolved latent's
    carry beside its pages, the rest are seeded from it), one request
    generates alone; the served log-probs are held to the float32
    reference given the program's routing (``rollout_cca_inproc._check``,
    which also reports how often the two routers agree), and the decode
    chunk's program is searched for the kernels it should hold."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.gen.engine import GenerationEngine, GenRequest
    from benchmark import sut, weights
    from benchmark.drivers import rollout_cca_inproc as drv

    with open(os.path.join(ROOT, "benchmark/configs/zaya1-8b-l16.json")) as f:
        arch = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/traffic/grpo16_closed256_out8k_cca.json")) as f:
        chk = json.load(f)["check"]
    arch.update(num_hidden_layers=2, layer_types=["hybrid", "hybrid"])
    page, prompt_len, new = 128, 300, 48
    if arg["rehearse"]:
        arch.update(hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, vocab_size=512,
                    serving_dtype="float32")
        arch = drv._rehearsal_arch(arch)
        page, prompt_len, new = 16, 40, 12
    cfg = sut.model_config(arch, {})
    params = drv._cca_init(
        weights.make_weights(
            sut.weight_shapes(cfg, cfg.dtype), arg["seed"],
            jnp.dtype(cfg.dtype)),
        arg["seed"])
    eng = GenerationEngine(
        cfg, params, max_slots=8, max_seqlen=4 * page + 64,
        max_new_tokens_cap=64, page_size=page, record_routing=True,
        seed=arg["seed"] % (2**31 - 1))
    rng = np.random.default_rng(arg["seed"])
    shared = rng.integers(1, cfg.vocab_size, prompt_len).tolist()
    alone = rng.integers(1, cfg.vocab_size, prompt_len // 3).tolist()
    prompts = {**{f"g{i}": shared for i in range(4)}, "alone": alone}
    for rid, p in prompts.items():
        eng.submit(GenRequest(
            rid=rid, input_ids=p, max_new_tokens=new, temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(decode_steps=8)}
    (key,) = [k for k in eng._jit_chunk]
    chunk = eng._chunk_fn(*key)
    names = sorted(set(re.findall(
        r'kernel_name = "([^"]+)"',
        chunk.lower(
            eng.params, eng.state,
            jnp.asarray(eng._table_arg(slice(None), key[1])),
            jnp.zeros((key[2],), jnp.int32)).as_text())))
    samples = []
    for rid, o in sorted(outs.items()):
        p = prompts[rid]
        forced = np.full((cfg.n_layers, len(p) + len(o.output_ids)), -1, np.int32)
        forced[:, len(p) - 1 : -1] = np.asarray(o.output_routing)[:, :, 0].T
        samples.append({"tokens": p + list(o.output_ids), "start": len(p),
                        "logprobs": o.output_logprobs, "forced": forced})
    stats, grouped = dict(eng.stats), eng._moe_grouped(eng.B)
    eng.state = None
    verdict = drv._check(params, arch, cfg.dtype, samples, chk)
    emit({
        "widths": [cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads,
                   cfg.head_dim, cfg.cca_carry_dim, cfg.moe.num_experts,
                   cfg.moe.router_dim],
        "kernels": names, "moe_grouped": grouped,
        "state_snapshots_taken": stats["state_snapshots_taken"],
        "state_snapshot_hits": stats["state_snapshot_hits"],
        "prefix_hit_tokens": [outs[f"g{i}"].prefix_hit_tokens
                              for i in range(4)],
        "state_slots": stats["state_slots"],
        "moe_skip_rows": stats["moe_skip_rows"], "moe_rows": stats["moe_rows"],
        **{k: verdict.get(k) for k in (
            "router_agreement_free_running",
            "router_agreement_given_earlier_choices")},
        "stand_ins_refused": {
            name: not verdict[name]["correct"] for name in drv._STAND_INS
            if name in verdict},
        "logprobs": {k: verdict.get(k) for k in (
            "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
            "mean_abs_diff_nats", "seq_mean_abs_diff_nats", "n_positions")},
        "compiled": jax.devices()[0].platform == "tpu",
    })


def child_trinity(arg):
    """A four-layer model of family ``afmoe`` at Trinity-Mini's widths (toy
    widths under ``--rehearse``) through the generation engine, seeded as
    the benchmark seeds it: ONE dense + three expert layers, kinds S, S,
    S, F, so the period crosses the stacks' boundary, a gate on attention's
    output, four norms a layer. A group of four shares a prompt longer than
    the window (the first prefills in chunks past it, the rest hit its
    pages), one request generates alone; the served log-probs are held to
    the float32 reference given the program's routing
    (``rollout_afmoe_inproc._verdict``), the reference with every layer
    FULL has to be refused, and the decode chunk's program is searched for
    the kernels it should hold (``paged_decode_window``, ``paged_decode``,
    ``kv_page_write``)."""
    from areal_tpu.base import compile_cache

    compile_cache.configure()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from areal_tpu.gen.engine import GenerationEngine, GenRequest
    from benchmark import correct, sut, weights
    from benchmark.drivers import rollout_afmoe_inproc as drv

    with open(os.path.join(
            ROOT, "benchmark/configs/trinity-mini-l8.json")) as f:
        arch = json.load(f)
    with open(os.path.join(
            ROOT, "benchmark/traffic/grpo16_closed80_out15k_w2k.json")) as f:
        chk = json.load(f)["check"]
    arch.update(num_hidden_layers=4, layer_types=arch["layer_types"][:4],
                num_dense_layers=1, sliding_window=256, num_experts=16)
    page, prompt_len, new = 128, 600, 48
    chk = dict(chk, long_max_tokens=prompt_len + new)
    if arg["rehearse"]:
        arch.update(hidden_size=64, intermediate_size=96, head_dim=16,
                    num_attention_heads=4, num_key_value_heads=2,
                    moe_intermediate_size=32, num_experts=4,
                    num_experts_per_tok=2, vocab_size=512, sliding_window=16,
                    serving_dtype="float32")
        page, prompt_len, new = 8, 40, 12
        chk = dict(chk, seq_mean_abs_diff_limit_nats=1e-3, long_max_tokens=64)
    cfg = sut.model_config(arch, {})
    params = weights.make_weights(
        sut.weight_shapes(cfg, cfg.dtype), arg["seed"], jnp.dtype(cfg.dtype))
    eng = GenerationEngine(
        cfg, params, max_slots=8, max_seqlen=6 * page + 64,
        max_new_tokens_cap=64, page_size=page, record_routing=True,
        seed=arg["seed"] % (2**31 - 1))
    rng = np.random.default_rng(arg["seed"])
    shared = rng.integers(1, cfg.vocab_size, prompt_len).tolist()
    alone = rng.integers(1, cfg.vocab_size, prompt_len // 3).tolist()
    prompts = {**{f"g{i}": shared for i in range(4)}, "alone": alone}
    # the first member fills the registry, its siblings hit it
    eng.submit(GenRequest(rid="g0", input_ids=shared, max_new_tokens=new,
                          temperature=1.0))
    outs = {o.rid: o for o in eng.run_until_done(decode_steps=8)}
    for rid, p in list(prompts.items())[1:]:
        eng.submit(GenRequest(
            rid=rid, input_ids=p, max_new_tokens=new, temperature=1.0))
    outs.update((o.rid, o) for o in eng.run_until_done(decode_steps=8))
    key = sorted(eng._jit_chunk)[-1]
    chunk = eng._chunk_fn(*key)
    names = sorted(set(re.findall(
        r'kernel_name = "([^"]+)"',
        chunk.lower(
            eng.params, eng.state,
            jnp.asarray(eng._table_arg(slice(None), key[1])),
            jnp.zeros((key[2],), jnp.int32)).as_text())))
    samples = []
    for rid, o in sorted(outs.items()):
        p = prompts[rid]
        toks = p + list(o.output_ids)
        forced = np.full(
            (cfg.n_moe_layers, len(toks), cfg.moe.top_k), -1, np.int32)
        forced[:, len(p) - 1: -1] = np.asarray(
            o.output_routing, np.int32).transpose(1, 0, 2)
        samples.append({"tokens": toks, "start": len(p), "forced": forced,
                        "logprobs": o.output_logprobs})
    stats = dict(eng.stats)
    eng.state = None
    ref = correct.reference_module(arch["reference"])
    verdict = drv._verdict(ref, params, arch, cfg.dtype, samples, chk)
    full = drv._stand_in(
        ref, params, arch, cfg.dtype, samples[:2], chk, "float32", window=None)
    emit({
        "widths": [cfg.hidden_dim, cfg.n_q_heads, cfg.n_kv_heads,
                   cfg.head_dim, cfg.moe.num_experts, cfg.n_dense_layers],
        "layer_kinds": [list(k) for k in cfg.layer_kinds],
        "kernels": names,
        "prefix_hit_tokens": [outs[f"g{i}"].prefix_hit_tokens
                              for i in range(4)],
        "window_pages_released": stats["window_pages_released"],
        "router_agreement_given_earlier_choices": verdict.get(
            "router_agreement_given_earlier_choices"),
        "control_full_attention_correct": full["correct"],
        "logprobs": {k: verdict.get(k) for k in (
            "correct", "reason", "max_abs_diff_nats", "tolerance_nats",
            "mean_abs_diff_nats", "seq_mean_abs_diff_nats", "n_positions")},
        "compiled": jax.devices()[0].platform == "tpu",
    })


CHILDREN = {
    "zaya": child_zaya, "trinity": child_trinity,
    "device": child_device, "ckpt": child_ckpt, "recompute": child_recompute,
    "tokenizer": child_tokenizer, "kvwrite": child_kvwrite,
    "pageddecode": child_pageddecode, "fusedsample": child_fusedsample,
    "moegrouped": child_moegrouped, "statespace": child_statespace,
    "yoco": child_yoco, "kdadecode": child_kdadecode,
}


# --------------------------------------------------------------------------- #


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any device; always exits non-zero")
    ap.add_argument("--child", nargs=2, metavar=("NAME", "ARGFILE"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        with open(args.child[1]) as f:
            CHILDREN[args.child[0]](json.load(f))
        return 0

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    sz = sizes(args.rehearse)
    phases = (
        [phase_train, phase_serve, phase_rl] if args.chips == 1
        else [phase_sharded_sft, phase_async_world]
    )
    device = {"platform": None, "kind": None, "count": 0}
    ok = True
    try:
        device = phase_device(sz, args, args.chips)
        for phase in phases:
            phase(sz, args)
    except Exception as e:  # any failed phase fails the smoke, loudly
        ok = False
        device = getattr(e, "device", None) or device
        save_failure_logs()
        emit({"phase": "failed", "error": f"{type(e).__name__}: {e}"[:4000]})
    if args.rehearse:
        emit({"ok": False, "rehearsal": True, "phases_passed": ok,
              "device": device})
        return 3
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
