#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dynamo_tpu still starts on the chip.

    python3 chip_smoke.py              one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4    the four-chip paths only (run by hand)

Drives the main path once, through the entry points a user would call, at
the published widths and full depth of Qwen2.5-1.5B (bf16, weights random
from ``--seed``, no network): ``python -m dynamo_tpu.cli.run in=http out=jax``
answering real HTTP requests, started twice (cold and warm compile cache);
then, in one child process, the Pallas decode kernels compiled (not
interpreted) against the jnp reference, engine-vs-plain-forward parity, a
kernel-tier engine against a dense-tier engine, and one decode dispatch
timed by the host clock against its device duration in a profiler trace.

ONE PROCESS PER CHIP. This process never imports JAX. Every phase that needs
the chip is a child process (the server, or this file re-run with
``--phase``), and each child has exited before the next starts.

Output: one JSON object per phase on stdout; the LAST line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
and the exit code 0 only if every phase passed on a TPU. Without a TPU the
run stops at the first child's device report with ``"ok": false`` and a
non-zero exit code; it never carries on on the CPU. A phase that raises
fails the run. Timings printed here are smoke timings, not measurements.

``--rehearse`` runs the same control flow at a tiny width on whatever JAX
finds (the CPU here), to debug the script without chip time. It can never
print ``"ok": true``.
"""

from __future__ import annotations

import argparse
import glob
import http.client
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")

# config.json of the published checkpoints (Qwen/Qwen2.5-1.5B-Instruct,
# Qwen/Qwen2.5-7B-Instruct), widths and depth untouched
QWEN = {
    "qwen2.5-1.5b": {
        "hidden_size": 1536, "intermediate_size": 8960,
        "num_hidden_layers": 28, "num_attention_heads": 12,
        "num_key_value_heads": 2, "vocab_size": 151936,
        "tie_word_embeddings": True, "max_window_layers": 21,
    },
    "qwen2.5-7b": {
        "hidden_size": 3584, "intermediate_size": 18944,
        "num_hidden_layers": 28, "num_attention_heads": 28,
        "num_key_value_heads": 4, "vocab_size": 152064,
        "tie_word_embeddings": False, "max_window_layers": 28,
    },
    # --rehearse only: the control flow at a width the CPU finishes
    "tiny": {
        "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "vocab_size": 2048, "tie_word_embeddings": True,
        "max_window_layers": 2,
    },
}
SPECIALS = ("<|endoftext|>", "<|im_start|>", "<|im_end|>")
CHAT_TEMPLATE = (
    "{% for message in messages %}<|im_start|>{{ message['role'] }}\n"
    "{{ message['content'] }}<|im_end|>\n{% endfor %}"
    "{% if add_generation_prompt %}<|im_start|>assistant\n{% endif %}"
)
CHAT_OVERHEAD = 5  # <|im_start|> user … <|im_end|> <|im_start|> assistant


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- model directory (no JAX) -------------------------------------------------

def vocab_words(vocab_size: int, seed: int) -> list:
    """One distinct word per token id, from the seed: the tokenizer covers
    the whole LM head, so every sampled id decodes to text and the text maps
    back to ids. The last three ids below the published special-token base
    (151643..151645 on the Qwen2.5 widths) are the chat control tokens."""
    rng = random.Random(seed)
    words = ["unk", "user", "assistant", "system"]
    taken = set(words)
    for code in rng.sample(range(26 ** 4), vocab_size + 8):
        w = "".join(chr(97 + (code // 26 ** i) % 26) for i in range(4))
        if w not in taken:
            words.append(w)
        if len(words) == vocab_size:
            break
    base = 151643 if vocab_size > 151646 else vocab_size - 3
    for i, tok in enumerate(SPECIALS):
        words[base + i] = tok
    return words


def write_model_dir(path: str, model: str, seed: int) -> list:
    """HF-layout directory: the published config.json and a word-level
    tokenizer over the full vocabulary. No weight files: the engine
    random-initialises from its seed. Returns the id → word table."""
    from tokenizers import AddedToken, Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    shape = QWEN[model]
    words = vocab_words(shape["vocab_size"], seed)
    tk = Tokenizer(models.WordLevel(
        vocab={w: i for i, w in enumerate(words)}, unk_token="unk"
    ))
    tk.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tk.add_special_tokens([AddedToken(t, special=True) for t in SPECIALS])
    tk.save(os.path.join(path, "tokenizer.json"))
    eos = words.index("<|im_end|>")
    config = {
        "architectures": ["Qwen2ForCausalLM"], "model_type": "qwen2",
        "hidden_act": "silu", "max_position_embeddings": 32768,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
        "torch_dtype": "bfloat16", "bos_token_id": words.index("<|endoftext|>"),
        "eos_token_id": eos, **shape,
    }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f, indent=1)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "bos_token": "<|endoftext|>", "eos_token": "<|im_end|>",
            "chat_template": CHAT_TEMPLATE, "model_max_length": 32768,
        }, f, indent=1)
    return words


def prompt_words(words: list, n: int, seed: int) -> str:
    rng = random.Random(seed)
    plain = [w for w in words[4:] if w not in SPECIALS]
    return " ".join(rng.choice(plain) for _ in range(n))


# -- HTTP client (stdlib, this process) ---------------------------------------

def http_json(port: int, method: str, path: str, body=None, timeout=300.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            method, path, body=json.dumps(body) if body is not None else None,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        raw = resp.read().decode()
        return resp.status, raw
    finally:
        conn.close()


def http_stream(port: int, path: str, body: dict, timeout=300.0):
    """POST with ``stream: true``; returns (status, chunks, arrival times)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        t0 = time.perf_counter()
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        chunks, times = [], []
        if resp.status != 200:
            return resp.status, [resp.read().decode()], []
        for line in resp:
            line = line.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                break
            chunks.append(json.loads(data))
            times.append(time.perf_counter() - t0)
        return resp.status, chunks, times
    finally:
        conn.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- child processes -----------------------------------------------------------

class Child:
    """A child process with its output in a log file; always stopped."""

    def __init__(self, argv: list, log_name: str, env: dict = None):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.log_path = os.path.join(OUT_DIR, log_name)
        self._log = open(self.log_path, "w")
        full_env = dict(os.environ, PYTHONPATH=REPO, PYTHONUNBUFFERED="1")
        full_env.update(env or {})
        self.proc = subprocess.Popen(
            argv, stdout=self._log, stderr=subprocess.STDOUT, env=full_env,
            cwd=REPO, start_new_session=True,
        )

    def log(self) -> str:
        with open(self.log_path) as f:
            return f.read()

    def log_json(self, marker: str):
        """All ``<marker> {json}`` payloads the child logged, in order."""
        return [
            json.loads(m) for m in
            re.findall(rf"{marker} (\{{.*\}})\s*$", self.log(), re.M)
        ]

    def stop(self, grace: float = 40.0) -> None:
        if self.proc.poll() is None:
            # the whole session: a launcher's grandchildren hold chips too
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._log.close()


def wait_device(child: Child, n: int = 1, timeout: float = 180.0) -> list:
    """The first ``n`` device reports a child tree logged at start-up (the
    process that holds the chip names it; this process stays off JAX)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        devs = child.log_json("device")
        if len(devs) >= n:
            return devs
        check(child.proc.poll() is None,
              f"child exited rc={child.proc.returncode} before naming its "
              f"device; see {child.log_path}:\n{child.log()[-2000:]}")
        time.sleep(0.5)
    raise SmokeFailure(f"no device report within {timeout}s; see {child.log_path}")


def wait_http(child: Child, port: int, timeout: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        check(child.proc.poll() is None,
              f"server exited rc={child.proc.returncode}; see "
              f"{child.log_path}:\n{child.log()[-3000:]}")
        try:
            status, raw = http_json(port, "GET", "/v1/models", timeout=5.0)
            if status == 200 and json.loads(raw).get("data"):
                return time.monotonic() - t0
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(1.0)
    raise SmokeFailure(f"server not ready within {timeout}s; see {child.log_path}")


def require_tpu(dev: dict, rehearse: bool) -> None:
    if dev["platform"] != "tpu" and not rehearse:
        raise SmokeFailure(f"no TPU: JAX found platform {dev['platform']!r}")


def cache_entries(cache_dir: str) -> int:
    return len(glob.glob(os.path.join(cache_dir, "*"))) if cache_dir else 0


# -- phase A: the server -------------------------------------------------------

def ids_of(text: str, word_id: dict) -> list:
    return [word_id[w] for w in text.split()]


def chat_body(model: str, content: str, max_tokens: int, **extra) -> dict:
    return {
        "model": model, "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "nvext": {"ignore_eos": True},
        # the frontend reports usage only when asked, unary responses too
        "stream_options": {"include_usage": True}, **extra,
    }


def check_unary(status: int, raw: str, n_prompt: int, n_out: int, what: str):
    check(status == 200, f"{what}: HTTP {status}: {raw[:300]}")
    body = json.loads(raw)
    choice = body["choices"][0]
    check(choice["finish_reason"] == "length",
          f"{what}: finish_reason {choice['finish_reason']!r}")
    usage = body["usage"]
    check(usage["prompt_tokens"] == n_prompt,
          f"{what}: prompt_tokens {usage['prompt_tokens']} != {n_prompt}")
    check(usage["completion_tokens"] == n_out,
          f"{what}: completion_tokens {usage['completion_tokens']} != {n_out}")
    return choice.get("text") if "text" in choice else choice["message"]["content"]


def drive_requests(port: int, model: str, words: list, seed: int,
                   n_out: int, long_len: int, full: bool = True) -> dict:
    """The requests of ISSUE 21 §A against a live server; returns findings.
    ``full=False`` stops after the first greedy request (a restarted or
    re-configured server is only asked whether it still answers the same)."""
    word_id = {w: i for i, w in enumerate(words)}

    def engine_state():
        status, raw = http_json(port, "GET", "/debug/engine")
        check(status == 200, f"/debug/engine: HTTP {status}")
        return json.loads(raw)

    before = engine_state()
    # 1+2: the same short greedy prompt twice, engine otherwise idle. Shorter
    # than one KV block, so both runs take the same path (no prefix hit).
    short = prompt_words(words, 8, seed + 1)
    texts = []
    for i in range(2 if full else 1):
        status, raw = http_json(
            port, "POST", "/v1/chat/completions",
            chat_body(model, short, n_out, temperature=0.0),
        )
        texts.append(check_unary(
            status, raw, 8 + CHAT_OVERHEAD, n_out, f"greedy chat #{i}"
        ))
    ids_a = ids_of(texts[0], word_id)
    after_first = engine_state()
    if not full:
        return {"greedy_ids_head": ids_a[:8],
                "attention_tiers": after_first["attention_tiers"],
                "device_memory": after_first.get("device_memory")}
    ids_b = ids_of(texts[1], word_id)
    check(ids_a == ids_b, f"same greedy prompt, different ids:\n{ids_a}\n{ids_b}")

    # 3: streaming chat
    status, chunks, times = http_stream(
        port, "/v1/chat/completions",
        chat_body(model, prompt_words(words, 40, seed + 2), n_out,
                  temperature=0.0, stream=True),
    )
    check(status == 200, f"streaming chat: HTTP {status}: {chunks[:1]}")
    finishes = [c["choices"][0].get("finish_reason")
                for c in chunks if c.get("choices")]
    check("length" in finishes,
          f"streaming chat: finish reasons {finishes[-3:]}")
    usage = next((c["usage"] for c in reversed(chunks) if c.get("usage")), None)
    check(usage is not None, "streaming chat: no usage chunk")
    check(usage["prompt_tokens"] == 40 + CHAT_OVERHEAD
          and usage["completion_tokens"] == n_out,
          f"streaming chat: usage {usage}")
    content_t = [t for c, t in zip(chunks, times) if c.get("choices")
                 and (c["choices"][0].get("delta") or {}).get("content")]
    check(len(content_t) >= 2, "streaming chat: fewer than 2 content chunks")

    # 4: /v1/completions with a prompt of >= 1,024 tokens (several prefill
    # chunks plus paged history)
    long_prompt = prompt_words(words, long_len, seed + 3)
    status, raw = http_json(port, "POST", "/v1/completions", {
        "model": model, "prompt": long_prompt, "max_tokens": n_out,
        "temperature": 0.0, "nvext": {"ignore_eos": True},
        "stream_options": {"include_usage": True},
    })
    check_unary(status, raw, long_len, n_out, "long completion")

    # 5: a wave in flight at once — greedy, sampled, and one long prompt
    wave = [
        chat_body(model, prompt_words(words, 30 + 7 * i, seed + 10 + i), n_out,
                  **({"temperature": 0.8, "top_p": 0.9, "seed": seed + i}
                     if i % 2 else {"temperature": 0.0}))
        for i in range(5)
    ] + [chat_body(model, prompt_words(words, long_len, seed + 20), n_out,
                   temperature=0.0)]
    results = [None] * len(wave)

    def fire(i):
        results[i] = http_json(port, "POST", "/v1/chat/completions", wave[i])

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(len(wave))]
    for t in threads:
        t.start()
    peak_active = 0
    while any(t.is_alive() for t in threads):
        peak_active = max(peak_active, engine_state()["request_active_slots"])
        time.sleep(0.05)
    for t in threads:
        t.join()
    for i, (status, raw) in enumerate(results):
        n_prompt = len(wave[i]["messages"][0]["content"].split()) + CHAT_OVERHEAD
        check_unary(status, raw, n_prompt, n_out, f"wave request {i}")
    check(peak_active >= 2, f"never more than {peak_active} slot live in the wave")

    status, raw = http_json(port, "GET", "/metrics")
    check(status == 200 and "dynamo_frontend" in raw, f"/metrics: HTTP {status}")
    after = engine_state()
    check(after["jit_recompiles"] == before["jit_recompiles"],
          f"jit_recompiles rose {before['jit_recompiles']} -> "
          f"{after['jit_recompiles']} between the first and the last request "
          f"(a shape leaked into a jit signature)")
    return {
        "requests": 4 + len(wave), "greedy_ids_equal": True,
        "greedy_ids_head": ids_a[:8],
        "jit_recompiles": [before["jit_recompiles"],
                           after_first["jit_recompiles"],
                           after["jit_recompiles"]],
        "peak_active_slots": peak_active,
        "attention_tiers": after["attention_tiers"],
        "device_memory": after.get("device_memory"),
        "smoke_timing_first_token_s": round(content_t[0], 4),
        "smoke_timing_per_token_s": round(
            (content_t[-1] - content_t[0]) / (len(content_t) - 1), 5
        ),
    }


def check_tiers(tiers: dict, platform: str) -> None:
    check(tiers, "engine reported no compiled attention tier")
    for name, t in tiers.items():
        check(not (platform == "tpu" and t["interpret"]),
              f"{name} holds an INTERPRETED kernel on a TPU")


def server_phase(model_dir: str, words: list, args, *, name: str, extra=(),
                 env=None, full: bool = True, timeout: float = 600.0) -> dict:
    """Start ``cli.run in=http out=jax``, drive it, stop it."""
    port = free_port()
    child = Child(
        [sys.executable, "-m", "dynamo_tpu.cli.run", "in=http", "out=jax",
         "--model-path", model_dir, "--host", "127.0.0.1", "--port", str(port),
         *extra],
        f"{name}.log", env,
    )
    try:
        dev = wait_device(child)[0]
        require_tpu(dev, args.rehearse)
        entries_before = cache_entries(dev["compile_cache"])
        ready_s = wait_http(child, port, timeout)
        warm = child.log_json("warmup")[0]
        model = os.path.basename(model_dir)
        n_out, long_len = (64, 1100) if not args.rehearse else (16, 150)
        found = drive_requests(
            port, model, words, args.seed, n_out, long_len, full
        )
        check_tiers(found["attention_tiers"], dev["platform"])
        return {
            "device": dev, "ready_s": round(ready_s, 1),
            "warmup_s": warm, "warmup_total_s": round(sum(warm.values()), 2),
            "compile_cache": dev["compile_cache"],
            "cache_entries": [entries_before, cache_entries(dev["compile_cache"])],
            **found,
        }
    finally:
        child.stop()


def one_chip(args, tmp: str) -> dict:
    model = "tiny" if args.rehearse else "qwen2.5-1.5b"
    model_dir = os.path.join(tmp, model)
    words = write_model_dir(model_dir, model, args.seed)
    emit("model_dir", model=model, vocab=len(words), config=QWEN[model])

    cold = server_phase(model_dir, words, args, name="server_cold")
    emit("server", start="first", **cold)
    warm = server_phase(model_dir, words, args, name="server_warm", full=False)
    check(warm["greedy_ids_head"] == cold["greedy_ids_head"],
          "greedy ids differ across a restart with the same seed")
    # the second start hit the cache if it had nothing new to write, or (a
    # program at the cache's one-second threshold may be written late) if its
    # warm-up took a small fraction of the first's
    hit = (warm["cache_entries"][1] == warm["cache_entries"][0] > 0
           or warm["warmup_total_s"] < 0.6 * cold["warmup_total_s"])
    emit("server", start="second", compile_cache_hit=hit, **warm)
    check(hit, f"second start missed the compile cache: entries "
               f"{warm['cache_entries']}, warm-up {warm['warmup_total_s']} s "
               f"after {cold['warmup_total_s']} s")

    run_child_phase("engine", args, model_dir)
    return cold["device"]


def run_child_phase(phase: str, args, model_dir: str, timeout: float = 900.0):
    """Re-run this file with ``--phase``: a child that may use JAX. Its
    stdout lines are this run's lines; non-zero exit fails the run."""
    argv = [sys.executable, os.path.abspath(__file__), "--phase", phase,
            "--model-dir", model_dir, "--seed", str(args.seed)]
    if args.rehearse:
        argv.append("--rehearse")
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.Popen(argv, cwd=REPO, env=env, start_new_session=True)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
        raise SmokeFailure(f"phase {phase} did not finish within {timeout}s")
    check(rc == 0, f"phase {phase} failed (exit {rc})")


# -- phase C: kernels, parity, clocks — one child, JAX in-process --------------

N_OUT = 16  # greedy tokens per prompt in the in-process engine phases


def smoke_prompts(vocab_size: int, seed: int, lengths=(24, 150, 40)) -> list:
    """Token-id prompts; 150 spans two prefill chunks (history path)."""
    rng = random.Random(seed + 5)
    return [[rng.randrange(4, vocab_size - 400) for _ in range(n)]
            for n in lengths]


def greedy_ids(engine, prompts: list) -> list:
    """Each prompt's greedy tokens through the engine's own generate(), one
    request at a time (the same schedule for every engine compared)."""
    import asyncio

    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    async def collect(prompt):
        req = PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=N_OUT, ignore_eos=True),
            sampling_options=SamplingOptions(),
        )
        toks = []
        async for item in engine.generate(Context(req)):
            if item.data:
                toks.extend(item.data.get("token_ids", []))
        return toks

    ids = [asyncio.run(collect(p)) for p in prompts]
    check(all(len(o) == N_OUT for o in ids), f"short outputs: {ids}")
    return ids


def first_fork(a: list, b: list):
    return next(([i, k] for i, (x, y) in enumerate(zip(a, b))
                 for k, (u, v) in enumerate(zip(x, y)) if u != v), None)


def parity_vs_forward(cfg, params, prompts: list, ids_by_engine: dict) -> dict:
    """Every token an engine emitted must be the argmax of a plain
    ``models.llama.forward`` over the same prefix on this device, or tie with
    it within bf16 tolerance. Random weights give near-flat logits (top-1 and
    top-2 a few hundredths apart), so two correct bf16 computations that
    round differently — chunked vs whole prefill, kernel vs einsum, a tp
    all-reduce — can fork a free-running greedy sequence; teacher forcing
    the reference on each engine's own tokens tells a tie from a fault."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.llama import forward, make_kv_cache

    t_pad = 192
    ref_blocks = t_pad // 16
    fwd = jax.jit(lambda p, t, pos, c, tb: forward(
        p, cfg, t, pos, c, tb, use_pallas=False)[0])
    tables = jnp.arange(ref_blocks, dtype=jnp.int32)[None]

    def teacher_forced(prompt, out):
        seq = list(prompt) + list(out[:-1])
        toks = np.zeros((1, t_pad), np.int32)
        pos = np.full((1, t_pad), -1, np.int32)
        toks[0, : len(seq)] = seq
        pos[0, : len(seq)] = np.arange(len(seq))
        logits = np.asarray(fwd(
            params, jnp.asarray(toks), jnp.asarray(pos),
            make_kv_cache(cfg, ref_blocks, 16), tables,
        ))[0, len(prompt) - 1: len(seq)]
        check(np.isfinite(logits).all(), "reference logits not finite")
        gap = logits.max(axis=-1) - logits[np.arange(len(out)), out]
        return (int((gap == 0).sum()), float(gap.max()),
                float(np.abs(logits).max()))

    report = {}
    for name, ids in ids_by_engine.items():
        rows = [teacher_forced(p, o) for p, o in zip(prompts, ids)]
        worst_gap, scale = max(r[1] for r in rows), max(r[2] for r in rows)
        report[name] = {
            "tokens": sum(len(o) for o in ids),
            "argmax_matches": sum(r[0] for r in rows),
            "worst_logit_gap_to_argmax": round(worst_gap, 5),
            "max_abs_logit": round(scale, 3),
            "tolerance": round(2 ** -6 * scale, 5),
        }
        check(all(r[0] >= 1 for r in rows),
              f"{name}: no token matches the reference argmax")
        check(worst_gap <= 2 ** -6 * scale,
              f"{name} engine emitted a token {worst_gap} below the "
              f"reference argmax (logit scale {scale}): beyond bf16 tolerance")
    return report


def phase_engine(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu import native
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine
    from dynamo_tpu.engine_jax.weights import config_from_card, load_params
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.ops.attention import paged_attention
    from dynamo_tpu.ops.pallas import paged_attention as pk

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    require_tpu({"platform": dev.platform}, args.rehearse)
    interpret = not on_tpu
    card = ModelDeploymentCard.from_local_path(args.model_dir)
    cfg = config_from_card(card)

    # 1. the three decode kernels, compiled, against the jnp path: ragged
    # lengths with a zero-length lane, at the model's head geometry
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    bs, mb = 16, (64 if on_tpu else 8)
    lengths = [0, 1, 15, 16, 17, mb * bs // 2 - 3, mb * bs - 1, mb * bs]
    s = len(lengths)
    rng = np.random.default_rng(args.seed)
    n_blocks = s * mb + 1
    kc = jnp.asarray(rng.normal(size=(n_blocks, bs, kvh, d)), cfg.dtype)
    vc = jnp.asarray(rng.normal(size=(n_blocks, bs, kvh, d)), cfg.dtype)
    q = jnp.asarray(rng.normal(size=(s, 1, h, d)), cfg.dtype)
    tables = jnp.asarray(
        rng.permutation(n_blocks)[: s * mb].reshape(s, mb).astype(np.int32)
    )
    lens = jnp.asarray(lengths, jnp.int32)
    ref = np.asarray(paged_attention(
        q, kc, vc, tables, (lens - 1)[:, None], use_pallas=False
    )[:, 0].astype(jnp.float32))
    kernels = {
        "v1": lambda **kw: pk.paged_attention_decode(
            q[:, 0], kc, vc, tables, lens, interpret=interpret, **kw),
        "v2": lambda **kw: pk.paged_attention_decode_v2(
            q[:, 0], kc, vc, tables, lens, interpret=interpret, **kw),
        "v4": lambda **kw: pk.paged_attention_decode_v4(
            q[:, 0], kc, vc, tables, lens, interpret=interpret,
            pages_per_chunk=pk.v4_plan(s, bs, kvh, d, kc.dtype.itemsize, mb),
            **kw),
    }
    errs = {}
    for name, fn in kernels.items():
        t0 = time.perf_counter()
        got = np.asarray(fn().astype(jnp.float32))
        got_s = np.asarray(fn(return_stats=True)[0].astype(jnp.float32))
        errs[name] = {
            "max_abs_err": float(np.abs(got - ref).max()),
            "max_abs_err_with_stats": float(np.abs(got_s - ref).max()),
            "smoke_timing_compile_and_run_s": round(time.perf_counter() - t0, 2),
        }
        check(np.isfinite(got).all(), f"kernel {name}: non-finite output")
        check(max(errs[name]["max_abs_err"],
                  errs[name]["max_abs_err_with_stats"]) < 3e-2,
              f"kernel {name} disagrees with the jnp path: {errs[name]}")
    emit("kernels", geometry={"h": h, "kvh": kvh, "d": d, "lanes": s,
                              "max_ctx": mb * bs, "dtype": str(cfg.dtype.__name__)},
         lengths=lengths, interpret=interpret, results=errs)

    # 2. engines at full width: dense tier, then kernel tier, same prompts
    params = load_params(card, cfg, seed=args.seed)
    jax.block_until_ready(params)
    ecfg = EngineConfig(max_slots=4, kv_block_size=16, max_model_len=1024,
                        decode_steps=4)
    prompts = smoke_prompts(
        cfg.vocab_size, args.seed, (24, 150, 40) if on_tpu else (24, 150)
    )

    def run_engine(mode: str):
        os.environ["DYN_TPU_ATTENTION"] = mode
        eng = JaxServingEngine(cfg, params, ecfg)
        warm = eng.warmup("greedy")
        return eng, warm, greedy_ids(eng, prompts)

    eng_j, warm_j, ids_j = run_engine("jnp")
    clocks = host_clock_vs_trace(eng_j, jax, jnp, np)
    eng_j.close()
    del eng_j
    eng_p, warm_p, ids_p = run_engine("pallas")
    tiers = eng_p.metrics_snapshot()["attention_tiers"]
    check_tiers(tiers, dev.platform)
    decode_tier = tiers["decode(lp=False,pen=False,sample=False)"]
    check(decode_tier["tier"].startswith("pallas-"),
          f"forced kernel tier, engine holds {decode_tier}")
    program = eng_p._decode_fns[(False, False, False)].as_text()
    has_kernel = "tpu_custom_call" in program
    check(has_kernel or not on_tpu,
          "no tpu_custom_call in the compiled kernel-tier decode program")
    eng_p.close()

    # 3. parity against a plain loop over models.llama.forward
    parity = parity_vs_forward(cfg, params, prompts,
                               {"jnp": ids_j, "pallas": ids_p})
    emit("parity", engine_config={"slots": 4, "max_model_len": 1024,
                                  "decode_steps": 4, "prompts": [len(p) for p in prompts]},
         vs_plain_forward=parity, pallas_ids_equal_jnp_ids=ids_p == ids_j,
         first_fork=first_fork(ids_j, ids_p),
         kernel_tier=decode_tier, tpu_custom_call_in_decode_program=has_kernel,
         warmup_s={"jnp": warm_j, "pallas": warm_p}, compile_cache=cache_dir)
    emit("host_clock_vs_device", **clocks)

    for lib in ("radix_tree", "kv_events", "codec_core"):
        native.load(lib)
    stats = dev.memory_stats() or {}
    emit("process", native_loaded=native.loaded(),
         peak_hbm_bytes=stats.get("peak_bytes_in_use"),
         hbm_limit_bytes=stats.get("bytes_limit"))


def host_clock_vs_trace(eng, jax, jnp, np) -> dict:
    """One decode dispatch timed by the host clock around block_until_ready,
    against the same dispatches' device durations in a ~2 s profiler trace."""
    cfg = eng.config
    S, MB = cfg.max_slots, cfg.max_blocks_per_seq
    fn = eng._decode(False, False, False)
    tokens = jnp.zeros((S,), jnp.int32)
    positions = jnp.full((S,), 200, jnp.int32)
    tables = jnp.asarray(
        (np.arange(S * MB).reshape(S, MB) % eng.num_blocks).astype(np.int32)
    )
    ctr = jnp.int32(0)
    ipack = jnp.zeros((2, S), jnp.int32)
    fpack = jnp.asarray(np.stack([np.zeros(S), np.ones(S), np.zeros(S),
                                  np.zeros(S)]).astype(np.float32))
    wd = eng._wd_args()
    state = {"cache": eng.cache, "counts": eng._dummy_counts}

    def dispatch() -> float:
        t0 = time.perf_counter()
        out, _, _, state["cache"], state["counts"] = fn(
            eng.params_decode, state["cache"], state["counts"], tokens,
            positions, tables, ctr, ipack, fpack, *wd,
        )
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    for _ in range(3):
        dispatch()
    untraced = sorted(dispatch() for _ in range(20))
    # the trace is tens of MiB: read here, in a directory that goes away
    with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as trace_dir:
        jax.profiler.start_trace(trace_dir)
        traced, t_end = [], time.perf_counter() + 2.0
        while time.perf_counter() < t_end:
            traced.append(dispatch())
        jax.profiler.stop_trace()
        paths = glob.glob(
            os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
        )
        check(paths, "profiler wrote no xplane file")
        data = jax.profiler.ProfileData.from_file(paths[0])
    eng.cache, eng._dummy_counts = state["cache"], state["counts"]
    planes = {}
    module_durs = []
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if "TPU" in plane.name and line.name == "XLA Modules":
                module_durs += [e.duration_ns / 1e9 for e in events
                                if "decode" in e.name]
        planes[plane.name] = lines
    on_tpu = jax.devices()[0].platform == "tpu"
    check(module_durs or not on_tpu,
          f"no decode module events on a device plane; planes: {planes}")
    med = lambda xs: sorted(xs)[len(xs) // 2] if xs else None  # noqa: E731
    host, device = med(traced), med(module_durs)
    return {
        "note": "smoke timings of one decode dispatch "
                f"({cfg.decode_steps} steps x {S} lanes), not measurements",
        "host_clock_median_s": med(untraced),
        "host_clock_median_s_traced": host,
        "device_trace_median_s": device,
        "dispatches_traced": len(traced), "device_events": len(module_durs),
        "host_over_device": (host / device) if device else None,
        "trace_planes": {k: v for k, v in planes.items() if "TPU" in k}
        or list(planes),
    }


# -- four chips: --chips 4 -----------------------------------------------------

def phase_mesh(args) -> None:
    """G.1: Qwen2.5-1.5B over a tp=2 x dp=2 mesh against the one-chip engine,
    both built by the entry point the CLI uses."""
    import jax

    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.engine_jax.engine import build_jax_serving_engine
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    enable_compile_cache()
    devs = jax.devices()
    require_tpu({"platform": devs[0].platform}, args.rehearse)
    check(len(devs) >= 4, f"--chips 4 needs four devices, JAX found {len(devs)}")
    card = ModelDeploymentCard.from_local_path(args.model_dir)
    prompts = smoke_prompts(card.model_config["vocab_size"], args.seed)

    def per_device_bytes(tree) -> dict:
        out = {}
        for leaf in jax.tree.leaves(tree):
            for sh in leaf.addressable_shards:
                out[str(sh.device.id)] = out.get(str(sh.device.id), 0) + sh.data.nbytes
        return out

    def run(**mesh_kw):
        eng = build_jax_serving_engine(
            card, max_batch_size=4, max_model_len=1024, seed=args.seed,
            **mesh_kw,
        )
        warm = eng.warmup("greedy")
        return eng, warm, greedy_ids(eng, prompts)

    one, warm_one, ids_one = run()
    one_bytes = {"params": per_device_bytes(one.params),
                 "cache": per_device_bytes(one.cache)}
    one.close()
    cfg, params_one = one.model_config, one.params  # the reference's, device 0
    del one
    mesh_eng, warm_mesh, ids_mesh = run(tensor_parallel_size=2, data_parallel_size=2)
    mesh_bytes = {"params": per_device_bytes(mesh_eng.params),
                  "cache": per_device_bytes(mesh_eng.cache)}
    # the collectives the compiler put into the mesh decode program
    S, MB = mesh_eng.config.max_slots, mesh_eng.config.max_blocks_per_seq
    import numpy as np

    i32 = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
    text = mesh_eng._decode(False, False, False).lower(
        mesh_eng.params_decode, mesh_eng.cache, mesh_eng._dummy_counts,
        i32(S), i32(S), i32(S, MB), np.int32(0), i32(2, S),
        np.zeros((4, S), np.float32), *mesh_eng._wd_args(),
    ).compile().as_text()
    collectives = {
        op: len(re.findall(rf"= \S+ {op}(?:-start)?\(", text))
        for op in ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")
    }
    tiers = mesh_eng.metrics_snapshot()["attention_tiers"]
    mesh_eng.close()
    parity = parity_vs_forward(cfg, params_one, prompts,
                               {"one_chip": ids_one, "mesh": ids_mesh})
    emit("mesh_vs_one_chip", model=os.path.basename(args.model_dir),
         mesh={"tp": 2, "dp": 2}, ids_equal=ids_mesh == ids_one,
         first_fork=first_fork(ids_one, ids_mesh), vs_plain_forward=parity,
         bytes_per_device={"one_chip": one_bytes, "mesh": mesh_bytes},
         collectives_in_decode_program=collectives, attention_tiers=tiers,
         warmup_s={"one_chip": warm_one, "mesh": warm_mesh})
    check(len(mesh_bytes["params"]) == 4 and len(mesh_bytes["cache"]) == 4,
          f"mesh engine does not span four devices: {mesh_bytes}")
    total = sum(one_bytes["params"].values())
    check(max(mesh_bytes["params"].values()) < 0.75 * total,
          "a device of the mesh holds (nearly) the whole parameter tree")
    check(sum(collectives.values()) > 0, "no collective in the mesh decode program")


def replicas_phase(model_dir: str, words: list, args) -> dict:
    """G.3: examples/llm/launch.py agg_router --workers 4 — four worker
    processes, one distinct chip each, behind the routing frontend."""
    port, ss_port, bus_port = free_port(), free_port(), free_port()
    child = Child(
        [sys.executable, os.path.join(REPO, "examples", "llm", "launch.py"),
         "agg_router", "--model-path", model_dir, "--workers", "4",
         "--port", str(port), "--statestore-port", str(ss_port),
         "--bus-port", str(bus_port)],
        "replicas.log",
    )
    try:
        devs = wait_device(child, n=4, timeout=150.0)
        for dev in devs:
            require_tpu(dev, args.rehearse)
            check(dev["count"] == 1 or args.rehearse,
                  f"a worker sees {dev['count']} chips, not its one: {dev}")
        check(len({d.get("visible_chips") for d in devs}) == 4 or args.rehearse,
              f"workers do not hold four distinct chips: {devs}")
        wait_http(child, port, 600.0)
        model = os.path.basename(model_dir)
        n_out = 32 if not args.rehearse else 8
        results = [None] * 12

        def fire(i):
            results[i] = http_json(
                port, "POST", "/v1/chat/completions",
                chat_body(model, prompt_words(words, 20 + i, args.seed + 40 + i),
                          n_out, temperature=0.0),
            )

        # wait until the frontend has discovered every worker
        deadline = time.monotonic() + 300.0
        while child.log().count("serving dyn://") < 4:
            check(time.monotonic() < deadline and child.proc.poll() is None,
                  f"four workers never registered; see {child.log_path}")
            time.sleep(1.0)
        threads = [threading.Thread(target=fire, args=(i,)) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (status, raw) in enumerate(results):
            check_unary(status, raw, 20 + i + CHAT_OVERHEAD, n_out, f"replica request {i}")
        served = worker_request_counts(
            f"127.0.0.1:{ss_port}", f"127.0.0.1:{bus_port}"
        )
        check(len(served) == 4 and sum(1 for n in served if n > 0) >= 2,
              f"the frontend routed to fewer than two workers: {served}")
        return {"workers": devs, "requests": 12, "served_per_worker": served}
    finally:
        child.stop()


def worker_request_counts(statestore: str, bus: str) -> list:
    """Requests each worker's engine admitted, asked of the ``stats``
    endpoint every worker registers (round-robin reaches each instance
    once; no JAX in this process)."""
    import asyncio

    async def go():
        from dynamo_tpu.runtime import Context
        from dynamo_tpu.runtime.distributed import DistributedRuntime

        drt = await DistributedRuntime.create(
            statestore_url=statestore, bus_url=bus
        )
        try:
            client = await drt.namespace("dynamo").component("backend") \
                .endpoint("stats").client("round_robin")
            await client.wait_for_instances(4, timeout=30.0)
            out = []
            for _ in client.instance_ids():
                async for item in client.generate(Context({})):
                    if item.data:
                        out.append(item.data["request_total"])
            return out
        finally:
            await drt.shutdown()

    return asyncio.run(go())


def four_chips(args, tmp: str) -> dict:
    small = "tiny" if args.rehearse else "qwen2.5-1.5b"
    big = "tiny" if args.rehearse else "qwen2.5-7b"
    small_dir = os.path.join(tmp, small)
    small_words = write_model_dir(small_dir, small, args.seed)
    # G.1 first: sharded vs whole
    run_child_phase("mesh", args, small_dir, timeout=1500.0)

    # G.2: the configuration that needs the chips
    big_dir = os.path.join(tmp, big + "-tp4")
    big_words = write_model_dir(big_dir, big, args.seed)
    tp = ["--tensor-parallel-size", "4"]
    served = server_phase(big_dir, big_words, args, name="server_tp4", extra=tp,
                          timeout=1500.0)
    emit("server_tp4", model=big, attention="auto", **served)
    dev = served["device"]
    check(dev["count"] == 4 or args.rehearse, f"server saw {dev['count']} devices")
    check_spread(served["device_memory"], args.rehearse)
    decode_tier = served["attention_tiers"]["decode(lp=False,pen=False,sample=False)"]
    if not decode_tier["tier"].startswith("pallas-"):
        forced = server_phase(big_dir, big_words, args, name="server_tp4_pallas",
                              extra=tp, env={"DYN_TPU_ATTENTION": "pallas"},
                              full=False, timeout=1500.0)
        emit("server_tp4", model=big, attention="pallas", **forced)
        check(forced["attention_tiers"]["decode(lp=False,pen=False,sample=False)"]
              ["tier"].startswith("pallas-"), "forced kernel tier not taken")
    else:
        emit("server_tp4", model=big, attention="pallas",
             note="the auto policy already chose the kernel tier above "
                  f"({decode_tier['tier']}); not run twice")

    # G.3: replicas
    emit("replicas", **replicas_phase(small_dir, small_words, args))
    return dev


def check_spread(device_memory, rehearse: bool) -> None:
    """No device may ever have held the whole tree: every device's PEAK
    bytes stay far below the model's total."""
    if rehearse or not device_memory:
        check(rehearse, "server reported no device memory")
        return
    peaks = [m["peak_bytes_in_use"] for m in device_memory]
    check(len(peaks) == 4, f"device_memory covers {len(peaks)} devices")
    # qwen2.5-7b bf16 is 15.2 GB; a quarter plus cache plus temporaries
    check(max(peaks) < 9 << 30,
          f"a device peaked at {max(peaks)} bytes: the tree was not created "
          f"in its shardings")
    check(min(peaks) > 0.5 * max(peaks), f"uneven spread across devices: {peaks}")


# -- entry ---------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny width on whatever JAX finds; never prints ok: true")
    ap.add_argument("--phase", choices=("engine", "mesh"), help=argparse.SUPPRESS)
    ap.add_argument("--model-dir", help=argparse.SUPPRESS)
    args = ap.parse_args()

    device = None
    try:
        if args.phase:  # a child of this script: JAX is allowed here
            {"engine": phase_engine, "mesh": phase_mesh}[args.phase](args)
            return 0
        check(os.path.isdir(os.path.join(REPO, "dynamo_tpu")),
              f"{REPO} holds chip_smoke.py but not the dynamo_tpu package")
        # the model directory may be temporary; the compile cache may not
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            device = (four_chips if args.chips == 4 else one_chip)(args, tmp)
    except SmokeFailure as e:
        emit("failed", error=str(e))
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"]}
    if dev["platform"] != "tpu" or dev["count"] != args.chips:
        print(json.dumps({
            "ok": False, "device": dev,
            "rehearsal": "every phase passed, but not on the TPU(s) asked for",
        }), flush=True)
        return 3
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
