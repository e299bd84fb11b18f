"""Self-contained test fixtures: a tiny byte-level BPE tokenizer and an
HF-layout model directory (config.json + tokenizer_config.json + tokenizer.json),
built programmatically so tests need no network or checked-in binary blobs.

Mirrors the reference's checked-in sample-model fixtures
(lib/llm/tests/data/sample-models/) without copying them.
"""

from __future__ import annotations

import contextlib
import json
import os
import socket
import subprocess
import sys
import time

CHAT_TEMPLATE = (
    "{% for message in messages %}"
    "{{ '<|' + message['role'] + '|>' }}{{ message['content'] }}{{ eos_token }}"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)

_CORPUS = [
    "hello world this is a tiny tokenizer for tests",
    "the quick brown fox jumps over the lazy dog",
    "streaming tokens over the response plane",
    "café naïve résumé 你好世界 こんにちは",
    "```python\nprint('hi')\n```",
    "STOP sequences and <|assistant|> markers",
    "0123456789 !@#$%^&*()",
]


def build_tokenizer():
    """Train a tiny byte-level BPE tokenizer in-process."""
    from tokenizers import Tokenizer, models, pre_tokenizers, decoders, trainers

    tk = Tokenizer(models.BPE(unk_token=None))
    tk.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tk.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=512,
        special_tokens=["<s>", "</s>", "<|user|>", "<|assistant|>", "<|system|>"],
        show_progress=False,
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    tk.train_from_iterator(_CORPUS, trainer)
    return tk


def build_model_dir(path: str, n_layers: int = 2, hidden: int = 64) -> str:
    """Write an HF-layout model directory with the tiny tokenizer."""
    os.makedirs(path, exist_ok=True)
    tk = build_tokenizer()
    tk.save(os.path.join(path, "tokenizer.json"))

    eos_id = tk.token_to_id("</s>")
    bos_id = tk.token_to_id("<s>")
    config = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": tk.get_vocab_size(),
        "hidden_size": hidden,
        "intermediate_size": hidden * 4,
        "num_hidden_layers": n_layers,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": hidden // 4,
        "max_position_embeddings": 2048,
        "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0,
        "bos_token_id": bos_id,
        "eos_token_id": eos_id,
        "tie_word_embeddings": False,
    }
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f, indent=1)

    tok_cfg = {
        "bos_token": "<s>",
        "eos_token": "</s>",
        "chat_template": CHAT_TEMPLATE,
        "model_max_length": 2048,
    }
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump(tok_cfg, f, indent=1)
    return path


def run_ranks(script, n_ranks: int, deadline_s: float) -> list:
    """Run ``script <rank> <coordinator address>`` once per rank as fresh
    CPU processes, wait for all of them under ONE deadline and return their
    outputs. The first rank to exit non-zero (or the deadline) kills the
    rest — a peer of a crashed rank would otherwise wait on its collective
    until its own time-out — and no rank outlives the call, whatever ends
    it. Unless every rank exited 0 the outputs of ALL ranks are shown: the
    one that was killed usually only waited for the one that crashed."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        addr = f"127.0.0.1:{s.getsockname()[1]}"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    logs = [open(f"{script}.rank{rank}.log", "w+b") for rank in range(n_ranks)]
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank), addr],
            env=env, stdout=log, stderr=subprocess.STDOUT,
        )
        for rank, log in enumerate(logs)
    ]
    end = time.monotonic() + deadline_s
    try:
        while time.monotonic() < end:
            codes = [p.poll() for p in procs]
            if any(codes) or None not in codes:
                break
            time.sleep(0.1)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
    outs = []
    for log in logs:
        log.seek(0)
        outs.append(log.read().decode(errors="replace"))
        log.close()
    assert all(p.returncode == 0 for p in procs), "\n".join(
        f"--- rank {rank} exited {p.returncode} ---\n{out}"
        for rank, (p, out) in enumerate(zip(procs, outs))
    )
    return outs


@contextlib.contextmanager
def engines_held_back(delay: float = 0.1):
    """Slow every engine dispatch by ``delay`` s (the ``slow`` fault) while
    a test reads the first tokens of a stream it means to freeze mid-way: a
    free-running tiny engine is through a 10-token answer in 30 ms, so where
    it stands when the test gets to it (or whether it still runs at all) is
    the machine's load."""
    from dynamo_tpu.runtime import faults

    with faults.active(faults.FaultInjector([faults.FaultRule(
        plane="engine", point="dispatch", action="slow", delay=delay,
    )])):
        yield
