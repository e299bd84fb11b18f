"""Starting and stopping the system under test, and its model directory.

No JAX here: the benchmark's parent never touches the chip. ``vocab_words``,
``write_model_dir``, ``Child``, ``wait_device``, ``wait_http`` and
``free_port`` were copied from ``chip_smoke.py`` (PR 21); from now on these
copies are the yardstick's own.
"""

from __future__ import annotations

import http.client
import json
import os
import random
import re
import signal
import socket
import subprocess
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPECIALS = ("<|endoftext|>", "<|im_start|>", "<|im_end|>")


class BenchFailure(Exception):
    """The run cannot produce a result line."""


def check(cond, what: str) -> None:
    if not cond:
        raise BenchFailure(what)


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


def write_model_dir(path: str, shape: dict, seed: int) -> list:
    """HF-layout directory: ``shape`` as config.json and a word-level
    tokenizer over the full vocabulary. No weight files: the engine
    random-initialises from its seed. Returns the id -> word table."""
    from tokenizers import AddedToken, Tokenizer, models, pre_tokenizers

    os.makedirs(path, exist_ok=True)
    words = vocab_words(shape["vocab_size"], seed)
    tk = Tokenizer(models.WordLevel(
        vocab={w: i for i, w in enumerate(words)}, unk_token="unk"
    ))
    tk.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tk.add_special_tokens([AddedToken(t, special=True) for t in SPECIALS])
    tk.save(os.path.join(path, "tokenizer.json"))
    config = dict(
        shape, bos_token_id=words.index("<|endoftext|>"),
        eos_token_id=words.index("<|im_end|>"),
    )
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f, indent=1)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({
            "bos_token": "<|endoftext|>", "eos_token": "<|im_end|>",
            "model_max_length": shape.get("max_position_embeddings", 32768),
        }, f, indent=1)
    return words


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, method: str, path: str, body=None, timeout=60.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            method, path, body=json.dumps(body) if body is not None else None,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


class Child:
    """A child process with its output in a log file; always stopped."""

    def __init__(self, argv: list, log_path: str, env: dict = None):
        self.log_path = log_path
        self._log = open(log_path, "w")
        full_env = dict(os.environ, PYTHONPATH=ROOT, PYTHONUNBUFFERED="1")
        full_env.update(env or {})
        self.proc = subprocess.Popen(
            argv, stdout=self._log, stderr=subprocess.STDOUT, env=full_env,
            cwd=ROOT, start_new_session=True,
        )

    @property
    def pid(self) -> int:
        return self.proc.pid

    def log(self) -> str:
        with open(self.log_path) as f:
            return f.read()

    def log_json(self, marker: str) -> list:
        """All ``<marker> {json}`` payloads the child logged, in order."""
        return [
            json.loads(m) for m in
            re.findall(rf"{marker} (\{{.*\}})\s*$", self.log(), re.M)
        ]

    def cpu_seconds(self) -> float:
        """utime + stime of the child so far, from /proc/<pid>/stat."""
        with open(f"/proc/{self.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

    def stop(self, grace: float = 20.0) -> None:
        if self.proc.poll() is None:
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


def wait_device(child: Child, timeout: float = 180.0) -> dict:
    """The device report the child logged at start-up (the process that
    holds the chip names it; this process stays off JAX)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        devs = child.log_json("device")
        if devs:
            return devs[0]
        check(child.proc.poll() is None,
              f"child exited rc={child.proc.returncode} before naming its "
              f"device; see {child.log_path}:\n{child.log()[-2000:]}")
        time.sleep(0.2)
    raise BenchFailure(f"no device report within {timeout}s; see {child.log_path}")


def wait_http(child: Child, port: int, timeout: float) -> None:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        check(child.proc.poll() is None,
              f"server exited rc={child.proc.returncode}; see "
              f"{child.log_path}:\n{child.log()[-3000:]}")
        try:
            status, raw = http_json(port, "GET", "/v1/models", timeout=5.0)
            if status == 200 and json.loads(raw).get("data"):
                return
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.25)
    raise BenchFailure(f"server not ready within {timeout}s; see {child.log_path}")
