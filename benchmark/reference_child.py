"""What the server answered, held against the plain reference.

    python3 benchmark/reference_child.py --model-dir DIR --seed N --case FILE [--reference MODULE]

A short-lived child of a traced run, started after the server has exited
(one process holds the chip at a time). It makes the weights as the server
made them (the program's ``load_params`` from the same seed: the weights are
the system's input, not its output), runs ``reference.logits`` teacher-forced
over the probe's prompt and the tokens the server returned for it, and logs

    reference {"tokens": n, "argmax_matches": m, "worst_gap": g,
               "max_abs_logit": s, "tolerance": t, "agrees": bool}

Random weights give near-flat logits (the first and the second choice lie a
few hundredths apart), so a correct bf16 server may pick a near-tie of the
float32 reference; teacher forcing on the server's own tokens tells a tie
from a fault. ``agrees``: every token the server chose lies within
``tolerance`` = max|logit| / 16 of the reference's first choice, and at least
half are that first choice. A server that skips work or reads the wrong cache
picks tokens whose gap is of the order of max|logit|.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model-dir", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--case", required=True)
    p.add_argument("--reference", default="reference",
                   help="the module under benchmark/ whose logits() is the reference")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module(f"benchmark.{args.reference}")
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.engine_jax.weights import config_from_card, load_params
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    enable_compile_cache()
    with open(os.path.join(args.model_dir, "config.json")) as f:
        shape = json.load(f)  # the configuration as the server read it
    with open(args.case) as f:
        case = json.load(f)
    prompt, out = case["prompt_ids"], case["output_ids"]
    card = ModelDeploymentCard.from_local_path(args.model_dir)
    params = load_params(card, config_from_card(card), seed=args.seed)

    seq = jnp.asarray(prompt + out[:-1], jnp.int32)
    at = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
    logits = np.asarray(jax.jit(
        lambda prm, toks, where: reference.logits(prm, shape, toks, where)
    )(params, seq, at))
    finite = bool(np.isfinite(logits).all())
    gap = logits.max(axis=-1) - logits[np.arange(len(out)), out]
    scale = float(np.abs(logits).max())
    matches = int((gap == 0).sum())
    report = {
        "tokens": len(out), "argmax_matches": matches,
        "worst_gap": float(gap.max()), "max_abs_logit": scale,
        "tolerance": scale / 16.0,
        "agrees": bool(finite and gap.max() <= scale / 16.0 and 2 * matches >= len(out)),
    }
    print("reference " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
