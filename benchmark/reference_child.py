"""What the server answered, held against the plain reference.

    python3 benchmark/reference_child.py --model-dir DIR --seed N --case FILE [--reference MODULE] [-- SERVER FLAGS]

A short-lived child of a traced run, started after the server has exited
(one process holds the chip at a time). It makes the weights as the server
made them (the program's ``load_params`` from the same seed: the weights are
the system's input, not its output), runs ``reference.logits`` teacher-forced
over the probe's prompt and the tokens the server returned for it, and logs

    reference {"tokens": n, "argmax_matches": m, "worst_gap": g,
               "max_abs_logit": s, "tolerance": t, "logprob_pairs": k,
               "logprob_rms": r, "logprob_rms_limit": l, "agrees": bool}

once per case of the file. Two comparisons decide ``agrees``.

Tokens, a fault from a tie: random weights give near-flat logits (the first
and the second choice lie a few hundredths apart), so a correct bf16 server
may pick a near-tie of the float32 reference; teacher forcing on the server's
own tokens tells a tie from a fault. Every token the server chose lies within
``tolerance`` = max|logit| / 16 of the reference's first choice, and at least
half are that first choice. A server that skips work or reads the wrong cache
picks tokens whose gap is of the order of max|logit|.

Log-probabilities, bf16 from a coarser arithmetic: the answer carries the
server's 20 likeliest tokens with their log-probabilities at every position
(``logprobs: 20``), and ``logprob_rms`` is the root mean square of claimed
minus reference log-probability over all of them (480 numbers): steady from
seed to seed, where the token numbers are not (on the chip, Qwen2.5-1.5B, 14
seeds: 0.0159-0.0183 for the program, 0.0694-0.0916 for the int8 control,
while the control's tokens read no worse than the program's). The limit is the
configuration's ``correct_limits.logprob_rms``, set from such readings
(``correct_readings.py``; PERF.md 2); without it only the tokens decide.
``--control MODULE`` puts that module's ``logits`` in the program's place (its
own first choices and 20 likeliest after the same history) and logs its
verdict under ``control``; no benchmark run passes it.

Where the server's flags (after ``--``) ask for a mesh of more than one
device, the weights are made in ``param_shardings`` on that mesh, exactly as
``build_jax_serving_engine`` makes them (a model that needs the mesh to fit
never exists whole on one chip), and the reference runs under it: the
compiler partitions the plain float32 forward pass, the mathematics stays
``reference.py``'s. One chip: no mesh, as before.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def mesh_of(server_flags: list):
    """The mesh ``build_jax_serving_engine`` builds for these ``cli.run``
    flags, or None where they ask for one device."""
    p = argparse.ArgumentParser()
    for axis in ("tensor", "pipeline", "context"):
        p.add_argument(f"--{axis}-parallel-size", type=int, default=1)
    flags, _ = p.parse_known_args(server_flags)
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = MeshConfig(tp=flags.tensor_parallel_size, pp=flags.pipeline_parallel_size,
                     sp=flags.context_parallel_size)
    return make_mesh(cfg) if cfg.size > 1 else None


def log_softmax(logits):
    import numpy as np

    shifted = logits - logits.max(axis=-1, keepdims=True)
    return shifted - np.log(np.exp(shifted).sum(axis=-1, keepdims=True))


def held_against(logits, chosen, top_logprobs=(), rms_limit: float = None) -> dict:
    """``chosen`` ([n] token ids) and ``top_logprobs`` (per position, pairs of
    token id and the log-probability claimed for it) against the reference's
    float32 ``logits`` ([n, vocab]): how far below the reference's first
    choice each chosen token lies, and ``logprob_rms``, the root mean square
    of claimed minus reference log-probability over every pair (None without
    pairs). ``agrees``: every chosen token within max|logit| / 16 of the first
    choice and half of them that choice (a fault, not a tie), and, where the
    configuration sets ``rms_limit``, ``logprob_rms`` at or under it (bf16,
    not a coarser arithmetic)."""
    import numpy as np

    gap = logits.max(axis=-1) - logits[np.arange(len(chosen)), chosen]
    scale = float(np.abs(logits).max())
    matches = int((gap == 0).sum())
    want = log_softmax(logits.astype(np.float64))
    errors = [lp - want[i, tok] for i, top in enumerate(top_logprobs) for tok, lp in top]
    rms = float(np.sqrt(np.mean(np.square(errors)))) if errors else None
    return {
        "tokens": len(chosen), "argmax_matches": matches,
        "worst_gap": float(gap.max()), "max_abs_logit": scale, "tolerance": scale / 16.0,
        "logprob_pairs": len(errors),
        "logprob_rms": rms, "logprob_rms_limit": rms_limit,
        "agrees": bool(np.isfinite(logits).all() and gap.max() <= scale / 16.0
                       and 2 * matches >= len(chosen)
                       and (rms_limit is None or (rms is not None and rms <= rms_limit))),
    }


def answer_of(logits, k: int):
    """What a model with these logits would have returned over HTTP: its first
    choice at every position, and its ``k`` likeliest tokens with their
    log-probabilities."""
    import numpy as np

    lps = log_softmax(logits.astype(np.float64))
    top = np.argsort(-logits, axis=-1)[:, :k]
    return logits.argmax(axis=-1), [[(int(t), float(lps[i, t])) for t in row]
                                    for i, row in enumerate(top)]


def main(argv: list) -> int:
    split = argv.index("--") if "--" in argv else len(argv)
    p = argparse.ArgumentParser()
    p.add_argument("--model-dir", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--case", required=True,
                   help="JSON: a list of {prompt_ids, output_ids, top_logprobs}")
    p.add_argument("--reference", default="reference",
                   help="the module under benchmark/ whose logits() is the reference")
    p.add_argument("--logprob-rms-limit", type=float, default=None,
                   help="the configuration's correct_limits.logprob_rms")
    p.add_argument("--control", default=None,
                   help="a module under benchmark/ whose logits() stands in the program's "
                        "place: its verdict is logged under 'control' (never in a benchmark run)")
    args = p.parse_args(argv[:split])

    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module(f"benchmark.{args.reference}")
    control = importlib.import_module(f"benchmark.{args.control}") if args.control else None
    from dynamo_tpu.engine_jax.compile_cache import enable_compile_cache
    from dynamo_tpu.engine_jax.weights import config_from_card, load_params
    from dynamo_tpu.llm.model_card import ModelDeploymentCard
    from dynamo_tpu.models.llama import param_shardings

    enable_compile_cache()
    with open(os.path.join(args.model_dir, "config.json")) as f:
        shape = json.load(f)  # the configuration as the server read it
    with open(args.case) as f:
        cases = json.load(f)
    card = ModelDeploymentCard.from_local_path(args.model_dir)
    model_config = config_from_card(card)
    mesh = mesh_of(argv[split + 1:])
    params = load_params(
        card, model_config, seed=args.seed,
        shardings=param_shardings(model_config, mesh) if mesh is not None else None)

    def logits_of(module):
        return jax.jit(lambda prm, toks, where: module.logits(prm, shape, toks, where))

    plain, other = logits_of(reference), logits_of(control) if control else None
    for case in cases:
        prompt, out = case["prompt_ids"], case["output_ids"]
        seq = jnp.asarray(prompt + out[:-1], jnp.int32)
        at = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        with mesh if mesh is not None else contextlib.nullcontext():
            logits = np.asarray(plain(params, seq, at))
            top = case.get("top_logprobs") or ()
            report = held_against(logits, np.asarray(out), top, args.logprob_rms_limit)
            if other is not None:
                # the control's own answer after the same history
                report["control"] = held_against(logits, *answer_of(
                    np.asarray(other(params, seq, at)), max((len(t) for t in top), default=0)),
                    args.logprob_rms_limit)
        report["mesh"] = dict(mesh.shape) if mesh is not None else None
        print("reference " + json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
