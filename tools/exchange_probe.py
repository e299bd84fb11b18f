"""What the shards' partial sums go through on the chips (`models/mellum.py:_all_reduce`),
and how far the mesh's chunk program lies from one chip's.

`chiprun --chips 4 -- python3 tools/exchange_probe.py` (4 min; `--tiny` on four
virtual CPU devices, 45 s). Two parts, one JSON line each, appended to
`chiprun_out/exchange_probe.jsonl`:

1. `arithmetic`: ONE all-reduce of float32 at the shapes the step programs
   hand over (`[8, 128, 2304]` a chunk group, `[2, 128, 2304]` the 2-row
   program, `[16, 1, 2304]` a decode step), its four inputs seeded, its output
   on every shard against the inputs' sum in float64 on the host: the error in
   units of float32's and of bfloat16's rounding of the sum, and whether the
   four shards hold the same bits.
2. `program`: the module's chunk program at the published widths and `--layers`
   layers (whole periods), a prompt of 150 tokens (one dispatch) and one of
   `--prompt-tokens`, its final hidden states against the plain reference's
   under the mesh (`benchmark/reference_mellum.py`, the head an identity). The
   `--runs`, each `where:all_reduce:deal`: on the `tp=4` `mesh` or whole on
   `one_chip`; the all-reduce as it is (`psum`) or as an all-gather whose four
   parts every shard adds itself (`gathered`); the prompt dealt as the `engine`
   deals it (8 rows of the 16-row program, then the 2-row program) or through
   the 2-row program alone (`two_rows`). A `one_chip` run also holds the mesh's
   hidden states against its own, no reference between. Beside them the
   reference against itself with every embedding moved by 1e-6 and by 1e-5:
   what the MODEL makes of a difference (a router that changes its choice
   could multiply one; this one does not).

What it read on the four chips of a v5e host (PR 68) is in PERF.md 7 (a).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--tiny" in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmark import reference_mellum as ref  # noqa: E402
from dynamo_tpu.models import mellum  # noqa: E402
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh, model_axis  # noqa: E402

ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                           "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def gathered(y, axis):
    """`_all_reduce` without a reduction on the wire: every shard gathers the
    four parts and adds them itself, in float32, in the shards' order."""
    if axis is None:
        return y
    parts = jax.lax.all_gather(y, axis)
    total = parts[0]
    for k in range(1, parts.shape[0]):
        total = total + parts[k]
    return total


def arithmetic(mesh, axis, shapes, seed):
    """One `psum` a shape: its output on every shard against the float64 sum."""
    n = mesh.shape[axis]
    out = []
    for shape in shapes:
        rng = np.random.default_rng(seed)
        # partial sums as a layer's are: of one size, signs mixed, so the sum cancels in places
        parts = (rng.standard_normal((n, *shape)) * rng.uniform(0.5, 2.0, (n, 1, 1, 1))).astype(np.float32)
        on = jax.device_put(parts, NamedSharding(mesh, P(axis)))
        run = jax.jit(shard_map(lambda a: jax.lax.psum(a[0], axis)[None], mesh=mesh,
                                in_specs=P(axis), out_specs=P(axis), check_vma=False))
        got = np.asarray(run(on)).astype(np.float64)
        exact = parts.astype(np.float64).sum(0)
        scale = np.abs(parts.astype(np.float64)).sum(0)  # what a rounding of the sum is relative to
        err = np.abs(got - exact[None]) / scale[None]
        out.append({
            "shape": list(shape),
            "max_error_over_sum_of_magnitudes": float(err.max()),
            "in_float32_roundings": float(err.max() / 2.0 ** -24),
            "in_bfloat16_roundings": float(err.max() / 2.0 ** -9),
            "rms_error_over_rms_sum": float(np.sqrt(((got - exact[None]) ** 2).mean() / (exact ** 2).mean())),
            "shards_hold_the_same_bits": bool((got == got[:1]).all()),
        })
    return out


SEED = 0  # of the weights and of the all-reduce's inputs; a prompt's tokens are seeded by its length
PERTURBED = (1e-6, 1e-5)  # relative sizes the reference's embeddings are moved by
RUNS = ("mesh:psum:engine", "mesh:psum:two_rows", "mesh:gathered:engine", "one_chip:psum:engine")


def _error(got, want):
    """(rms of the difference over the rms, [median, 9th decile, largest] of the
    same a token): arithmetic moves every token alike, a router's changed
    choice a few tokens by much."""
    by_token = np.sqrt(((got - want) ** 2).mean(-1) / (want ** 2).mean(-1))
    return (float(np.sqrt(((got - want) ** 2).mean() / (want ** 2).mean())),
            [float(np.quantile(by_token, q)) for q in (0.5, 0.9, 1.0)])


def program(mesh, layers, vocab, prompt_tokens, tiny, seed, runs, perturbed):
    try:
        return _program(mesh, layers, vocab, prompt_tokens, tiny, seed, runs, perturbed)
    finally:
        mellum._all_reduce = ALL_REDUCE


def _program(mesh, layers, vocab, prompt_tokens, tiny, seed, runs, perturbed):
    widths = dict(hidden_size=64, num_heads=8, num_kv_heads=4, head_dim=16, moe_intermediate_size=32,
                  num_experts=8, num_experts_per_tok=2, sliding_window=256) if tiny else {}
    kinds = ((mellum.WINDOW,) * 3 + (mellum.FULL,)) * (layers // 4)
    c = mellum.MellumConfig(num_layers=layers, layer_types=kinds, vocab_size=vocab, **widths)
    shape = {"hidden_size": c.hidden_size, "num_hidden_layers": layers, "layer_types": list(kinds),
             "sliding_window": c.sliding_window, "num_attention_heads": c.num_heads,
             "num_key_value_heads": c.num_kv_heads, "head_dim": c.head_dim, "num_experts": c.num_experts,
             "num_experts_per_tok": c.num_experts_per_tok, "norm_topk_prob": True, "rms_norm_eps": c.rms_norm_eps,
             "rope_parameters": ROPE}
    params = jax.jit(lambda: mellum.init_params(jax.random.PRNGKey(seed % 2 ** 31), c),
                     out_shardings=mellum.param_shardings(c, mesh))()
    whole = None  # the same tree on ONE chip, made when a run asks for it
    # the reference's hidden states after the final norm: its head an identity, its table float32
    eye = {**params, "lm_head": jnp.eye(c.hidden_size, dtype=jnp.float32),
           "embed": params["embed"].astype(jnp.float32)}
    hidden = jax.jit(lambda prm, toks, at: ref.logits(prm, shape, toks, at))
    slots, mb, width, slot = 16, 256, 128, 5
    table = np.arange(1, mb + 1, dtype=np.int32)
    out = []
    for n in (150, prompt_tokens):
        tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(n), (n,), 0, vocab))
        tail = min(width, n - (-(-n // width) - 1) * width)  # the last row's positions
        at_tail = jnp.arange(n - tail, n)
        with mesh:
            want = np.asarray(hidden(eye, jnp.asarray(tokens), at_tail), np.float64)
            # what the MODEL makes of a difference of that size: the reference against itself,
            # every embedding moved by a relative `level` (seeded)
            for level in perturbed:
                noise = jax.random.normal(jax.random.PRNGKey(7), eye["embed"].shape, jnp.float32)
                moved = {**eye, "embed": eye["embed"] * (1.0 + level * noise)}
                rms, by_token = _error(np.asarray(hidden(moved, jnp.asarray(tokens), at_tail), np.float64), want)
                out.append({"prompt_tokens": n, "reference_against_itself_embeddings_moved_by": level,
                            "hidden_rms_error_over_rms": rms, "by_token_median_p90_max": by_token})
                print(json.dumps(out[-1]), file=sys.stderr, flush=True)
        seen = {}
        for run in runs:
            where, form, deal = run.split(":")
            if n <= 2 * width and deal == "two_rows":
                continue  # the engine's deal of so short a prompt IS the 2-row program
            on = mesh if where == "mesh" else None
            if on is None and whole is None:
                whole = jax.device_put(params, jax.devices()[0])
            mellum._all_reduce = gathered if form == "gathered" else ALL_REDUCE
            chunk = jax.jit(lambda prm, t, p, cache, tb, st, ln: mellum.forward_chunk(
                prm, c, t, p, cache, tb, st, ln, mesh=on), donate_argnums=(3, 5))
            cache = mellum.make_kv_cache(c, mb + 1, 16, mesh=on)
            state = mellum.make_slot_state(c, slots, mesh=on)
            at, last = 0, None
            while at < n:
                left = -(-(n - at) // width)
                rows, fed = (16, min(8, left)) if left > 2 and deal == "engine" else (2, min(2, left))
                t, p = np.zeros((rows, width), np.int32), np.full((rows, width), -1, np.int32)
                tb, ln = np.zeros((rows, mb), np.int32), np.full((rows,), slots, np.int32)
                for r in range(fed):
                    k = min(width, n - at)
                    t[r, :k], p[r, :k] = tokens[at:at + k], np.arange(at, at + k)
                    tb[r], ln[r] = table, slot
                    last = (r, k)
                    at += k
                h, cache, state, _ = chunk(params if on is not None else whole, jnp.asarray(t), jnp.asarray(p),
                                           cache, jnp.asarray(tb), state, jnp.asarray(ln))
            got = seen[run] = np.asarray(h[last[0], :last[1]], np.float64)
            assert got.shape == want.shape, (got.shape, want.shape)
            rms, by_token = _error(got, want)
            out.append({"prompt_tokens": n, "where": where, "all_reduce": form, "deal": deal,
                        "hidden_rms_error_over_rms": rms, "by_token_median_p90_max": by_token})
            if where == "one_chip" and RUNS[0] in seen:  # the mesh's program against one chip's, no reference between
                rms, by_token = _error(seen[RUNS[0]], got)
                out[-1]["the_mesh_s_against_it"] = {"hidden_rms_error_over_rms": rms,
                                                    "by_token_median_p90_max": by_token}
            print(json.dumps(out[-1]), file=sys.stderr, flush=True)
            del cache, state
    return out


ALL_REDUCE = mellum._all_reduce


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--layers", type=int, default=8, help="whole periods of four")
    ap.add_argument("--prompt-tokens", type=int, default=2304)
    ap.add_argument("--parts", default="arithmetic,program")
    ap.add_argument("--runs", default=",".join(RUNS), help="where:all_reduce:deal, comma-separated")
    ap.add_argument("--tiny", action="store_true", help="tiny widths on four virtual CPU devices")
    args = ap.parse_args()
    mesh = make_mesh(MeshConfig(tp=4))
    axis, _ = model_axis(mesh)
    hidden = 64 if args.tiny else 2304
    device = {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind, "count": jax.device_count()}
    parts = {
        "arithmetic": lambda: {"psum": arithmetic(
            mesh, axis, ((8, 128, hidden), (2, 128, hidden), (16, 1, hidden)), SEED)},
        "program": lambda: {"layers": args.layers, "runs": program(
            mesh, args.layers, 512 if args.tiny else 8192, args.prompt_tokens, args.tiny, SEED,
            [r for r in args.runs.split(",") if r], PERTURBED)},
    }
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/exchange_probe.jsonl", "a") as f:
        for part in args.parts.split(","):
            line = json.dumps({"part": part, "device": device, **parts[part]()})
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()


if __name__ == "__main__":
    main()
