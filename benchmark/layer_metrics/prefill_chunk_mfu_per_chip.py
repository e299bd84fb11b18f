"""The share of ONE chip's peak that the traced chunk programs reached in a
cell whose model lies over several chips: the operations their tokens need
(``prefill_chunk_flops`` of the module the configuration names under
``bytes_and_flops``, the WHOLE model's: every product counted ONCE, whatever the
parts the program takes it in, and a window layer's attention capped at the
window) / the cell's ``chips`` / the device seconds of the ``jit_chunk`` module
events on device 0 / one chip's published bf16 peak (``peaks.json``). The share
of the whole step in a cell whose time is prefill. ``prefill_chunk_mfu`` divides
the whole model's operations by one chip's peak, which is right on one chip
alone (ROADMAP B11: it takes ``chips``, and this file folds into it).

The tokens of a traced dispatch and a query's context: ``prefill_chunk_mfu``'s.
None without a trace, the peaks, the counters or a chunk event."""

from benchmark import bytes_and_flops, counters, trace_reduce

NAME = "prefill_chunk_mfu_per_chip"
UNIT = "%"
LAYER = "model, prompt processing"
MOVES = "ttft_mean_ms"

TOKENS, DISPATCHES = "chunk_tokens_fed", "chunk_dispatches_by_rows"


def read(ctx):
    if not ctx["trace"] or not ctx["peaks"]:
        return None
    m = trace_reduce.module_medians_ms(ctx["trace"]).get("jit_chunk")
    ends = counters.window_ends(ctx, TOKENS, DISPATCHES)
    prompt = ctx["summary"].get("mean_prompt_tokens")
    if not m or ends is None or prompt is None:
        return None
    dispatches = sum(ends[1][DISPATCHES].values()) - sum(ends[0][DISPATCHES].values())
    if dispatches <= 0 or m["total_ms"] <= 0:
        return None
    tokens = (ends[1][TOKENS] - ends[0][TOKENS]) / dispatches * m["count"]
    flops = bytes_and_flops.for_config(ctx["config"]).prefill_chunk_flops(
        ctx["shape"], tokens, prompt / 2.0)
    return 100.0 * flops / ctx["chips"] / (m["total_ms"] / 1e3) / ctx["peaks"]["bf16_flops_per_s"]
