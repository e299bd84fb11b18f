"""A token-level mock engine whose stream is a pure function of its context (a
helper, not collected): any two workers continue an identical prefix
identically, so a resumed, migrated or re-routed stream can be byte-compared
against an undisturbed control. Used by the tests of every plane that moves a
stream between workers."""

import asyncio

from dynamo_tpu.runtime.annotated import Annotated
from dynamo_tpu.runtime.engine import AsyncEngine, Context


def payload(prompt, max_tokens=16, **sc_extra):
    return {
        "token_ids": list(prompt),
        "stop_conditions": dict({"max_tokens": max_tokens}, **sc_extra),
        "sampling_options": {"temperature": 0.0},
        "eos_token_ids": [],
    }


def _next_token(toks):
    """Pure function of the full context — the greedy-decode stand-in. Any
    two workers continue an identical prefix identically, so resumed
    output can be byte-compared against an undisturbed control."""
    return (toks[-1] * 31 + len(toks) * 7 + 13) % 50021


def expected_stream(prompt, max_tokens):
    toks = list(prompt)
    out = []
    for _ in range(max_tokens):
        nxt = _next_token(toks)
        toks.append(nxt)
        out.append(nxt)
    return out


class TokenEngine(AsyncEngine):
    """Token-level mock engine honoring the PreprocessedRequest wire shape:
    emits one LLMEngineOutput dict per step, each the deterministic
    function of prompt+generated, finishing at max_tokens."""

    def __init__(self, tag: str, delay: float = 0.0):
        self.tag = tag
        self.delay = delay

    async def generate(self, request: Context):
        req = request.data
        toks = list(req["token_ids"])
        max_t = int(req["stop_conditions"]["max_tokens"])
        for _ in range(max_t):
            if request.context.is_stopped:
                return
            nxt = _next_token(toks)
            toks.append(nxt)
            yield Annotated.from_data({"token_ids": [nxt]})
            if self.delay:
                await asyncio.sleep(self.delay)
            else:
                await asyncio.sleep(0)
        yield Annotated.from_data({"token_ids": [], "finish_reason": "length"})
