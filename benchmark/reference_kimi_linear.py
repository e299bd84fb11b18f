"""The plain reference of the Kimi-Linear decoder (stub: filled in below in this PR)."""


def logits(params, shape, tokens, at):
    raise NotImplementedError
