"""Bytes and operations of the Kimi-Linear decoder (stub: filled in below in this PR)."""


def param_count(shape):
    raise NotImplementedError
