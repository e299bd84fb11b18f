"""A minimal parser and validator of the Prometheus text exposition format (a
helper, not collected): ``tests/test_promtext.py`` holds it against malformed
input and against every exposition the system renders, and the tests of the
planes that render one parse theirs with it."""

from __future__ import annotations

import math
import re

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
# one label: name="value" with \\, \", \n escapes allowed in the value
_LABEL_RE = re.compile(
    r'\s*([a-zA-Z_][a-zA-Z0-9_]*)\s*=\s*"((?:[^"\\\n]|\\.)*)"\s*(,|$)'
)
_VALID_TYPES = {"counter", "gauge", "histogram", "summary", "untyped"}


class PromParseError(AssertionError):
    pass


def parse_prometheus_text(text: str) -> dict:
    """Validate + parse an exposition. Returns {family: {"help", "type",
    "samples": [(name, labels_dict, value)]}}. Raises PromParseError with
    the offending line on any violation:

    - sample/metadata line syntax and metric-name grammar
    - label name grammar + quoted, escaped label values
    - HELP and TYPE present (and non-empty HELP) for every sampled family
    - at most one HELP/TYPE per family, TYPE from the known set
    - sample names must match their family (modulo _bucket/_sum/_count
      for histograms and summaries)
    """
    families: dict = {}

    def fam(name: str) -> dict:
        return families.setdefault(
            name, {"help": None, "type": None, "samples": []}
        )

    def base_name(sample_name: str) -> str:
        for suffix in ("_bucket", "_sum", "_count", "_total"):
            base = sample_name[: -len(suffix)] if sample_name.endswith(suffix) else None
            if base and base in families and families[base]["type"] in (
                "histogram", "summary", "counter"
            ):
                return base
        return sample_name

    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            rest = line[len("# HELP "):]
            name, _, help_text = rest.partition(" ")
            if not _NAME_RE.match(name):
                raise PromParseError(f"line {lineno}: bad HELP name {name!r}")
            if not help_text.strip():
                raise PromParseError(f"line {lineno}: empty HELP for {name}")
            f = fam(name)
            if f["help"] is not None:
                raise PromParseError(f"line {lineno}: duplicate HELP for {name}")
            f["help"] = help_text
            continue
        if line.startswith("# TYPE "):
            rest = line[len("# TYPE "):]
            name, _, type_text = rest.partition(" ")
            type_text = type_text.strip()
            if not _NAME_RE.match(name):
                raise PromParseError(f"line {lineno}: bad TYPE name {name!r}")
            if type_text not in _VALID_TYPES:
                raise PromParseError(
                    f"line {lineno}: unknown TYPE {type_text!r} for {name}"
                )
            f = fam(name)
            if f["type"] is not None:
                raise PromParseError(f"line {lineno}: duplicate TYPE for {name}")
            f["type"] = type_text
            continue
        if line.startswith("#"):
            continue  # free-form comment
        # sample line: name[{labels}] value
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?\s+(\S+)$", line)
        if m is None:
            raise PromParseError(f"line {lineno}: unparsable sample {line!r}")
        name, label_blob, value_text = m.group(1), m.group(2), m.group(3)
        labels = {}
        if label_blob:
            inner = label_blob[1:-1]
            pos = 0
            while pos < len(inner):
                lm = _LABEL_RE.match(inner, pos)
                if lm is None:
                    raise PromParseError(
                        f"line {lineno}: bad label syntax at {inner[pos:]!r}"
                    )
                key = lm.group(1)
                if not _LABEL_NAME_RE.match(key):
                    raise PromParseError(f"line {lineno}: bad label name {key!r}")
                if key in labels:
                    raise PromParseError(f"line {lineno}: duplicate label {key!r}")
                labels[key] = lm.group(2)
                pos = lm.end()
        try:
            value = float(value_text)
        except ValueError:
            if value_text not in ("+Inf", "-Inf", "NaN"):
                raise PromParseError(
                    f"line {lineno}: bad value {value_text!r}"
                ) from None
            value = math.inf if value_text == "+Inf" else math.nan
        fam(base_name(name))["samples"].append((name, labels, value))

    # every family that rendered samples or metadata must be fully declared
    for name, f in families.items():
        if f["help"] is None:
            raise PromParseError(f"family {name}: missing HELP")
        if f["type"] is None:
            raise PromParseError(f"family {name}: missing TYPE")
    return families


# -- parser self-tests (it must actually reject malformed input) -------------
