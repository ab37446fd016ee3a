"""Plain reference of upstream veneur's `metric_sink_routing`, written
from its description (README "metric_sink_routing"; `flusher.go:97-113`,
`util/matcher/matcher.go`) over the configuration as YAML gives it:
plain dicts and lists, no classes, nothing compiled ahead.

    metric_sink_routing:
      - name: <rule>
        match:                      # a list of matchers; ANY may match
          - name: {kind: any | exact | prefix | regex, value: <str>}
            tags:                   # EVERY tag test must hold
              - {kind: exact | prefix | regex, value: <str>, unset: <bool>}
        sinks: {matched: [<sink>...], not_matched: [<sink>...]}

A tag test holds when some tag of the series satisfies it, or, with
`unset: true`, when none does. A regex matches anywhere in the string
(Go's `regexp.MatchString`). A rule gives its `matched` sinks to a
series that one of its matchers accepts and its `not_matched` sinks to
every other; a series goes to the union over the rules, and to no sink
when that is empty.

What the tests hold `util/matcher.py` and the server's routed flush to
(`tests/test_routed_flush.py`). Imports nothing from either.
"""

from __future__ import annotations

import re
from typing import Iterable, Mapping, Sequence, Set


def _holds(kind: str, value: str, text: str) -> bool:
    if kind == "exact":
        return text == value
    if kind == "prefix":
        return text[:len(value)] == value
    if kind == "regex":
        return re.search(value, text) is not None
    raise ValueError(f"unknown matcher kind {kind!r}")


def name_matches(test: Mapping, name: str) -> bool:
    """A matcher with no `name` test, or of kind `any`, takes every
    name."""
    kind = (test or {}).get("kind", "any")
    return kind == "any" or _holds(kind, test.get("value", ""), name)


def tag_test_holds(test: Mapping, tags: Iterable[str]) -> bool:
    present = False
    for tag in tags:
        if _holds(test.get("kind", "exact"), test.get("value", ""), tag):
            present = True
    return not present if test.get("unset", False) else present


def matcher_accepts(matcher: Mapping, name: str,
                    tags: Sequence[str]) -> bool:
    if not name_matches(matcher.get("name"), name):
        return False
    for test in matcher.get("tags") or ():
        if not tag_test_holds(test, tags):
            return False
    return True


def rule_sinks(rule: Mapping, name: str, tags: Sequence[str]) -> list:
    """The sinks one rule names for a series."""
    accepted = False
    for matcher in rule.get("match") or ():
        if matcher_accepts(matcher, name, tags):
            accepted = True
    sinks = rule.get("sinks") or {}
    return list(sinks.get("matched" if accepted else "not_matched") or ())


def route(rules: Sequence[Mapping], name: str,
          tags: Sequence[str]) -> Set[str]:
    """The sinks a series reaches under a rule list."""
    out: Set[str] = set()
    for rule in rules:
        out.update(rule_sinks(rule, name, tags))
    return out
