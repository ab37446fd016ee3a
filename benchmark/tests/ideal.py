"""What a faultless server would post for a set of lines: the plain
references applied to the truth itself. The comparison's own tests use
it in the server's place."""

from __future__ import annotations

import numpy as np

from harness.compare import (COUNTER, GAUGE, LLHIST, SET, TIMER, pct_name)
from harness.refs import hll_ref, llhist_ref
from harness.refs.tdigest_ref import MergingDigest
from harness.traffic import key_name

PERCENTILES = (0.5, 0.9, 0.99)


def ideal_flush(parts, weights=None, percentiles=PERCENTILES) -> dict:
    """{series name: value} for [(Lines, copies)]; `weights` overrides
    the copies line by line (0 = the line never arrived)."""
    got: dict = {}
    for index, (lines, copies) in enumerate(parts):
        w = (np.full(len(lines), copies) if weights is None
             else weights[index])
        for fam, key, value, n in zip(lines.fam.tolist(), lines.key.tolist(),
                                      lines.value.tolist(), w.tolist()):
            if n <= 0:
                continue
            if fam == COUNTER:
                name = key_name("counter", key)
                got[name] = got.get(name, 0.0) + value * n
            elif fam == GAUGE:
                got[key_name("gauge", key)] = value
            elif fam == TIMER:
                got.setdefault(("t", key), []).append((value, n))
            elif fam == SET:
                got.setdefault(("s", key), []).append(value)
            elif fam == LLHIST:
                got.setdefault(("l", key), []).append((value, n))
    for tag, key in [k for k in got if isinstance(k, tuple)]:
        items = got.pop((tag, key))
        if tag == "t":
            name = key_name("timer", key)
            values = np.array([v for v, _ in items])
            ref = MergingDigest(100.0)
            for v, n in items:
                ref.add(v, float(n))
            got[name + ".count"] = float(sum(n for _, n in items))
            got[name + ".min"] = float(values.astype(np.float32).min())
            got[name + ".max"] = float(values.astype(np.float32).max())
            for p in percentiles:
                got[pct_name(name, p)] = float(np.float32(ref.quantile(p)))
        elif tag == "s":
            ref = hll_ref.HLL()
            for member in items:
                ref.insert(f"u{int(member)}".encode())
            got[key_name("set", key)] = ref.estimate()
        else:
            name = key_name("llhist", key)
            ref = llhist_ref.LLHist()
            ref.insert_many(np.array([v for v, _ in items]),
                            np.array([n for _, n in items]))
            got[name + ".count"] = float(ref.count())
            got[name + ".bucket|le:+Inf"] = float(ref.count())
            got[name + ".sum"] = ref.sum()
            for p, q in zip(percentiles, ref.quantiles(percentiles)):
                got[pct_name(name, p)] = float(q)
    return got
