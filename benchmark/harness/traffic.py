"""Seeded DogStatsD traffic and the plain truth it encodes.

One general generator reads a traffic file (`benchmark/traffic/*.json`)
and a configuration file (`benchmark/configs/*.json`). Two kinds:

  per_interval  every interval k has its own lines, drawn from
                (seed, k): each key of each family reports as the file's
                `samples` say, sent once across the send window.
  replay        a corpus drawn once from (seed, "corpus") is sent in
                whole cycles at `datagrams_per_s` across the send window;
                an optional small `once` part (fresh per interval, like
                per_interval) rides in front of the first cycle. Whole
                cycles make an interval's truth known without rendering
                it: a key's multiset is its corpus values times the
                cycles sent.

Every interval's lines leave inside `[tick + lead_s, tick + lead_s +
send_window * interval]`, and the generator is quiet from there to the
next tick: DogStatsD lines carry no timestamp, so a line's interval is
known only if it is read well clear of the server's swap.

`Traffic.render_*` gives datagrams (the loadgen child calls it);
`Traffic.truth(k)` gives what the references need (the parent calls it
after the window has closed). Both draw the same random streams, so the
same seed gives the same lines and the same truth. Imports no JAX and
nothing of the program.
"""

from __future__ import annotations

import json

import numpy as np

FAMILIES = ("counter", "gauge", "timer", "set", "llhist")
NAME_PREFIX = "bench."
# interval numbers from here up are warm-up rounds: same keys, own values
WARMUP_BASE = 1_000_000
_CORPUS, _ONCE = 7_000_001, 7_000_002    # seed words of the replay parts
_SUFFIX = {"counter": "c", "gauge": "g", "timer": "ms", "set": "s",
           "llhist": "l"}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def key_name(fam: str, i: int) -> str:
    return f"{NAME_PREFIX}{fam}.{i:06d}"


def key_tags(i: int) -> str:
    return f"env:bench,zone:z{i % 8}"


def tiers_to_samples(tiers, n_keys: int) -> np.ndarray:
    """[[keys, samples], ...] -> samples per key, first tier first. The
    last tier's key count may be null: the rest of the keys."""
    out = np.zeros(n_keys, np.int64)
    at = 0
    for keys, samples in tiers:
        keys = n_keys - at if keys is None else min(int(keys), n_keys - at)
        out[at:at + keys] = samples
        at += keys
    if at != n_keys:
        raise ValueError(f"sample tiers cover {at} of {n_keys} keys")
    return out


class Lines:
    """The lines of one part in one interval, as arrays in generation
    order: family code, key id, parsed value (what a DogStatsD server
    reads from the text sent; a set member's number for sets), and the
    datagram each line rides in."""

    def __init__(self, fam, key, value, datagram, n_datagrams, text=None):
        self.fam, self.key, self.value = fam, key, value
        self.datagram, self.n_datagrams = datagram, n_datagrams
        self.text = text   # rendered lines, in send order, when asked for

    def __len__(self) -> int:
        return len(self.key)


class Part:
    """One seeded set of lines over (a slice of) the configuration's
    keys: the per-interval lines, a replay corpus, or its `once` part.
    A counter or gauge key has one line per draw."""

    def __init__(self, spec: dict, per_datagram: int):
        self.first = {f: int(spec.get("first_key", {}).get(f, 0))
                      for f in FAMILIES}
        self.keys = {f: int(spec.get("keys", {}).get(f, 0))
                     for f in FAMILIES}
        samples = spec.get("samples", {})
        self.per_key = {
            "counter": np.ones(self.keys["counter"], np.int64),
            "gauge": np.ones(self.keys["gauge"], np.int64),
            "timer": tiers_to_samples(samples.get("timer", [[None, 3]]),
                                      self.keys["timer"]),
            "set": np.full(self.keys["set"],
                           int(samples.get("set_members", 16)), np.int64),
            "llhist": np.full(self.keys["llhist"],
                              int(samples.get("llhist", 6)), np.int64),
        }
        self.per_datagram = per_datagram
        self.lines = int(sum(int(v.sum()) for v in self.per_key.values()))
        self.datagrams = -(-self.lines // per_datagram)

    def ids(self, fam: str) -> np.ndarray:
        return self.first[fam] + np.arange(self.keys[fam])

    def draw(self, seed_words, render: bool = False) -> Lines:
        """Every line of this part for one draw of the seed."""
        rng = np.random.default_rng([int(w) for w in seed_words])
        fams, keys, values, texts = [], [], [], []

        def add(code, fam, value, text):
            ids = np.repeat(self.ids(fam), self.per_key[fam])
            fams.append(np.full(ids.size, code, np.int8))
            keys.append(ids)
            values.append(np.asarray(value, np.float64))
            if render:
                sfx = _SUFFIX[fam]
                texts.extend(f"{key_name(fam, i)}:{t}|{sfx}|#{key_tags(i)}"
                             for i, t in zip(ids.tolist(), text))

        v = rng.integers(1, 1000, self.keys["counter"])
        add(0, "counter", v, map(str, v.tolist()))
        # quarter-integers: exact in float32, so "exactly" is well defined
        v = rng.integers(0, 1 << 20, self.keys["gauge"]) / 4.0
        add(1, "gauge", v, map(str, v.tolist()))
        text = [f"{x:.3f}" for x in rng.lognormal(
            3.0, 1.0, int(self.per_key["timer"].sum())).tolist()]
        add(2, "timer", [float(t) for t in text], text)
        members = int(self.per_key["set"][0]) if self.keys["set"] else 0
        base = rng.integers(0, 1 << 40, self.keys["set"]) * max(members, 1)
        v = (np.repeat(base, members)
             + np.tile(np.arange(members), self.keys["set"]))
        add(3, "set", v, (f"u{m}" for m in v.tolist()))
        text = [f"{x:.4g}" for x in rng.lognormal(
            1.0, 2.0, int(self.per_key["llhist"].sum())).tolist()]
        add(4, "llhist", [float(t) for t in text], text)

        order = rng.permutation(self.lines)
        position = np.empty(self.lines, np.int64)
        position[order] = np.arange(self.lines)
        return Lines(np.concatenate(fams), np.concatenate(keys),
                     np.concatenate(values), position // self.per_datagram,
                     self.datagrams,
                     [texts[j] for j in order.tolist()] if render else None)

    def render(self, seed_words) -> list:
        text, per = self.draw(seed_words, render=True).text, self.per_datagram
        return ["\n".join(text[i:i + per]).encode()
                for i in range(0, len(text), per)]


class Traffic:
    """A traffic file bound to a configuration and a seed."""

    def __init__(self, traffic: dict, config: dict, seed: int):
        self.kind = traffic["kind"]
        if self.kind not in ("per_interval", "replay"):
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        self.spec, self.seed = traffic, int(seed)
        self.interval_s = float(config["interval_s"])
        self.lead_s = float(traffic.get("lead_s", 0.5))
        self.send_s = float(traffic["send_window"]) * self.interval_s
        self.warmup_send_s = float(traffic.get("warmup_send_s", 3.0))
        self.senders = int(traffic.get("sender_processes", 1))
        per = int(traffic["lines_per_datagram"])
        for name in ("per_interval", "corpus", "once"):
            part = traffic.get(name)
            if part:
                for fam, n in part.get("keys", {}).items():
                    top = int(part.get("first_key", {}).get(fam, 0)) + n
                    if top > config["keys"].get(fam, 0):
                        raise ValueError(
                            f"traffic part {name!r} reaches {fam} key {top}"
                            f", the configuration has "
                            f"{config['keys'].get(fam, 0)}")
        if self.kind == "per_interval":
            self.fresh = Part(traffic["per_interval"], per)
            self.corpus, self.cycles, self.warmup_cycles = None, 0, 0
        else:
            self.fresh = Part(traffic.get("once") or {}, per)
            self.corpus = Part(traffic["corpus"], per)
            rate = float(traffic["datagrams_per_s"])
            self.cycles = max(1, int(rate * self.send_s)
                              // self.corpus.datagrams)
            # the real send time of those whole cycles at that rate
            self.send_s = self.cycles * self.corpus.datagrams / rate
            self.warmup_cycles = max(1, int(
                rate * self.warmup_send_s) // self.corpus.datagrams)

    def cycles_of(self, k: int) -> int:
        return self.warmup_cycles if k >= WARMUP_BASE else self.cycles

    def lines_of(self, k: int) -> int:
        corpus = self.corpus.lines if self.corpus else 0
        return self.fresh.lines + corpus * self.cycles_of(k)

    def _fresh_words(self, k: int):
        if self.kind == "per_interval":
            return (self.seed, k)
        return (self.seed, _ONCE, k)

    def render_fresh(self, k: int) -> list:
        return self.fresh.render(self._fresh_words(k)) if self.fresh.lines \
            else []

    def render_corpus(self) -> list:
        return self.corpus.render((self.seed, _CORPUS)) if self.corpus else []

    def truth(self, k: int) -> list:
        """[(Lines, copies)] for interval k: each part's lines and how
        many times each was sent."""
        parts = []
        if self.fresh.lines:
            parts.append((self.fresh.draw(self._fresh_words(k)), 1))
        if self.corpus:
            parts.append((self.corpus.draw((self.seed, _CORPUS)),
                          self.cycles_of(k)))
        return parts
