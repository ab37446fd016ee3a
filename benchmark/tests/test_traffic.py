"""Rendered lines and `truth(k)` agree, for both traffic kinds; the same
seed gives the same bytes, another seed others."""

import numpy as np
import pytest

from harness.traffic import FAMILIES, Traffic, WARMUP_BASE

CONFIG = {"interval_s": 10.0, "keys": {"counter": 40, "gauge": 20,
                                       "timer": 70, "set": 9, "llhist": 5}}
PER_INTERVAL = {
    "kind": "per_interval", "lines_per_datagram": 7, "send_window": 0.84,
    "per_interval": {"keys": CONFIG["keys"], "samples": {
        "timer": [[2, 40], [8, 5], [None, 3]], "set_members": 16,
        "llhist": 6}}}
REPLAY = {
    "kind": "replay", "lines_per_datagram": 13, "send_window": 0.84,
    "datagrams_per_s": 100, "sender_processes": 2,
    "corpus": {"keys": {"timer": 50}, "samples": {"timer": [[None, 26]]}},
    "once": {"first_key": {"timer": 50}, "keys": {"timer": 8},
             "samples": {"timer": [[None, 3]]}}}
SUFFIX = {"c": 0, "g": 1, "ms": 2, "s": 3, "l": 4}


def parse(datagrams):
    """[(family code, key id, value, datagram index)] as a plain
    DogStatsD reader sees the bytes."""
    out = []
    for d, datagram in enumerate(datagrams):
        for line in datagram.decode().split("\n"):
            head, kind, tags = line.split("|")
            name, value = head.split(":")
            fam = SUFFIX[kind]
            key = int(name.rsplit(".", 1)[1])
            assert name == f"bench.{FAMILIES[fam]}.{key:06d}"
            assert tags == f"#env:bench,zone:z{key % 8}"
            out.append((fam, key,
                        float(value[1:]) if fam == 3 else float(value), d))
    return sorted(out)


def as_rows(lines):
    return sorted(zip(lines.fam.tolist(), lines.key.tolist(),
                      lines.value.tolist(), lines.datagram.tolist()))


@pytest.mark.parametrize("k", [0, 3, WARMUP_BASE])
def test_per_interval_lines_are_the_truth(k):
    traffic = Traffic(PER_INTERVAL, CONFIG, seed=2_200_000_123)
    [(lines, copies)] = traffic.truth(k)
    datagrams = traffic.render_fresh(k)
    assert copies == 1 and len(datagrams) == lines.n_datagrams
    assert parse(datagrams) == as_rows(lines)
    assert len(lines) == traffic.lines_of(k) == 40 + 20 + 2 * 40 + 8 * 5 \
        + 60 * 3 + 9 * 16 + 5 * 6
    assert all(d.count(b"\n") + 1 <= 7 for d in datagrams)


def test_replay_lines_are_the_truth():
    traffic = Traffic(REPLAY, CONFIG, seed=5)
    (once, one), (corpus, cycles) = traffic.truth(2)
    assert one == 1 and cycles == traffic.cycles == traffic.cycles_of(2)
    assert parse(traffic.render_fresh(2)) == as_rows(once)
    assert parse(traffic.render_corpus()) == as_rows(corpus)
    # whole cycles at the stated rate inside the send window
    assert corpus.n_datagrams == 100 and cycles == 8
    assert traffic.send_s == pytest.approx(8.0)
    assert traffic.lines_of(2) == 24 + 8 * 1300
    # the corpus is the same in every interval, the once part is not
    assert as_rows(traffic.truth(3)[1][0]) == as_rows(corpus)
    assert as_rows(traffic.truth(3)[0][0]) != as_rows(once)
    assert set(once.key.tolist()) == set(range(50, 58))


def test_seed_decides_the_bytes():
    big = 2**31 + 77
    a = Traffic(PER_INTERVAL, CONFIG, big).render_fresh(1)
    assert a == Traffic(PER_INTERVAL, CONFIG, big).render_fresh(1)
    assert a != Traffic(PER_INTERVAL, CONFIG, big + 1).render_fresh(1)
    assert a != Traffic(PER_INTERVAL, CONFIG, big).render_fresh(2)


def test_a_part_may_not_outgrow_its_configuration():
    small = {"interval_s": 10.0, "keys": {"timer": 10}}
    with pytest.raises(ValueError):
        Traffic(REPLAY, small, 0)


def test_every_seed_sends_the_same_sizes():
    sizes = {Traffic(PER_INTERVAL, CONFIG, s).lines_of(0) for s in range(4)}
    assert len(sizes) == 1
    lengths = [np.bincount(Traffic(PER_INTERVAL, CONFIG, s).truth(0)[0][0]
                           .datagram).tolist() for s in range(3)]
    assert lengths[0] == lengths[1] == lengths[2]
