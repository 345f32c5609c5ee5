"""The request generator: the same seed gives the same schedule, and
every seed gives the same work in another order."""
from collections import Counter

import cells

GEN = cells.generator("requests")
CHAT = cells.traffic("chat")
BATCH = cells.traffic("batch")
BIG = 2 ** 31 + 12345          # seeds beyond 32 signed bits


def _lengths(reqs):
    return Counter((len(r["prompt"]), r["max_tokens"]) for r in reqs)


def test_open_loop_is_deterministic_from_the_seed():
    a = GEN.build(CHAT, BIG, 151936, 40.0)
    assert a == GEN.build(CHAT, BIG, 151936, 40.0)
    assert a != GEN.build(CHAT, BIG + 1, 151936, 40.0)


def test_open_loop_segments_hold_rate_times_length_requests():
    a = GEN.build(CHAT, 7, 151936, 40.0)
    n = Counter(r["segment"] for r in a)
    rate = CHAT["rate_rps"]
    assert n["window"] == round(rate * 40.0)
    assert n["lead"] == round(rate * CHAT["lead_s"])
    win = [r["due"] for r in a if r["segment"] == "window"]
    assert 0.0 <= min(win) and max(win) < 40.0
    assert sorted(r["due"] for r in a) == [r["due"] for r in a]


def test_every_seed_gets_the_same_lengths_in_another_order():
    a = GEN.build(CHAT, 1, 151936, 40.0)
    b = GEN.build(CHAT, BIG, 151936, 40.0)
    for seg in ("lead", "window", "drain"):
        sa = [r for r in a if r["segment"] == seg]
        sb = [r for r in b if r["segment"] == seg]
        assert _lengths(sa) == _lengths(sb)
        assert [r["max_tokens"] for r in sa] != [r["max_tokens"] for r in sb]
    assert all(32 <= len(r["prompt"]) <= 1024 and 16 <= r["max_tokens"]
               <= 256 for r in a)


def test_closed_loop_clients_start_at_mixed_points():
    per = GEN.build(BATCH, BIG, 32064, 40.0)
    assert per == GEN.build(BATCH, BIG, 32064, 40.0)
    assert len(per) == BATCH["clients"]
    firsts = [c[0]["max_tokens"] for c in per]
    later = [r["max_tokens"] for c in per for r in c[1:]]
    assert min(firsts) < 256 <= min(later) and max(later) <= 1536
    ids = [r["id"] for c in per for r in c]
    assert len(set(ids)) == len(ids)


def test_closed_loop_seeds_offer_the_same_lists_to_other_clients():
    def sizes(per):
        return sorted(tuple((len(r["prompt"]), r["max_tokens"]) for r in c)
                      for c in per)
    a, b = GEN.build(BATCH, 3, 32064, 40.0), GEN.build(BATCH, BIG, 32064, 40)
    assert sizes(a) == sizes(b)
    assert [len(c[0]["prompt"]) for c in a] != [len(c[0]["prompt"])
                                                for c in b]
    assert a[0][0]["prompt"] != b[0][0]["prompt"]
