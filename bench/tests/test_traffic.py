"""The traffic generator gives every seed the same work, in another order."""
import numpy as np
from tiny import spec

from benchlib import readers

gen = readers.module("traffic", "generator")


def mix(name):
    return spec.load_json(spec.BENCH / "traffic" / f"{name}.json")


def test_same_requests_for_every_seed():
    chat = mix("chat")
    a = gen.plan(chat, 1, 151936, 45.0)
    b = gen.plan(chat, 2**33 + 7, 151936, 45.0)
    for phase in ("warm", "window", "drain"):
        pa = [it for it in a.arrivals if it.phase == phase]
        pb = [it for it in b.arrivals if it.phase == phase]
        assert len(pa) == len(pb) == round(chat["arrivals"]["rate_per_s"]
                                           * {"warm": chat["warm_s"],
                                              "window": 45.0,
                                              "drain": chat["drain_s"]}[phase])
        assert sorted(len(i.prompt) for i in pa) == \
            sorted(len(i.prompt) for i in pb)
        assert sorted(i.max_new for i in pa) == sorted(i.max_new for i in pb)
        assert [len(i.prompt) for i in pa] != [len(i.prompt) for i in pb]
    w = [it for it in a.arrivals if it.phase == "window"]
    assert chat["warm_s"] <= w[0].due and w[-1].due < chat["warm_s"] + 45.0
    lens = np.array([len(i.prompt) for i in a.arrivals])
    assert lens.min() >= 64 and lens.max() <= 3072
    assert 350 < np.median(lens) < 700


def test_sessions_and_shapes():
    long = mix("long-decode")
    p = gen.plan(long, 5, 151936, 45.0)
    assert not p.arrivals and not p.shape_groups
    assert sorted(len(s) for s, _ in p.sessions) == sorted(
        len(s) for s, _ in gen.plan(long, 6, 151936, 45.0).sessions)
    assert all(17000 <= len(s) <= 20000 and n == 12288 for s, n in p.sessions)
    groups = gen.plan(mix("chat"), 5, 151936, 45.0).shape_groups
    batched = [g for g in groups if g[0][1] == 1]
    assert len(batched) == 4 * 8        # buckets 64..512 x batch 1..8
    singles = [len(g[0][0]) for g in groups if g[0][1] == 2]
    assert singles == [64, 128, 256, 512, 1024, 2048, 3072]
