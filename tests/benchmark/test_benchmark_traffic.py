"""The traffic generator: replay identity, one trace for every seed,
ragged lengths inside each traffic file's clips."""

import glob
import json
import os

import pytest

from benchmark.harness import traffic
from benchmark.harness.spec import ROOT
from benchmark.harness.tokenizer import IntTokenizer

FILES = sorted(glob.glob(os.path.join(ROOT, "traffic", "*.json")))


def load(path):
    with open(path) as f:
        doc = json.load(f)
    doc["name"] = os.path.basename(path)[:-5]
    return doc


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_same_arguments_same_schedule(path):
    t = load(path)
    a = traffic.schedule(t, 2**31 + 12345, 5.0, 10, 32768)
    b = traffic.schedule(t, 2**31 + 12345, 5.0, 10, 32768)
    assert a == b and len(a) == 50
    assert all(0 <= x.t < 10 for x in a)
    assert [x.t for x in a] == sorted(x.t for x in a)


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_every_seed_offers_the_same_trace_with_other_contents(path):
    t = load(path)
    a = traffic.schedule(t, 1, 6.0, 20, 50257)
    b = traffic.schedule(t, 2, 6.0, 20, 50257)
    size = lambda s: [(len(x.prompt_ids), x.max_new, x.prefix_id, x.t)
                      for x in s]
    assert size(a) == size(b)
    assert [x.prompt_ids for x in a] != [x.prompt_ids for x in b]
    assert size(a)[:10] == [(len(x.prompt_ids), x.max_new, x.prefix_id, x.t)
                            for x in traffic.schedule(t, 1, 6.0, 20, 50257)][:10]


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_lengths_are_ragged_and_stay_inside_the_clips(path):
    t = load(path)
    p, o = t["prompt"], t["output"]
    arrivals = traffic.schedule(t, 3, 20.0, 20, 32768)
    for a in arrivals:
        assert p["min"] <= len(a.prompt_ids) <= p["max"]
        assert o["min"] <= a.max_new <= o["max"]
        assert all(0 <= i < 32768 for i in a.prompt_ids)
    assert len({len(a.prompt_ids) % 16 for a in arrivals}) > 8


@pytest.mark.parametrize("path", FILES, ids=os.path.basename)
def test_shared_prefixes_do_not_depend_on_the_seed(path):
    t = load(path)
    sp = t.get("shared_prefix")
    if not sp:
        pytest.skip("no shared prefix in this traffic")
    heads = {}
    for seed in (1, 2):
        for a in traffic.schedule(t, seed, 10.0, 10, 32768):
            if a.prefix_id >= 0:
                heads.setdefault(a.prefix_id, set()).add(
                    a.prompt_ids[:sp["tokens"]])
    assert heads and all(len(v) == 1 for v in heads.values())
    share = sum(a.prefix_id >= 0 for a in traffic.schedule(
        t, 1, 50.0, 10, 32768)) / 500
    assert abs(share - sp["share"]) < 0.08


@pytest.mark.parametrize("n", [13, 40, 82])
def test_a_shorter_window_is_the_head_of_the_trace(n):
    """Set-up replays the head of the window's own trace and warms the
    lengths of ``sizes``: both must be what the window then offers."""
    t = load(os.path.join(ROOT, "traffic", "chat.json"))
    whole = traffic.sizes(t, 82)
    assert traffic.sizes(t, n) == whole[:n]
    assert traffic.count(1.6, 51) == 82
    s = traffic.schedule(t, 9, 1.6, 51, 32768)
    assert [(len(a.prompt_ids), a.max_new, a.prefix_id) for a in s] == whole


def test_another_base_seed_is_another_trace():
    a = load(os.path.join(ROOT, "traffic", "chat.json"))
    b = load(os.path.join(ROOT, "traffic", "chat-b.json"))
    assert {k: v for k, v in a.items() if k not in ("about", "base_seed", "name")} \
        == {k: v for k, v in b.items() if k not in ("about", "base_seed", "name")}
    assert traffic.sizes(a, 82) != traffic.sizes(b, 82)
    assert sorted(traffic.sizes(a, 82)) != sorted(traffic.sizes(b, 82))


def test_bursts_come_in_clumps():
    t = load(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fixture", "bench", "traffic", "tiny.json"))
    s = traffic.schedule(t, 5, 10.0, 30, 512)
    gaps = [y.t - x.t for x, y in zip(s, s[1:])]
    near = sum(g <= t["burst_gap_s"] * 1.01 for g in gaps)
    assert 0.55 < near / len(gaps) < 0.78     # 2 of 3 follow within 2 ms


def test_tokenizer_is_one_to_one():
    tok = IntTokenizer(100)
    assert tok.encode("17 5 99") == [17, 5, 99]
    assert tok.decode([17, 5, 99], skip_special_tokens=True) == "17 5 99"
    with pytest.raises(ValueError):
        tok.encode("100")
