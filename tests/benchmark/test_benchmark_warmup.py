"""Set-up warms the window's own lengths, each alone and as a joiner."""

import time

import pytest

from benchmark.harness import server

_sleep = time.sleep


class FakeServed:
    """Answers at once; a request "joins" when it is sent with others
    or while the live row is out, unless ``stubborn`` says it seeds."""

    def __init__(self, stubborn=()):
        self.config = {"serving_env": {"MAX_BATCH": "4", "MAX_SEQ": "256"}}
        self.alone, self.together_calls, self.stubborn = [], [], set(stubborn)
        self._traces, self._n = {}, 0

    def post(self, ids, max_new, rid=None):
        self.alone.append((len(ids), max_new))
        if max_new > 2:
            _sleep(0.05)               # the live row decodes for a while
        return {"rid": "solo", "new": []}

    def together(self, rows, stagger_s=0.0):
        self.together_calls.append([len(p) for p, _ in rows])
        _sleep(0.001)
        out = []
        for prompt, _ in rows:
            self._n += 1
            rid = f"r{self._n}"
            joined = len(prompt) not in self.stubborn
            self._traces[rid] = {"spans": [{
                "name": "prefill", "labels": {"prefix": True} if joined
                else {"kind": "seed"}}]}
            out.append({"rid": rid, "new": []})
        return out

    def traces(self):
        return self._traces


SIZES = [(40, 8, -1), (17, 4, -1), (40, 16, 0), (100, 8, -1), (33, 8, 1)]
TRAFFIC = {"name": "t", "shared_prefix": {"count": 2, "tokens": 16, "share": .5}}


def test_every_length_of_the_trace_is_met_alone_and_as_a_joiner(monkeypatch):
    monkeypatch.setattr(server.time, "sleep", lambda s: None)
    monkeypatch.setattr(time, "sleep", lambda s: None)
    served = FakeServed()
    got = server.warm_iter(served, TRAFFIC, SIZES, 512)
    assert [n for n, new in served.alone if new == 1] == [17, 33, 40, 100]
    joiners = [n for call in served.together_calls[2:] for n in call]
    # the four lengths, then the pair behind one shared prefix (16 + 1, 16 + 100)
    assert joiners == [17, 33, 40, 100, 17, 116]
    assert got["lengths"] == 4 and got["never_joined"] == 0


def test_a_request_that_seeded_goes_again_and_the_loop_is_bounded(monkeypatch):
    monkeypatch.setattr(server.time, "sleep", lambda s: None)
    served = FakeServed(stubborn={33})
    got = server.warm_iter(served, TRAFFIC, SIZES, 512)
    again = [call for call in served.together_calls if call == [33]]
    assert len(again) >= 2 and got["never_joined"] == 1
    assert got["lives"] <= 3 * 4 + 4


@pytest.mark.parametrize("trace,want", [
    (None, False),
    ({"spans": [{"name": "prefill", "labels": {"kind": "seed"}}]}, False),
    ({"spans": [{"name": "handle", "spans": [
        {"name": "prefill", "labels": {"prefix": True}}]}]}, True),
])
def test_joined_reads_the_prefill_span(trace, want):
    assert server._joined(trace) is want
