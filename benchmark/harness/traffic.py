"""The one traffic generator: a data file of parameters in, a schedule out.

Open loop: arrival k is due at its offset whether or not earlier
requests have finished. A traffic file is a TRACE: the request sizes and
the inter-arrival gaps are drawn from the file's own ``base_seed``, in
one order, and every run of every seed offers exactly them. The run's
seed draws the token contents (and, outside this module, the weights).
Arrival k is a pure function of ``(seed, traffic, rate, seconds, k)``.
The seed does not reorder: with this scheduler the order of prompt sizes
is the work (``PERF.md``, section 4), so another order is another
traffic file with another ``base_seed``, and a cell of its own. Shared
prefixes depend on the traffic file alone, never on the seed, the way
system prompts do. Lengths are in tokens and ragged (any length inside
the clips); a prompt is the decimal ids joined by spaces
(``harness.tokenizer.IntTokenizer`` maps them back one to one).

Parameters of a traffic file (all lengths in tokens):

- ``arrival``: ``"poisson"`` or ``"bursts"`` (``burst_mean`` requests a
  burst, geometric; ``burst_gap_s`` between requests of one burst);
- ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  log-normal, clipped;
- ``shared_prefix``: ``{"count", "tokens", "share"}``: that share of
  requests starts with one of ``count`` fixed prefixes;
- ``base_seed``: what the sizes and gaps are drawn from.
"""

from __future__ import annotations

import dataclasses
import math
import random
from typing import List


@dataclasses.dataclass(frozen=True)
class Arrival:
    k: int
    t: float            # seconds after the window opens at which it is due
    prompt_ids: tuple
    max_new: int
    prefix_id: int      # -1: no shared prefix

    @property
    def prompt(self) -> str:
        return " ".join(map(str, self.prompt_ids))


def _lognormal(rng: random.Random, p: dict) -> int:
    x = math.exp(rng.gauss(math.log(p["median"]), p["sigma"]))
    return int(round(min(max(x, p["min"]), p["max"])))


def sizes(traffic: dict, n: int) -> List[tuple]:
    """``(prompt_len, max_new, prefix_id)`` of the first ``n`` requests
    of this traffic, in the order they arrive: the same for every seed
    and every rate (set-up warms exactly these lengths)."""
    out = []
    sp = traffic.get("shared_prefix") or {}
    for i in range(n):
        rng = random.Random(f"{traffic['base_seed']}/{traffic['name']}/size/{i}")
        plen = _lognormal(rng, traffic["prompt"])
        new = _lognormal(rng, traffic["output"])
        pid = -1
        if sp and rng.random() < sp["share"]:
            pid = rng.randrange(sp["count"])
            # the prefix plus at least one token of the request's own
            plen = max(plen, sp["tokens"] + 1)
        out.append((plen, new, pid))
    return out


def gaps(traffic: dict, n: int, seconds: float) -> List[float]:
    """``n`` inter-arrival gaps summing to ``seconds``: the same for
    every seed."""
    raw = []
    burst = float(traffic.get("burst_mean", 1))
    for i in range(n):
        rng = random.Random(f"{traffic['base_seed']}/{traffic['name']}/gap/{i}")
        if traffic["arrival"] == "bursts" and rng.random() >= 1.0 / burst:
            raw.append(None)                      # inside a burst
        else:
            raw.append(rng.expovariate(1.0))
    inside = sum(1 for g in raw if g is None) * float(
        traffic.get("burst_gap_s", 0.002))
    scale = max(seconds - inside, 0.0) / max(
        sum(g for g in raw if g is not None), 1e-9)
    return [float(traffic.get("burst_gap_s", 0.002)) if g is None
            else g * scale for g in raw]


def shared_prefix_ids(traffic: dict, prefix_id: int, vocab: int) -> tuple:
    rng = random.Random(f"prefix/{traffic['name']}/{prefix_id}")
    return tuple(rng.randrange(vocab)
                 for _ in range(traffic["shared_prefix"]["tokens"]))


def schedule(traffic: dict, seed: int, rate_rps: float, seconds: float,
             vocab: int) -> List[Arrival]:
    """The arrivals of one window: ``round(rate * seconds)`` requests,
    all due inside ``[0, seconds)``."""
    n = count(rate_rps, seconds)
    sz = sizes(traffic, n)
    gp = gaps(traffic, n, seconds)
    out, t = [], 0.0
    for k, (plen, new, pid) in enumerate(sz):
        rng = random.Random(f"{seed}/{traffic['name']}/{k}")
        head = shared_prefix_ids(traffic, pid, vocab) if pid >= 0 else ()
        body = tuple(rng.randrange(vocab) for _ in range(plen - len(head)))
        out.append(Arrival(k=k, t=round(t, 9), prompt_ids=head + body,
                           max_new=new, prefix_id=pid))
        t += gp[k]
    return out


def count(rate_rps: float, seconds: float) -> int:
    """How many requests a window of that rate and length offers."""
    return max(int(round(rate_rps * seconds)), 1)
