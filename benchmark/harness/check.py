"""How ``correct`` is decided: the tokens the window served, against the
plain reference.

The endpoint returns tokens and no logits, so the comparison is made on
tokens, and on the requests that were measured: after the window closes,
a sample of its answered requests (evenly spaced through the window, so
seeds and joiners, store hits and misses, every batch width and every
pool mover take part) is scored. For each request the reference computes
float32 logits over prompt + served tokens in one pass (teacher-forced on
what the program served, so one early parting does not spoil the rest),
and every served token gets a DEFICIT: how far the reference's logit for
it lies under the reference's largest logit at that position, in units
of that position's logit spread. 0 where the served token is the
reference's own choice. Two numbers, each printed beside its limit:

- ``deficit_mean`` over all sampled tokens (some thousands): the served
  path's arithmetic noise flips the choice between near-tied logits, and
  both how often and by how much grow with the noise, so the mean grows
  with its square. Steady from seed to seed; the control (the reference
  with int8 weights choosing the tokens) reads several times higher.
- ``deficit_max``: a wrong row, pad, block or position anywhere between
  the handler and the pool serves tokens that are far from the
  reference's choice: several units, against hundredths for noise.

The limits live in the configuration file (``check.limits``), set from
chip readings of sound runs and of the controls (``--control 1``): see
``PERF.md``, section 2.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def deficits(ref_logits: np.ndarray, tokens: List[int]) -> np.ndarray:
    """Per served token: (largest reference logit - the token's) / spread."""
    z = ref_logits[:len(tokens)].astype(np.float64)
    chosen = z[np.arange(len(tokens)), np.asarray(tokens)]
    return (z.max(-1) - chosen) / z.std(-1)


def _up(n: int, q: int) -> int:
    return -(-n // q) * q


def reference_logits(reference, params, config, seq, first: int,
                     weights: Optional[str] = None) -> np.ndarray:
    """Reference logits at positions ``first .. len(seq)-1``. The
    sequence is padded on the right to a multiple of 128 and the
    positions to a multiple of 64 (attention is causal: what follows a
    position changes nothing at it), so ragged lengths share programs."""
    n = len(seq) - first
    ids = list(seq) + [0] * (_up(len(seq), 128) - len(seq))
    pos = list(range(first, len(seq)))
    pos += [pos[-1]] * (_up(n, 64) - n)
    out = reference.logits(params, config, ids, pos, weights=weights)
    return np.asarray(out, np.float32)[:n]


def sample(rows: List[dict], n: int) -> List[dict]:
    """``n`` answered requests, evenly spaced through the window."""
    ok = [r for r in rows if r["ok"] and r.get("text")]
    if len(ok) <= n:
        return ok
    return [ok[int(i * len(ok) / n)] for i in range(n)]


def score(reference, params, config, pairs: List[tuple],
          control: bool = False) -> dict:
    """``pairs`` of (prompt ids, tokens served after it) ->
    ``{"readings": {name: value}, "tokens": n}``; with ``control`` also
    ``{"control": {...}}``: the same numbers with the reference at int8
    weights choosing every token in the program's place, and with each
    request's tokens scored against the logits of ANOTHER request (the
    structural fault ``deficit_max`` is there for)."""
    every, low, wrong, previous = [], [], [], None
    for prompt, new in pairs:
        seq = list(prompt) + list(new[:-1])
        ref = reference_logits(reference, params, config, seq,
                               len(prompt) - 1)
        every.append(deficits(ref, new))
        if control:
            z = reference_logits(reference, params, config, seq,
                                 len(prompt) - 1, weights="int8")
            low.append(deficits(ref, list(z.argmax(-1))))
            if previous is not None:
                m = min(len(previous), len(new))
                wrong.append(float(deficits(ref[:m], previous[:m]).max()))
            previous = new
    d = np.concatenate(every) if every else np.asarray([np.inf])
    out = {"tokens": int(sum(map(len, every))),
           "readings": {"deficit_mean": float(d.mean()),
                        "deficit_max": float(d.max())}}
    if low:
        c = np.concatenate(low)
        out["control"] = {"deficit_mean_int8": float(c.mean()),
                          "deficit_max_int8": float(c.max()),
                          "deficit_max_wrong_row": min(wrong, default=None)}
    return out


def served_tokens(served, arrivals, rows: List[dict],
                  control: bool = False) -> dict:
    """Scores a sample of the window's answered requests (``score``). A
    request whose answer is not its prompt plus exactly the tokens asked
    for makes both readings infinite."""
    config = served.config
    picked = sample(rows, config["check"]["requests"])
    pairs = []
    for row in picked:
        prompt = list(arrivals[row["k"]].prompt_ids)
        ids = [int(t) for t in row["text"].split()]
        new = ids[len(prompt):]
        if ids[:len(prompt)] != prompt or len(new) != row["max_new"]:
            return {"requests": len(picked), "tokens": 0, "readings": {
                "deficit_mean": float("inf"), "deficit_max": float("inf")}}
        pairs.append((prompt, new))
    out = score(served.reference, served.params, config, pairs, control)
    out["requests"] = len(picked)
    return out
