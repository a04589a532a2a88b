"""How ``correct`` is decided for a family that generates by blocks: what
the window served, token and SCHEDULE, against the plain reference.

An answer made by block diffusion is its tokens and, for each, the
denoise forward of its block's round that fixed it (``fixed_at``, which
the program says on its ``decode`` spans): a token fixed at forward 1
was chosen with the block's forward-0 tokens in sight. So the check
scores WHAT THE TIMED PATH SERVED at the timed sizes: a sample of the
window's answered requests (``check.sample``), and for each ONE
reference pass over ``[prompt + answer | the answer's blocks as they
stood before forward 0 | before forward 1 | ...]``
(``reference.sdar_moe.denoise_layout``), which gives the reference's
float32 logits of every denoise forward of the request at once; two
passes more, one at int8 weights over the same tokens and one over the
tokens another request was served. The readings:

- ``deficit_mean`` and ``deficit_max`` of every served token AT THE
  FORWARD THAT FIXED IT (``check.deficits``'s number: how far the
  reference's logit for the token lies under its largest, in units of
  the position's logit spread; the mask token's logit is no candidate
  and is left out);
- ``noise_over_int8``: ``deficit_mean`` over the ``deficit_mean`` of the
  tokens that the reference AT INT8 WEIGHTS would have fixed in the
  same forwards, under the same float32 logits. Seeded weights make
  answers of few distinct tokens, so both means move together from seed
  to seed by a factor of two and their quotient does not: the program's
  arithmetic is held to a share of what the nearest precision below its
  own costs ON THE SAME WEIGHTS AND SAMPLE; the int8 reference reads 1
  by construction;
- ``own_over_other_row``: every sampled request has a partner in the
  sample, one behind the same shared prefix wherever there is one
  (``_partners``), and a third pass scores THE PARTNER'S served tokens
  under this request's prompt. The reading is the largest, over the
  requests, of this request's own mean deficit over that of its
  partner's tokens, both over the same positions: the served tokens have
  to fit their own prompt better than another row's tokens fit it.
  Behind one system prompt seeded weights give answers so alike that no
  single token of the other row is far from the reference's choice
  (``deficit_max`` does not see a swap there): only the two means over
  the SAME logits tell the rows apart. Were the two rows swapped (a swap
  in a live batch, a wrong store tail) the reading would be its own
  reciprocal, so the limit is 1. A pair is NOT TOLD APART, and does not
  count, where both means lie under three times ``deficit_mean``'s
  limit: the partner's answer then fits this prompt as closely as
  rounding lets the prompt's own answer fit it (the two part in a tenth
  of their tokens, as few as noise parts), a swap would serve an answer
  the reference rates like the program's own arithmetic, and whichever of
  two such means is the larger is chance. The control says how many
  pairs those are;
- ``choice_deficit_mean`` and ``choice_deficit_max``: for every
  position the program fixed while another of its block stayed masked,
  how far its log-confidence lies under that of the best position left
  masked in the same forward, in the reference's arithmetic (0 where
  it lies over it): 0 where the program fixed the reference's most
  confident positions; near-ties flip under arithmetic noise, a few of
  them and by little, so the MEAN stays small; under a wrong transfer
  rule every choice is out by the spread between a block's confidences,
  which on seeded weights is itself small, so the maximum does not tell
  the two apart as surely as the mean does (``PERF.md``, section 2).

``run.py`` compares the readings that ``check.limits`` names, and every
cell's file keeps that to the two token readings (a test of the loader
pins the names). The procedure's other readings have their limits under
``check.own_limits``; ``served_blocks`` prints each beside its limit,
names those that are over (``over`` in what it returns, the readings
left as they were read under ``as_read``) and, since the two token
readings are all that ``run.py`` looks at, reports those two infinite
when any is over: ``PERF.md``'s open questions ask a ``benchmark`` PR to
let ``check.limits`` name any reading a procedure returns. An answer
that is not its prompt plus exactly the tokens asked for, or a
``fixed_at`` that is no valid schedule (not one entry a token; a forward
past ``denoising_steps``; a whole block in which a forward that left
masks fixed fewer than the floor) makes the readings infinite. An answer
whose budget ends inside a block is scored up to its last whole block:
what the round put behind the budget is in no answer, and the block's
other positions saw it.

With ``control`` each control is scored by the same comparison
(``over``) and says whether it came out not correct: the int8
reference's own tokens; every sampled request served ITS PARTNER'S
tokens, pair by pair (all pairs count: how many were caught, how many
cannot be told apart, the smallest readings); the same tokens with
their schedule turned round.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from . import stats
from .check import _up, sample
from ..reference import sdar_moe as ref_mod

INF = float("inf")
_PAD_TOKENS, _PAD_OUT = 1024, 256


def fixed_at_of(trace: dict) -> List[int]:
    """A request's ``fixed_at``, from its ``decode`` spans in order."""
    out: List[int] = []
    for s in sorted(stats.find_spans(trace["spans"], "decode"),
                    key=lambda s: s["start_ms"]):
        out += s.get("labels", {}).get("fixed_at", [])
    return out


def valid_schedule(n_prompt: int, fixed_at: List[int], o: dict) -> bool:
    """Every entry a denoise forward of its round, and in every WHOLE
    block each forward that left masks behind fixed the floor at
    least."""
    length, steps = o["block_length"], o["denoising_steps"]
    if any(not 0 <= f < steps for f in fixed_at):
        return False
    given = n_prompt % length
    when = [-1] * given + list(fixed_at)
    for lo in range(0, len(when) - len(when) % length, length):
        block = [f for f in when[lo:lo + length] if f >= 0]
        floor = -(-len(block) // steps)
        left = len(block)
        for f in range(max(block, default=-1) + 1):
            took = sum(x == f for x in block)
            if took < min(floor, left):
                return False
            left -= took
    return True


@functools.partial(jax.jit, static_argnames=("mask_id",))
def _stats(z, tokens, mask_id: int):
    """z [N, V] float32, tokens [N] -> per row, the mask token's logit
    left out: (largest logit, log-sum-exp, spread, the token's logit,
    the argmax)."""
    col = jnp.arange(z.shape[1]) == mask_id
    live = jnp.where(col, -jnp.inf, z)
    n = z.shape[1] - 1
    mean = jnp.sum(jnp.where(col, 0.0, z), -1) / n
    var = jnp.sum(jnp.where(col, 0.0, (z - mean[:, None]) ** 2), -1) / n
    chosen = jnp.take_along_axis(live, tokens[:, None], axis=-1)[:, 0]
    return (live.max(-1), jax.nn.logsumexp(live, axis=-1), jnp.sqrt(var),
            chosen, jnp.argmax(live, -1))


def request_readings(reference, params, config, prompt, new, fixed_at,
                     o: dict, int8_too: bool = True) -> dict:
    """One request through one reference pass: ``{"deficits": [scored],
    "choices": the choice deficit of every position fixed while another
    of its block stayed masked}``; with ``int8_too`` a second
    pass at int8 weights over the same tokens, and ``"deficits_int8"``:
    the deficits of the tokens THAT pass would have fixed, under the
    float32 logits."""
    length, mask_id = o["block_length"], o["mask_token_id"]
    ids, pos, seen, at, scored = ref_mod.denoise_layout(
        prompt, new, fixed_at, o)
    if not scored:
        return {"deficits": np.zeros(0), "deficits_int8": np.zeros(0),
                "choices": np.zeros(0)}
    p = len(prompt)
    lo = p - p % length
    when = np.asarray(list(fixed_at[:scored]))
    block = (p + np.arange(scored)) // length
    last = {b: int(when[block == b].max()) for b in set(block.tolist())}
    # every (forward, position) at which the position was still masked
    # and its block's round ran that forward
    where = {}
    for t in range(scored):
        for f in range(min(int(when[t]), last[int(block[t])]) + 1):
            where[(f, t)] = len(where)
    wanted = [at[f] + (p + t - lo) for f, t in where]
    n = len(ids)
    total = _up(n, _PAD_TOKENS)
    ids = list(ids) + [0] * (total - n)
    pos = list(pos) + [0] * (total - n)
    full = np.zeros((total, total), bool)
    full[:n, :n] = seen
    full[np.arange(n, total), np.arange(n, total)] = True
    out = wanted + [wanted[-1]] * (_up(len(wanted), _PAD_OUT) - len(wanted))
    tokens = np.zeros(len(out), np.int32)
    for (f, t), i in where.items():
        tokens[i] = new[t]
    z = reference.forward(params, config, ids, pos, full, out)
    top, lse, std, chosen, _ = (np.asarray(x, np.float64) for x in _stats(
        z, jnp.asarray(tokens), mask_id))
    mine = [where[(int(when[t]), t)] for t in range(scored)]
    got = {"deficits": (top[mine] - chosen[mine]) / std[mine]}
    if int8_too:
        z8 = reference.forward(params, config, ids, pos, full, out, "int8")
        theirs = _stats(z8, jnp.asarray(tokens), mask_id)[4]
        chosen8 = np.asarray(_stats(z, theirs, mask_id)[3], np.float64)
        got["deficits_int8"] = (top[mine] - chosen8[mine]) / std[mine]
    logconf = top - lse
    choices = []
    for (f, t), i in where.items():
        left = np.flatnonzero((block == block[t]) & (when > f))
        if when[t] != f or not left.size:
            continue
        if o["remasking"] == "sequential":
            choices.append(INF if left.min() < t else 0.0)
        else:
            best = max(logconf[where[(f, int(u))]] for u in left)
            choices.append(max(best - logconf[i], 0.0))
    got["choices"] = np.asarray(choices)
    return got


def _behind_one_prefix(a, b, tokens: int = 64) -> bool:
    """Do two prompts start alike (a shared system prompt)? Seeded
    token contents never collide otherwise."""
    return list(a[:tokens]) == list(b[:tokens])


def _mean(d: np.ndarray, empty: float) -> float:
    return float(d.mean()) if d.size else empty


def _quotient(a: float, b: float) -> float:
    return a / b if b > 0 else (INF if a > 0 else 0.0)


def token_readings(every: List[np.ndarray], low: List[np.ndarray]) -> dict:
    """The readings of a sample's token deficits, a request an entry,
    beside those of the int8 reference's own tokens in the same
    forwards."""
    d = np.concatenate(every) if every else np.zeros(0)
    mean = _mean(d, INF)
    mean8 = _mean(np.concatenate(low), 0.0) if low else 0.0
    return {"deficit_mean": mean,
            "deficit_max": float(d.max()) if d.size else INF,
            "noise_over_int8": _quotient(mean, mean8)}


def over(readings: dict, check: dict) -> List[str]:
    """The readings that lie over their limit, ``check.limits`` and
    ``check.own_limits`` alike: the comparison that decides ``correct``
    (``run.py`` makes it over ``check.limits``; ``served_blocks`` makes
    it over the rest and says so through those)."""
    limits = dict(check.get("own_limits", {}), **check.get("limits", {}))
    return [k for k, v in limits.items()
            if k in readings and not readings[k] <= v]


def _partners(triples: List[tuple]) -> List[int]:
    """For every request another one of the sample: the next behind the
    same shared prefix where there is one, else the next."""
    n = len(triples)
    out = []
    for i in range(n):
        order = [(i + k) % n for k in range(1, n)]
        alike = [j for j in order
                 if _behind_one_prefix(triples[i][0], triples[j][0])]
        out.append((alike or order or [i])[0])
    return out


def score(reference, params, config, triples: List[tuple],
          control: bool = False) -> dict:
    """``triples`` of (prompt ids, tokens served, their ``fixed_at``) ->
    ``{"readings": {...}, "tokens": n}``; with ``control`` also the
    controls, each with its readings and the names of those over their
    limit (``config["check"]``'s): the reference at int8 weights
    choosing every token in the program's place; each request served
    its partner's tokens (``_partners``: every pair counts: how many
    were caught and how many cannot be told apart, over all of them and
    beside that over the pairs behind one prefix); the same tokens with
    their schedule turned round (what was fixed first said to be fixed
    last)."""
    o = ref_mod.options(config)
    check = config.get("check", {})
    every, low, choices, pairs = [], [], [], []
    for i, j in enumerate(_partners(triples)):
        prompt, new, fixed_at = triples[i]
        got = request_readings(reference, params, config, prompt, new,
                               fixed_at, o)
        every.append(got["deficits"])
        low.append(got["deficits_int8"])
        choices.append(got["choices"])
        # the partner's tokens under this prompt, over the positions
        # both answers have
        theirs = triples[j][1]
        m = min(len(theirs), len(new))
        other = request_readings(
            reference, params, config, prompt, theirs[:m], fixed_at[:m], o,
            int8_too=False)["deficits"] if j != i else np.zeros(0)
        own = got["deficits"][:len(other)]
        pairs.append({"one_prefix": _behind_one_prefix(prompt, triples[j][0]),
                      "own": _mean(own, 0.0), "other": _mean(other, 0.0),
                      "other_max": float(other.max(initial=0.0))})
    # a pair is told apart where one of its two means stands clear of
    # what rounding alone gives a request (three times the limit that
    # holds the pooled mean)
    clear = 3.0 * check.get("limits", {}).get("deficit_mean", 0.0)
    for r in pairs:
        r["told"] = max(r["own"], r["other"]) > clear
    c = np.concatenate(choices) if choices else np.asarray([INF])
    readings = dict(
        token_readings(every, low),
        own_over_other_row=max((_quotient(r["own"], r["other"])
                                for r in pairs if r["told"]), default=0.0),
        choice_deficit_mean=_mean(c, 0.0),
        choice_deficit_max=float(c.max()) if c.size else 0.0)
    out = {"tokens": int(sum(map(len, every))), "readings": readings}
    if not control or not triples:
        return out
    int8 = token_readings(low, low)
    turned = []
    for prompt, new, fixed_at in triples:
        last = max(fixed_at, default=0)
        turned.append(request_readings(
            reference, params, config, prompt, new,
            [last - f for f in fixed_at], o, int8_too=False)["choices"])
    t = np.concatenate(turned)
    late = {"choice_deficit_mean": _mean(t, 0.0),
            "choice_deficit_max": float(t.max(initial=0.0))}
    # a pair swapped: the request is served its partner's tokens, and
    # its own are what the partner was served
    swapped = [{"one_prefix": r["one_prefix"], "told": r["told"],
                "deficit_max": r["other_max"],
                "own_over_other_row": _quotient(r["other"], r["own"])
                if r["told"] else 0.0} for r in pairs]
    for r in swapped:
        r["over"] = over(r, check)

    def smallest(rows):
        told = [r for r in rows if r["told"]]
        return {"pairs": len(rows), "told": len(told),
                "caught": sum(bool(r["over"]) for r in rows),
                "deficit_max": min((r["deficit_max"] for r in rows),
                                   default=None),
                "own_over_other_row": min(
                    (r["own_over_other_row"] for r in told), default=None)}

    out["control"] = {
        "int8": dict(int8, over=over(int8, check)),
        "wrong_row": dict(smallest(swapped), one_prefix=smallest(
            [r for r in swapped if r["one_prefix"]])),
        "turned": dict(late, over=over(late, check)),
        "pairs": [[int(r["one_prefix"]), int(r["told"]), round(r["own"], 5),
                   round(r["other"], 5), round(r["other_max"], 3)]
                  for r in pairs]}
    return out


_READINGS = ("deficit_mean", "deficit_max", "noise_over_int8",
             "own_over_other_row", "choice_deficit_mean",
             "choice_deficit_max")


def served_blocks(served, arrivals, rows: List[dict],
                  control: bool = False) -> dict:
    """Scores a sample of the window's answered requests (``score``),
    each with the schedule its ``decode`` spans state."""
    config = served.config
    o = ref_mod.options(config)
    picked = sample(rows, config["check"]["requests"])
    traces = served.traces()
    bad = {"requests": len(picked), "tokens": 0,
           "readings": dict.fromkeys(_READINGS, INF)}
    triples = []
    for row in picked:
        prompt = list(arrivals[row["k"]].prompt_ids)
        ids = [int(t) for t in row["text"].split()]
        new = ids[len(prompt):]
        trace = traces.get(row["rid"])
        fixed_at = fixed_at_of(trace) if trace is not None else []
        if (ids[:len(prompt)] != prompt or len(new) != row["max_new"]
                or len(fixed_at) != len(new)
                or not valid_schedule(len(prompt), fixed_at, o)):
            print("check: an answer is not its prompt and the tokens asked "
                  f"for, or its fixed_at is no schedule ({row['rid']})",
                  flush=True)
            return bad
        triples.append((prompt, new, fixed_at))
    out = score(served.reference, served.params, config, triples, control)
    out["requests"] = len(picked)
    # the procedure's own limits: ``run.py`` compares the readings that
    # ``check.limits`` names, which every cell's file keeps to the two
    # token readings, so a reading over a limit of ITS OWN is said here
    # and reaches ``correct`` through those two
    own = config["check"].get("own_limits", {})
    out["over"] = [k for k in over(out["readings"], config["check"])
                   if k in own]
    print("check (own limits): " + ", ".join(
        f"{k} {out['readings'][k]:.6g} (limit {v})" for k, v in own.items())
        + ("; OVER: " + ", ".join(out["over"]) + ": deficit_mean and "
           "deficit_max are reported infinite" if out["over"] else ""),
        flush=True)
    if out["over"]:
        out["as_read"] = dict(out["readings"])
        out["readings"].update(deficit_mean=INF, deficit_max=INF)
    return out
