"""The one general traffic generator: a mix's data file in, a plan out.

A mix's file holds a multiset of (prompt length, output length) `pairs`
and, for an open loop, a multiset of inter-arrival gaps `gaps_s`, as
data (the file says which distributions they are the stratified
quantiles of). `--seed` only chooses the order and the token ids: the
stream of requests is block after block, each block a seeded
permutation of the whole multiset, so every seed offers the same work
with the same burstiness and any window holds whole blocks plus a part
of one. Nothing here touches JAX.
"""

from __future__ import annotations

import math
import random


def length_pairs(mix):
    """The mix's multiset of (prompt length, output length)."""
    return [(int(p), int(o)) for p, o in mix["pairs"]]


def arrival_gaps(mix):
    """The open loop's multiset of inter-arrival gaps in seconds."""
    return [float(g) for g in mix["gaps_s"]]


def rate_rps(mix):
    """The rate the open loop's gaps offer."""
    gaps = arrival_gaps(mix)
    return len(gaps) / sum(gaps)


def longest_prompt(mix):
    return max(p for p, _ in length_pairs(mix))


def _blocks(items, rng, n):
    """`n` items: seeded permutations of `items`, one after another."""
    out = []
    while len(out) < n:
        block = list(items)
        rng.shuffle(block)
        out.extend(block)
    return out[:n]


def request_stream(mix, seed, n_requests):
    """What the load generator is handed: for each of `n_requests`
    requests its prompt length and the tokens it asks for and, in an
    open loop, when it is due (seconds from the generator's start).
    Token ids are drawn by the generator from (seed, index)."""
    pairs = _blocks(length_pairs(mix), random.Random(f"{seed}:lengths"),
                    n_requests)
    plan = {"loop": mix["loop"], "seed": int(seed),
            "requests": [{"prompt_len": p, "max_tokens": o}
                         for p, o in pairs]}
    if mix["loop"] == "open":
        gaps = _blocks(arrival_gaps(mix), random.Random(f"{seed}:gaps"),
                       n_requests)
        due = 0.0
        for req, gap in zip(plan["requests"], gaps):
            due += gap
            req["due_s"] = due
    return plan


# -- reduction of what the client saw ----------------------------------------


def tokens_in_window(records, t0, t1):
    """Tokens whose arrival at the client fell in [t0, t1): a request
    across an edge gives the tokens inside."""
    return sum(1 for r in records for t in r["arrivals"] if t0 <= t < t1)


def gaps_in_window(records, t0, t1):
    """Every gap between two successive tokens of one request, both of
    which arrived in [t0, t1); seconds."""
    out = []
    for r in records:
        a = r["arrivals"]
        out.extend(b - c for c, b in zip(a, a[1:]) if t0 <= c and b < t1)
    return out


def first_token_waits(records, t0, t1):
    """Due time to first token, for every request that was due in
    [t0, t1); a request that got none counts as the time it had waited
    when the generator gave up on it. Seconds."""
    out = []
    for r in records:
        if t0 <= r["due"] < t1:
            end = r["arrivals"][0] if r["arrivals"] else r["t_end"]
            out.append(end - r["due"])
    return out


def quantile(values, q):
    """The q-quantile by linear interpolation between order statistics
    (numpy's default), or None of nothing."""
    if not values:
        return None
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
