"""The one generator of inputs: every mix is a file of parameters it reads.

Serving mixes (``traffic/<mix>.json``) give ``arrivals``, ``prompt_len``,
``output_len`` and optionally ``shared_prefix``; training mixes give ``tokens``.
Everything is drawn from ``numpy.random.default_rng`` seeded by ``--seed`` and
a fixed stream number, so the same seed gives the same inputs in any process.

A run holds some tens of requests, and every run has another seed, so the
draws are made steady: a Poisson process is conditioned on its expected count,
and the lengths of a schedule are stratified (one draw from each of n equal
slices of the distribution, in an order the seed shuffles). Every seed then
offers the same amount of work, to rounding, in another order and at other
instants; each request's marginal distribution is the one the file states.

    arrivals    {"process": "poisson", "rate_per_s": r}   conditioned on its
                    expected count: round(r x span) instants, uniform over the span
                {"process": "gamma", "rate_per_s": r, "cv": c}   bursts: c > 1
                {"process": "closed", "clients": n}   no schedule: the n
                    clients' i-th requests are one stratified draw of n, so
                    each round of the loop offers the same work in any seed
    lengths     {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
                {"dist": "uniform", "min": a, "max": b}
                {"dist": "mixture", "parts": [{"weight": w, ...a length spec}]}
    shared_prefix {"tokens": n, "pool": k}   each prompt starts with one of k
                                             fixed prefixes of n tokens
    tokens      {"dist": "zipf", "exponent": a}  token ids by rank
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np
from scipy.special import ndtri

# fixed stream numbers, so that adding a draw to one stream moves no other
_ARRIVALS, _LENGTHS, _TOKENS, _PREFIXES = 1, 2, 3, 4


def _rng(seed: int, stream: int, *more: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream, *more])


def _uniforms(rng: np.random.Generator, n: int, stratified: bool) -> np.ndarray:
    if not stratified:
        return rng.random(n)
    return (rng.permutation(n) + rng.random(n)) / n


def lengths(spec: Dict[str, Any], rng: np.random.Generator, n: int,
            stratified: bool = False) -> np.ndarray:
    dist = spec["dist"]
    if dist == "mixture":
        weights = np.cumsum([p["weight"] for p in spec["parts"]], dtype=float)
        which = np.searchsorted(weights / weights[-1],
                                _uniforms(rng, n, stratified), side="right")
        out = np.zeros(n, np.int64)
        for i, part in enumerate(spec["parts"]):
            idx = np.flatnonzero(which == i)
            out[idx] = lengths(part, rng, len(idx), stratified)
        return out
    u = _uniforms(rng, n, stratified)
    if dist == "lognormal":
        raw = np.exp(np.log(spec["median"]) + spec["sigma"] * ndtri(u))
        return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)
    if dist == "uniform":
        span = spec["max"] - spec["min"] + 1
        return (spec["min"] + np.minimum(np.floor(u * span), span - 1)
                ).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def arrival_times(spec: Dict[str, Any], rng: np.random.Generator,
                  span_s: float) -> np.ndarray:
    """Arrival instants in [0, span_s) of an open-loop process."""
    rate = float(spec["rate_per_s"])
    if spec["process"] == "poisson":
        return np.sort(rng.random(int(round(rate * span_s))) * span_s)
    if spec["process"] != "gamma":
        raise ValueError(f"no schedule for arrivals {spec['process']!r}")
    shape = 1.0 / float(spec["cv"]) ** 2    # gaps: mean 1/rate, this CV
    n = int(span_s * rate * 1.5 + 50)
    times = np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), size=n))
    while times[-1] < span_s:
        times = np.concatenate([times, times[-1] + np.cumsum(
            rng.gamma(shape, 1.0 / (rate * shape), size=n))])
    return times[times < span_s]


def requests(mix: Dict[str, Any], seed: int, n: int, stream: int = 0,
             stratified: bool = False) -> List[Dict[str, int]]:
    """``n`` requests of a serving mix: prompt and output lengths, and the
    numbers from which ``prompt_tokens`` makes the ids. ``stream`` separates
    the sequences of closed-loop clients and the parts of a schedule."""
    rng = _rng(seed, _LENGTHS, stream)
    prompt = lengths(mix["prompt_len"], rng, n, stratified)
    output = lengths(mix["output_len"], rng, n, stratified)
    pool = mix.get("shared_prefix", {}).get("pool", 0)
    group = rng.integers(0, pool, size=n) if pool else np.full(n, -1)
    return [{"prompt_len": int(p), "output_len": int(o), "prefix": int(g),
             "token_seed": [seed, _TOKENS, stream, i]}
            for i, (p, o, g) in enumerate(zip(prompt, output, group))]


def closed_loop_request(mix: Dict[str, Any], seed: int, client: int,
                        i: int) -> Dict[str, int]:
    """The ``i``-th request of one client of a closed loop. The clients move
    at about one pace, so a window holds whole rounds and two partial ones:
    with a round stratified across the clients, the lengths a window offers
    sum to nearly the same in every seed (independent draws of 180 documents
    differ by 1.3% in their sum, more than the system's own noise)."""
    n = int(mix["arrivals"]["clients"])
    return requests(mix, seed, n, stream=i, stratified=True)[client]


def prompt_tokens(mix: Dict[str, Any], request: Dict[str, Any],
                  vocab: int) -> np.ndarray:
    """The request's token ids: uniform over the vocabulary (id 0 left out),
    after its shared prefix if the mix has one."""
    ids = np.random.default_rng(request["token_seed"]).integers(
        1, vocab, size=request["prompt_len"]).astype(np.int32)
    shared = mix.get("shared_prefix")
    if shared and request["prefix"] >= 0:
        head = _rng(request["token_seed"][0], _PREFIXES,
                    request["prefix"]).integers(
            1, vocab, size=shared["tokens"]).astype(np.int32)
        n = min(len(head), len(ids))
        ids[:n] = head[:n]
    return ids


def open_loop_schedule(mix: Dict[str, Any], seed: int, lead_s: float,
                       seconds: float,
                       after_s: float = 0.0) -> List[Dict[str, Any]]:
    """Requests with their due instants (seconds from the schedule's zero):
    a lead-in of ``lead_s``, then the window, then ``after_s`` more seconds
    (a traced run's profiler follows its window), each a schedule of its own,
    so that the window's amount of work depends on neither of the others."""
    out = []
    for part, (start, span) in enumerate(((0.0, lead_s), (lead_s, seconds),
                                          (lead_s + seconds, after_s))):
        due = start + arrival_times(mix["arrivals"],
                                    _rng(seed, _ARRIVALS, part), span)
        reqs = requests(mix, seed, len(due), stream=part, stratified=True)
        for r, t in zip(reqs, due):
            r["due_s"] = float(t)
        out.extend(reqs)
    return out


def token_batches(mix: Dict[str, Any], seed: int, n_seq: int, seq_len: int,
                  vocab: int):
    """Training data: ``(ids, labels)`` of ``n_seq`` packed sequences whose
    tokens follow a Zipf law over the vocabulary by id; labels are the ids
    shifted left by one."""
    spec = mix["tokens"]
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -float(spec["exponent"])
    rng = _rng(seed, _TOKENS)
    ids = rng.choice(vocab, size=(n_seq, seq_len), p=p / p.sum()).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1)
