"""The one traffic generator: a traffic file's parameters + a seed -> work.

The seed chooses token ids and where a fixed sequence starts, never how much
work a run does: the multiset of prompt lengths, output lengths and
inter-arrival gaps of a cell, and the order they come in up to a rotation,
depend only on the traffic file (and, for an open loop, on the window's
length). tests/test_traffic.py pins that for a dozen seeds.

Traffic kinds (the `kind` key of bench/traffic/<name>.json):
  closed-serve  `clients` callers, each sending its next request when the
                last one finished
  open-serve    requests on a wall-clock schedule at `rate_rps`
  train         a fresh token batch per step, drawn on the device
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Request:
    due_s: float              # offset from the window's start (open loop)
    prompt: tuple             # token ids
    max_tokens: int


def lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths, sorted: the stratified quantiles (i + 0.5) / n of the
    distribution `spec` names, so the multiset is a function of (spec, n).
      {"dist": "uniform", "lo": a, "hi": b}     evenly spaced, ends included
      {"dist": "lognormal", "median": m, "sigma": s, "lo": a, "hi": b}
                                                clipped to [a, b]: a heavy tail
    """
    if spec["dist"] == "uniform":
        vals = np.linspace(spec["lo"], spec["hi"], n)
    elif spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
        vals = np.clip(spec["median"] * np.exp(spec["sigma"] * z),
                       spec["lo"], spec["hi"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.rint(vals).astype(np.int64)


def gaps(rate_rps: float, n: int) -> np.ndarray:
    """`n` inter-arrival gaps: stratified quantiles of Exp(rate)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate_rps


def _prompt(rng, vocab: int, n: int) -> tuple:
    return tuple(rng.integers(0, vocab, int(n)).tolist())


def open_schedule(traffic: dict, seconds: float, vocab: int,
                  seed: int) -> list:
    """Requests of an open loop, in due order: round(rate * seconds) of
    them for every seed. Gaps, prompt lengths and output lengths are each
    the stratified multiset in ONE fixed shuffle (a property of the traffic
    file, not of the seed); the seed ROTATES that sequence to start
    somewhere else and draws the token ids. Bursts and the long prompts
    that fall into them are then the same for every seed, only at another
    time: tails of latency depend on exactly that coincidence, and read
    50% apart between seeds when the seed shuffled freely (PERF.md)."""
    n = max(1, round(traffic["rate_rps"] * seconds))
    pattern = np.random.default_rng(0)
    gap = pattern.permutation(gaps(traffic["rate_rps"], n))
    p_len = pattern.permutation(lengths(traffic["prompt_len"], n))
    o_len = pattern.permutation(lengths(traffic["output_len"], n))
    rng = np.random.default_rng(seed)
    k = int(rng.integers(n))
    due = np.cumsum(np.roll(gap, -k))
    p_len, o_len = np.roll(p_len, -k), np.roll(o_len, -k)
    return [Request(float(due[i]), _prompt(rng, vocab, p_len[i]),
                    int(o_len[i])) for i in range(n)]


def closed_clients(traffic: dict, vocab: int, seed: int,
                   requests_per_client: int = 8) -> list:
    """One list of Requests per client of a closed loop, in the order the
    clients are to be started. Every client walks the same cycle of the
    `clients` prompt lengths (a fixed shuffle of the multiset, so that
    neighbours differ), client i starting at cycle position i; with
    `stagger`, client i's FIRST request asks for (i + 1) / clients of
    `output_tokens`, so that completions are spread evenly over time
    instead of arriving together. All of that is the same for every seed.
    The seed permutes the order in which clients start (which engine slot
    each gets) and draws the token ids."""
    n = traffic["clients"]
    cycle = np.random.default_rng(0).permutation(
        lengths(traffic["prompt_len"], n))
    out_tokens = traffic["output_tokens"]
    rng = np.random.default_rng(seed)
    clients = []
    for i in range(n):
        reqs = []
        for k in range(requests_per_client):
            first = traffic.get("stagger") and k == 0
            reqs.append(Request(
                0.0, _prompt(rng, vocab, cycle[(i + k) % n]),
                max(1, out_tokens * (i + 1) // n) if first else out_tokens))
        clients.append(reqs)
    return [clients[i] for i in rng.permutation(n)]


def summary(requests) -> dict:
    """What a seed may not change: counts and totals of a request list."""
    reqs = list(requests)
    return {"requests": len(reqs),
            "prompt_lens": sorted(len(r.prompt) for r in reqs),
            "output_lens": sorted(r.max_tokens for r in reqs),
            "prompt_tokens": sum(len(r.prompt) for r in reqs),
            "output_tokens": sum(r.max_tokens for r in reqs),
            "gaps_ms": sorted(round(1e3 * g, 6) for g in np.diff(
                [0.0] + [r.due_s for r in reqs]))}


def train_batch(key, step, batch: int, seq: int, vocab: int):
    """(tokens, labels) of optimizer step `step`, [batch, seq] int32 each,
    drawn on the device; every row and every step differs. jit it."""
    import jax
    import jax.numpy as jnp

    t = jax.random.randint(jax.random.fold_in(key, step), (batch, seq + 1),
                           0, vocab, jnp.int32)
    return t[:, :-1], t[:, 1:]


def pool_blocks(traffic: dict, block_size: int = 16) -> int:
    """KV pages the traffic file asks for: every slot's longest context,
    plus `pool_slack_blocks` (the engine's scratch page and headroom)."""
    per_seq = math.ceil(traffic["pool_tokens_per_slot"] / block_size)
    return traffic["max_batch_size"] * per_seq + traffic["pool_slack_blocks"]
