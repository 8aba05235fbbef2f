"""The one traffic generator: reads a mix's parameters, makes its requests.

A serving mix draws a fixed set of request sizes and arrival gaps from its
own ``sizes_seed``; the run's ``--seed`` only shuffles them and draws the
token ids.  The shuffle keeps to blocks of ``shuffle_block`` consecutive
requests: every seed offers the same work in each block, and each block ends
at the same time, in another order within it.  So runs with different seeds
differ by the order alone, and never by how much work a window holds.
A ``shuffle_block`` of 1 keeps one order: every seed then offers the same
sizes at the same times and differs by its token ids alone.
Arrivals are open loop:
each request is due at its scheduled time whether or not the server keeps
up (the arithmetic of ``repro.fleet.traffic._arrival_times``: exponential
gaps, summed in order).

Training batches are the program's own (``Dataset``); ``lm_batch`` repeats
its arithmetic here so that the reference reads the same tokens without
taking anything the program made.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths from ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}`` or ``{"dist": "fixed", "value"}``, clipped to [min, max]."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _shuffle(rng, n: int, block: int) -> np.ndarray:
    """A permutation of ``range(n)`` that moves each entry only within its
    run of ``block`` consecutive ones."""
    return np.concatenate([s + rng.permutation(min(block, n - s))
                           for s in range(0, n, block)])


def longest(spec: dict) -> int:
    """The largest length ``spec`` can draw."""
    return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])


@dataclasses.dataclass
class Requests:
    """Requests in order of arrival: scheduled time (s from the window's
    start), prompt tokens and output budget of each."""
    t_arrival: np.ndarray
    prompts: list
    output_len: np.ndarray

    def __len__(self):
        return len(self.prompts)


def serve_requests(mix: dict, seed: int, seconds: float,
                   vocab: int) -> Requests:
    """Open-loop requests for a window of ``seconds``: Poisson arrivals at
    ``mix["rate_per_s"]`` and lengths as the mix declares, enough of them
    that every seed's arrivals outlast the window."""
    rate = float(mix["rate_per_s"])
    n = int(np.ceil(rate * seconds * 1.5)) + 16
    sizes = np.random.default_rng(mix["sizes_seed"])
    gaps = sizes.exponential(1.0 / rate, n)
    plen = _lengths(sizes, mix["prompt_len"], n)
    olen = _lengths(sizes, mix["output_len"], n)
    block = int(mix.get("shuffle_block") or n)
    order = np.random.default_rng([seed % 2**63, 1])
    gaps = gaps[_shuffle(order, n, block)]
    pick = _shuffle(order, n, block)
    plen, olen = plen[pick], olen[pick]
    tok = np.random.default_rng([seed % 2**63, 2])
    prompts = [tok.integers(0, vocab, int(k)).astype(np.int32) for k in plen]
    return Requests(np.cumsum(gaps), prompts, olen)


# ---------------------------------------------------------------------------
# training batches (repro.data.synthetic.Dataset, LM streams)
# ---------------------------------------------------------------------------

def lm_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int):
    """(tokens, labels) of one step, as the program's ``Dataset`` makes
    them: zipf-flavoured ids ``floor(u**3 * vocab)`` from a generator
    seeded by ``(seed * 1_000_003 + step) & 0x7FFFFFFF``."""
    rng = np.random.default_rng((seed * 1_000_003 + step) & 0x7FFFFFFF)
    u = rng.random((batch, seq_len + 1))
    stream = np.minimum((u ** 3.0) * vocab, vocab - 1).astype(np.int32)
    return stream[:, :-1], stream[:, 1:]
