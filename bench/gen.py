"""Seeded traffic for the benchmark's cells.

Training batches come from the traffic's `task`, a generator of its own
found by name (bench/tasks/<task>.py). ListOps (Nangia & Bowman 2018, the
LRA task the paper trains on) is made here, from a copy of the grammar
the repository's data module uses. Serving requests are an open loop: a
fixed count of arrivals spread uniformly over the window (a Poisson
process conditioned on its count), with prompt and output lengths drawn
once from the mix's population seed and dealt out in the run seed's
order, so every seed serves the same work.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# -- ListOps -----------------------------------------------------------------

OPS = ["MIN", "MAX", "MED", "SM"]
PAD, CLS, OPEN, CLOSE = 0, 1, 2, 3
OP0, DIG0 = 4, 8
LISTOPS_VOCAB = 18


def _tree(rng, depth, max_args):
    if depth <= 0 or rng.random() < 0.3:
        return int(rng.integers(0, 10))
    op = OPS[rng.integers(0, len(OPS))]
    n = int(rng.integers(2, max_args + 1))
    return (op, [_tree(rng, depth - 1, max_args) for _ in range(n)])


def _tokens(node, out):
    if isinstance(node, int):
        out.append(DIG0 + node)
        return
    op, args = node
    out.append(OPEN)
    out.append(OP0 + OPS.index(op))
    for a in args:
        _tokens(a, out)
    out.append(CLOSE)


def listops_row(rng, length, depth=6, max_args=5):
    """One ListOps expression, tokenised and padded to `length`."""
    while True:
        toks = [CLS]
        _tokens(_tree(rng, depth, max_args), toks)
        if 8 <= len(toks) <= length:
            row = np.full((length,), PAD, np.int32)
            row[:len(toks)] = toks
            return row


def listops_batches(seed, *, count, batch, seq_len):
    """`count` language-model batches {"tokens", "labels"} of ListOps rows
    of seq_len + 1 tokens, every row drawn anew, each batch's rows in order
    of length."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        xs = np.stack([listops_row(rng, seq_len + 1) for _ in range(batch)])
        # shortest row first, as a loader that buckets by length gives them
        xs = xs[np.argsort((xs != PAD).sum(1), kind="stable")]
        out.append({"tokens": xs[:, :-1], "labels": xs[:, 1:]})
    return out


# -- serving -----------------------------------------------------------------

@dataclasses.dataclass
class Arrival:
    rid: int
    due: float            # seconds after the window opens
    prompt: np.ndarray    # (P,) int32
    max_new: int


def clipped_lognormal(rng, n, spec):
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def rate_per_s(mix):
    """The mix's arrival rate: a share (`load_of_knee`) of the knee that a
    sweep measured (`knee_per_s`), unless a rate is given outright."""
    if "rate_per_s" in mix:
        return mix["rate_per_s"]
    return mix["knee_per_s"] * mix["load_of_knee"]


def request_count(mix, seconds):
    return max(int(round(rate_per_s(mix) * seconds)), 1)


def chat_arrivals(mix, seed, seconds, vocab):
    """The open-loop schedule of one run, sorted by due time."""
    n = request_count(mix, seconds)
    pop = np.random.default_rng(mix["population_seed"])
    plen = clipped_lognormal(pop, n, mix["prompt"])
    olen = clipped_lognormal(pop, n, mix["output"])
    rng = np.random.default_rng(seed)
    plen = plen[rng.permutation(n)]
    olen = olen[rng.permutation(n)]
    due = np.sort(rng.uniform(0.0, seconds, n))
    return [Arrival(i, float(due[i]),
                    rng.integers(0, vocab, int(plen[i])).astype(np.int32),
                    int(olen[i])) for i in range(n)]
