"""The one generator every traffic mix is read by.

A traffic file (``bench/traffic/<name>.json``) holds parameters only; its
``kind`` picks the generator below.

* ``train``: batches of the seeded order-1 Markov corpus (a copy of the
  arithmetic of the program's ``data/synthetic.py`` ``SyntheticLM``, made on
  the host with numpy so that the feed never waits on the device).
* ``serve``: an open-loop request trace. Arrivals are Poisson at ``rate``
  requests per second (a copy of the exponential-gap arithmetic of the
  program's ``benchmarks/bench_serve.py``), and prompt and output lengths are
  lognormal about their medians, clipped. Token ids are uniform, so no two
  prompts share a prefix, and every request decodes greedily.  The trace
  starts ``ramp_s`` seconds before the window opens, so that the window
  finds the scheduler as full as the rate keeps it.

Every seed gets the same work: the arrivals and the (prompt, output)
lengths of the ramp and of the window are drawn from the file's
``base_seed``, in that order, and the run's seed draws only the token ids.
A window holds a few requests only.  Dealing the window's lengths to its
arrivals in another order per seed changed which prefill chunks fell
inside the window, and with it every serving metric: the 95th percentile
of the token gaps sat at one of two step times by the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


# -- training: the Markov corpus ------------------------------------------------

def markov_table(vocab: int, branching: int, seed: int) -> np.ndarray:
    """(vocab, branching) successor table, fixed by the seed."""
    return np.random.default_rng(seed).integers(0, vocab, size=(vocab, branching))


def markov_batch(table: np.ndarray, seed: int, step: int, batch: int,
                 seq: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens, labels), each (batch, seq) int32, for one step: a random
    start token per row, then ``tokens[t+1] = table[tokens[t], c]`` with
    ``c`` uniform over the branching; labels are the next tokens."""
    vocab, branching = table.shape
    rng = np.random.default_rng([seed, step])
    x = rng.integers(0, vocab, size=batch)
    choices = rng.integers(0, branching, size=(seq, batch))
    out = np.empty((seq + 1, batch), np.int64)
    out[0] = x
    for t in range(seq):
        x = table[x, choices[t]]
        out[t + 1] = x
    seq_toks = out.T.astype(np.int32)
    return seq_toks[:, :-1], seq_toks[:, 1:]


# -- serving: the open-loop request trace ---------------------------------------

@dataclasses.dataclass(frozen=True)
class TraceRequest:
    rid: str
    due_s: float  # seconds after the window opens
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int


def _lognormal_ints(rng, n: int, median: float, sigma: float, lo: int,
                    hi: int) -> np.ndarray:
    x = rng.lognormal(np.log(median), sigma, size=n)
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def request_trace(traffic: dict, seed: int, seconds: float, vocab: int,
                  rate: float | None = None) -> list[TraceRequest]:
    """The requests of the ramp (due in [-ramp_s, 0)) and of a window of
    `seconds` (due in [0, seconds)), in order of arrival.

    Each phase of length L holds ``round(rate * L)`` requests; their gaps
    are exponential with mean ``1 / rate``, scaled so that the phase's last
    one is due before the phase ends."""
    rate = float(traffic["rate"] if rate is None else rate)
    p, o = traffic["prompt_len"], traffic["output_len"]
    rng = np.random.default_rng(seed)
    out = []
    phases = [("w", -float(traffic.get("ramp_s", 0)), 0.0), ("r", 0.0, float(seconds))]
    for i, (tag, t0, t1) in enumerate(phases):
        if t1 <= t0:
            continue
        n = max(1, int(round(rate * (t1 - t0))))
        base = np.random.default_rng([traffic["base_seed"], i])
        gaps = base.exponential(1.0 / rate, size=n + 1)
        gaps *= (t1 - t0) / gaps.sum()  # n arrivals inside the phase
        due = t0 + np.cumsum(gaps)[:n]
        plen = _lognormal_ints(base, n, p["median"], p["sigma"], p["min"], p["max"])
        olen = _lognormal_ints(base, n, o["median"], o["sigma"], o["min"], o["max"])
        out += [TraceRequest(rid=f"{tag}{j:05d}", due_s=float(due[j]),
                             prompt=rng.integers(0, vocab, size=int(plen[j])).astype(np.int32),
                             max_new_tokens=int(olen[j]))
                for j in range(n)]
    return out
