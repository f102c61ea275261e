"""The one traffic generator.  A mix is a data file under ``bench/traffic``;
everything here is a pure function of that file's parameters and the run's
seed, so the driver under test and the plain reference draw the same work.

* Training mixes give token batches: ``train_batch(mix, vocab, seed, step)``.
* Serving mixes give a closed backlog of requests: ``Backlog``.  The
  sequence of (prompt, output) lengths is drawn once from the mix's own
  ``size_seed`` and only the token ids follow the run's seed, so every
  seed offers the same work.  (A window serves only the head of the
  backlog, so an order that followed the seed changed the work: 326–359
  served tokens a second between seeds on one v5e chip, against 0.3%
  between two runs of one seed.)
"""

from __future__ import annotations

import functools
import math

import numpy as np


def _rng(*words: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(w) for w in words]))


@functools.lru_cache(maxsize=4)
def _zipf_cdf(vocab: int, a: float) -> np.ndarray:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / np.power(ranks, a)
    return np.cumsum(p / p.sum())


def train_batch(mix: dict, vocab: int, seed: int, step: int
                ) -> tuple[np.ndarray, np.ndarray]:
    """``(tokens, labels)``, int32 ``(global_batch, seq_len)``: Zipf token
    ids over the published vocabulary with EOS-delimited documents; the
    labels are the next tokens.  Every (seed, step) gives other rows."""
    tk = mix["tokens"]
    b, s = mix["global_batch"], mix["seq_len"]
    rng = _rng(seed, step)
    cdf = _zipf_cdf(vocab, tk["zipf_a"])
    toks = np.minimum(np.searchsorted(cdf, rng.random((b, s + 1))), vocab - 1)
    eos = rng.random((b, s + 1)) < 1.0 / tk["mean_doc_len"]
    toks = np.where(eos, tk["eos_id"], toks).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _lognormal_lengths(rng: np.random.Generator, spec: dict, n: int
                       ) -> np.ndarray:
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Backlog:
    """A closed backlog of ``mix["backlog"]`` requests, served in order.

    ``prompt_len``/``output_len`` are fixed by the mix; the prompt token
    ids come from ``seed``."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        n = mix["backlog"]
        sizes = _rng(mix["size_seed"])
        self.prompt_len = _lognormal_lengths(sizes, mix["prompt"], n)
        self.output_len = _lognormal_lengths(sizes, mix["output"], n)
        self.vocab, self.seed = vocab, seed

    def __len__(self) -> int:
        return len(self.prompt_len)

    def prompt(self, i: int) -> np.ndarray:
        return _rng(self.seed, 1 << 20, i).integers(
            0, self.vocab, int(self.prompt_len[i]), dtype=np.int32)


def max_context(mix: dict) -> int:
    """Longest prompt plus output the mix can draw."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])
