"""Draft proposers for speculative decoding.

The serving tick verifies k draft tokens per request in ONE compiled
dispatch (serving/executables.py `verify`), so anything that can guess
the next few tokens cheaply on the host turns into decoded tokens at
verify cost. The built-in proposer is self-drafting n-gram lookup
(prompt-lookup decoding): find the most recent earlier occurrence of
the sequence's trailing n-gram and propose the tokens that followed
it — free, model-less, and strong on repetitive continuations
(code, templated text, retrieval-heavy traffic). A tiny draft MODEL
plugs into the same interface: anything with `.k` and `.propose(tokens) -> array` works.

Contract: proposals are CANDIDATES only. The verify executable scores
them against the real model and keeps the longest accepted prefix, so
a bad proposer costs speed, never correctness — greedy output is
token-identical to the non-speculative tick regardless of what is
proposed here.

This module is intentionally telemetry-free (accept-rate accounting
lives in the server, behind the `telemetry._ENABLED` gate the AST
lint enforces).
"""
from __future__ import annotations

import numpy as np

__all__ = ["NgramProposer", "as_proposer"]

_EMPTY = np.zeros((0,), np.int32)


class NgramProposer:
    """Self-drafting n-gram proposer.

    k: max draft tokens proposed per tick (the verify window is
    k + 1 positions wide — keep it small, rejected positions are
    wasted compute).
    ngram: longest trailing n-gram matched against history; falls
    back n, n-1, ..., 1 so even a single repeated token drafts.
    max_context: cap on how much history each propose() scans.
    """

    def __init__(self, k: int = 4, ngram: int = 2,
                 max_context: int = 2048):
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.k = int(k)
        self.ngram = max(1, int(ngram))
        self.max_context = int(max_context)

    def propose(self, tokens) -> np.ndarray:
        """tokens: the request's full context (prompt + output so
        far). Returns up to k draft tokens (possibly empty)."""
        toks = np.asarray(tokens, np.int64).reshape(-1)
        if toks.size > self.max_context:
            toks = toks[-self.max_context:]
        L = int(toks.size)
        for n in range(min(self.ngram, L - 1), 0, -1):
            suffix = toks[L - n:]
            # windows starting before L - n have at least one
            # continuation token; the trailing window (the suffix
            # itself) is excluded
            w = np.lib.stride_tricks.sliding_window_view(toks, n)
            cand = np.flatnonzero((w[:L - n] == suffix).all(axis=1))
            if cand.size == 0:
                continue
            i = int(cand[-1])        # most recent occurrence wins
            # k + 1 guesses: the server checks the FIRST one against
            # the token its tick computes anyway, so k drafts survive
            # the one-position shift into the verify window
            cont = toks[i + n:min(i + n + self.k + 1, L)]
            return cont.astype(np.int32)
        return _EMPTY


def as_proposer(spec):
    """Normalize the server's `speculative=` argument: None/False ->
    off, True -> NgramProposer(), int k -> NgramProposer(k=k), any
    object with .k and .propose -> itself."""
    if spec is None or spec is False:
        return None
    if spec is True:
        return NgramProposer()
    if isinstance(spec, (int, np.integer)):
        return NgramProposer(k=int(spec))
    if not (hasattr(spec, "propose") and hasattr(spec, "k")):
        raise TypeError(
            "speculative= expects None, True, an int draft length, or "
            f"a proposer with .k and .propose(tokens); got {spec!r}")
    return spec
