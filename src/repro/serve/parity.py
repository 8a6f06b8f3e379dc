"""Greedy-stream parity measures between two programs that should emit
the same tokens (sharded vs single device, paged vs contiguous, an
engine vs a teacher-forced reference).

Greedy decoding turns a float epsilon into a cliff: one argmax flip at
a near-tie and the rest of the stream diverges.  ``token_match_fraction``
measures how long two streams agree; ``greedy_gap`` checks every token
of one stream against the other program's scores, so an early near-tie
flip does not hide the rest.  ``tests/tolerance.py`` builds the test
assertions on these.
"""
import numpy as np


def token_match_fraction(a, b) -> float:
    """Fraction of the longer stream covered by the common prefix on
    which ``a`` and ``b`` agree exactly (1.0 = identical streams)."""
    a = np.asarray(a).ravel()
    b = np.asarray(b).ravel()
    n = max(len(a), len(b))
    if n == 0:
        return 1.0
    m = min(len(a), len(b))
    neq = np.nonzero(a[:m] != b[:m])[0]
    prefix = int(neq[0]) if len(neq) else m
    return prefix / n


def greedy_gap(tokens, logits) -> float:
    """Teacher-forced greedy check of a stream that another program
    emitted: ``logits[i]`` is the reference's score row at the position
    that emitted ``tokens[i]``, given the same context.  Returns the
    largest shortfall of an emitted token's score below its row's best,
    relative to the largest |logit| — 0.0 when every token is the
    reference's argmax, small when the stream only broke near-ties the
    other way, and of order 1 for a token the reference ranks low."""
    tokens = np.asarray(tokens).ravel()
    logits = np.asarray(logits, np.float64)
    if len(tokens) == 0:
        return 0.0
    chosen = logits[np.arange(len(tokens)), tokens]
    return float(np.max(logits.max(axis=-1) - chosen)
                 / np.max(np.abs(logits)))
