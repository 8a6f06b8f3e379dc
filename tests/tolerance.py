"""Shared tolerance helpers for sharded-vs-single-device parity tests.

Weight-sharded tensor parallelism changes matmul reduction order (each
shard partial-sums its slice, then one psum), so logits drift by float
epsilons relative to the single-device program.  Greedy decoding turns
an epsilon into a cliff: one argmax flip near a tie and the rest of the
stream diverges.  Elementwise comparison is therefore the wrong shape
for banded token parity — the right invariant is that the streams agree
on a long PREFIX (an early flip means a real bug, a late flip means a
near-tie), which ``assert_close_tokens`` checks.  Logit-space checks
stay elementwise with float tolerances (``assert_close_logits``).  The
measures themselves live in ``repro.serve.parity``, where the chip
smoke run uses them too.

Kept importable by name (tests/ is put on the subprocess PYTHONPATH by
the multi-device tests) so every banded assertion shares one policy
instead of per-test ad-hoc ``np.testing`` calls.
"""
import numpy as np

from repro.serve.parity import token_match_fraction


def assert_close_tokens(a, b, *, min_match_frac: float = 0.9,
                        context="") -> None:
    """Banded greedy-stream parity: the two token streams must share a
    matching prefix covering at least ``min_match_frac`` of their
    length.  Use for cross-program comparisons (sharded weights vs
    single device, dp replicas vs one engine); bitwise contracts should
    keep using ``np.array_equal``."""
    frac = token_match_fraction(a, b)
    assert frac >= min_match_frac, (
        f"token streams diverge too early: matching prefix covers "
        f"{frac:.3f} < {min_match_frac} "
        f"(a={np.asarray(a).tolist()}, b={np.asarray(b).tolist()})"
        + (f" [{context}]" if context else ""))


def assert_close_logits(a, b, *, rtol: float = 2e-5, atol: float = 1e-5,
                        context="") -> None:
    """Elementwise float tolerance for logits/activations across
    reduction-order-changing program variants (psum vs single-device
    sum)."""
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=rtol, atol=atol,
                               err_msg=f"logits differ [{context}]")
