"""How ``correct`` is decided for a served model.

Once the window has closed and the program's state is freed, a sample of
the requests the clients received tokens for, drawn from the seed and
always holding the one with the most served tokens, is run through the
plain reference (``bench/reference``): for every served token, the gap
by which its reference logit lies below the reference's best logit at
that position.  The widest gap over the sample is compared with the
configuration's limit.  Greedy decoding only: a correct program serves
tokens the reference also ranks at or near the top.

The control reads the same prompts and served tokens through the
reference computed in float8 (``low_precision=True``): at each position,
the gap of the token the control puts first.
"""
from __future__ import annotations

import numpy as np

from load import rng_for


def sample(records, seed: int, tokens: int, max_requests: int):
    """The records to check: the one with the most served tokens, then
    others in the seed's order until ``tokens`` served tokens or
    ``max_requests`` requests."""
    cands = sorted((r for r in records if r.tokens and r.error is None),
                   key=lambda r: r.rid)
    if not cands:
        return []
    longest = max(cands, key=lambda r: len(r.tokens))
    chosen, total = [longest], len(longest.tokens)
    for i in rng_for(seed, 7).permutation(len(cands)):
        r = cands[int(i)]
        if total >= tokens or len(chosen) >= max_requests:
            break
        if r is not longest:
            chosen.append(r)
            total += len(r.tokens)
    return chosen


def served_gaps(ref, recs) -> np.ndarray:
    """Per served token of ``recs``: reference best logit minus the
    reference logit of the served token."""
    out = []
    for r in recs:
        lg = np.asarray(ref.logits(r.prompt, r.tokens))
        got = lg[np.arange(len(r.tokens)), np.asarray(r.tokens)]
        out.append(lg.max(-1) - got)
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(ref, control, recs) -> np.ndarray:
    """Per served position of ``recs``: reference best logit minus the
    reference logit of the token the control ranks first."""
    out = []
    for r in recs:
        lg = np.asarray(ref.logits(r.prompt, r.tokens))
        pick = np.asarray(control.logits(r.prompt, r.tokens)).argmax(-1)
        out.append(lg.max(-1) - lg[np.arange(len(pick)), pick])
    return np.concatenate(out) if out else np.zeros(0)
