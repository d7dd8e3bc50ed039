"""The comparison that decides ``correct``.

After the window closes, a sample of the finished requests, drawn from
the seed and always holding the one with the most served tokens, is
scored by the plain reference: one float32 forward pass over each prompt
followed by its served tokens.  At each served token the number read is
the gap by which the reference's logit of that token lies below the
reference's best logit at that position; the cell's number is the widest
gap over the sample.  Greedy serving of exact arithmetic reads 0; a
bfloat16 engine reads the size of its near-ties; a wrong token reads
the whole spread of the logits."""

from __future__ import annotations

import numpy as np

from bench.traffic import rng


def sample(finished: list, min_tokens: int, seed: int) -> list:
    """Requests to score: the longest by served tokens, then others in a
    seed-drawn order until ``min_tokens`` served tokens are covered."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (-len(r.generated), r.uid))
    chosen, rest = [by_len[0]], by_len[1:]
    order = rng(seed, "sample").permutation(len(rest))
    n = len(by_len[0].generated)
    for i in order:
        if n >= min_tokens:
            break
        chosen.append(rest[i])
        n += len(rest[i].generated)
    return chosen


def _positions(req):
    toks = np.concatenate([req.prompt, np.asarray(req.generated[:-1],
                                                  np.int32)])
    rows = np.arange(len(req.prompt) - 1, len(toks))
    return toks, rows


def gaps(ref, w, cfg, reqs, precision: str = "f32") -> np.ndarray:
    """Per served token, max reference logit minus the reference logit of
    the token read: the served token, or, with ``precision`` other than
    f32, the token that the reference computed at that precision puts
    first (the control)."""
    out = []
    for r in reqs:
        toks, rows = _positions(r)
        exact = np.asarray(ref.logits(w, cfg, toks, rows, "f32"))
        if precision == "f32":
            picked = np.asarray(r.generated, np.int64)
        else:
            low = np.asarray(ref.logits(w, cfg, toks, rows, precision))
            picked = np.argmax(low, axis=-1)
        best = exact.max(axis=-1)
        out.append(best - exact[np.arange(len(rows)), picked])
    return np.concatenate(out) if out else np.zeros((0,))
