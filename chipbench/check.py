"""Whether what the timed path served is correct.

After the window, a sample of the finished requests, drawn from the seed
and holding the one with the most served tokens, is run once through the
plain float32 reference that the configuration names
(``chipbench/references/<name>.py``) over its prompt and served tokens.
The number compared is the widest gap by which a served (greedy) token's
reference logit lies below the reference's best logit at that position:
zero where the program picked the reference's own argmax, small where
rounding swapped two near-equal logits, large where the program computed
something else.
"""
from __future__ import annotations

import numpy as np


def sample(finished: list, seed: int, served_tokens: int,
           max_requests: int) -> list:
    """The finished request with most served tokens, then others drawn
    from the seed until ``served_tokens`` are covered."""
    if not finished:
        return []
    finished = sorted(finished, key=lambda r: r.index)
    first = max(finished, key=lambda r: (len(r.tokens), r.prompt_len))
    rest = [r for r in finished if r is not first]
    order = np.random.default_rng([seed, 0xC4EC]).permutation(len(rest))
    out, total = [first], len(first.tokens)
    for i in order:
        if total >= served_tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        total += len(rest[i].tokens)
    return out


def _round_up(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def _batch(reqs: list) -> tuple:
    """-> tokens (N, T) right-padded, rows (N, P), served (N, P), valid.
    N is rounded up to a power of two, T to a multiple of 256 and P of 64,
    so that the reference's programs come from the compile cache in most
    runs; padded rows and positions are not valid and compare nothing."""
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in reqs]
    t = _round_up(max(len(s) for s in seqs), 256)
    p = _round_up(max(len(r.tokens) for r in reqs), 64)
    n = 1 << (len(reqs) - 1).bit_length()
    tokens = np.zeros((n, t), np.int32)
    rows = np.zeros((n, p), np.int32)
    served = np.zeros((n, p), np.int32)
    valid = np.zeros((n, p), bool)
    for i, (s, r) in enumerate(zip(seqs, reqs)):
        n = len(r.tokens)
        tokens[i, :len(s)] = s
        rows[i, :n] = np.arange(r.prompt_len - 1, r.prompt_len - 1 + n)
        rows[i, n:] = rows[i, n - 1]
        served[i, :n] = r.tokens
        valid[i, :n] = True
    return tokens, rows, served, valid


def logit_gaps(ref, config: dict, seed: int, reqs: list,
               control: bool = False) -> dict:
    """Widest logit gap of the served tokens in the reference module
    ``ref``; with ``control`` also that of the tokens its control (the
    precision below the configuration's) puts first."""
    tokens, rows, served, valid = _batch(reqs)
    logits = np.asarray(ref.logits_at(config, seed, tokens, rows))
    best = logits.max(-1)

    def widest(picked):
        got = np.take_along_axis(logits, picked[..., None], -1)[..., 0]
        return float(np.max(np.where(valid, best - got, 0.0)))

    out = {"max_logit_gap": widest(served), "served_tokens": int(valid.sum()),
           "requests": len(reqs)}
    if control:
        low = np.asarray(ref.logits_at(config, seed, tokens, rows,
                                       control=True))
        out["control_max_logit_gap"] = widest(low.argmax(-1).astype(np.int32))
    return out
