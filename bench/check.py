"""Decide ``correct``: served tokens against the plain float32 reference.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, the longest among them.  The reference runs once over each
prompt followed by its served tokens and gives the float32 logits at every
position that produced a served token.  The number compared is the widest
gap by which a served token's reference logit lies below the reference's
best logit at that position (all traffic is greedy).  A wrong symbol, layer,
cache row or sampled id moves that gap by the logits' own spread; bfloat16
rounding moves it a little.

``control=True`` reads the same gap for the token that the reference
computed in float8 ranks first at each position, in place of the served
one: the precision step below the configuration's, which the limit has to
fail.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def sample(done: Sequence[Tuple[np.ndarray, list]], seed: int,
           min_tokens: int, max_requests: int) -> List[int]:
    """Indices into ``done``: the longest request (prompt plus output),
    then others in an order drawn from the seed, until ``min_tokens``
    served tokens or ``max_requests`` requests."""
    if not done:
        return []
    sizes = [len(p) + len(o) for p, o in done]
    first = int(np.argmax(sizes))
    rng = np.random.default_rng([int(seed) >> 32, int(seed) & 0xFFFFFFFF, 2])
    rest = [int(i) for i in rng.permutation(len(done)) if i != first]
    picked, tokens = [first], len(done[first][1])
    for i in rest:
        if tokens >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(i)
        tokens += len(done[i][1])
    return picked


def widest_gap(ref, d, weights, requests, seq_len: int, out_max: int,
               *, control: bool = False) -> Tuple[float, int]:
    """``(widest gap, tokens compared)`` over ``requests``, each a
    ``(prompt, served tokens)`` pair.  Every sequence is padded to
    ``seq_len`` and every set of rows to ``out_max``, so one compiled
    program serves them all."""
    import jax.numpy as jnp
    widest, count = 0.0, 0
    for prompt, served in requests:
        P, n = len(prompt), len(served)
        if n == 0 or min(served) < 0 or max(served) >= d.vocab:
            return float("inf"), count      # no token, or one off the vocab
        toks = np.zeros(seq_len, np.int32)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        toks[:len(seq)] = seq
        rows = np.full(out_max, P - 1 + n - 1, np.int32)
        rows[:n] = np.arange(P - 1, P - 1 + n)
        logits = ref.logits_at(d, weights, toks, rows)
        if control:
            low = ref.logits_at(d, weights, toks, rows, control=True)
            pick = jnp.argmax(low[:n], axis=-1)
        else:
            pick = jnp.asarray(served, jnp.int32)
        lg = logits[:n]
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, pick[:, None], axis=-1)[:, 0]
        widest = max(widest, float(jnp.max(gap)))
        count += n
    return widest, count
