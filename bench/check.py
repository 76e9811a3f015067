"""The comparison that decides `correct` for a served cell.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed with the longest among them,
is run through the float32 reference (`bench.reference.model`) over its
prompt and its served tokens.  For each served token the gap is the
reference's best logit at that position minus the reference's logit of
the served token; the number compared is the widest gap.  Under greedy
decoding a served token the reference also ranks first has gap 0.

The control puts the reference itself in the program's place, computed
one precision step below the served bfloat16 (`quant`: float8 e4m3), and
reads the gap of the token that it ranks first at the same positions.
"""
from __future__ import annotations

from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from .reference import model as R


def sample(requests, k: int, seed: int) -> List:
    """The longest finished request and k - 1 others drawn from the seed."""
    done = [s for s in requests if s.tokens is not None]
    if not done:
        return []
    longest = max(done, key=lambda s: (s.gen, -s.rid))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def padded_len(conf: dict, mix: dict) -> int:
    """One sequence length for every sampled request of the cell, so the
    reference compiles once."""
    n = max(mix["prompt_buckets"]) + mix["gen_cap"]
    return -(-n // R.Q_BLOCK) * R.Q_BLOCK


def gaps(conf: dict, seed: int, seq_len: int, prompt: np.ndarray,
         served: np.ndarray, *, control: bool = False) -> Dict[str, float]:
    """Widest gap of the served tokens (and, with `control`, of the
    float8 reference's own first choices) under the float32 reference."""
    dtype = jnp.dtype(conf["dtype"])
    seq = np.zeros(seq_len, np.int32)
    full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    seq[:len(full)] = full
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(served))
    ref = R.logits(conf, seed, R.hidden(conf, seed, seq, dtype)[at], dtype)
    best = ref.max(axis=1)
    out = {"gap": float(jnp.max(best - ref[jnp.arange(len(served)),
                                           jnp.asarray(served)]))}
    if control:
        low = R.logits(conf, seed,
                       R.hidden(conf, seed, seq, dtype, quant=True)[at],
                       dtype, quant=True)
        pick = jnp.argmax(low, axis=1)
        out["control_gap"] = float(jnp.max(
            best - ref[jnp.arange(len(served)), pick]))
    return out


def compare(conf: dict, mix: dict, seed: int, picked, *,
            control: bool = False) -> Dict[str, float]:
    """Widest gaps over the sampled requests, and how many tokens."""
    L = padded_len(conf, mix)
    out = {"gap": 0.0, "tokens": 0}
    if control:
        out["control_gap"] = 0.0
    for s in picked:
        g = gaps(conf, seed, L, s.prompt, s.tokens, control=control)
        for k, v in g.items():
            out[k] = max(out[k], v)
        out["tokens"] += len(s.tokens)
    return out
