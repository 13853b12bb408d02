"""Decide ``correct``: compare what the timed path served with the
plain reference.

After the window, a sample of the measured requests that finished,
drawn from the seed and always holding the longest, is re-run through
the configuration's reference (``bench/references/<name>.py``), one
causal forward over each prompt with its served tokens. For every
served token the gap by which its reference logit lies below the
reference's best is read; the widest gap over the sample is compared
with the cell's limit. Every served token is greedy, so a correct
server reads a gap of rounding size, and a wrong token reads a gap of
the logits' own spread.

With ``controls`` the same pass also reads the controls: the
reference in a lower precision (``"fp8"``, ``"int8"``), whose own first
choice at each position is scored the same way. The benchmark's runs
never compute them (``bench/control.py`` does).
"""

from __future__ import annotations

import importlib
import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.seeds import numpy_rng

SEQ_BUCKET = 1024       # reference programs: one per 1024 positions
POS_BUCKET = 128        # and per 128 served tokens


def sample(flights: Sequence, seed: int, min_tokens: int) -> List:
    """The measured requests to check: the longest finished one (prompt
    plus output), then others in an order drawn from the seed until
    they hold ``min_tokens`` served tokens."""
    done = [f for f in flights if f.done is not None and not f.failed
            and f.request.output_tokens]
    if not done:
        return []
    key = lambda f: len(f.prompt.tokens) + len(f.request.output_tokens)
    first = max(done, key=key)
    rest = [f for f in done if f is not first]
    order = numpy_rng(seed ^ 0x5EED).permutation(len(rest))
    out, n = [first], len(first.request.output_tokens)
    for i in order:
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].request.output_tokens)
    return out


def _bucket(n: int, b: int) -> int:
    return max(int(math.ceil(n / b)), 1) * b


def gaps(reference: str, dims, weights, flights: Sequence,
         controls: Sequence[str] = ()) -> Dict[str, np.ndarray]:
    """Per served token of ``flights``: ``program`` = reference best
    minus the reference logit of the served token; for each of
    ``controls`` the same for that precision's own first choice.
    Requests run one after another, padded to buckets, so a few
    programs serve every length."""
    import jax
    import jax.numpy as jnp
    ref_mod = importlib.import_module(f"bench.references.{reference}")
    ref = ref_mod.compiled(dims)
    ctls = {q: ref_mod.compiled(dims, quant=q) for q in controls}
    parts = []
    cparts: Dict[str, list] = {q: [] for q in controls}
    for f in flights:
        prompt, out = list(f.prompt.tokens), list(f.request.output_tokens)
        seq = prompt + out[:-1]       # the last served token is not fed
        toks = np.zeros(_bucket(len(seq), SEQ_BUCKET), np.int32)
        toks[:len(seq)] = seq
        pos = np.full(_bucket(len(out), POS_BUCKET), len(seq) - 1, np.int32)
        pos[:len(out)] = np.arange(len(prompt) - 1, len(seq))
        served = jnp.asarray(np.array(out, np.int32))
        lg = ref(weights, toks, pos)[:len(out)]
        best = lg.max(-1)
        parts.append(best - jnp.take_along_axis(lg, served[:, None],
                                                -1)[:, 0])
        for q, ctl in ctls.items():
            pick = jnp.argmax(ctl(weights, toks, pos)[:len(out)], -1)
            cparts[q].append(best - jnp.take_along_axis(lg, pick[:, None],
                                                        -1)[:, 0])
    res = {"program": np.concatenate(jax.device_get(parts))}
    for q, cp in cparts.items():
        res[q] = np.concatenate(jax.device_get(cp))
    return res


def verdict(checks: Dict[str, Dict[str, float]]) -> bool:
    """Every compared number at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())
