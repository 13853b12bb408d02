"""The one traffic generator: every mix is a data file under
``bench/traffic/<mix>.json`` that this module reads.

A mix describes prompts built as

    system prompt  +  one shared segment  +  a unique part,   then output

where the shared segment is one of ``segments.count`` (documents, tool
instructions, videos) chosen by a Zipf law over their ranks, or none
when the count is 0 (an unshared control mix). Arrivals are Poisson.
Lengths are normal, clipped to ``[min, max]``. The structure and lengths follow the generators of the Preble paper's Table
1 (toolbench: system 430, tool 1130+-420, question 275+-120, output
43+-16, 64 tools); the program's ``repro.data.workloads.gen_toolbench``
and ``repro.data.arrivals.poisson_arrivals`` are the originals this
copies, kept here so that the yardstick does not move with the program.

Sampling is stratified: every length, every inter-arrival gap and
every segment rank is a fixed quantile of its distribution, placed in
one fixed order (``SCHEDULE_SEED``); the segments' lengths sit on their
ranks in one fixed order too (``LAYOUT_SEED``). Arrivals come in blocks
(ramp, window, after) that each hold a fixed number of requests. The
schedule, the sizes and the arrivals of a cell are so one trace,
replayed by every run; ``seed`` draws what the requests say (their
token ids), as it draws the model's weights. Runs with different seeds
then differ by what the system does, not by a different arrival order:
on a TPU v5e, smollm-360m's 51 s window of 28 requests, in an order
each seed drew, read p90 TTFT from 1.5 s to 6.7 s over six seeds. The
price: a tail latency measured here is that of this one trace, and a
scheduling change is judged on it alone.

Token ids are random draws from the vocabulary, one span per component,
so two prompts share exactly the components they were built from.
"""

from __future__ import annotations

import json
import statistics
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Sequence

import numpy as np

from bench.seeds import numpy_rng

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
LAYOUT_SEED = 0
SCHEDULE_SEED = 1
_NORMAL = statistics.NormalDist()


@dataclass
class Prompt:
    """One generated request: prompt token ids and output length."""
    tokens: tuple
    max_new_tokens: int
    segment: int            # shared segment's rank, -1 for none


def load_mix(name: str) -> dict:
    mix = json.loads((TRAFFIC_DIR / f"{name}.json").read_text())
    if mix["arrivals"]["process"] != "poisson":
        raise ValueError(f"{name}: only Poisson arrivals are generated")
    return mix


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_lengths(spec: Dict, n: int, rng) -> np.ndarray:
    """``n`` lengths at the quantiles of N(mean, std) clipped to
    [min, max], in an order drawn from ``rng``."""
    z = np.array([_NORMAL.inv_cdf(float(q)) for q in _quantiles(n)])
    v = spec["mean"] + spec["std"] * z
    v = np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)
    return rng.permutation(v)


def stratified_ranks(count: int, zipf: float, n: int, rng) -> np.ndarray:
    """``n`` segment ranks whose histogram follows the Zipf weights as
    closely as whole counts allow (largest remainders), shuffled."""
    w = 1.0 / np.arange(1, count + 1) ** zipf
    want = w / w.sum() * n
    k = np.floor(want).astype(int)
    short = n - k.sum()
    k[np.argsort(-(want - k), kind="stable")[:short]] += 1
    return rng.permutation(np.repeat(np.arange(count), k))


def gaps(rate: float, n: int, rng) -> np.ndarray:
    """Poisson inter-arrival gaps with mean ``1/rate``: exponential
    quantiles, shuffled."""
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


class Layout:
    """A mix's shared components for one run: the system prompt and the
    segments, with token ids drawn from ``ids``."""

    def __init__(self, mix: Dict, vocab: int, ids):
        self.mix, self.vocab = mix, vocab
        seg = mix["segments"]
        self.system = tuple(ids.integers(0, vocab,
                                         mix["system_len"]).tolist())
        lens = (stratified_lengths(seg["len"], seg["count"],
                                   np.random.default_rng(LAYOUT_SEED))
                if seg["count"] else [])
        self.segments = [tuple(ids.integers(0, vocab, int(l)).tolist())
                         for l in lens]

    def prompts(self, ranks: Sequence[int], order, ids) -> List[Prompt]:
        """One prompt per entry of ``ranks`` (-1: no segment); unique
        parts and outputs stratified over them in an order from
        ``order``, token ids from ``ids``."""
        uniq = stratified_lengths(self.mix["unique_len"], len(ranks), order)
        outs = stratified_lengths(self.mix["output_len"], len(ranks), order)
        out = []
        for r, u, o in zip(ranks, uniq, outs):
            body = self.segments[r] if r >= 0 else ()
            tail = tuple(ids.integers(0, self.vocab, int(u)).tolist())
            out.append(Prompt(self.system + body + tail, int(o), int(r)))
        return out

    def draw(self, n: int, order, ids) -> List[Prompt]:
        """``n`` prompts with segment ranks stratified over the Zipf
        law."""
        seg = self.mix["segments"]
        ranks = (stratified_ranks(seg["count"], seg["zipf"], n, order)
                 if seg["count"] else np.full(n, -1))
        return self.prompts(ranks, order, ids)

    def fill(self, order, ids) -> List[Prompt]:
        """One prompt per segment, the least popular first: what a cache
        that has served the mix for long holds, the most used most
        recently."""
        return self.prompts(np.arange(len(self.segments))[::-1], order,
                            ids)


@dataclass
class Stream:
    """A run's traffic: ``fill`` prompts that warm the prefix cache
    before the ramp, then ``timed`` prompts with due times in seconds
    from the start of the ramp."""
    fill: List[Prompt]
    timed: List[Prompt]
    due: np.ndarray


def stream(mix: Dict, *, rate: float, blocks: Sequence[float], vocab: int,
           max_context: int, seed: int) -> Stream:
    """The cache warm-up prompts (one per segment), then consecutive
    blocks of arrivals (the ramp, the window, what follows it). A block
    of ``L`` seconds holds exactly ``round(rate * L)`` arrivals whose
    gaps are the stratified Poisson gaps scaled to fill it,
    and prompts stratified on their own. The schedule is the same for
    every seed; the token ids come from ``seed``."""
    order, ids = np.random.default_rng(SCHEDULE_SEED), numpy_rng(seed)
    lay = Layout(mix, vocab, ids)
    fill = lay.fill(order, ids)
    timed, due, t = [], [], 0.0
    for length in blocks:
        n = int(round(rate * length))
        if n == 0:
            t += length
            continue
        g = gaps(rate, n, order)
        g *= length / g.sum()
        due.extend(t + np.concatenate([[0.0], np.cumsum(g)[:-1]]))
        timed.extend(lay.draw(n, order, ids))
        t += length
    longest = max(len(p.tokens) + p.max_new_tokens for p in fill + timed)
    if longest > max_context:
        raise ValueError(f"a {mix['name']} request needs {longest} "
                         f"positions, more than max_context {max_context}")
    return Stream(fill, timed, np.asarray(due))


def length_stats(ps: Sequence[Prompt]) -> Dict[str, float]:
    """Mean and standard deviation of prompt and output lengths."""
    pl = np.array([len(p.tokens) for p in ps], float)
    ol = np.array([p.max_new_tokens for p in ps], float)
    return {"prompt_mean": float(pl.mean()), "prompt_std": float(pl.std()),
            "output_mean": float(ol.mean()), "output_std": float(ol.std())}
