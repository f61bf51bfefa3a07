"""The one traffic generator: a mix is a data file of parameters beside
this module, and this code reads any of them.

Copied in idea from ``paddle_tpu/serving/traffic.py:synth_trace`` (seeded,
open loop, tenants that share page-aligned system prompts) and changed in
what made PR 22's overload cell unsteady: lengths and gaps are not drawn
one request at a time. For each block of ``block`` consecutive requests
the generator takes the ``block`` mid-quantiles of the stated distribution
(prompt tail, output length, gap to the next arrival) and permutes them
within the block, each of the three independently, with each tenant equally
often in a block. ``--seed`` chooses the permutations, the token ids, the
system prompts and the weights. Every seed therefore offers the same lengths
and gaps in every block, in another order: the distribution users send is
kept, its sampling noise is not.

Parameters of a mix (``chipbench/traffic/<name>.json``):

``block``            requests to a block (one batch's worth)
``tenants``          tenant populations; each has one system prompt
``prefix_tokens``    length of a tenant's system prompt (0: nothing shared)
``tail_tokens``      [lo, hi] of the unique tail after the system prompt
``output_tokens``    [lo, hi] of the answer; a request ends at its length
``dist``             "log_uniform" or "uniform", for both ranges
``arrivals``         {"kind": "poisson", "rate_per_s": r}: exponential gaps
                     at a fixed rate on the wall clock, or
                     {"kind": "backlog", "min_waiting": n}: every request is
                     due at the window's start and the next block is handed
                     over whenever fewer than n wait
``start``            "preroll_block": block 0 arrives before the window and
                     is not counted; "staggered_block": block 0 is admitted
                     in set-up with row k already k/block of the way
                     through its answer; which answer length stands at
                     which depth is the same for every seed
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Sequence, Set, Tuple

import numpy as np


@dataclasses.dataclass
class Request:
    index: int             # position in the whole stream
    block: int
    tenant: int
    prompt: np.ndarray     # int32, system prompt + tail (+ the part of the
    #                        answer a staggered start has already "served")
    max_new: int           # tokens left to generate
    due_s: float           # seconds after the stream's start (0 in a backlog)
    prompt_tokens: int     # system prompt + tail, without any staggered part
    output_tokens: int     # the whole answer's length


def mid_quantiles(dist: str, lo: float, hi: float, n: int) -> np.ndarray:
    """The n mid-quantiles ((i + 1/2) / n) of the distribution on [lo, hi],
    as whole numbers."""
    q = (np.arange(n) + 0.5) / n
    if dist == "log_uniform":
        vals = lo * (hi / lo) ** q
    elif dist == "uniform":
        vals = lo + (hi - lo) * q
    else:
        raise ValueError(f"unknown dist {dist!r}")
    return np.rint(vals).astype(np.int64)


def gap_quantiles(rate_per_s: float, n: int) -> np.ndarray:
    """The n mid-quantiles of the exponential gap, scaled so that a block's
    gaps sum to exactly n / rate."""
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    return g * (n / rate_per_s) / g.sum()


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def system_prompt(mix: Dict, seed: int, tenant: int, vocab: int) -> np.ndarray:
    return _rng(seed, 1, tenant).integers(
        3, vocab, (int(mix["prefix_tokens"]),)).astype(np.int32)


def make_block(mix: Dict, seed: int, b: int, vocab: int,
               t_start: float = 0.0) -> List[Request]:
    """Block ``b`` of the stream; ``t_start`` is when its first gap begins."""
    n = int(mix["block"])
    rng = _rng(seed, 2, b)                                  # token ids
    order = _rng(seed, 6, b)                                # who comes when
    tails = order.permutation(mid_quantiles(mix["dist"], *mix["tail_tokens"], n))
    outs = order.permutation(mid_quantiles(mix["dist"], *mix["output_tokens"], n))
    tenants = order.permutation(np.arange(n) % int(mix["tenants"]))
    if mix["arrivals"]["kind"] == "poisson":
        gaps = order.permutation(gap_quantiles(mix["arrivals"]["rate_per_s"], n))
        due = t_start + np.cumsum(gaps)
    else:
        due = np.zeros(n)
    reqs = []
    for i in range(n):
        tail = rng.integers(3, vocab, (int(tails[i]),)).astype(np.int32)
        if mix["prefix_tokens"]:
            prompt = np.concatenate(
                [system_prompt(mix, seed, int(tenants[i]), vocab), tail])
        else:
            prompt = tail
        reqs.append(Request(index=b * n + i, block=b, tenant=int(tenants[i]),
                            prompt=prompt, max_new=int(outs[i]),
                            due_s=float(due[i]), prompt_tokens=prompt.size,
                            output_tokens=int(outs[i])))
    return reqs


def stagger(reqs: Sequence[Request], seed: int, vocab: int) -> List[Request]:
    """The staggered first block: row k has k/n of its answer behind it. That
    share is appended to its prompt as seeded tokens, to be prefilled in
    set-up, and only the rest is left to generate. Which request is row k
    follows from the rank of its answer's length by one fixed shuffle, so
    every seed opens its window on the same depths and the same total
    context, whatever order its block came in."""
    n = len(reqs)
    rng = _rng(seed, 3)
    by_length = sorted(reqs, key=lambda r: (r.output_tokens, r.prompt_tokens))
    rows = [by_length[j] for j in np.random.default_rng([7, n]).permutation(n)]
    out = []
    for k, r in enumerate(rows):
        pre = min((r.output_tokens * k) // n, r.output_tokens - 1)
        served = rng.integers(3, vocab, (pre,)).astype(np.int32)
        out.append(dataclasses.replace(
            r, prompt=np.concatenate([r.prompt, served]),
            max_new=r.output_tokens - pre))
    return out


def stream(mix: Dict, seed: int, vocab: int) -> Iterator[List[Request]]:
    """Blocks 0, 1, 2, ... without end; arrival times run on from block to
    block."""
    b, t = 0, 0.0
    while True:
        reqs = make_block(mix, seed, b, vocab, t_start=t)
        if mix["arrivals"]["kind"] == "poisson":
            t = reqs[-1].due_s
        yield reqs
        b += 1


# ---- which chunk programs a mix can reach ----
# The engine compiles one chunked-prefill program for each pair of context
# bucket and chunk width. Its two rounding rules (inference/predictor.py
# prefill_dispatch, serving/paged_cache.py ctx_cap_pages) are repeated here
# so that set-up can meet every pair the window can; the counter
# engine.programs_first_met_in_window says whether they still agree.

def chunk_pairs(seq_tokens: int, shared_tokens: int, page: int, chunk: int,
                max_len: int) -> List[Tuple[int, int]]:
    """(context pages bucketed up to a power of two, chunk width in tokens)
    of every chunk that prefills ``seq_tokens`` after ``shared_tokens``."""
    pages_per_seq = -(-max_len // page)
    done, pairs = shared_tokens, []
    while done < seq_tokens:
        remaining = seq_tokens - done
        width = min(-(-remaining // page) * page, chunk)
        n_pages = -(-done // page)
        cap = 0 if n_pages <= 0 else min(1 << (n_pages - 1).bit_length(),
                                         pages_per_seq)
        pairs.append((cap, width))
        done += min(remaining, width)
    return pairs


def covering_tails(mix: Dict, engine: Dict, shared: bool) -> List[int]:
    """Tail lengths, within the mix's range, whose prefills between them meet
    every (context bucket, width) pair that a request of this mix can reach:
    with its system prompt served from the cache (``shared``) or not."""
    lo, hi = mix["tail_tokens"]
    prefix = int(mix["prefix_tokens"])
    page, chunk = engine["page_size"], engine["prefill_chunk"]
    have = prefix if shared else 0
    need: Set[Tuple[int, int]] = set()
    per_tail = {}
    for t in range(int(lo), int(hi) + 1):
        per_tail[t] = set(chunk_pairs(prefix + t, have, page, chunk,
                                      engine["max_len"]))
        need |= per_tail[t]
    chosen = []
    while need:
        t = max(per_tail, key=lambda t: (len(per_tail[t] & need), -t))
        chosen.append(t)
        need -= per_tail[t]
    return chosen


def block_totals(reqs: Sequence[Request]) -> Dict[str, float]:
    """What a block offers, whatever its order: the numbers two seeds must
    agree on."""
    return {"prompt_tokens": int(sum(r.prompt_tokens for r in reqs)),
            "output_tokens": int(sum(r.output_tokens for r in reqs)),
            "span_s": float(max(r.due_s for r in reqs)
                            - min(r.due_s for r in reqs))}
