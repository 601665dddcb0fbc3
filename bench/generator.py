"""Traffic from a mix's parameter file and a seed.

One generator serves every mix under ``bench/traffic/``: a mix is data, not
code.  Its keys:

- ``loop``: ``"open"`` (sessions arrive on a schedule, ``sessions_per_s``)
  or ``"backlog"`` (no schedule: either ``queued`` requests wait for a slot
  at all times, or ``concurrent`` sessions are alive at all times, each
  offering its next turn when its last one finishes).  A finished backlog
  session is replaced at once by the next session of the pool; the window
  cycles through the pool, so a window that outlasts it serves the same
  sizes again with new prompts.
- ``prompt_tokens`` / ``output_tokens``: lognormal lengths given by
  ``median`` and ``sigma`` (of the log), clipped to ``[min, max]``.
- ``burst`` (optional, default 1): open-mix sessions arrive in bursts of
  this many at one instant, the gaps between bursts keeping the rate.
- ``followups``: the number of further turns per session, uniform in
  ``[min, max]``; ``think_s_mean``: the mean of the exponential think time
  between a turn's completion and its follow-up.
- ``pool_seed``: the sizes of every session (lengths, turns, think times,
  arrival gaps) are drawn once from this fixed seed; a run's ``--seed``
  only permutes their order and draws the prompt tokens.  So every seed
  offers the same work in another order.
- ``pool``: how many sessions a backlog mix draws (open mixes draw as many
  as the window's schedule needs).

A session's context (prompt plus every turn's output) is clipped to the
engine's ``max_len``: a prompt leaves room for one token, the last turns
are shortened, and follow-ups are dropped where less than
``output_tokens.min`` would be left.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Session:
    index: int                      # order of arrival within the run
    due_s: float                    # first turn's due time after window start
    prompt_len: int
    outputs: Tuple[int, ...]        # new tokens per turn (first, follow-ups)
    think_s: Tuple[float, ...]      # think time before each follow-up

    @property
    def context(self) -> int:
        return self.prompt_len + sum(self.outputs)


def lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def fit_envelope(prompt_len: int, outputs: List[int], max_len: int,
                 min_out: int) -> Tuple[int, ...]:
    """Shorten the turns so that prompt + outputs <= max_len, keeping the
    first turn and dropping follow-ups shorter than ``min_out``."""
    room = max_len - prompt_len
    kept = []
    for i, n in enumerate(outputs):
        n = min(n, room)
        if n < (1 if i == 0 else min_out):
            break
        kept.append(int(n))
        room -= n
    return tuple(kept)


def pool_size(traffic: dict, seconds: float) -> int:
    if traffic["loop"] == "open":
        return max(1, round(traffic["sessions_per_s"] * seconds))
    return int(traffic["pool"])


def sessions(traffic: dict, *, seed: int, seconds: float,
             max_len: int) -> List[Session]:
    """The run's sessions in arrival order.  An open mix gets the window's
    pool, ``sessions_per_s * seconds`` sessions whose gaps are scaled to
    span the window, and as many again after it, so the schedule never runs
    dry before the window closes."""
    n = pool_size(traffic, seconds)
    total = 2 * n if traffic["loop"] == "open" else n
    master = np.random.default_rng(traffic["pool_seed"])
    plen = lengths(master, traffic["prompt_tokens"], total)
    outs = lengths(master, traffic["output_tokens"],
                   total * (1 + traffic["followups"]["max"])).reshape(
                       total, -1)
    nfollow = master.integers(traffic["followups"]["min"],
                              traffic["followups"]["max"] + 1, total)
    think = master.exponential(traffic["think_s_mean"] or 1.0,
                               outs.shape) * (traffic["think_s_mean"] > 0)
    # arrivals come in bursts of `burst` sessions at one instant (1: none),
    # the gaps between bursts keeping the mean rate
    burst = int(traffic.get("burst", 1))
    units = -(-total // burst)
    in_window = -(-n // burst)
    gaps = np.zeros(units)
    if traffic["loop"] == "open":
        gaps = master.exponential(burst / traffic["sessions_per_s"], units)
        gaps[:in_window] *= seconds / gaps[:in_window].sum()
    rng = np.random.default_rng(seed_words(seed))
    # permute within the window's pool and within the spill-over, so each
    # seed offers the same set of sessions and gaps inside the window
    order = np.concatenate([rng.permutation(n), n + rng.permutation(total - n)])
    gap_order = np.concatenate([rng.permutation(in_window),
                                in_window + rng.permutation(units - in_window)])
    due = np.concatenate([[0.0], np.cumsum(gaps[gap_order])[:-1]])
    out = []
    for i, j in enumerate(order):
        n_prompt = min(int(plen[j]), max_len - 1)
        o = fit_envelope(n_prompt, list(outs[j, :1 + nfollow[j]]),
                         max_len, traffic["output_tokens"]["min"])
        out.append(Session(index=i, due_s=float(due[i // burst]),
                           prompt_len=n_prompt, outputs=o,
                           think_s=tuple(float(t) for t in
                                         think[j, 1:len(o)])))
    return out


def seed_words(seed: int) -> List[int]:
    """A seed of any size as 32-bit words (numpy and JAX take these)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def prompt_tokens(seed: int, session: int, n: int, vocab: int) -> np.ndarray:
    """The prompt of one session: uniform token ids from (seed, session)."""
    rng = np.random.default_rng(seed_words(seed) + [session, 0x70])
    return rng.integers(0, vocab, n).astype(np.int32)
