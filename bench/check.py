"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample of the sessions it served, drawn from
the seed, with the longest among them and as many as possible with a
follow-up turn (a resume of the suspended snapshot), is run through the
plain reference, once over each session's whole history: its prompt and
every token served in its finished turns.  At each position where the
engine served a token, the gap is the reference's best logit minus the
reference's logit of the served token: 0 where the engine chose the
reference's best, small where two logits lie within rounding of each
other, large where a position, a cache row or a restored page is wrong.
The widest gap over every served position of the sample is held to the
configuration's ``widest_logit_gap_limit``, which a single wrong token
fails, and the mean gap to its ``mean_logit_gap_limit``, which a slight
error at many positions fails (``PERF.md`` gives the readings each limit
was set from).

The control is the same reference one precision below the bfloat16 that
the configurations state, its linear layers in int8 (weights and inputs):
at the same positions of the same histories, the gap of the token it ranks
first.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import List, Optional

import numpy as np

from bench import generator

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str, required: bool = True) -> Optional[dict]:
    """The chip's published peaks; an unknown device kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if kind in table:
        return table[kind]
    if required:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS} "
                       f"(known: {sorted(table)})")
    return None


@dataclasses.dataclass
class Served:
    index: int
    prompt: np.ndarray
    served: List[int]
    resumes: int            # finished follow-up turns


def finished(win) -> List[Served]:
    """Every session with a finished turn: its prompt and the tokens of its
    finished turns, in order."""
    out = []
    for live in win.sessions:
        done = [t for t in live.turns if t.done]
        if done:
            out.append(Served(index=live.spec.index, prompt=live.prompt,
                              served=[x for t in done for x in t.tokens],
                              resumes=sum(t.index > 0 for t in done)))
    return out


def sample(sessions: List[Served], *, seed: int, n: int) -> List[Served]:
    """``n`` sessions drawn from the seed: the longest, then sessions with
    a follow-up turn, then any."""
    if not sessions:
        return []
    rng = np.random.default_rng(generator.seed_words(seed) + [0xC4EC])
    longest = max(sessions, key=lambda s: (len(s.served), s.index))
    rest = [s for s in sessions if s is not longest]
    rng.shuffle(rest)
    rest.sort(key=lambda s: s.resumes == 0)         # stable: resumed first
    return [longest] + rest[:n - 1]


def padded_len(n: int, max_len: int) -> int:
    """Histories are padded to a power of two (at least 256, at most
    ``max_len``), so the reference compiles once per length class."""
    return min(max(256, 1 << (n - 1).bit_length()), max_len)


def _history(s: Served, max_len: int):
    toks = np.concatenate([s.prompt, np.asarray(s.served, np.int32)])
    n = padded_len(len(toks), max_len)
    pad = np.zeros(n, np.int32)
    pad[:len(toks)] = toks
    nxt = np.zeros(n, np.int32)
    nxt[:len(toks) - 1] = toks[1:]
    # positions whose next token the engine served
    at = np.arange(len(s.prompt) - 1, len(toks) - 1)
    return pad, nxt, at


def _summary(gaps: List[np.ndarray]) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"positions": int(g.size),
            "mean": float(g.mean()) if g.size else float("nan"),
            "widest": float(g.max()) if g.size else float("nan"),
            "off_best": float((g > 0).mean()) if g.size else float("nan")}


def served_gaps(ref, conf, weights, sessions: List[Served],
                max_len: int) -> dict:
    """At every served position of ``sessions``: the reference's best logit
    minus its logit of the served token.  Returns their mean, the widest,
    the share above 0 and the number of positions."""
    gaps = []
    for s in sessions:
        toks, nxt, at = _history(s, max_len)
        best, _, got = ref.logit_stats(conf, weights, toks, nxt)
        gaps.append(np.asarray(best)[at] - np.asarray(got)[at])
    return _summary(gaps)


def control_gaps(ref, conf, weights, sessions: List[Served],
                 max_len: int) -> dict:
    """The same readings for the control: at every served position, the
    token the int8 reference ranks first, judged by the float32
    reference."""
    import jax.numpy as jnp
    gaps = []
    for s in sessions:
        toks, _, at = _history(s, max_len)
        _, pick, _ = ref.logit_stats(conf, weights, toks, toks,
                                     dtype=jnp.bfloat16, precision="default",
                                     quant="int8")
        best, _, got = ref.logit_stats(conf, weights, toks, np.asarray(pick))
        gaps.append(np.asarray(best)[at] - np.asarray(got)[at])
    return _summary(gaps)
