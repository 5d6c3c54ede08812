"""One general generator of chat traffic from a mix's parameters.

A mix is a JSON file under ``bench/traffic/``. Two shapes exist:

``returning``: ``sessions`` conversations are opened in set-up, each
with a first exchange (a user message and an earlier answer of
``first_answer_tokens``) whose stored history is drawn log-uniform from
``history_tokens``. In the window, arrivals are
open-loop at ``rate_per_s``; each is the next turn of a session drawn
by Zipf(``zipf_s``) popularity over a seeded ranking, among sessions
whose previous turn was due at least ``min_gap_s`` earlier and whose
transcript stays within ``max_transcript``.

``fresh``: every arrival opens a new conversation with one prompt.

Sizes (user turns, answers, prompts, histories) are the mid-stratum
quantiles of their log-uniform ranges, shuffled. Arrival times are
``round(rate * seconds)`` uniform draws over the window, sorted: a
Poisson process given its count. The schedule — arrivals, sessions and
lengths — is drawn from the mix's ``structure_seed``, the same for every
run seed: the program compiles every (chunk, history) prefill shape and
every restore length apart, so a seed that moved them would compile in
every run's set-up. The run seed draws the token contents and weights.

Alignment (``align``, a cut forced by the program's eager per-shape
prefill): answers are rounded to ``1 mod answer_align`` tokens and each
user turn or prompt is lengthened so that the stored history after
every turn is a multiple of ``align``. Every prefill chunk then starts
at a history that is a multiple of ``align``, and only the last chunk
of a turn is shorter than ``align``, by a multiple of ``answer_align``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


@dataclasses.dataclass
class Template:
    """Token overheads of the chat template around user content."""
    first: int      # first turn: header + content + end + assistant header
    next: int       # later turn: the previous answer's last token, its end,
    #                 header + content + end + assistant header


@dataclasses.dataclass
class Turn:
    due_s: float                 # seconds after the window opens
    session: int                 # -1 for a fresh conversation
    user_tokens: int             # content tokens of the user message
    answer_tokens: int
    history: int                 # stored history before the turn
    prompt_end: int              # transcript length when the answer starts


@dataclasses.dataclass
class Session:
    first_user_tokens: int
    first_answer_tokens: int
    history: int                 # stored history after the first turn


@dataclasses.dataclass
class Schedule:
    kind: str
    sessions: List[Session]
    turns: List[Turn]
    align: int

    def prefill_shapes(self):
        """Sorted distinct (chunk, history) pairs of the window's prefill
        chunks."""
        out = set()
        for lo, hi in ((t.history, t.prompt_end) for t in self.turns):
            h = lo
            while h < hi:
                c = min(self.align, hi - h)
                out.add((c, h))
                h += c
        return sorted(out)


def _stratified(rng, lo: float, hi: float, n: int) -> np.ndarray:
    """The n log-uniform quantiles on [lo, hi] at the middle of n
    equal-probability strata, in random order: the same sizes for every
    seed."""
    u = (np.arange(n) + 0.5) / max(n, 1)
    x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    return rng.permutation(x)


def _round_answer(a: float, lo: int, hi: int, step: int) -> int:
    """Nearest ``1 mod step`` length within [lo, hi] (rounded bounds)."""
    lo_q = step * math.ceil((lo - 1) / step) + 1
    hi_q = step * math.ceil((hi - 1) / step) + 1
    q = step * round((a - 1) / step) + 1
    return int(min(max(q, lo_q), hi_q))


def _pad_to(x: int, align: int, residue: int = 0) -> int:
    """Smallest y >= x with y % align == residue % align."""
    return x + (residue - x) % align


def make_schedule(mix: dict, seconds: float,
                  template: Template) -> Schedule:
    rng = np.random.default_rng([int(mix["structure_seed"]), 0x7A11])
    align = int(mix["align"])
    step = int(mix["answer_align"])
    n = int(round(float(mix["rate_per_s"]) * seconds))
    dues = np.sort(rng.uniform(0.0, seconds, n))
    a_lo, a_hi = mix["answer_tokens"]
    answers = [_round_answer(a, a_lo, a_hi, step)
               for a in _stratified(rng, a_lo, a_hi, n)]
    kind = mix["kind"]
    if kind == "fresh":
        p_lo, p_hi = mix["prompt_tokens"]
        turns = []
        for due, p, a in zip(dues, _stratified(rng, p_lo, p_hi, n), answers):
            prompt = _pad_to(max(int(p), template.first + 1), align)
            turns.append(Turn(float(due), -1, prompt - template.first, a,
                              0, prompt))
        return Schedule(kind, [], turns, align)
    if kind != "returning":
        raise ValueError(f"unknown traffic kind {kind!r}")
    n_s = int(mix["sessions"])
    h_lo, h_hi = mix["history_tokens"]
    a0 = int(mix["first_answer_tokens"])
    if (a0 - 1) % step:
        raise ValueError("first_answer_tokens must be 1 mod answer_align")
    sessions = []
    for h in _stratified(rng, h_lo, h_hi, n_s):
        hist = max(align * round(h / align), align)
        prompt = hist - a0 + 1
        sessions.append(Session(prompt - template.first, a0, hist))
    u_lo, u_hi = mix["user_tokens"]
    users = _stratified(rng, u_lo, u_hi, n)
    rank = rng.permutation(n_s)                 # session -> popularity rank
    weight = 1.0 / (rank + 1.0) ** float(mix["zipf_s"])
    gap = float(mix["min_gap_s"])
    cap = int(mix["max_transcript"])
    last = np.full(n_s, -np.inf)
    hist = np.array([s.history for s in sessions])
    turns = []
    for due, u, a in zip(dues, users, answers):
        # lengthen the user turn so the stored history after the answer
        # (prompt end + answer - 1) is a multiple of ``align``
        def grown(s):
            ut = _pad_to(int(u), align, -(template.next + a - 1))
            return ut, hist[s] + template.next + ut + a - 1
        ok = [s for s in range(n_s)
              if due - last[s] >= gap and grown(s)[1] <= cap]
        if not ok:
            ok = [s for s in range(n_s) if grown(s)[1] <= cap]
        if not ok:
            raise ValueError("max_transcript leaves no session to continue")
        w = weight[ok] / weight[ok].sum()
        s = int(ok[rng.choice(len(ok), p=w)])
        ut, new_hist = grown(s)
        turns.append(Turn(float(due), s, ut, a, int(hist[s]),
                          int(hist[s] + template.next + ut)))
        hist[s] = new_hist
        last[s] = due
    return Schedule(kind, sessions, turns, align)


def check_mix(mix: dict) -> None:
    """Reject a mix whose parameters the generator cannot honour."""
    need = {"kind", "entry", "align", "answer_align", "rate_per_s",
            "answer_tokens", "cap_s", "check_requests", "structure_seed"}
    if mix.get("kind") == "returning":
        need |= {"sessions", "history_tokens", "user_tokens", "zipf_s",
                 "min_gap_s", "max_transcript", "first_answer_tokens"}
    else:
        need |= {"prompt_tokens"}
    missing = need - set(mix)
    if missing:
        raise ValueError(f"traffic mix lacks {sorted(missing)}")
    if mix["align"] % mix["answer_align"]:
        raise ValueError("align must be a multiple of answer_align")
