"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the program
finished — the one with the longest transcript and others drawn from the
seed — is run through the float32 reference of its configuration. For
every served token the gap by which its reference logit lies below the
reference's best logit at that position is read; the widest gap over
the sample is compared with the configuration's limit.

The control puts the reference itself, computed in float8, in the
program's place: at the same positions it reads the gap of the token
that the float8 logits rank first (``control_gap``), and is judged
against the same limit (``control``): it has to come out not correct.
"""
from __future__ import annotations

import importlib
from typing import List, Sequence

import numpy as np


def reference(cfg: dict):
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def sample(served, n: int, seed: int) -> list:
    """The finished request with the longest transcript, then others
    drawn from the seed, ``n`` in all."""
    ok = [s for s in served if s.ok]
    if not ok:
        return []
    ok.sort(key=lambda s: len(s.transcript) + len(s.tokens), reverse=True)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xC4EC])
    rest = ok[1:]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest else []
    return [ok[0]] + [rest[int(i)] for i in pick]


def positions(s) -> tuple:
    """(full transcript, rows predicting each served token)."""
    full = np.concatenate([np.asarray(s.transcript, np.int32),
                           np.asarray(s.tokens, np.int32)])
    P = len(s.transcript)
    return full, list(range(P - 1, P - 1 + len(s.tokens)))


def served_gaps(ref_logits: Sequence[np.ndarray], picked) -> List[float]:
    out = []
    for lg, s in zip(ref_logits, picked):
        best = lg.max(axis=-1)
        got = lg[np.arange(len(s.tokens)), np.asarray(s.tokens)]
        out.append(float((best - got).max()))
    return out


def compare(cell, seed: int, served) -> dict:
    cfg = cell.cfg
    ref = reference(cfg)
    arch = ref.arch_from_config(cfg["config"])
    limit = float(cfg["check"]["max_logit_gap"])
    picked = sample(served, int(cell.mix["check_requests"]), seed)
    if not picked:
        return {"correct": False,
                "checks": [("max_logit_gap", None, limit),
                           ("requests_compared", 0, ">=1")]}
    fulls, rows = zip(*(positions(s) for s in picked))
    logits = ref.logits_at(arch, seed, fulls, rows)
    gaps = served_gaps(logits, picked)
    widest = max(gaps)
    n_tok = sum(len(s.tokens) for s in picked)
    return {"correct": bool(widest <= limit),
            "checks": [("max_logit_gap", widest, limit),
                       ("tokens_compared", n_tok, None),
                       ("longest_transcript",
                        max(len(f) for f in fulls), None)],
            "sample": (fulls, rows)}


def control(cell, seed: int, fulls, rows) -> dict:
    """The control judged as a run is: its widest gap against the cell's
    limit."""
    limit = float(cell.cfg["check"]["max_logit_gap"])
    gap = control_gap(cell.cfg, seed, fulls, rows)
    return {"correct": bool(gap <= limit),
            "checks": [("control_logit_gap", gap, limit)]}


def control_gap(cfg: dict, seed: int, fulls, rows) -> float:
    """Widest gap of the tokens the float8 reference ranks first, read on
    the float32 reference, over the same positions."""
    ref = reference(cfg)
    arch = ref.arch_from_config(cfg["config"])
    exact = ref.logits_at(arch, seed, fulls, rows)
    low = ref.logits_at(arch, seed, fulls, rows, precision="fp8")
    widest = 0.0
    for e, l in zip(exact, low):
        pick = l.argmax(axis=-1)
        widest = max(widest, float((e.max(axis=-1)
                                    - e[np.arange(len(e)), pick]).max()))
    return widest
