"""The benchmark's client.

Two entries, named by the traffic mix:

``front_door``: every request goes through ``FrontDoor.handle("POST",
"/v1/chat/completions", body)`` with ``stream`` on — the handler the
HTTP binding serves — and is read the way an HTTP client reads it, one
SSE chunk per token. Tokens travel as message content lists of ids;
served ids come back as private-use codepoints (``PUA``), one per id.

``engine_pump``: requests go to the engine pump (``EnginePump.submit``),
the layer under the front door, with the tokens the router would have
submitted: the new suffix of the transcript, under the session's id.

Times are taken here, on the client side, from each request's
open-loop due time.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import Dict, List, Optional

import numpy as np

from bench.harness.traffic import Schedule, Template, Turn

PUA = 0xF0000
ENTRIES = ("front_door", "engine_pump")


def _enc(text: str, vocab: int) -> List[int]:
    return [b % vocab for b in text.encode()]


def template(vocab: int) -> Template:
    user, end, asst = (len(_enc(s, vocab)) for s in
                       ("<|user|>", "<|end|>", "<|assistant|>"))
    return Template(first=user + end + asst, next=1 + end + user + end + asst)


def render(messages: List[dict], vocab: int) -> np.ndarray:
    """The transcript the model sees: ``<|role|>content<|end|>`` per
    message, then ``<|assistant|>``."""
    out: List[int] = []
    for m in messages:
        out += _enc(f"<|{m['role']}|>", vocab)
        out += [int(t) % vocab for t in m["content"]]
        out += _enc("<|end|>", vocab)
    out += _enc("<|assistant|>", vocab)
    return np.asarray(out, np.int32)


@dataclasses.dataclass
class Served:
    turn: Turn
    due: float = 0.0                      # perf_counter when due
    sent: Optional[float] = None
    held: bool = False                    # waited for its session's answer
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: int = 0
    error: Optional[str] = None
    matched: int = -1                     # history the router restored
    transcript: Optional[np.ndarray] = None   # prompt the model saw

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.error is None
                and len(self.tokens) == self.turn.answer_tokens)


@dataclasses.dataclass
class Opened:
    """A session's first exchange, made in set-up: the user message and
    an earlier answer (ids from the seed), and the stored history."""
    user: List[int]
    answer: List[int]
    prompt: np.ndarray              # render([user])
    history: np.ndarray             # prompt + answer[:-1]: what is stored
    conv: str
    sid: Optional[str] = None       # engine session id, once opened


class Client:
    def __init__(self, entry: str, schedule: Schedule, vocab: int,
                 seed: int, *, fd=None, pump=None):
        if entry not in ENTRIES:
            raise ValueError(f"unknown entry {entry!r} (have {ENTRIES})")
        self.entry = entry
        self.fd, self.pump = fd, pump
        self.schedule = schedule
        self.vocab = vocab
        self.template = template(vocab)
        self.rng = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                          int(seed) >> 32, 0xC11E])
        n = len(schedule.sessions)
        self.messages: List[List[dict]] = [[] for _ in range(n)]
        self.conv: List[Optional[str]] = [None] * n
        self.sid: List[Optional[str]] = [None] * n
        self.last: Dict[int, asyncio.Future] = {}
        self._fresh = 0

    def _content(self, n: int) -> List[int]:
        return self.rng.integers(0, self.vocab, n).tolist()

    def openings(self) -> List[Opened]:
        """Every session's first exchange, drawn from the seed."""
        out = []
        for i, s in enumerate(self.schedule.sessions):
            user = self._content(s.first_user_tokens)
            answer = self._content(s.first_answer_tokens)
            prompt = render([{"role": "user", "content": user}], self.vocab)
            hist = np.concatenate([prompt, np.asarray(answer[:-1],
                                                      np.int32)])
            if len(hist) != s.history:
                raise RuntimeError(f"session {i}: history {len(hist)} != "
                                   f"scheduled {s.history}")
            out.append(Opened(user, answer, prompt, hist, f"conv-{i}"))
        return out

    def adopt(self, opened: List[Opened]) -> None:
        for i, o in enumerate(opened):
            self.messages[i] = [{"role": "user", "content": o.user},
                                {"role": "assistant", "content": o.answer}]
            self.conv[i] = o.conv
            self.sid[i] = o.sid

    # ------------------------------------------------------------ entries
    async def _post(self, messages, conv, served: Served) -> Optional[str]:
        body = {"messages": messages, "stream": True,
                "max_tokens": served.turn.answer_tokens}
        if conv is not None:
            body["conversation_id"] = conv
        served.transcript = render(messages, self.vocab)
        served.sent = time.perf_counter()
        status, payload = await self.fd.handle(
            "POST", "/v1/chat/completions", body)
        served.status = status
        if status != 200:
            served.error = json.dumps(payload)[:200]
            return conv
        async for chunk in payload:
            if not chunk.startswith("data: {"):
                continue
            obj = json.loads(chunk[len("data: "):])
            conv = obj.get("conversation_id", conv)
            if obj.get("hcache"):
                served.matched = int(obj["hcache"]["matched_tokens"])
            text = obj["choices"][0]["delta"].get("content")
            if text:
                now = time.perf_counter()
                for ch in text:
                    served.tokens.append(ord(ch) - PUA)
                    served.times.append(now)
        return conv

    async def _submit(self, sid: str, messages, stored: int,
                      served: Served) -> None:
        """The router's submission without the router: the transcript's
        suffix past the ``stored`` history, under ``sid``."""
        from repro.serving import Request
        full = render(messages, self.vocab)
        served.transcript = full
        served.matched = stored
        served.sent = time.perf_counter()
        sub = self.pump.submit(Request(sid, full[stored:],
                                       max_new_tokens=served.turn.answer_tokens),
                               asyncio.get_running_loop())
        served.status = 200
        async for ev in sub.events():
            if ev[0] == "token":
                served.tokens.append(int(ev[1]))
                served.times.append(time.perf_counter())

    async def turn(self, t0: float, turn: Turn) -> Served:
        sv = Served(turn, due=t0 + turn.due_s)
        # draw content in schedule order, before any waiting
        content = self._content(turn.user_tokens)
        prev = self.last.get(turn.session)
        done = asyncio.get_running_loop().create_future()
        if turn.session >= 0:
            self.last[turn.session] = done
        try:
            delay = sv.due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            if prev is not None and not prev.done():
                sv.held = True
                await asyncio.shield(prev)
            s = turn.session
            msgs = ([] if s < 0 else self.messages[s]) + [
                {"role": "user", "content": content}]
            if self.entry == "front_door":
                conv = await self._post(msgs, None if s < 0
                                        else self.conv[s], sv)
                if s >= 0:
                    self.conv[s] = conv
            else:
                if s < 0:
                    self._fresh += 1
                    sid = f"fresh-{self._fresh}"
                else:
                    sid = self.sid[s]
                await self._submit(sid, msgs, turn.history, sv)
            if s >= 0:
                self.messages[s] = msgs + [{"role": "assistant",
                                            "content": sv.tokens}]
        except Exception as e:            # noqa: BLE001 - counted as failed
            sv.error = f"{type(e).__name__}: {e}"
        finally:
            if not done.done():
                done.set_result(None)
        return sv
