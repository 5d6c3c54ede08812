"""Serving front door (DESIGN.md §14): OpenAI-compatible async API,
similarity-steered session router, engine pump, stdlib HTTP binding."""
from repro.frontend.api import SSE_DONE, FrontDoor, sse
from repro.frontend.pump import EnginePump, Overloaded, Subscription
from repro.frontend.router import (RouteDecision, RouterBusy, RouterSlot,
                                   SessionRouter, StoredSession)
from repro.frontend.server import (HttpFrontDoor, serve_engine,
                                   start_front_door)
from repro.frontend.tokenizer import ByteTokenizer, ChatTemplate

__all__ = ["ByteTokenizer", "ChatTemplate", "EnginePump", "FrontDoor",
           "HttpFrontDoor", "Overloaded", "RouteDecision", "RouterBusy",
           "RouterSlot", "SSE_DONE", "SessionRouter", "StoredSession",
           "Subscription", "serve_engine", "sse", "start_front_door"]
