"""FamilyAdapter — the per-family seam between models and serving.

Every model family (lm / ssm / hybrid / encdec) differs in the same few
places: how a prefill chunk is built and absorbed into the KV cache, how
a batched decode step is invoked, which cache keys hold the stacked
attention KV, and which pieces of a prefill output are persisted by the
HCache save path. Before this module those differences lived as
``model.kind == ...`` switches scattered through ``serving/engine.py``,
``models/model.py`` and ``core/hcache.py``; they now live here, one
class per family, so the engine and the manager are family-agnostic
(DESIGN.md §11).

The adapter deliberately does NOT import ``repro.serving``: the serving
seam methods are duck-typed over the engine's ``SequenceState`` and the
backend's ``CacheView`` handle (same convention as ``core/capacity.py``),
so models stay importable without the serving stack.

Capability flags
----------------
``chunkable``           the prompt may be split into SplitFuse chunks
                        (attention-history models only: a chunk attends
                        over the already-written prefix via ``hist_kv``;
                        ssm/hybrid compute their recurrent states in one
                        scan and have no state carry-in, so their prefill
                        must stay unchunked — see the regression test in
                        tests/test_encdec_engine.py);
``supports_resume``     a paused/stored session can resume by prefilling
                        new tokens on top of restored state (lm: prefill
                        with ``hist_kv``; encdec: decoder prefill with
                        restored self-KV history + cross state from the
                        view). ssm/hybrid resume would restart recurrent
                        states from zero, so they are not preemptable;
``supports_paged``      the block-table paged KV backend applies;
``supports_recompute``  the restoration scheduler may assign recompute-
                        from-tokens (undefined for interleaved-recurrent
                        and enc-dec stacks);
``kv_names``            (k, v) cache keys of the stacked attention KV;
``n_state_blobs``       whole-object state blobs in the restore graph;
``has_cross``           restoration includes the encoder-side tasks
                        (``io_enc`` read + ``project_cross`` compute).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.trace import PREFILL_GATHER, span
from repro.distributed import tp as tp_lib


class FamilyAdapter:
    kind: str = "?"
    chunkable: bool = False
    supports_resume: bool = False
    supports_paged: bool = False
    supports_recompute: bool = False
    kv_names: Optional[Tuple[str, str]] = None
    n_state_blobs: int = 0
    has_cross: bool = False
    # prefill programs traced so far (a family whose prefill runs eagerly
    # builds none)
    prefill_traces: int = 0

    def __init__(self, model):
        self.model = model

    # ------------------------------------------------------- model compute
    def init(self, rng):
        raise NotImplementedError

    def forward(self, params, batch, *, skip_logits=False):
        raise NotImplementedError

    def prefill(self, params, batch, *, capture_hidden=False,
                hist_kv=None, hist_len=None):
        raise NotImplementedError

    def decode_step_full(self, params, cache, tokens):
        """(logits, new cache, per-layer hidden states)."""
        raise NotImplementedError

    def decode_step_paged(self, params, cache, tokens):
        raise NotImplementedError(
            f"paged decode requires an lm-family model; "
            f"{self.model.cfg.name} is {self.kind!r}")

    def restore_kv_from_hidden(self, params, hidden, *, positions):
        raise ValueError(f"{self.model.cfg.name}: attention-free arch; use "
                         "restore_ssm_states (ssm-rescan)")

    def restore_ssm_states(self, params, hidden):
        raise ValueError(f"{self.model.cfg.name}: no SSM states")

    # -------------------------------------------------- serving: prefill
    def prefill_chunk(self, params, seq, chunk, hist, *, capture_hidden):
        """Run one prefill chunk for a resident sequence. ``chunk`` is a
        1-D token array, ``hist`` the tokens already in the sequence's
        ``CacheView`` (restored history + earlier chunks)."""
        raise NotImplementedError

    def absorb_prefill(self, view, out, n, hist) -> None:
        """Map a prefill output's cache pieces to ``CacheView`` writes
        (``n`` chunk tokens landing at offset ``hist``). The caller owns
        ``view.set_length``."""
        raise NotImplementedError

    def decode_hidden(self, hidden):
        """The (L, B, 1, D) hidden stack to persist from a decode step's
        raw hidden output."""
        return hidden

    # ------------------------------------------------ serving: save naming
    def kv_row(self, li: int) -> int:
        """Stacked-KV row of global layer ``li`` (the row order sinks,
        snapshots and prefill outputs share)."""
        return li

    def prefill_hidden(self, out, li: int) -> np.ndarray:
        """Layer ``li``'s saved hidden states (S, D) from a B=1 prefill
        output."""
        return np.asarray(out["hidden"][li][0])

    def prefill_kv(self, out, li: int):
        """Layer ``li``'s (k, v) from a B=1 prefill output, (S, Kv, hd)."""
        idx = self.kv_row(li)
        return (np.asarray(out["kv"][0][idx][0]),
                np.asarray(out["kv"][1][idx][0]))


# ------------------------------------------------------------------- lm
class LMAdapter(FamilyAdapter):
    kind = "lm"
    chunkable = True
    supports_resume = True
    supports_paged = True
    supports_recompute = True
    kv_names = ("k", "v")

    def __init__(self, model):
        super().__init__(model)
        from repro.models import transformer as tfm
        h = model.h

        def program(params, tokens, patches, hist_kv, *, capture_hidden,
                    hist_len, tp):
            # runs on a trace only, so it counts programs, not calls;
            # ``tp`` keys the cache: the seams read the active context
            # while tracing
            self.prefill_traces += 1
            return tfm.lm_forward(params, tokens, h, patch_embeds=patches,
                                  hist_kv=hist_kv, hist_len=hist_len,
                                  capture_hidden=capture_hidden,
                                  emit_kv=True, final_logits_only=True)

        # params are an argument, never a constant baked into the program
        self._prefill = jax.jit(program, static_argnames=(
            "capture_hidden", "hist_len", "tp"))

    def init(self, rng):
        from repro.models import transformer as tfm
        return tfm.init_lm(rng, self.model.h)

    def forward(self, params, batch, *, skip_logits=False):
        from repro.models import transformer as tfm
        return tfm.lm_forward(params, batch["tokens"], self.model.h,
                              patch_embeds=batch.get("patches"),
                              skip_logits=skip_logits)

    def prefill(self, params, batch, *, capture_hidden=False,
                hist_kv=None, hist_len=None):
        """One compiled program per (shapes, ``capture_hidden``,
        ``hist_len``, active TP context)."""
        return self._prefill(
            params, batch["tokens"], batch.get("patches"), hist_kv,
            capture_hidden=bool(capture_hidden),
            hist_len=None if hist_len is None else int(hist_len),
            tp=tp_lib.active())

    def decode_step_full(self, params, cache, tokens):
        from repro.models import transformer as tfm
        return tfm.lm_decode_step(params, cache, tokens, self.model.h)

    def decode_step_paged(self, params, cache, tokens):
        from repro.models import transformer as tfm
        return tfm.lm_decode_step_paged(params, cache, tokens, self.model.h)

    def restore_kv_from_hidden(self, params, hidden, *, positions):
        from repro.models import transformer as tfm
        return tfm.lm_restore_kv(params, hidden, self.model.h,
                                 positions=positions)

    def prefill_chunk(self, params, seq, chunk, hist, *, capture_hidden):
        hist_kv = None
        if hist:
            with span(PREFILL_GATHER):
                hist_kv = seq.view.gather_hist(hist)
        batch = {"tokens": jnp.asarray(chunk, jnp.int32)[None]}
        return self.prefill(params, batch, capture_hidden=capture_hidden,
                            hist_kv=hist_kv,
                            hist_len=hist if hist_kv is not None else None)

    def absorb_prefill(self, view, out, n, hist):
        k, v = out["kv"]
        view.write_kv(k, v, hist)

    def kv_row(self, li):
        from repro.config.arch import BlockKind
        return [i for i, bk in enumerate(self.model.cfg.block_kinds())
                if bk == BlockKind.ATTENTION].index(li)


# ------------------------------------------------------------------ ssm
class SSMAdapter(FamilyAdapter):
    kind = "ssm"
    n_state_blobs = 1
    kv_names = None

    def init(self, rng):
        from repro.models import ssm as ssm_mod
        return ssm_mod.init_ssm_lm(rng, self.model.h)

    def forward(self, params, batch, *, skip_logits=False):
        from repro.models import ssm as ssm_mod
        return ssm_mod.ssm_forward(params, batch["tokens"], self.model.h,
                                   skip_logits=skip_logits)

    def prefill(self, params, batch, *, capture_hidden=False,
                hist_kv=None, hist_len=None):
        from repro.models import ssm as ssm_mod
        return ssm_mod.ssm_forward(params, batch["tokens"], self.model.h,
                                   capture_hidden=capture_hidden,
                                   emit_state=True, final_logits_only=True)

    def decode_step_full(self, params, cache, tokens):
        from repro.models import ssm as ssm_mod
        return ssm_mod.ssm_decode_step(params, cache, tokens, self.model.h)

    def restore_ssm_states(self, params, hidden):
        from repro.models import ssm as ssm_mod
        return ssm_mod.ssm_restore_states(params, hidden, self.model.h)

    def prefill_chunk(self, params, seq, chunk, hist, *, capture_hidden):
        return self.prefill(
            params, {"tokens": jnp.asarray(chunk, jnp.int32)[None]},
            capture_hidden=capture_hidden)

    def absorb_prefill(self, view, out, n, hist):
        conv, ssmst = out["states"]
        view.write_states({"conv": conv, "ssm": ssmst})

    def prefill_kv(self, out, li):
        raise ValueError(f"{self.model.cfg.name}: attention-free arch "
                         "has no KV to persist")


# --------------------------------------------------------------- hybrid
class HybridAdapter(FamilyAdapter):
    kind = "hybrid"
    # NOT chunkable: hybrid_forward computes every mamba layer's conv/ssm
    # state in one scan over the full chunk with no state carry-in — a
    # second chunk would restart the recurrence from zero. The whole
    # prompt must prefill in one engine step (regression-tested).
    kv_names = ("attn_k", "attn_v")
    n_state_blobs = 1

    def init(self, rng):
        from repro.models import hybrid
        return hybrid.init_hybrid(rng, self.model.h)

    def forward(self, params, batch, *, skip_logits=False):
        from repro.models import hybrid
        return hybrid.hybrid_forward(params, batch["tokens"], self.model.h,
                                     skip_logits=skip_logits)

    def prefill(self, params, batch, *, capture_hidden=False,
                hist_kv=None, hist_len=None):
        from repro.models import hybrid
        return hybrid.hybrid_forward(params, batch["tokens"], self.model.h,
                                     capture_hidden=capture_hidden,
                                     emit_state=True, final_logits_only=True)

    def decode_step_full(self, params, cache, tokens):
        from repro.models import hybrid
        return hybrid.hybrid_decode_step(params, cache, tokens, self.model.h)

    def restore_kv_from_hidden(self, params, hidden, *, positions):
        from repro.models import hybrid
        return hybrid.hybrid_restore_attn_kv(params, hidden, self.model.h,
                                             positions=positions)

    def restore_ssm_states(self, params, hidden):
        from repro.models import hybrid
        return hybrid.hybrid_restore_mamba_states(params, hidden,
                                                  self.model.h)

    def prefill_chunk(self, params, seq, chunk, hist, *, capture_hidden):
        return self.prefill(
            params, {"tokens": jnp.asarray(chunk, jnp.int32)[None]},
            capture_hidden=capture_hidden)

    def absorb_prefill(self, view, out, n, hist):
        k, v = out["kv"]
        view.write_kv(k, v, hist)
        conv, ssmst = out["mamba_states"]
        view.write_states({"conv": conv, "ssm": ssmst})

    def decode_hidden(self, hidden):
        return hidden[1]                       # (mamba_hidden, attn_hidden)

    def kv_row(self, li):
        return li // self.model.h.k

    def prefill_hidden(self, out, li):
        return np.asarray(out["attn_hidden"][self.kv_row(li)][0])


# --------------------------------------------------------------- encdec
class EncDecAdapter(FamilyAdapter):
    kind = "encdec"
    # chunkable: the encoder pass and the cross-KV projection run once,
    # on the FIRST chunk of a residency (hist == 0); later chunks — and
    # resume / multi-round prefill — attend over the self-KV history and
    # the cross state already sitting in the view (the hist > 0 path
    # below), so a long decoder prompt no longer monopolizes an engine
    # step: it interleaves with the decode batch like the LM family.
    chunkable = True
    supports_resume = True
    # the decoder self-KV region pages like an lm cache (the scatter /
    # block-table gather never touch the cross side); the paged serving
    # backend pairs the pool with whole-object cross state
    supports_paged = True
    kv_names = ("self_k", "self_v")
    has_cross = True

    def init(self, rng):
        from repro.models import encdec
        return encdec.init_encdec(rng, self.model.h)

    def forward(self, params, batch, *, skip_logits=False):
        from repro.models import encdec
        enc_out, _ = encdec.encode(params, batch["frames"], self.model.h)
        return encdec.decode_prefill(params, batch["tokens"], enc_out,
                                     self.model.h, skip_logits=skip_logits)

    def prefill(self, params, batch, *, capture_hidden=False,
                hist_kv=None, hist_len=None):
        from repro.models import encdec
        enc_out, enc_hidden = encdec.encode(params, batch["frames"],
                                            self.model.h,
                                            capture_hidden=capture_hidden)
        out = encdec.decode_prefill(params, batch["tokens"], enc_out,
                                    self.model.h,
                                    capture_hidden=capture_hidden,
                                    emit_kv=True, final_logits_only=True)
        out["enc_out"] = enc_out
        out["enc_hidden"] = enc_hidden
        return out

    def decode_step_full(self, params, cache, tokens):
        from repro.models import encdec
        return encdec.decode_step(params, cache, tokens, self.model.h)

    def decode_step_paged(self, params, cache, tokens):
        from repro.models import encdec
        return encdec.decode_step_paged(params, cache, tokens,
                                        self.model.h)

    def restore_kv_from_hidden(self, params, hidden, *, positions):
        from repro.models import encdec
        return encdec.restore_self_kv(params, hidden, self.model.h,
                                      positions=positions)

    def prefill_chunk(self, params, seq, chunk, hist, *, capture_hidden):
        from repro.models import encdec
        toks = jnp.asarray(chunk, jnp.int32)[None]
        if hist:
            # resume / round-N prefill: no encoder pass — self-attention
            # history and the cross state come from the slot's view
            with span(PREFILL_GATHER):
                hk, hv = seq.view.gather_hist(hist)
                ck, cv, _ = seq.view.cross_state()
            return encdec.decode_prefill(
                params, toks, None, self.model.h,
                capture_hidden=capture_hidden, emit_kv=True,
                final_logits_only=True, hist_kv=(hk, hv), hist_len=hist,
                cross=(ck, cv), pos_offset=hist)
        frames = seq.request.frames
        if frames is None:
            raise ValueError(
                f"enc-dec session {seq.request.session_id!r} has no stored "
                "state and no Request.frames — a first-residency whisper "
                "request must carry its encoder frame embeddings")
        frames = jnp.asarray(frames)
        if frames.ndim == 2:
            frames = frames[None]
        return self.prefill(params, {"tokens": toks, "frames": frames},
                            capture_hidden=capture_hidden)

    def absorb_prefill(self, view, out, n, hist):
        k, v = out["kv"]
        view.write_kv(k, v, hist)
        if hist == 0:
            # first residency: the cross context lands whole; on resume
            # it is already in the view (restored or never evicted)
            ck, cv = out["cross_kv"]
            view.write_states({"cross_k": ck, "cross_v": cv,
                               "enc_len": int(ck.shape[2])})


ADAPTERS = {"lm": LMAdapter, "ssm": SSMAdapter, "hybrid": HybridAdapter,
            "encdec": EncDecAdapter}


def make_adapter(model) -> FamilyAdapter:
    return ADAPTERS[model.kind](model)
