"""Serving: batched greedy decoding with tiered KV caches.

``build_decode_step`` produces the jit-able one-token step the dry-run
lowers for ``decode_32k`` / ``long_500k``.  The engine below drives it for
real batches (prefill = scanned decode, which works uniformly across the
attention / hybrid / xlstm cache families) and integrates the Unimem
runtime: params and the KV cache are registered as data objects by size
only (jit owns the buffers), so the runtime profiles and plans them but
moves none of their bytes.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models import lm


def build_decode_step(cfg: ArchConfig, sample: str = "greedy") -> Callable:
    """Returns decode_step(params, cache, token, pos) ->
    (next_token, logits, cache)."""

    def decode_step(params, cache, token, pos):
        logits, cache = lm.decode_step(params, cfg, cache, token, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, cache

    return decode_step


@dataclasses.dataclass
class ServeStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0


class ServeEngine:
    """Minimal batched serving loop (greedy) on the Runtime API v2.

    With a ``runtime`` (a v2 :class:`~repro.core.session.Session` /
    ``UnimemRuntime``), the engine is a serving *front-end*: params and the
    KV cache are registered as runtime data objects (sizes only — jit owns
    the buffers), every ``generate`` call is one runtime iteration, and
    prefill/decode run as instrumented phases, so the runtime profiles the
    cache traffic and plans tier placement across calls.  ``tenant`` scopes
    all of it to a tenant namespace (``rt.tenant(tenant, ...)``): object
    and phase names carry the ``tenant/`` prefix, so one runtime can host
    many engines — one per request stream — and the bandwidth-partition
    policy splits the fast tier between them by the (priority, slo)
    contract.  ``runtime=None`` keeps the plain jit loop, untouched."""

    def __init__(self, cfg: ArchConfig, params: Any, *, max_seq: int,
                 batch: int, runtime=None, tenant: Optional[str] = None,
                 priority: float = 1.0, slo: float = 1.0):
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.batch = batch
        self.runtime = runtime
        self._ns = None       # registration namespace: tenant handle or rt
        self._registered = False
        if runtime is not None:
            self._ns = (runtime.tenant(tenant, priority=priority, slo=slo)
                        if tenant else runtime)
        self.step = jax.jit(build_decode_step(cfg))
        self.stats = ServeStats()

    # ------------------------------------------------------------------
    def _register(self, cache: Any) -> None:
        if self._ns is None or self._registered:
            return
        self._ns.register("params", self.params, manage_payload=False,
                          pinned=True)
        self._ns.register("kv_cache", cache, manage_payload=False,
                          chunkable=True)
        self._registered = True

    def _phase(self, name: str):
        return (contextlib.nullcontext() if self._ns is None
                else self._ns.phase(name))

    def generate(self, prompts: jax.Array, n_new: int) -> jax.Array:
        """prompts: (B, P) int32.  Returns (B, P + n_new)."""
        B, P = prompts.shape
        assert B == self.batch
        cache = lm.init_cache(self.cfg, B, self.max_seq)
        self._register(cache)
        with (self.runtime.iteration() if self.runtime is not None
              else contextlib.nullcontext()):
            tok = prompts[:, 0]
            out = [prompts]
            # prefill by scanned decode (uniform across cache families).
            # Each phase ends on block_until_ready of its last output, so
            # the runtime times the device's work, not its enqueue.
            with self._phase("prefill"):
                for i in range(P):
                    nxt, _, cache = self.step(self.params, cache,
                                              prompts[:, i], jnp.int32(i))
                    self.stats.prefill_tokens += B
                jax.block_until_ready((nxt, cache))
            tok = nxt
            gen = []
            with self._phase("decode"):
                for j in range(n_new):
                    gen.append(tok[:, None])
                    nxt, _, cache = self.step(self.params, cache, tok,
                                              jnp.int32(P + j))
                    tok = nxt
                    self.stats.decode_tokens += B
                jax.block_until_ready((tok, cache))
            return jnp.concatenate(out + gen, axis=1)
