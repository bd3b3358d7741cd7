"""Real serving engine: iteration-level scheduling over actual JAX models.

This is the data plane the analytic simulator abstracts: each
``ExpertServer`` wraps an architecture with a slot-based
continuous-batching cache (per-sequence positions), runs Orca-style
iterations — admit-one-prefill OR decode-all — with jitted prefill/decode
steps that update the cache in place, and measures real wall-clock latency
per token.

``calibrate`` fits the paper's latency gradients (k1, k2 — Eq. 13/14) from
engine measurements by linear regression, replacing the paper's RTX-4090
vLLM profiling with TPU/CPU-native profiling of our own engine.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_lib


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray          # prompt token ids
    max_new: int = 32
    ignore_eos: bool = False    # run to max_new whatever tokens come
    submit_time: float = 0.0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    generated: list = dataclasses.field(default_factory=list)
    slot: int = -1

    @property
    def latency_per_token(self) -> Optional[float]:
        if self.finish_time is None or not self.generated:
            return None
        return (self.finish_time - self.submit_time) / len(self.generated)


def _bucket(n: int, max_len: int) -> int:
    """The padded prompt length: the power of two from 16 that holds ``n``,
    at most ``max_len``."""
    if n > max_len:
        raise ValueError(f"prompt of {n} tokens over max_len {max_len}")
    b = 16
    while b < n:
        b *= 2
    return min(b, max_len)


def cache_batch_axes(cfg: ModelConfig, max_len: int) -> Dict[str, Optional[int]]:
    """For each cache leaf of the family, the axis that holds the slots
    (``None`` for a leaf that has none)."""
    one, two = (jax.eval_shape(lambda b=b: model_lib.init_cache(cfg, b, max_len))
                for b in (1, 2))
    return {k: next((i for i, (a, b) in enumerate(zip(one[k].shape,
                                                      two[k].shape))
                     if a != b), None) for k in one}


def make_steps(cfg: ModelConfig, max_len: int):
    """The jitted (prefill, decode) of a server: ``serve_prefill(params,
    cache, tokens, length, slot, plen)`` runs one padded prompt and writes
    whatever cache leaves the family's prefill returns into ``slot``;
    ``serve_decode(params, cache, tokens)`` steps every slot.  Both take
    the cache donated and return (next tokens, float logits, cache)."""
    axes = cache_batch_axes(cfg, max_len)

    @functools.partial(jax.jit, static_argnames=("plen",),
                       donate_argnames=("cache",))
    def serve_prefill(params, cache, tokens, length, slot, plen):
        del plen  # static: distinct bucket lengths compile separately
        logits, pc = model_lib.prefill(params, cfg, tokens[None],
                                       max_len, lengths=length[None])
        cache = dict(cache)
        for k, leaf in pc.items():
            start = [0] * leaf.ndim
            start[axes[k]] = slot
            cache[k] = jax.lax.dynamic_update_slice(
                cache[k], leaf.astype(cache[k].dtype), start)
        return jnp.argmax(logits[0]).astype(jnp.int32), logits[0], cache

    @functools.partial(jax.jit, donate_argnames=("cache",))
    def serve_decode(params, cache, tokens):
        logits, cache = model_lib.decode_step(params, cfg, cache, tokens)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits, cache

    return serve_prefill, serve_decode


class ExpertServer:
    """One edge expert: a model instance + slot-based continuous batching.

    The slots' positions are mirrored on the host (``pos``), so an
    iteration reads nothing back from the device but its next tokens.
    ``logits`` holds the last iteration's logits on the device: the
    prefilled prompt's (V,) or every slot's (slots, V)."""

    def __init__(self, name: str, cfg: ModelConfig, params, *,
                 slots: int = 4, max_len: int = 256, eos_token: int = 1):
        assert cfg.family in ("dense", "moe"), "engine serves LM families"
        self.name = name
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos = eos_token
        self.cache = model_lib.init_cache(cfg, slots, max_len)
        self.pos = np.zeros((slots,), np.int64)
        self.logits = None
        self.active: Dict[int, Request] = {}
        self.waiting: collections.deque = collections.deque()
        self.cur_tokens = np.zeros((slots,), np.int32)
        self.iteration_log: List[dict] = []  # (kind, p or total_tokens, dt)
        self._prefill_one, self._decode_all = make_steps(cfg, max_len)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        req.submit_time = req.submit_time or time.perf_counter()
        self.waiting.append(req)

    @property
    def n_running(self) -> int:
        return len(self.active)

    @property
    def n_waiting(self) -> int:
        return len(self.waiting)

    def has_work(self) -> bool:
        return bool(self.active) or bool(self.waiting)

    def _free_slot(self) -> Optional[int]:
        used = set(r.slot for r in self.active.values())
        for s in range(self.slots):
            if s not in used:
                return s
        return None

    def prefill(self, tokens: np.ndarray, slot: int):
        """Prefill one prompt into ``slot``; returns its first token on the
        device.  Set-up uses it to compile each bucket length."""
        p = len(tokens)
        plen = _bucket(p, self.max_len)
        toks = np.zeros((plen,), np.int32)
        toks[:p] = tokens
        first, self.logits, self.cache = self._prefill_one(
            self.params, self.cache, jnp.asarray(toks),
            jnp.asarray(p, jnp.int32), jnp.asarray(slot, jnp.int32),
            plen=plen)
        self.pos[slot] = p
        return first

    def step(self) -> List[Request]:
        """One engine iteration; returns finished requests."""
        finished: List[Request] = []
        slot = self._free_slot()
        if self.waiting and slot is not None:
            req = self.waiting.popleft()
            p = len(req.tokens)
            t0 = time.perf_counter()
            first = int(jax.block_until_ready(self.prefill(req.tokens, slot)))
            dt = time.perf_counter() - t0
            req.slot = slot
            req.generated.append(first)
            req.first_token_time = time.perf_counter()
            self.active[req.rid] = req
            self.cur_tokens[slot] = first
            self.iteration_log.append(
                {"kind": "prefill", "x": p, "dt": dt, "expert": self.name,
                 "rid": req.rid})
            return finished
        if self.active:
            tokens = jnp.asarray(self.cur_tokens)
            total_tokens = int(sum(self.pos[r.slot]
                                   for r in self.active.values()))
            t0 = time.perf_counter()
            nxt, self.logits, self.cache = self._decode_all(
                self.params, self.cache, tokens)
            nxt = np.asarray(jax.block_until_ready(nxt))
            dt = time.perf_counter() - t0
            self.pos += 1  # the decode step advances every slot
            self.iteration_log.append(
                {"kind": "decode", "x": total_tokens, "n": len(self.active),
                 "dt": dt, "expert": self.name})
            for rid in list(self.active):
                req = self.active[rid]
                tok = int(nxt[req.slot])
                req.generated.append(tok)
                self.cur_tokens[req.slot] = tok
                done = ((tok == self.eos and not req.ignore_eos)
                        or len(req.generated) >= req.max_new
                        or self.pos[req.slot] >= self.max_len - 1)
                if done:
                    req.finish_time = time.perf_counter()
                    finished.append(req)
                    del self.active[rid]
        return finished


def calibrate(server: ExpertServer) -> dict:
    """Fit k1 (prefill s/token) and k2 (decode s/queued-token) from the
    engine's measured iterations — Eq. 13/14 done on OUR hardware."""
    log = server.iteration_log
    pre = [(e["x"], e["dt"]) for e in log if e["kind"] == "prefill"]
    dec = [(e["x"], e["dt"]) for e in log if e["kind"] == "decode"]

    def fit(points):
        if len(points) < 2:
            return 0.0, 0.0
        x = np.array([p[0] for p in points], np.float64)
        y = np.array([p[1] for p in points], np.float64)
        A = np.stack([x, np.ones_like(x)], axis=1)
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        return float(coef[0]), float(coef[1])

    k1, b1 = fit(pre)
    k2, b2 = fit(dec)
    return {"k1": max(k1, 0.0), "k1_intercept": b1,
            "k2": max(k2, 0.0), "k2_intercept": b2,
            "n_prefill": len(pre), "n_decode": len(dec)}
