"""Batched serving loop: continuous batching over prefill + decode.

A :class:`Server` owns a params copy and a slot-based KV cache; requests
join free slots (prefill), decode steps advance all active slots together,
finished sequences free their slots.  ``serve_step`` is one fused decode
for the whole batch.  The server
is malleable the same way the trainer is: at reconfiguration points the
cache+params reshard onto the granted mesh (a serving job can donate chips
to the queue under the paper's policy).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int = 32
    out: Optional[List[int]] = None


class Server:
    def __init__(self, model, params, *, batch: int, max_len: int,
                 temperature: float = 0.0):
        self.model = model
        self.params = params
        self.batch = batch
        self.max_len = max_len
        self.temperature = temperature
        self.cache = model.init_cache(batch, max_len)
        self.pos = np.zeros(batch, np.int32)
        self.active: Dict[int, Request] = {}
        self.slot_of: Dict[int, int] = {}
        self._decode = jax.jit(model.decode_step)

    def free_slots(self) -> List[int]:
        used = set(self.slot_of.values())
        return [i for i in range(self.batch) if i not in used]

    def add(self, req: Request) -> bool:
        if len(req.prompt) > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f"exceeds the KV cache (max_len={self.max_len})")
        slots = self.free_slots()
        if not slots:
            return False
        slot = slots[0]
        self.slot_of[req.rid] = slot
        self.active[req.rid] = req
        req.out = []
        # prefill this slot by stepping the prompt (slot-local decode);
        # production path would batch prefills — sequential keeps the demo
        # simple and exact.
        for t, tok in enumerate(req.prompt[:-1]):
            self._step_slot(slot, int(tok), t)
        self.pos[slot] = len(req.prompt) - 1
        return True

    def _step_slot(self, slot: int, token: int, pos: int):
        toks = jnp.zeros((self.batch, 1), jnp.int32).at[slot, 0].set(token)
        _, self.cache = self._decode(self.params, self.cache, toks,
                                     jnp.int32(pos))

    def serve_step(self) -> Dict[int, int]:
        """One batched decode step for all active requests."""
        if not self.active:
            return {}
        toks = np.zeros((self.batch, 1), np.int32)
        for rid, req in self.active.items():
            slot = self.slot_of[rid]
            last = req.out[-1] if req.out else int(req.prompt[-1])
            toks[slot, 0] = last
        pos = int(max(self.pos[self.slot_of[r]] for r in self.active))
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks), jnp.int32(pos))
        emitted = {}
        logits = np.asarray(logits[:, -1])
        for rid, req in list(self.active.items()):
            slot = self.slot_of[rid]
            if self.temperature > 0:
                p = np.exp(logits[slot] / self.temperature)
                nxt = int(np.argmax(np.random.default_rng(rid).multinomial(
                    1, p / p.sum())))
            else:
                nxt = int(np.argmax(logits[slot]))
            req.out.append(nxt)
            self.pos[slot] += 1
            emitted[rid] = nxt
            # finish on budget, or evict when the next decode position
            # would fall outside the KV cache — the sequence ends early
            # rather than writing past max_len
            if len(req.out) >= req.max_new_tokens or \
                    self.pos[slot] >= self.max_len:
                del self.active[rid]
                del self.slot_of[rid]
        return emitted

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        queue = list(requests)
        done: Dict[int, List[int]] = {}
        while queue or self.active:
            while queue and self.add(queue[0]):
                queue.pop(0)
            before = set(self.active)
            self.serve_step()
            for rid in before - set(self.active):
                req = next(r for r in requests if r.rid == rid)
                done[rid] = req.out
        return done
