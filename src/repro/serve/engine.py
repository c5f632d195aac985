"""Batched serving engine: slot-based continuous batching over the models'
cached ``decode_step``, with per-slot positions.

The engine is intentionally simple (greedy sampling, fixed slot count) —
its role in this reproduction is to exercise the cold-start path and give
the serve examples/benchmarks a real request loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.telemetry import COUNTERS, span


@dataclass
class Request:
    rid: int
    prompt: list
    max_new: int = 16
    out: list = field(default_factory=list)
    done: bool = False
    first_logits: np.ndarray | None = None   # the step that gave out[0]


class ServeEngine:
    def __init__(self, model, params, *, max_batch: int = 4, max_len: int = 128):
        self.model = model
        # resident in device memory for the engine's life: the jitted step
        # takes device arrays, so no step copies parameters (a no-op for
        # a tree ``cold_start`` already placed)
        self.params = jax.device_put(params)
        self.B = max_batch
        self.max_len = max_len
        self.state = model.init_decode_state(max_batch, max_len)
        self.pos = np.zeros(max_batch, np.int32)
        self.slot_req: list = [None] * max_batch
        self.queue: list = []
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        self.tokens = np.zeros(max_batch, np.int32)
        self.steps = 0

    def submit(self, req: Request):
        self.queue.append(req)
        COUNTERS.inc("serve.requests")

    def _admit(self):
        for slot in range(self.B):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                self.slot_req[slot] = req
                # prefill-by-decode: feed prompt tokens one by one (simple,
                # exact; bulk prefill is used by the cold-start path)
                self.pos[slot] = 0
                req._feed = list(req.prompt)
                self.tokens[slot] = req._feed.pop(0)

    def step(self):
        self._admit()
        active = [s for s in range(self.B) if self.slot_req[s] is not None]
        if not active:
            return False
        with span("repro.serve.step", first=self.steps == 0):
            logits, self.state = self._decode(
                self.params, self.state, jnp.asarray(self.tokens),
                jnp.asarray(self.pos))
            logits = np.asarray(logits)
        self.steps += 1
        for s in active:
            req = self.slot_req[s]
            self.pos[s] += 1
            if req._feed:                        # still consuming the prompt
                self.tokens[s] = req._feed.pop(0)
                continue
            row = logits[s, :self.model.cfg.vocab_size]
            if not req.out:
                req.first_logits = row
            nxt = int(np.argmax(row))
            req.out.append(nxt)
            self.tokens[s] = nxt
            if len(req.out) >= req.max_new or self.pos[s] >= self.max_len - 1:
                req.done = True
                self.slot_req[s] = None
                COUNTERS.inc("serve.completed")
        return True

    def run_until_drained(self, max_steps: int = 10_000):
        t0 = time.time()
        while (self.queue or any(self.slot_req)) and self.steps < max_steps:
            self.step()
        return {"steps": self.steps, "seconds": time.time() - t0}
