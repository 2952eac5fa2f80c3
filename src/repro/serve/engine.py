"""Continuous-batching serving engine (Orca-style iteration batching).

A fixed pool of B cache *slots*; requests are admitted into free slots as
they arrive, every engine iteration runs ONE batched decode step across
all active slots (per-slot positions — see layers.attention_block's
vmap'd cache update), and finished slots are freed immediately for the
next waiting request.  Prefill runs per-request (batch=1) and its cache
rows are spliced into the slot pool.  Retiring brings every slot's new
token and position to the host in one read, so a decode step costs one
host sync whatever the batch.

This is the serve-side analog of the paper's D-MGPU lesson: placement is
explicit — each slot's KV rows live at a fixed batch index, sharded per
sharding/specs.py, and admission never moves resident data.

Under ``jax.profiler`` each phase of ``Engine.step`` writes a host span on
the device trace's clock: ``serve.admit`` (with ``serve.prefill`` and
``serve.splice`` per admitted request inside it), ``serve.decode`` and
``serve.retire``.  The two compiled programs are named ``serve_decode`` and
``serve_prefill``, so their modules read ``jit_serve_decode`` and
``jit_serve_prefill`` in the trace.  ``stats()`` counts the work and the
host syncs; each ``Request`` carries when it was submitted and admitted.
"""
from __future__ import annotations

import dataclasses
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import api
from repro.models.base import ModelConfig


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    # filled by the engine:
    output: typing.List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    rejected: bool = False          # prompt too long for the cache
    t_submit: float = None          # time.perf_counter() at submit
    t_admit: float = None           # ... at the start of its prefill


# cache leaf -> batch axis (transformer/encdec/ssm/hybrid layouts)
_BATCH_AXIS = {"k": 1, "v": 1, "xk": 1, "xv": 1, "ssm": 1, "conv": 1,
               "ssm_tail": 1, "conv_tail": 1}
_HYBRID_AXIS = {"k": 1, "v": 1, "ssm": 2, "conv": 2,
                "ssm_tail": 1, "conv_tail": 1}


def _axis_for(cfg, key):
    table = _HYBRID_AXIS if cfg.family == "hybrid" else _BATCH_AXIS
    return table.get(key)


class Engine:
    def __init__(self, cfg: ModelConfig, params, slots: int = 4,
                 max_seq: int = 512, eos_token: int = -1) -> None:
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.eos = eos_token
        cache = api.init_cache(cfg, slots, max_seq)
        # engine-managed per-slot positions
        cache["pos"] = jnp.zeros((slots,), jnp.int32)
        self.cache = cache
        self.active: typing.Dict[int, Request] = {}      # slot -> request
        self.remaining: typing.Dict[int, int] = {}
        self.last_token = jnp.zeros((slots,), jnp.int32)
        self.queue: typing.List[Request] = []
        self._finished_early: typing.List[Request] = []
        self.steps = 0
        self.prefills = 0
        self.prefill_tokens = 0     # prompt tokens prefilled
        self.slot_steps = 0         # active slots, summed over decode steps
        self.host_syncs = 0         # device-to-host reads

        def serve_decode(p, c, t):
            return api.decode_step(p, cfg, c, t)

        def serve_prefill(p, c, b):
            return api.prefill(p, cfg, c, b)

        self._decode = jax.jit(serve_decode)
        self._prefill = jax.jit(serve_prefill)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request.  Returns False (request marked done+rejected,
        never queued) when the prompt cannot fit the cache: admitting it
        would splice/decode past row ``max_seq-1``, and jax's clamping
        ``.at[].set`` would silently corrupt the last cache row instead
        of raising."""
        req.t_submit = time.perf_counter()
        if len(req.prompt) >= self.max_seq:
            req.rejected = True
            req.done = True
            return False
        self.queue.append(req)
        return True

    def _free_slots(self) -> typing.List[int]:
        return [s for s in range(self.slots) if s not in self.active]

    def _admit(self) -> None:
        free = self._free_slots()
        if not (free and self.queue):
            return
        with TraceAnnotation("serve.admit"):
            while free and self.queue:
                req = self.queue.pop(0)
                if req.max_new_tokens <= 0:
                    # nothing to generate: complete immediately, never
                    # touch a slot (previously this pinned a slot through
                    # a decode and emitted two spurious tokens)
                    req.done = True
                    self._finished_early.append(req)
                    continue
                slot = free[0]
                S = len(req.prompt)
                with TraceAnnotation("serve.prefill", uid=req.uid,
                                     prompt_len=S):
                    req.t_admit = time.perf_counter()
                    prompt = jnp.asarray(req.prompt, jnp.int32)[None]
                    mini = api.init_cache(self.cfg, 1, self.max_seq)
                    logits, mini = self._prefill(self.params, mini,
                                                 {"tokens": prompt})
                    tok = int(jnp.argmax(logits[0]))
                self.host_syncs += 1
                self.prefills += 1
                self.prefill_tokens += S
                with TraceAnnotation("serve.splice", uid=req.uid):
                    self._splice(mini, slot, S)
                req.output.append(tok)
                if tok == self.eos or req.max_new_tokens == 1:
                    # complete at admission: the prefill token is the
                    # whole answer, so the slot stays free for the next
                    # request
                    req.done = True
                    self._finished_early.append(req)
                    continue
                free.pop(0)
                self.last_token = self.last_token.at[slot].set(tok)
                self.active[slot] = req
                self.remaining[slot] = req.max_new_tokens - 1

    def _splice(self, mini: dict, slot: int, prompt_len: int) -> None:
        """Write the batch=1 prefill cache into slot `slot`."""
        new = {}
        for key, big in self.cache.items():
            if key == "pos":
                new["pos"] = big.at[slot].set(prompt_len)
                continue
            ax = _axis_for(self.cfg, key)
            small = mini[key]
            idx = [slice(None)] * big.ndim
            idx[ax] = slice(slot, slot + 1)
            new[key] = big.at[tuple(idx)].set(small.astype(big.dtype))
        self.cache = new

    def step(self) -> typing.List[Request]:
        """One engine iteration: admit -> batched decode -> retire.
        Returns requests completed this step."""
        self._admit()
        done, self._finished_early = self._finished_early, []
        if not self.active:
            return done
        batch = len(self.active)
        with TraceAnnotation("serve.decode", batch=batch):
            logits, self.cache = self._decode(self.params, self.cache,
                                              self.last_token)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.steps += 1
        self.slot_steps += batch
        # only active slots advance; idle slots re-decode garbage rows but
        # their outputs are ignored and their pos is reset on admission
        self.last_token = next_tok
        with TraceAnnotation("serve.retire"):
            # one read for the whole batch: every slot's token and position
            toks, pos = jax.device_get((next_tok, self.cache["pos"]))
            self.host_syncs += 1
            for slot, req in list(self.active.items()):
                tok = int(toks[slot])
                req.output.append(tok)
                self.remaining[slot] -= 1
                hit_cap = pos[slot] >= self.max_seq - 1
                if tok == self.eos or self.remaining[slot] <= 0 or hit_cap:
                    req.done = True
                    done.append(req)
                    del self.active[slot]
                    del self.remaining[slot]
        return done

    def run_until_drained(self, max_steps: int = 10_000
                          ) -> typing.List[Request]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.active and not self.queue:
                break
        return out

    def stats(self) -> dict:
        """Counters since the engine was made.  ``host_syncs`` counts
        device-to-host reads: one per admitted request (its prefill token)
        and one per decode step (every slot's token and position together),
        whatever the batch."""
        return {"decode_steps": self.steps, "prefills": self.prefills,
                "prefill_tokens": self.prefill_tokens,
                "slot_steps": self.slot_steps,
                "host_syncs": self.host_syncs,
                "active": len(self.active), "queued": len(self.queue)}
