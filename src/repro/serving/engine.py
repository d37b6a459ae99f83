"""Continuous-batching serving engine — the online workload's front-end.

The production shape of the paper's online container: a slot-based decode
engine (vLLM-style continuous batching, fixed-shape for TPU):

  * a fixed pool of B decode slots over one pre-allocated KV cache,
  * every engine step runs ONE fixed-shape `decode_step` over all slots with
    *per-slot positions* (the model's decode path supports ragged positions),
  * new requests are admitted into free slots and their prompts are
    piggy-backed: while a slot is still prefilling, its input token is the
    next prompt token and its logits are discarded; once the prompt is
    consumed the slot switches to generation,
  * finished sequences retire and free their slot immediately.

Fixed shapes mean exactly one compiled program regardless of traffic — which
is what makes MuxFlow's duty-cycle throttling well-behaved on TPU (no
recompilation storms when the multiplexer squeezes offline steps between
engine steps).

Every step records four spans in ``self.phases`` (always on; each is also a
host span in a JAX profiler trace, see ``repro.obs.phases``):

  engine.admit     waiting requests into free slots
  engine.launch    slot tokens and positions to the device, and the decode
                   call (``jit_engine_decode`` in a trace) until it returns
  engine.readback  the logits to a host array: the wait for the device and
                   the copy
  engine.sample    the per-slot loop: argmax, bookkeeping, retirement

For a model with MoE layers the engine also counts, per MoE layer, over its
decode steps (``moe_counts()``; ``reset_moe_counts()`` starts them again, as
``phases.reset()`` does the spans):

  experts_hit      the distinct experts the step's tokens were routed to
                   (every slot's row runs and counts, idle ones included)
  dropped          routed slots the grouped dispatch dropped (0 by
                   construction at one token a slot)
  steps            the decode steps counted

The totals accumulate on the device, in the decode's donated state beside
the cache (``self.cache`` is then ``(model cache, totals)``); a step moves
nothing more to the host, and a model with no MoE layer decodes as before.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import init_cache
from repro.models.model import ModelConfig, forward
from repro.obs.phases import PhaseProfiler


@dataclasses.dataclass
class ServeRequest:
    request_id: int
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int
    arrival: float = 0.0
    output: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 8
    kv_capacity: int = 256
    eos_id: int | None = None
    greedy: bool = True


class ServingEngine:
    """Slot-based continuous batching over the model zoo's decode step."""

    def __init__(self, cfg: ModelConfig, params,
                 ecfg: EngineConfig = EngineConfig()):
        assert cfg.frontend == "none" and not cfg.enc_layers, \
            "engine currently serves plain decoder LMs"
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        B = ecfg.num_slots
        self.cache = init_cache(cfg, B, ecfg.kv_capacity)
        self.slot_req: list[ServeRequest | None] = [None] * B
        self.slot_pos = np.zeros(B, np.int32)       # position being written
        self.slot_prompt_left = np.zeros(B, np.int32)
        self.slot_tok = np.zeros((B, 1), np.int32)
        self.waiting: list[ServeRequest] = []
        self.finished: list[ServeRequest] = []
        self.steps = 0
        self.phases = PhaseProfiler()
        moe = cfg.repeats * sum(f == "moe" for _, f in cfg.pattern)
        self._moe_layers = moe
        if moe:
            self.cache = (self.cache, jnp.zeros((moe, 3), jnp.int32))

        def engine_decode(p, c, t, pos):
            if not moe:
                return forward(p, cfg, {"tokens": t}, mode="decode", cache=c,
                               pos=pos)
            c, totals = c
            logits, c, counts = forward(p, cfg, {"tokens": t}, mode="decode",
                                        cache=c, pos=pos, moe_counts=True)
            step = jnp.ones((moe, 1), jnp.int32)
            return logits, (c, totals + jnp.concatenate([counts, step], 1))

        # the cache is donated: each step updates it in place on the device
        self._decode = jax.jit(engine_decode, donate_argnums=(1,))

    # -- admission ----------------------------------------------------------
    def submit(self, req: ServeRequest) -> None:
        assert len(req.prompt) >= 1
        assert len(req.prompt) + req.max_new_tokens < self.ecfg.kv_capacity
        self.waiting.append(req)

    def _admit(self) -> None:
        for slot in range(self.ecfg.num_slots):
            if self.slot_req[slot] is not None or not self.waiting:
                continue
            req = self.waiting.pop(0)
            self.slot_req[slot] = req
            self.slot_pos[slot] = 0
            self.slot_prompt_left[slot] = len(req.prompt)
            self.slot_tok[slot, 0] = req.prompt[0]

    # -- stepping -----------------------------------------------------------
    def step(self) -> int:
        """Admit + one fixed-shape decode step.  Returns #active slots."""
        phase = self.phases.phase
        with phase("engine.admit"):
            self._admit()
            active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        with phase("engine.launch"):
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(self.slot_tok),
                jnp.asarray(self.slot_pos))
        self.steps += 1
        with phase("engine.readback"):
            logits = np.asarray(logits[:, :self.cfg.vocab_size])
        with phase("engine.sample"):
            self._sample(active, logits)
        return len(active)

    def _sample(self, active: list[int], logits: np.ndarray) -> None:
        for slot in active:
            req = self.slot_req[slot]
            self.slot_pos[slot] += 1
            if self.slot_prompt_left[slot] > 1:
                # still prefilling: feed the next prompt token, drop logits
                self.slot_prompt_left[slot] -= 1
                idx = len(req.prompt) - int(self.slot_prompt_left[slot])
                self.slot_tok[slot, 0] = req.prompt[idx]
                continue
            self.slot_prompt_left[slot] = 0
            nxt = int(np.argmax(logits[slot]))
            req.output.append(nxt)
            self.slot_tok[slot, 0] = nxt
            done = (len(req.output) >= req.max_new_tokens
                    or (self.ecfg.eos_id is not None
                        and nxt == self.ecfg.eos_id)
                    or self.slot_pos[slot] >= self.ecfg.kv_capacity - 1)
            if done:
                self.finished.append(req)
                self.slot_req[slot] = None
                self.slot_pos[slot] = 0

    def drain(self, max_steps: int = 100_000) -> None:
        while self.waiting or any(r is not None for r in self.slot_req):
            self.step()
            max_steps -= 1
            if max_steps <= 0:
                raise RuntimeError("engine did not drain")

    def moe_counts(self) -> dict | None:
        """The MoE counters since the last reset, per MoE layer in depth
        order: {"experts_hit", "dropped", "steps"} of int arrays; None for a
        model with no MoE layer.  Waits for the last step and copies."""
        if not self._moe_layers:
            return None
        t = np.asarray(self.cache[1])
        return {"experts_hit": t[:, 0], "dropped": t[:, 1], "steps": t[:, 2]}

    def reset_moe_counts(self) -> None:
        if self._moe_layers:
            self.cache = (self.cache[0], jnp.zeros_like(self.cache[1]))

    @property
    def active_slots(self) -> int:
        return sum(r is not None for r in self.slot_req)
