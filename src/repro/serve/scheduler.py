"""Continuous batching: requests of DIFFERENT lengths share the decode batch.

Every scheduler tick is ONE jitted masked scan over all lanes (fixed
shapes — no recompilation as requests come and go):

  * a lane in PREFILL phase streams its prompt in CHUNKS: up to
    `prefill_chunk` tokens advance through the decode path in one tick
    (lmdeploy-style `max_prefill_token_num` splitting), interleaved with
    other lanes' generation;
  * a lane in DECODE phase feeds its previously sampled token (one step);
  * a FREE lane — or a lane whose step budget for this tick is exhausted —
    is FROZEN: the scan computes its step but the cache select keeps every
    leaf of that lane bit-identical, so shorter lanes idle inside a longer
    lane's chunk without touching their KV/recurrent state.

The tick scan's trip count buckets to the next power of two (capped at
`prefill_chunk`), so a bounded set of ≤ log2(prefill_chunk)+1 executables
serves every occupancy/phase mix. Per-lane positions (models.attention
decode paths take pos as a (B,) vector) make the lane interleave possible;
lane admission is O(1) — no cache reshuffling, the stamp semantics
invalidate stale entries naturally.

The batcher rides on a `ServeEngine` residency session: a quantized
engine compiles the model's GeMV sequence into a CAPACITY
`GemvProgram` (`b_max` = lanes), and every tick is accounted against the
resident program at the tick's actual per-step occupancy
(`decode_tick_cost_s`) — `sim_time_s` is the priced DDR4 clock a traffic
simulator advances, with zero re-staging and zero recompilation as lanes
join and leave (`tick_masks()` exposes the per-step occupancy masks a
masked `GemvProgram.run(lane_mask=…)` executes).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..models.config import ModelConfig
from .engine import _CACHE_AXES, ServeEngine
from .spans import span


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list            # token ids
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # traffic bookkeeping (Poisson benchmarks): priced-clock stamps
    arrival_s: float = 0.0
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None


@dataclasses.dataclass
class _Lane:
    req: Optional[Request] = None
    pos: int = 0            # next position to write
    fed: int = 0            # prompt tokens already fed
    last_tok: int = 0

    @property
    def free(self):
        return self.req is None

    @property
    def prefilling(self):
        return self.req is not None and self.fed < len(self.req.prompt)


class ContinuousBatcher:
    """Fixed-lane continuous batching over a resident-program engine."""

    def __init__(self, cfg: ModelConfig, params, max_seq: int = 256,
                 lanes: int = 4, kv_bits: Optional[int] = None,
                 quantized: bool = False, act_bits: Optional[int] = None,
                 prefill_chunk: int = 8,
                 engine: Optional[ServeEngine] = None):
        if not isinstance(prefill_chunk, int) or prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be a positive int, got "
                f"{prefill_chunk!r}")
        if engine is None:
            engine = ServeEngine(cfg, params, max_seq=max_seq,
                                 batch_slots=lanes, quantized=quantized,
                                 act_bits=act_bits, kv_bits=kv_bits)
        self.engine = engine
        self.cfg = engine.cfg
        self.params = engine.params
        self.model = engine.model
        self.max_seq = engine.max_seq
        self.prefill_chunk = prefill_chunk
        self.lanes = [_Lane() for _ in range(engine.slots)]
        self.cache = self.model.init_cache(engine.slots, engine.max_seq)
        self._reset = jax.jit(self._reset_lane)
        self._tick_fns: dict = {}
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self.ticks = 0
        # resident-program accounting: every inner decode step is one
        # execution of the engine's capacity program at that step's lane
        # occupancy — `sim_time_s` advances by the priced DDR4 cost of
        # exactly those masked program ticks (zero when unquantized)
        self.program_ticks = 0
        self.sim_time_s = 0.0
        self.occupancy_ticks: dict = {}
        self.tokens_out = 0

    @staticmethod
    def _reset_lane(cache, lane):
        """Invalidate one lane: position stamps → −1 (masks the previous
        occupant's KV entries), recurrent states → 0. k/v payloads can stay —
        stamps gate them."""
        def walk(tree, path=()):
            if isinstance(tree, dict):
                return {k: walk(v, path + (k,)) for k, v in tree.items()}
            name = path[-1]
            lead = tree.ndim - len(_CACHE_AXES[name])
            idx = (slice(None),) * lead + (lane,)
            if name == "positions":
                return tree.at[idx].set(-1)
            if name in ("ssm", "conv"):
                return tree.at[idx].set(0)
            return tree

        return walk(cache)

    @staticmethod
    def _freeze_lanes(new_cache, old_cache, active):
        """Per-lane cache select: a lane inactive at this inner step keeps
        EVERY leaf bit-identical (KV, scales, stamps, recurrent state) —
        idling inside another lane's prefill chunk is a true no-op, even
        for ring-slot (sliding-window) caches where a scratch write would
        land in a live slot."""
        def walk(n, o, path=()):
            if isinstance(n, dict):
                return {k: walk(n[k], o[k], path + (k,)) for k in n}
            name = path[-1]
            axes = _CACHE_AXES[name]
            lead = n.ndim - len(axes)
            shape = (1,) * lead + (active.shape[0],) + (1,) * (len(axes) - 1)
            return jnp.where(active.reshape(shape), n, o)

        return walk(new_cache, old_cache)

    def _tick_fn(self, trip: int):
        """ONE jitted masked scan of `trip` decode steps: lane i feeds
        tok_buf[i, t] at position pos0[i]+t while t < steps[i] and is
        frozen after; the returned per-lane token is the argmax of the
        logits at each lane's LAST active step (its next decode token, or
        the first generated token when the step closed the prompt)."""
        if trip not in self._tick_fns:
            model, max_seq = self.model, self.max_seq

            def run(params, cache, tok_buf, pos0, steps):
                def body(carry, t):
                    cache, nxt = carry
                    active = t < steps                             # (B,)
                    tok = jnp.where(active, tok_buf[:, t], 0)
                    pos = jnp.where(active, pos0 + t, max_seq - 1)
                    logits, new_cache = model.decode_step(params, cache,
                                                          tok, pos)
                    with jax.named_scope("freeze_lanes"):
                        new_cache = self._freeze_lanes(new_cache, cache,
                                                       active)
                    with jax.named_scope("sample"):
                        sampled = jnp.argmax(logits, axis=-1).astype(
                            jnp.int32)
                        nxt = jnp.where(t == steps - 1, sampled, nxt)
                    return (new_cache, nxt), None

                (cache, nxt), _ = jax.lax.scan(
                    body, (cache, jnp.zeros_like(steps)),
                    jnp.arange(trip, dtype=jnp.int32))
                return cache, nxt

            self._tick_fns[trip] = jax.jit(run, donate_argnums=(1,))
        return self._tick_fns[trip]

    # -- API -------------------------------------------------------------------

    def submit(self, req: Request):
        """Queue a request, validating it against the cache horizon UP
        FRONT: an oversized request used to be silently truncated mid-
        prefill (marked done with an empty/partial `out`), and an empty
        prompt crashed admission with a bare IndexError."""
        if not req.prompt:
            raise ValueError(
                f"request {req.rid}: empty prompt — there is no token to "
                f"prefill and no logits to decode from")
        if req.max_new < 1:
            raise ValueError(
                f"request {req.rid}: max_new={req.max_new} must be >= 1")
        if len(req.prompt) + req.max_new > self.max_seq - 1:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)} tokens) + "
                f"max_new ({req.max_new}) exceeds the usable horizon "
                f"max_seq - 1 = {self.max_seq - 1} (the last slot is the "
                f"frozen-lane scratch); it would be truncated mid-flight")
        self.queue.append(req)

    def run(self, max_ticks: int = 10_000):
        """Tick until every request finishes or the budget expires.

        Returns finished requests PLUS any the budget starved — queued or
        still in flight — flagged `done=False` (they also stay in
        `self.queue`/lanes and keep counting in `pending`/`in_flight`), so
        a caller can tell starvation from completion instead of watching
        requests silently vanish."""
        while (self.queue or any(not l.free for l in self.lanes)):
            if self.ticks >= max_ticks:
                break
            self.tick()
        starved = [l.req for l in self.lanes if l.req is not None]
        starved += self.queue
        return self.finished + starved

    @property
    def pending(self) -> int:
        """Requests still waiting for a lane."""
        return len(self.queue)

    @property
    def in_flight(self) -> int:
        """Requests currently occupying a lane."""
        return sum(0 if l.free else 1 for l in self.lanes)

    # -- one synchronized step ---------------------------------------------------

    def _admit(self):
        for i, lane in enumerate(self.lanes):
            if lane.free and self.queue:
                req = self.queue[0]
                with span("serve.admit", rid=req.rid):
                    self.queue.pop(0)
                    lane.req, lane.pos, lane.fed = req, 0, 0
                    lane.last_tok = req.prompt[0]
                    self.cache = self._reset(self.cache, jnp.int32(i))

    def _plan_steps(self) -> list:
        """Per-lane inner-step budget for this tick: 0 free / 1 decode /
        min(prefill_chunk, remaining prompt) prefill."""
        steps = []
        for lane in self.lanes:
            if lane.free:
                steps.append(0)
            elif lane.prefilling:
                steps.append(min(self.prefill_chunk,
                                 len(lane.req.prompt) - lane.fed))
            else:
                steps.append(1)
        return steps

    def tick_masks(self, steps: Optional[list] = None) -> list:
        """(trip,) per-inner-step lane-occupancy masks of the NEXT tick —
        exactly the `lane_mask` a capacity `GemvProgram.run` executes for
        each of the tick's decode steps (step t runs the lanes with more
        than t steps budgeted)."""
        import numpy as np
        if steps is None:
            steps = self._plan_steps()
        sv = np.asarray(steps)
        return [sv > t for t in range(int(sv.max(initial=0)))]

    def _account_program(self, steps: list):
        """Advance the priced DDR4 clock by this tick's resident-program
        executions: inner step t runs the capacity program at occupancy
        = |lanes with steps > t| (the masked lanes bill zero, so the
        per-occupancy price IS the masked execution's price — reconciled
        in the traffic bench)."""
        for m in self.tick_masks(steps):
            occ = int(m.sum())
            self.program_ticks += 1
            self.occupancy_ticks[occ] = self.occupancy_ticks.get(occ, 0) + 1
            cost = self.engine.decode_tick_cost_s(occ) \
                if self.engine.decode_program is not None else None
            if cost is not None:
                self.sim_time_s += cost

    def tick(self):
        """One synchronized step, as host spans: `serve.tick` (args `trip`
        and active `lanes`) tiled by `serve.admit` (one per admitted
        request), `serve.plan`, `serve.stage` (host→device copies),
        `serve.dispatch` (the jitted call), `serve.wait` (the sampled
        tokens' `device_get`) and `serve.commit`."""
        with span("serve.tick") as tick_span:
            self._admit()
            with span("serve.plan"):
                steps = self._plan_steps()
                trip_need = max(steps)
                if trip_need == 0:
                    return              # nothing in flight, nothing queued
                # power-of-two trip bucket: ≤ log2(prefill_chunk)+1 executables
                trip = min(self.prefill_chunk,
                           1 << (trip_need - 1).bit_length())
                tick_span.set_metadata(trip=trip,
                                       lanes=sum(s > 0 for s in steps))
                self._account_program(steps)
                tok_buf, poss = self._feed(steps, trip)
            with span("serve.stage"):
                args = (jnp.asarray(tok_buf, jnp.int32),
                        jnp.asarray(poss, jnp.int32),
                        jnp.asarray(steps, jnp.int32))
            with span("serve.dispatch"):
                self.cache, nxt = self._tick_fn(trip)(self.params,
                                                      self.cache, *args)
            with span("serve.wait"):
                nxt = jax.device_get(nxt)
            with span("serve.commit"):
                self._commit(steps, nxt)
                self.ticks += 1

    def _feed(self, steps: list, trip: int) -> tuple:
        """Per-lane (trip,) token rows and start positions of this tick: a
        prompt chunk, the last sampled token, or nothing (a free lane
        writes to the frozen scratch slot)."""
        tok_buf = []
        poss = []
        for lane, s in zip(self.lanes, steps):
            if lane.free:
                tok_buf.append([0] * trip)
                poss.append(self.max_seq - 1)
            elif lane.prefilling:
                chunk = lane.req.prompt[lane.fed:lane.fed + s]
                tok_buf.append(chunk + [0] * (trip - len(chunk)))
                poss.append(lane.pos)
            else:
                tok_buf.append([lane.last_tok] + [0] * (trip - 1))
                poss.append(lane.pos)
        return tok_buf, poss

    def _commit(self, steps: list, nxt):
        """Advance every busy lane by its steps; take each lane's sampled
        token where its prompt is done, and retire finished requests."""
        for i, lane in enumerate(self.lanes):
            if lane.free:
                continue
            adv = steps[i]
            lane.pos += adv
            if lane.fed < len(lane.req.prompt):
                lane.fed += adv
                if lane.fed == len(lane.req.prompt):     # prompt done →
                    lane.last_tok = int(nxt[i])          # first sampled tok
                    lane.req.out.append(lane.last_tok)
                    self.tokens_out += 1
                    if lane.req.first_token_s is None:
                        lane.req.first_token_s = self.sim_time_s
            else:
                lane.last_tok = int(nxt[i])
                lane.req.out.append(lane.last_tok)
                self.tokens_out += 1
            if len(lane.req.out) >= lane.req.max_new:
                lane.req.done = True
                lane.req.finish_s = self.sim_time_s
                self.finished.append(lane.req)
                lane.req = None
