"""Serving engine: batched prefill + decode with optional bit-plane weights.

`ServeEngine` owns the jitted prefill/decode executables and a fixed-slot
request batch (continuous batching at the granularity real schedulers use:
a request occupies one batch lane until finished). `make_serve_step` /
`cache_pspecs` are the pieces the multi-pod dry-run lowers.

Decode runs under ONE MASKED jitted `jax.lax.scan` per power-of-two
length bucket (capped at the cache horizon) with the KV cache donated
(`donate_argnums`): temperature is a traced scalar and per-lane length
masks freeze finished lanes, so a bounded set of ≤ log2(max_seq)
executables (per prompt length — the prefill already compiles per S0)
serves EVERY (steps, temperature) request mix with no recompilation. The
per-token Python loop is retained behind `scan=False` as the
token-for-token oracle (tested identical at temperature 0 and for the
seeded sampling path — the scan folds the same per-step PRNG keys).

Quantized serving is a RESIDENCY SESSION: at startup every 2-D quantized
weight leaf of the model is registered into ONE `DramPool` (each matrix
gets a persistent (channel, bank, row-range) home; heterogeneous shapes
co-reside), and the block's GeMV sequence is compiled into a
`GemvProgram` whose fused wave schedule re-stages nothing across decode
steps. Decode-time linears route through `core.engine.EngineLinear`
(installed as the model's `impl`) and its GROUPED hook: the model's
q/k/v and up/gate projections call `models.layers.dense_group`, so on a
Pallas backend each concurrency group of `_CONCURRENT_LEAVES` fuses into
ONE kernel launch (`kernels/bitplane_gemv/program.py` — the kernel-side
twin of the compiled program's shared waves) instead of one launch per
weight; other backends fall back per-leaf with identical results. The
whole-block single-launch path is `GemvProgram.run_kernel` /
`Backend.run_program` — one fused Pallas launch walks every layer of the
decode block given its per-layer activations, integer-identical to the
per-leaf path — while `decode_program` / `price_decode_step()` expose
the resident-decode accounting (zero repeated weight staging) and the
sim-audit path executes against the same staged rows.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import backends
from ..core.bitplane import BitplaneWeights
from ..core.engine import EngineLinear, GemvProgram, MVDRAMEngine
from ..core.pud.residency import CapacityError
from ..core.quant import QuantSpec
from ..models.config import ModelConfig
from ..models.model import Model
from ..parallel.sharding import axis_rules, logical_to_pspec
from .quantize import quantize_params
from .spans import phase

# Independent linears of one block — they read the SAME input, so their
# tiles may share waves in the compiled decode program (q/k/v on the
# attention input, up/gate on the FFN input).
_CONCURRENT_LEAVES = (("wq", "wk", "wv"), ("up", "gate"),
                      ("shared_up", "shared_gate"))


def make_serve_step(model: Model):
    def serve_step(params, cache, inp, pos):
        return model.decode_step(params, cache, inp, pos)
    return serve_step


_CACHE_AXES = {
    "k": ("batch", "kv_seq", "kv_heads", None),
    "v": ("batch", "kv_seq", "kv_heads", None),
    "c_kv": ("batch", "kv_seq", None),
    "k_rope": ("batch", "kv_seq", None),
    "positions": ("batch", "kv_seq"),
    "conv": ("batch", None, "inner"),
    "ssm": ("batch", "inner", None, None),
    "k_scale": ("batch", "kv_seq", "kv_heads"),
    "v_scale": ("batch", "kv_seq", "kv_heads"),
}


def cache_pspecs(cache_struct, mesh=None, rules=None):
    """PartitionSpecs for a decode-cache tree (stack dims → unsharded)."""
    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        name = path[-1]
        axes = _CACHE_AXES[name]
        lead = len(tree.shape) - len(axes)
        full = ("stack",) * lead + axes
        return logical_to_pspec(full, tree.shape, mesh, rules)
    return walk(cache_struct)


@dataclasses.dataclass
class Request:
    tokens: list
    max_new: int
    done: bool = False


class ServeEngine:
    """Greedy/temperature batched generation over fixed lanes."""

    def __init__(self, cfg: ModelConfig, params, max_seq: int = 512,
                 batch_slots: int = 4, quantized: bool = False,
                 act_bits: Optional[int] = None, impl=None,
                 mesh=None, rules=None, kv_bits: Optional[int] = None,
                 dimms: int = 1, spill_tier: bool = False):
        self.cfg = cfg
        self.mesh, self.rules = mesh, rules
        self.max_seq = max_seq
        self.slots = batch_slots
        self.mvdram: Optional[MVDRAMEngine] = None
        # GemvProgram on a single pool, FabricProgram when dimms > 1 or
        # spill_tier — both price/run through the same surface
        self.decode_program: Optional[GemvProgram] = None
        # True when the model did not fit the DramPool and serving fell
        # back to the program-less jit path (surfaced in residency_stats —
        # it used to be visible only as a warning at construction)
        self.placement_fallback = False
        model_impl = impl
        if quantized:
            with phase("quantize"):
                params = quantize_params(params, cfg.weight_bits)
            # residency session: the whole model co-resides in one pool,
            # and every lane-batched quantized linear routes through the
            # engine against those resident weights. on_full="raise" so a
            # model that outgrows the pool fails placement VISIBLY (and
            # falls back to program-less serving) instead of silently
            # LRU-evicting the layers just placed.
            # `dimms > 1` serves from a multi-module DRAM fabric (layers
            # stripe across `FabricPool` members, the decode program
            # compiles per-DIMM parts that overlap); `spill_tier=True`
            # additionally lets placements that fit NO module park in the
            # CXL capacity tier and page in on demand — a model larger
            # than any single pool still gets a resident program
            if dimms > 1 or spill_tier:
                from ..core.pud.fabric import FabricPool
                self.mvdram = MVDRAMEngine(
                    pool=FabricPool(dimms=max(1, dimms)),
                    on_full="spill" if spill_tier else "raise")
            else:
                self.mvdram = MVDRAMEngine(on_full="raise")
            with phase("place"):
                self.decode_program = self._place_model(params, act_bits)
            model_impl = EngineLinear(self.mvdram,
                                      backend=backends.get_backend(impl))
        self.params = params
        self.model = Model(cfg, act_bits=act_bits if quantized else None,
                           impl=model_impl, kv_bits=kv_bits)
        self._prefill = jax.jit(partial(self.model.prefill,
                                        max_seq=max_seq))
        self._step = jax.jit(make_serve_step(self.model))
        self._decode_fns: dict = {}
        self._tick_price_cache: dict = {}

    def _place_model(self, qparams, act_bits: Optional[int]
                     ) -> Optional[GemvProgram]:
        """Register every quantized weight leaf into the engine's pool
        (phase ① — the whole model becomes co-resident, heterogeneous
        shapes included) and compile the decode step's GeMV sequence into
        one fused program. Layer-stacked leaves (the scan-stacked stages)
        unstack into one resident matrix per layer; per-expert MoE stacks
        (w_up/w_gate/w_down) serve through the vmap'd expert path and stay
        un-pooled."""
        a_spec = QuantSpec(bits=act_bits) if act_bits else None
        leaves: list = []   # (stage_path, stack_idx, leaf_name, BitplaneWeights)

        def walk(tree, path=()):
            if isinstance(tree, dict):
                for k in tree:
                    walk(tree[k], path + (str(k),))
                return
            if not isinstance(tree, BitplaneWeights):
                return
            leaf = path[-1]
            if leaf in ("w_up", "w_gate", "w_down"):   # per-expert stacks
                return
            stage = "/".join(path[:-1])
            if tree.planes.ndim == 3:
                leaves.append((stage, -1, leaf, tree))
            elif tree.planes.ndim == 4:                # layer-stacked stage
                for i in range(tree.planes.shape[0]):
                    leaves.append((stage, i, leaf, BitplaneWeights(
                        planes=tree.planes[i], scale=tree.scale[i],
                        zero=tree.zero, col_sum=tree.col_sum[i],
                        n=tree.n, spec=tree.spec)))

        walk(qparams)
        if not leaves:
            return None
        # decode order: layer-major (stage, stack index), leaves within
        leaves.sort(key=lambda e: (e[0], e[1]))
        names = []

        def place(pending):
            for stage, idx, leaf, bw in pending:
                name = f"{stage}/{leaf}" + (f"#{idx}" if idx >= 0 else "")
                self.mvdram.register_packed(name, bw, a_spec=a_spec)
                names.append(name)

        try:
            try:
                place(leaves)
            except CapacityError:
                # first-fit gaps from earlier eviction churn may add up to
                # the rows we need without a contiguous run anywhere:
                # defragment the pool (moved layers restage lazily) and
                # retry the remaining placements once
                self.mvdram.pool.compact()
                place(leaves[len(names):])
        except CapacityError as e:
            # the model genuinely does not fit the pool: roll the partial
            # residency back (silent LRU churn would evict the layers we
            # just placed and make compile fail anyway) and serve through
            # the jit path without a resident decode program. The handles
            # go too: program-less serving never reads them, and each one
            # holds its matrix's unpacked codes
            import warnings
            self.placement_fallback = True
            for name in names:
                if self.mvdram.pool.is_resident(name):
                    self.mvdram.evict(name)
                self.mvdram.handles.pop(name, None)
            warnings.warn(
                f"model does not fit the DramPool even after compaction "
                f"({len(names)}/{len(leaves)} linears placed before "
                f"capacity ran out); serving without a resident decode "
                f"program. {e}", RuntimeWarning, stacklevel=2)
            return None
        # concurrency groups: leaves of one (stage, layer) that read the
        # same input (q/k/v, up/gate) may share waves; the rest serializes
        groups, used = [], set()
        index = {(e[0], e[1], e[2]): i for i, e in enumerate(leaves)}
        for i, (stage, idx, leaf, _bw) in enumerate(leaves):
            if i in used:
                continue
            group = [i]
            for peers in _CONCURRENT_LEAVES:
                if leaf in peers:
                    group = [index[(stage, idx, p)] for p in peers
                             if (stage, idx, p) in index]
            used.update(group)
            groups.append(group)
        # CAPACITY program: every tick launches all `slots` lanes and the
        # scheduler's occupancy rides in as run(lane_mask=…) — lanes
        # join/leave across ticks with zero recompilation and re-staging
        return self.mvdram.compile(names, groups=groups, b_max=self.slots)

    def price_decode_step(self, bit_density: float = 0.5,
                          batch: Optional[int] = None) -> Optional[dict]:
        """DDR4 price of one resident decode step through the compiled
        program (zero repeated weight staging), next to the per-layer
        re-staging baseline. None for unquantized engines."""
        if self.decode_program is None:
            return None
        cost = self.decode_program.price(bit_density=bit_density,
                                         batch=batch or self.slots)
        return cost.asdict()

    def decode_tick_cost_s(self, occupancy: int,
                           bit_density: float = 0.5) -> Optional[float]:
        """Priced DDR4 seconds of ONE decode tick of the resident program
        at the given lane occupancy — what a traffic simulator advances its
        clock by per tick. Cached per occupancy (the analytic price is a
        pure function of the compiled schedule and the lane count, so a
        long Poisson horizon prices from ≤ `slots` distinct entries).
        None for unquantized engines."""
        if self.decode_program is None:
            return None
        if not isinstance(occupancy, int) or not \
                (1 <= occupancy <= self.slots):
            raise ValueError(
                f"occupancy must be an int in [1, {self.slots}] "
                f"(the compiled lane capacity), got {occupancy!r}")
        key = (occupancy, bit_density)
        if key not in self._tick_price_cache:
            cost = self.decode_program.price(bit_density=bit_density,
                                             batch=occupancy)
            self._tick_price_cache[key] = (cost.t_total, cost.e_total)
        return self._tick_price_cache[key][0]

    def decode_tick_energy_j(self, occupancy: int,
                             bit_density: float = 0.5) -> Optional[float]:
        """Priced Joules of ONE decode tick at the given lane occupancy —
        the per-command `EnergyModel` twin of `decode_tick_cost_s`,
        sharing its cache (one pricing fills both). None for unquantized
        engines."""
        if self.decode_tick_cost_s(occupancy, bit_density) is None:
            return None
        return self._tick_price_cache[(occupancy, bit_density)][1]

    def residency_stats(self) -> Optional[dict]:
        """The engine's pool/fault counters plus the serving-level fallback
        flags: `placement_fallback` (the model did not fit the pool and
        serves program-less) and `resident_program` (a compiled fused
        decode program is live). None for unquantized engines."""
        if self.mvdram is None:
            return None
        stats = self.mvdram.residency_stats()
        stats["placement_fallback"] = self.placement_fallback
        stats["resident_program"] = self.decode_program is not None
        return stats

    def _decode_scan_fn(self, trip: int):
        """ONE masked jitted scan over `trip` decode slots (a power-of-two
        length bucket, capped at the cache horizon); cache donated so XLA
        reuses the KV buffers in place across the whole generation.

        Temperature rides as a TRACED scalar and `steps_vec` carries
        per-lane length masks (a finished lane re-emits its frozen token),
        so a bounded bucket set per prompt length serves every requested
        (max_new, temperature) — the recompile-per-request-length problem
        the per-(steps, temperature) cache had is gone. Token-for-token
        identical to the Python loop oracle on every step before a lane's
        budget (tested, greedy + seeded sampling)."""
        if trip not in self._decode_fns:
            model = self.model

            def run(params, cache, cur, pos0, key0, steps_vec, temperature):
                def body(carry, t):
                    cache, cur, key = carry
                    logits, cache = model.decode_step(params, cache, cur,
                                                      pos0 + t)
                    key = jax.random.fold_in(key, t)   # same chain as loop
                    sampled = self._sample_traced(logits, temperature, key)
                    nxt = jnp.where(t < steps_vec, sampled, cur)
                    return (cache, nxt, key), nxt

                (_, _, _), out = jax.lax.scan(
                    body, (cache, cur, key0),
                    jnp.arange(trip, dtype=jnp.int32))
                return out                       # (trip, B)

            self._decode_fns[trip] = jax.jit(run, donate_argnums=(1,))
        return self._decode_fns[trip]

    def generate(self, prompts, max_new: int = 32, temperature: float = 0.0,
                 seed: int = 0, scan: bool = True,
                 max_new_per_lane=None):
        """prompts: int32 (B, S0) (B ≤ slots; right-aligned padding NOT
        supported — equal-length prompts, as in the paper's benchmark).
        Returns (B, S0 + max_new) tokens.

        `scan=True` (default) runs the single masked lax.scan with the
        cache donated; `scan=False` keeps the per-token Python loop
        (oracle — token-for-token identical, same PRNG folds).
        `max_new_per_lane` (optional (B,) ints ≤ max_new) caps lanes
        individually: a lane past its budget re-emits its last token (a
        0-budget lane its final prompt token) — the per-lane masks of the
        single-executable decode, applied identically on the loop
        oracle."""
        b, s0 = prompts.shape
        if b > self.slots:
            raise ValueError(
                f"prompts batch {b} exceeds the engine's {self.slots} "
                f"lanes (prompts shape {tuple(prompts.shape)})")
        if s0 + max_new > self.max_seq:
            raise ValueError(
                f"prompt ({s0}) + max_new ({max_new}) exceeds the cache "
                f"horizon max_seq={self.max_seq}")
        steps_vec = jnp.full((b,), max_new - 1, jnp.int32)
        budget = None
        if max_new_per_lane is not None:
            budget = jnp.asarray(max_new_per_lane, jnp.int32)
            steps_vec = jnp.minimum(budget - 1, steps_vec)
        with axis_rules(self.mesh, self.rules):
            logits, cache = self._prefill(self.params, {"tokens": prompts})
            key = jax.random.PRNGKey(seed)
            cur = self._sample(logits, temperature, key)
            if budget is not None:
                # a 0-budget lane emits no generated tokens — its columns
                # repeat the final prompt token instead
                cur = jnp.where(budget > 0, cur, prompts[:, -1])
            if scan and max_new > 1:
                # bucket the trip count to the next power of two (capped at
                # the cache horizon): a bounded set of ≤ log2(max_seq)
                # executables per prompt length, without paying the full
                # horizon scan for short generations
                trip = min(self.max_seq - s0 - 1,
                           1 << (max_new - 2).bit_length())
                rest = self._decode_scan_fn(trip)(
                    self.params, cache, cur, jnp.int32(s0), key,
                    steps_vec, jnp.float32(temperature))
                return jnp.concatenate(
                    [prompts, cur[:, None],
                     jnp.transpose(rest[:max_new - 1])], axis=1)
            toks = [prompts]
            for t in range(max_new):
                toks.append(cur[:, None])
                if t == max_new - 1:
                    break
                logits, cache = self._step(self.params, cache, cur,
                                           jnp.int32(s0 + t))
                key = jax.random.fold_in(key, t)
                # same per-lane freeze as the masked scan (oracle parity)
                cur = jnp.where(t < steps_vec,
                                self._sample(logits, temperature, key), cur)
        return jnp.concatenate(toks, axis=1)

    @staticmethod
    def _sample(logits, temperature, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature
                                      ).astype(jnp.int32)

    @staticmethod
    def _sample_traced(logits, temperature, key):
        """`_sample` with temperature as a TRACED scalar: both branches are
        computed and selected, so one executable covers greedy and sampled
        decode. Bit-identical to `_sample` for temperature == 0 (argmax)
        and > 0 (same key, same logits/temperature ratio)."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # exact divide for EVERY positive temperature (the substitute value
        # only feeds the dead greedy branch, avoiding div-by-zero)
        safe_t = jnp.where(temperature > 0.0, temperature, 1.0)
        hot = jax.random.categorical(key, logits / safe_t).astype(jnp.int32)
        return jnp.where(temperature > 0.0, hot, greedy)

    def throughput_tokens_per_s(self, b: int = 1, n: int = 16) -> float:
        """Measured decode tokens/s on the current device (a CPU figure is
        meaningful only for RELATIVE comparisons, e.g. quantized vs dense).
        The timed call blocks on its result, so this is device time plus
        dispatch, not the enqueue.

        The masked decode scans to the power-of-two bucket of `n`, so the
        wall-clock includes any frozen tail past `n` — the honest cost of
        the bucketed single-executable engine; useful tokens (b·n) stay
        the numerator."""
        import time
        prompts = jnp.zeros((b, 8), jnp.int32)
        # warm the bucket executable
        jax.block_until_ready(self.generate(prompts, max_new=n))
        t0 = time.perf_counter()
        jax.block_until_ready(self.generate(prompts, max_new=n))
        dt = time.perf_counter() - t0
        return b * n / dt
