"""Unified decoder LM covering all assigned architectures.

The layer stack is compiled as a handful of lax.scan's over STACKED stage
parameters (leading "stack" axis), so XLA compiles each distinct stage body
once regardless of depth — essential for 27–81-layer full-size configs to
lower quickly in the 512-device dry-run:

  first    — leading heterogeneous layers (deepseek's first dense-FFN layer)
  stages   — the repeating pattern (e.g. ("local","global") × 21 for gemma2,
             ("mamba",)×6 per group for zamba2), one scan over repeats
  shared   — zamba2's alternating shared attention blocks, invoked once per
             pattern group from INSIDE the scan (params indexed r mod 2,
             never stacked — they are genuinely shared)
  trailing — remainder layers (zamba2: 81 = 13·6 + 3)

Three entry points, all pure functions of (params, …):
  forward(params, batch)                 → logits  [training / scoring]
  prefill(params, tokens)                → logits, cache
  decode_step(params, cache, tok, pos)   → logits, cache   [one token]
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..parallel.sharding import constrain
from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import dense, embed, ffn, lm_head, norm, softcap
from .params import ParamDef, init_params  # re-exported


# ---------------------------------------------------------------------------
# Stack plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StackPlan:
    first: int          # leading dense-FFN attn layers (deepseek)
    repeats: int        # pattern repeats in the main scan
    trailing: int       # trailing stages (same kind as pattern[0])

    @property
    def total(self):
        return self.first + self.repeats, self.trailing


def stack_plan(cfg: ModelConfig) -> StackPlan:
    first = cfg.moe.first_dense if cfg.moe else 0
    if cfg.family == "hybrid":
        groups = cfg.num_layers // cfg.shared_every
        return StackPlan(first=0, repeats=groups,
                         trailing=cfg.num_layers - groups * cfg.shared_every)
    body = cfg.num_layers - first
    assert body % len(cfg.pattern) == 0, (
        f"{cfg.name}: {body} layers not divisible by pattern "
        f"{cfg.pattern}")
    return StackPlan(first=first, repeats=body // len(cfg.pattern),
                     trailing=0)


# ---------------------------------------------------------------------------
# Parameter definitions (see params.ParamDef)
# ---------------------------------------------------------------------------

def _stk(stack, shape, axes, **kw):
    pre = ("stack",) * len(stack)
    return ParamDef(tuple(stack) + tuple(shape), pre + tuple(axes), **kw)


def _norm_defs(cfg, stack, dim=None):
    d = dim or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": _stk(stack, (d,), ("embed",), init="ones"),
                "bias": _stk(stack, (d,), ("embed",), init="zeros")}
    return {"scale": _stk(stack, (d,), ("embed",), init="zeros")}


def _attn_defs(cfg: ModelConfig, stack):
    e, a = cfg.d_model, cfg.attn
    if cfg.mla is not None:
        m = cfg.mla
        h = a.num_heads
        return {
            "wq": _stk(stack, (e, h * (m.qk_nope_head_dim + m.qk_rope_head_dim)),
                       ("embed", "heads")),
            "w_dkv": _stk(stack, (e, m.kv_lora_rank + m.qk_rope_head_dim),
                          ("embed", "lora")),
            "kv_norm": _norm_defs(
                dataclasses.replace(cfg, norm_type="rmsnorm"), stack,
                m.kv_lora_rank),
            "w_uk": _stk(stack, (m.kv_lora_rank, h * m.qk_nope_head_dim),
                         ("lora", "heads")),
            "w_uv": _stk(stack, (m.kv_lora_rank, h * m.v_head_dim),
                         ("lora", "heads")),
            "wo": _stk(stack, (h * m.v_head_dim, e), ("heads", "embed")),
        }
    d = {
        "wq": _stk(stack, (e, a.num_heads * a.head_dim), ("embed", "heads")),
        "wk": _stk(stack, (e, a.num_kv_heads * a.head_dim),
                   ("embed", "kv_heads")),
        "wv": _stk(stack, (e, a.num_kv_heads * a.head_dim),
                   ("embed", "kv_heads")),
        "wo": _stk(stack, (a.num_heads * a.head_dim, e), ("heads", "embed")),
    }
    if a.qkv_bias:
        d["bq"] = _stk(stack, (a.num_heads * a.head_dim,), ("heads",),
                       init="zeros")
        d["bk"] = _stk(stack, (a.num_kv_heads * a.head_dim,), ("kv_heads",),
                       init="zeros")
        d["bv"] = _stk(stack, (a.num_kv_heads * a.head_dim,), ("kv_heads",),
                       init="zeros")
    return d


def _ffn_defs(cfg: ModelConfig, stack, d_ff=None):
    e, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.ffn_type == "glu":
        return {"up": _stk(stack, (e, f), ("embed", "mlp")),
                "gate": _stk(stack, (e, f), ("embed", "mlp")),
                "down": _stk(stack, (f, e), ("mlp", "embed"))}
    return {"up": _stk(stack, (e, f), ("embed", "mlp")),
            "up_b": _stk(stack, (f,), ("mlp",), init="zeros"),
            "down": _stk(stack, (f, e), ("mlp", "embed")),
            "down_b": _stk(stack, (e,), ("embed",), init="zeros")}


def _moe_defs(cfg: ModelConfig, stack):
    e, mc = cfg.d_model, cfg.moe
    ex, f = mc.num_experts, mc.d_expert
    d = {
        "router": _stk(stack, (e, ex), ("embed", "experts"),
                       init="small_normal"),
        "w_up": _stk(stack, (ex, e, f), ("experts", "embed", "expert_mlp"),
                     fan_in_axes=(-2,)),
        "w_gate": _stk(stack, (ex, e, f), ("experts", "embed", "expert_mlp"),
                       fan_in_axes=(-2,)),
        "w_down": _stk(stack, (ex, f, e), ("experts", "expert_mlp", "embed"),
                       fan_in_axes=(-2,)),
    }
    shared = mc.shared_d_ff or (mc.num_shared * f if mc.num_shared else 0)
    if shared:
        d["shared_up"] = _stk(stack, (e, shared), ("embed", "mlp"))
        d["shared_gate"] = _stk(stack, (e, shared), ("embed", "mlp"))
        d["shared_down"] = _stk(stack, (shared, e), ("mlp", "embed"))
    return d


def _mamba_defs(cfg: ModelConfig, stack):
    e, s = cfg.d_model, cfg.ssm
    di, h = cfg.d_inner, cfg.ssm_heads
    conv_ch = di + 2 * s.n_groups * s.d_state
    proj_out = 2 * di + 2 * s.n_groups * s.d_state + h
    return {
        "in_proj": _stk(stack, (e, proj_out), ("embed", "inner")),
        "conv_w": _stk(stack, (s.d_conv, conv_ch), ("conv", "inner")),
        "conv_b": _stk(stack, (conv_ch,), ("inner",), init="zeros"),
        "dt_bias": _stk(stack, (h,), ("state",), init="zeros"),
        "a_log": _stk(stack, (h,), ("state",), init="arange_neg"),
        "d_skip": _stk(stack, (h,), ("state",), init="ones"),
        "out_norm": {"scale": _stk(stack, (di,), ("inner",), init="zeros")},
        "out_proj": _stk(stack, (di, e), ("inner", "embed")),
    }


def _stage_defs(cfg: ModelConfig, kind: str, stack, use_moe: bool,
                dense_d_ff: Optional[int] = None):
    if kind == "mamba":
        return {"ln": _norm_defs(cfg, stack),
                "mamba": _mamba_defs(cfg, stack)}
    d = {"ln1": _norm_defs(cfg, stack), "attn": _attn_defs(cfg, stack),
         "ln2": _norm_defs(cfg, stack)}
    if use_moe:
        d["moe"] = _moe_defs(cfg, stack)
    else:
        d["ffn"] = _ffn_defs(cfg, stack, dense_d_ff)
    if cfg.post_norms:
        d["ln1_post"] = _norm_defs(cfg, stack)
        d["ln2_post"] = _norm_defs(cfg, stack)
    return d


def param_defs(cfg: ModelConfig):
    plan = stack_plan(cfg)
    use_moe = cfg.moe is not None
    defs: dict = {}
    if cfg.input_mode == "tokens" or cfg.tie_embeddings:
        defs["embed"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                 ("vocab", "embed"))
    if plan.first:
        defs["first"] = _stage_defs(cfg, cfg.pattern[0], (plan.first,),
                                    use_moe=False,
                                    dense_d_ff=cfg.moe.first_dense_d_ff)
    defs["stages"] = {
        str(i): _stage_defs(cfg, kind, (plan.repeats,), use_moe)
        for i, kind in enumerate(cfg.pattern)}
    if cfg.num_shared_blocks:
        defs["shared"] = _stage_defs(cfg, "attn", (cfg.num_shared_blocks,),
                                     use_moe=False)
    if plan.trailing:
        defs["trailing"] = _stage_defs(cfg, cfg.pattern[0], (plan.trailing,),
                                       use_moe)
    defs["final_norm"] = _norm_defs(cfg, ())
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"))
    return defs


def param_pspecs(cfg: ModelConfig, mesh=None, rules=None):
    from ..parallel.sharding import defs_to_pspecs
    return defs_to_pspecs(param_defs(cfg), mesh, rules)


# ---------------------------------------------------------------------------
# Stage application — full-sequence
# ---------------------------------------------------------------------------

def _apply_stage(x, p, kind: str, cfg: ModelConfig, positions,
                 act_bits=None, impl=None):
    """One stage, full sequence. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "mamba":
        h, _ = ssm_mod.mamba_forward(norm(x, p["ln"], cfg.norm_type),
                                     p["mamba"], cfg, act_bits, impl)
        return x + h, aux
    window = cfg.attn.sliding_window if kind == "local" else None
    h = norm(x, p["ln1"], cfg.norm_type)
    if cfg.mla is not None:
        h = attn_mod.mla_forward(h, p["attn"], cfg.attn, cfg.mla, positions,
                                 act_bits, impl)
    else:
        h = attn_mod.gqa_forward(h, p["attn"], cfg.attn, window, positions,
                                 act_bits, impl)
    if cfg.post_norms:
        h = norm(h, p["ln1_post"], cfg.norm_type)
    x = x + h
    h = norm(x, p["ln2"], cfg.norm_type)
    if "moe" in p:
        h, aux = moe_mod.moe_ffn(h, p["moe"], cfg.moe, cfg.ffn_type,
                                 act_bits, impl)
    else:
        h = ffn(h, p["ffn"], cfg.ffn_type, act_bits, impl)
    if cfg.post_norms:
        h = norm(h, p["ln2_post"], cfg.norm_type)
    return x + h, aux


def _index_shared(shared_params, idx):
    return jax.tree_util.tree_map(
        lambda v: jax.lax.dynamic_index_in_dim(v, idx, 0, keepdims=False),
        shared_params)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    """Functional wrapper bound to a ModelConfig."""

    def __init__(self, cfg: ModelConfig, act_bits: Optional[int] = None,
                 impl=None, remat: bool = False,
                 kv_bits: Optional[int] = None, attn_impl: str = "sdpa"):
        self.cfg = cfg
        self.act_bits = act_bits
        self.impl = impl
        self.remat = remat  # checkpoint each scan body (layer-level remat)
        self.kv_bits = kv_bits  # 8 → int8 KV cache (GQA stages)
        self.attn_impl = attn_impl  # "sdpa" | "kernel" | "kernel_interpret"

    # -- embedding / head -----------------------------------------------------

    def _embed_in(self, params, batch):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        with jax.named_scope("embed"):
            if cfg.input_mode == "embeddings":
                x = batch["embeddings"].astype(dt)
            else:
                x = embed(batch["tokens"], params["embed"].astype(dt),
                          cfg.embed_scale, cfg.d_model)
        return constrain(x, "batch", "seq", "embed")

    @jax.named_scope("lm_head")
    def _logits(self, params, x):
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = jnp.einsum("...e,ve->...v", x,
                                params["embed"].astype(x.dtype))
            logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
            return constrain(logits, "batch", "seq", "vocab")
        return lm_head(x, params["lm_head"], cfg.final_softcap,
                       self.act_bits, self.impl)

    # -- full-sequence forward --------------------------------------------------

    def forward(self, params, batch):
        """batch: {"tokens" (B,S) | "embeddings" (B,S,E)} → logits (B,S,V),
        aux loss."""
        cfg, plan = self.cfg, stack_plan(self.cfg)
        x = self._embed_in(params, batch)
        s = x.shape[1]
        positions = jnp.arange(s)
        aux = jnp.zeros((), jnp.float32)
        ab, impl = self.act_bits, self.impl

        if plan.first:
            def first_body(carry, sp):
                h, a = _apply_stage(carry[0], sp, cfg.pattern[0], cfg,
                                    positions, ab, impl)
                return (h, carry[1] + a), None
            if self.remat:
                first_body = jax.checkpoint(first_body)
            (x, aux), _ = jax.lax.scan(first_body, (x, aux), params["first"])

        def body(carry, sp):
            h, a, r = carry
            for i, kind in enumerate(cfg.pattern):
                h, ai = _apply_stage(h, sp[str(i)], kind, cfg, positions,
                                     ab, impl)
                a = a + ai
            if cfg.num_shared_blocks:
                shp = _index_shared(params["shared"],
                                    r % cfg.num_shared_blocks)
                h, ai = _apply_stage(h, shp, "attn", cfg, positions, ab, impl)
                a = a + ai
            h = constrain(h, "batch", "seq", "embed")
            return (h, a, r + 1), None

        if self.remat:
            body = jax.checkpoint(body)
        (x, aux, _), _ = jax.lax.scan(body, (x, aux, jnp.int32(0)),
                                      params["stages"])

        if plan.trailing:
            def trail_body(carry, sp):
                h, a = _apply_stage(carry[0], sp, cfg.pattern[0], cfg,
                                    positions, ab, impl)
                return (h, carry[1] + a), None
            if self.remat:
                trail_body = jax.checkpoint(trail_body)
            (x, aux), _ = jax.lax.scan(trail_body, (x, aux),
                                       params["trailing"])

        x = norm(x, params["final_norm"], cfg.norm_type)
        return self._logits(params, x), aux

    # -- caches ----------------------------------------------------------------

    def _stage_cache(self, kind: str, batch: int, max_seq: int, lead):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        if kind == "mamba":
            c = ssm_mod.mamba_cache_init(batch, cfg, dt)
        elif cfg.mla is not None:
            c = attn_mod.mla_cache_init(batch, max_seq, cfg.mla, dt)
        else:
            slots = max_seq
            if kind == "local" and cfg.attn.sliding_window:
                slots = min(cfg.attn.sliding_window, max_seq)
            c = attn_mod.gqa_cache_init(batch, slots, cfg.attn, dt,
                                        self.kv_bits)
        if lead:
            c = jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(v, lead + v.shape), c)
        return c

    def init_cache(self, batch: int, max_seq: int):
        cfg, plan = self.cfg, stack_plan(self.cfg)
        cache: dict = {}
        if plan.first:
            cache["first"] = self._stage_cache(cfg.pattern[0], batch,
                                               max_seq, (plan.first,))
        cache["stages"] = {
            str(i): self._stage_cache(kind, batch, max_seq, (plan.repeats,))
            for i, kind in enumerate(cfg.pattern)}
        if cfg.num_shared_blocks:
            cache["shared"] = self._stage_cache("attn", batch, max_seq,
                                                (plan.repeats,))
        if plan.trailing:
            cache["trailing"] = self._stage_cache(cfg.pattern[0], batch,
                                                  max_seq, (plan.trailing,))
        return cache

    # -- decode ------------------------------------------------------------------

    def _apply_stage_decode(self, x, p, kind, cfg, cache, pos):
        ab, impl = self.act_bits, self.impl
        if kind == "mamba":
            h, cache = ssm_mod.mamba_decode(norm(x, p["ln"], cfg.norm_type),
                                            p["mamba"], cfg, cache, ab, impl)
            return x + h, cache
        window = cfg.attn.sliding_window if kind == "local" else None
        h = norm(x, p["ln1"], cfg.norm_type)
        if cfg.mla is not None:
            h, cache = attn_mod.mla_decode(h, p["attn"], cfg.attn, cfg.mla,
                                           cache, pos, ab, impl)
        else:
            h, cache = attn_mod.gqa_decode(h, p["attn"], cfg.attn, window,
                                           cache, pos, ab, impl,
                                           attn_impl=self.attn_impl)
        if cfg.post_norms:
            h = norm(h, p["ln1_post"], cfg.norm_type)
        x = x + h
        h = norm(x, p["ln2"], cfg.norm_type)
        if "moe" in p:
            h, _ = moe_mod.moe_ffn(h, p["moe"],
                                   dataclasses.replace(cfg.moe,
                                                       capacity_factor=2.0),
                                   cfg.ffn_type, ab, impl)
        else:
            h = ffn(h, p["ffn"], cfg.ffn_type, ab, impl)
        if cfg.post_norms:
            h = norm(h, p["ln2_post"], cfg.norm_type)
        return x + h, cache

    def decode_step(self, params, cache, inp, pos):
        """One token for the whole batch.

        inp: (B,) int tokens, or (B, E) embeddings for stubbed frontends.
        pos: scalar int32 — current position. Returns (logits (B, V), cache).
        """
        cfg, plan = self.cfg, stack_plan(self.cfg)
        dt = jnp.dtype(cfg.dtype)
        with jax.named_scope("embed"):
            if cfg.input_mode == "embeddings":
                x = inp.astype(dt)[:, None]
            else:
                x = embed(inp[:, None], params["embed"].astype(dt),
                          cfg.embed_scale, cfg.d_model)
        x = constrain(x, "batch", None, "embed")
        new_cache: dict = {}

        if plan.first:
            def fb(carry, xs):
                sp, c = xs
                h, c = self._apply_stage_decode(carry, sp, cfg.pattern[0],
                                                cfg, c, pos)
                return h, c
            x, new_cache["first"] = jax.lax.scan(
                fb, x, (params["first"], cache["first"]))

        def body(carry, xs):
            h, r = carry
            sp, c = xs
            new_c = dict(c)
            for i, kind in enumerate(cfg.pattern):
                h, new_c[str(i)] = self._apply_stage_decode(
                    h, sp[str(i)], kind, cfg, c[str(i)], pos)
            if cfg.num_shared_blocks:
                shp = _index_shared(params["shared"],
                                    r % cfg.num_shared_blocks)
                h, new_c["shared"] = self._apply_stage_decode(
                    h, shp, "attn", cfg, c["shared"], pos)
            return (h, r + 1), new_c

        stage_caches = {str(i): cache["stages"][str(i)]
                        for i in range(len(cfg.pattern))}
        if cfg.num_shared_blocks:
            stage_caches["shared"] = cache["shared"]
        (x, _), updated = jax.lax.scan(body, (x, jnp.int32(0)),
                                       (params["stages"], stage_caches))
        new_cache["stages"] = {k: updated[k] for k in updated
                               if k != "shared"}
        if cfg.num_shared_blocks:
            new_cache["shared"] = updated["shared"]

        if plan.trailing:
            def tb(carry, xs):
                sp, c = xs
                h, c = self._apply_stage_decode(carry, sp, cfg.pattern[0],
                                                cfg, c, pos)
                return h, c
            x, new_cache["trailing"] = jax.lax.scan(
                tb, x, (params["trailing"], cache["trailing"]))

        x = norm(x, params["final_norm"], cfg.norm_type)
        return self._logits(params, x)[:, 0], new_cache

    # -- prefill -------------------------------------------------------------------

    def _kv_to_cache(self, kind: str, kv, max_seq: int):
        """Full-sequence attention products → position-stamped decode cache."""
        cfg = self.cfg
        if kind == "mamba":
            return kv  # mamba_forward already returns its cache dict
        if cfg.mla is not None:
            c_kv, k_rope = kv
            b, s = c_kv.shape[:2]
            c = attn_mod.mla_cache_init(b, max_seq, cfg.mla, c_kv.dtype)
            c["c_kv"] = jax.lax.dynamic_update_slice(c["c_kv"], c_kv,
                                                     (0, 0, 0))
            c["k_rope"] = jax.lax.dynamic_update_slice(c["k_rope"], k_rope,
                                                       (0, 0, 0))
            c["positions"] = c["positions"].at[:, :s].set(jnp.arange(s))
            return c
        k, v = kv
        b, s = k.shape[:2]
        slots = max_seq
        if kind == "local" and cfg.attn.sliding_window:
            slots = min(cfg.attn.sliding_window, max_seq)
        c = attn_mod.gqa_cache_init(b, slots, cfg.attn, k.dtype,
                                    self.kv_bits)
        keep = min(s, slots)
        ps = jnp.arange(s - keep, s)
        ring = ps % slots
        if self.kv_bits == 8:
            kq, ks = attn_mod._kv_quant(k[:, s - keep:])
            vq, vs = attn_mod._kv_quant(v[:, s - keep:])
            c["k"] = c["k"].at[:, ring].set(kq)
            c["v"] = c["v"].at[:, ring].set(vq)
            c["k_scale"] = c["k_scale"].at[:, ring].set(ks)
            c["v_scale"] = c["v_scale"].at[:, ring].set(vs)
        else:
            c["k"] = c["k"].at[:, ring].set(k[:, s - keep:])
            c["v"] = c["v"].at[:, ring].set(v[:, s - keep:])
        c["positions"] = c["positions"].at[:, ring].set(ps)
        return c

    def _apply_stage_prefill(self, x, p, kind, cfg, positions, max_seq):
        """Stage forward that also emits its decode cache."""
        ab, impl = self.act_bits, self.impl
        if kind == "mamba":
            h, c = ssm_mod.mamba_forward(norm(x, p["ln"], cfg.norm_type),
                                         p["mamba"], cfg, ab, impl)
            return x + h, c
        window = cfg.attn.sliding_window if kind == "local" else None
        h = norm(x, p["ln1"], cfg.norm_type)
        if cfg.mla is not None:
            h, kv = attn_mod.mla_forward(h, p["attn"], cfg.attn, cfg.mla,
                                         positions, ab, impl, return_kv=True)
        else:
            h, kv = attn_mod.gqa_forward(h, p["attn"], cfg.attn, window,
                                         positions, ab, impl, return_kv=True)
        cache = self._kv_to_cache(kind, kv, max_seq)
        if cfg.post_norms:
            h = norm(h, p["ln1_post"], cfg.norm_type)
        x = x + h
        h = norm(x, p["ln2"], cfg.norm_type)
        if "moe" in p:
            h, _ = moe_mod.moe_ffn(h, p["moe"],
                                   dataclasses.replace(cfg.moe,
                                                       capacity_factor=2.0),
                                   cfg.ffn_type, ab, impl)
        else:
            h = ffn(h, p["ffn"], cfg.ffn_type, ab, impl)
        if cfg.post_norms:
            h = norm(h, p["ln2_post"], cfg.norm_type)
        return x + h, cache

    def prefill(self, params, batch, max_seq: int):
        """One full-sequence pass producing (last-token logits, decode cache).

        Same scan structure as forward(); each scan emits its stage caches as
        ys, which lands them already stacked in the decode-cache layout.
        """
        cfg, plan = self.cfg, stack_plan(self.cfg)
        x = self._embed_in(params, batch)
        s = x.shape[1]
        assert s <= max_seq
        positions = jnp.arange(s)
        cache: dict = {}

        if plan.first:
            def fb(h, sp):
                h, c = self._apply_stage_prefill(h, sp, cfg.pattern[0], cfg,
                                                 positions, max_seq)
                return h, c
            x, cache["first"] = jax.lax.scan(fb, x, params["first"])

        def body(carry, sp):
            h, r = carry
            cs = {}
            for i, kind in enumerate(cfg.pattern):
                h, cs[str(i)] = self._apply_stage_prefill(
                    h, sp[str(i)], kind, cfg, positions, max_seq)
            if cfg.num_shared_blocks:
                shp = _index_shared(params["shared"],
                                    r % cfg.num_shared_blocks)
                h, cs["shared"] = self._apply_stage_prefill(
                    h, shp, "attn", cfg, positions, max_seq)
            return (h, r + 1), cs

        (x, _), stage_caches = jax.lax.scan(body, (x, jnp.int32(0)),
                                            params["stages"])
        cache["stages"] = {k: v for k, v in stage_caches.items()
                           if k != "shared"}
        if cfg.num_shared_blocks:
            cache["shared"] = stage_caches["shared"]

        if plan.trailing:
            def tb(h, sp):
                h, c = self._apply_stage_prefill(h, sp, cfg.pattern[0], cfg,
                                                 positions, max_seq)
                return h, c
            x, cache["trailing"] = jax.lax.scan(tb, x, params["trailing"])

        x = norm(x, params["final_norm"], cfg.norm_type)
        logits = self._logits(params, x[:, -1:])[:, 0]
        return logits, cache
