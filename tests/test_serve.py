"""Serving: quantize transform structure, engine generation, dense-vs-
quantized agreement at 8 bits, serving-bytes accounting."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import tiny_config
from repro.core.bitplane import BitplaneWeights
from repro.models.model import Model, param_defs
from repro.models.params import init_params
from repro.serve.engine import ServeEngine
from repro.serve.quantize import (QUANT_LEAF_NAMES, init_quantized_params,
                                  quantize_defs, quantize_params,
                                  serving_bytes)

KEY = jax.random.PRNGKey(0)


def test_quantize_params_swaps_expected_leaves():
    cfg = tiny_config("llama2-7b")
    params = init_params(param_defs(cfg), KEY)
    pq = quantize_params(params, bits=4)
    stage = pq["stages"]["0"]
    assert isinstance(stage["attn"]["wq"], BitplaneWeights)
    assert isinstance(stage["ffn"]["down"], BitplaneWeights)
    assert isinstance(pq["lm_head"], BitplaneWeights)
    # norms / embeddings untouched
    assert not isinstance(stage["ln1"]["scale"], BitplaneWeights)
    assert not isinstance(pq["embed"], BitplaneWeights)
    # stacked leaves keep the stack dim on the packed planes
    assert stage["attn"]["wq"].planes.shape[0] == params["stages"]["0"][
        "attn"]["wq"].shape[0]


def test_init_quantized_params_matches_quantize_after_init():
    """Leaf-at-a-time init+quantize builds the same packed tree as
    quantizing a whole float model, and a ServeEngine serves that tree
    as it is (its leaves are already BitplaneWeights)."""
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                              weight_bits=4)
    defs = param_defs(cfg)
    params = init_params(defs, KEY)
    packed = init_quantized_params(defs, KEY, bits=4)
    two_step = quantize_params(params, bits=4)
    assert (jax.tree_util.tree_structure(packed)
            == jax.tree_util.tree_structure(two_step))
    for a, b in zip(jax.tree_util.tree_leaves(packed),
                    jax.tree_util.tree_leaves(two_step)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    prompts = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    out = ServeEngine(cfg, packed, max_seq=16, quantized=True).generate(
        prompts, max_new=4)
    ref = ServeEngine(cfg, params, max_seq=16, quantized=True).generate(
        prompts, max_new=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_quantize_defs_matches_quantize_params_structure():
    cfg = tiny_config("qwen2-7b")
    defs = param_defs(cfg)
    params = init_params(defs, KEY)
    pq = quantize_params(params, bits=3)
    dq = quantize_defs(defs, bits=3)
    t1 = jax.tree_util.tree_structure(pq)
    t2 = jax.tree_util.tree_structure(dq)
    assert t1 == t2
    for a, b in zip(jax.tree_util.tree_leaves(pq),
                    jax.tree_util.tree_leaves(dq)):
        assert a.shape == b.shape, (a.shape, b.shape)
        assert a.dtype == b.dtype


def test_generate_dense_vs_quantized_8bit():
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                              weight_bits=8)
    params = init_params(param_defs(cfg), KEY)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    e_dense = ServeEngine(cfg, params, max_seq=32, quantized=False)
    e_quant = ServeEngine(cfg, params, max_seq=32, quantized=True)
    t_dense = e_dense.generate(prompts, max_new=8)
    t_quant = e_quant.generate(prompts, max_new=8)
    assert t_dense.shape == t_quant.shape == (2, 16)
    # 8-bit quantization: greedy decode diverges rarely on 8 tokens
    agree = float((t_dense == t_quant).mean())
    assert agree > 0.8, agree


def test_serving_bytes_capacity_win():
    from repro.configs import get_config
    cfg = get_config("llama2-7b")          # 2-bit serving point
    rep = serving_bytes(param_defs(cfg), cfg.weight_bits)
    assert rep["ratio"] > 4.0              # ~bf16/2-bit on linear-dominated
    rep4 = serving_bytes(param_defs(cfg), 4)
    assert rep4["ratio"] < rep["ratio"]


def test_scan_decode_matches_python_loop():
    """The lax.scan decode (donated cache) is token-for-token identical to
    the retained per-token Python loop — greedy AND seeded sampling."""
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32")
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=48)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    greedy_scan = eng.generate(prompts, max_new=10)
    greedy_loop = eng.generate(prompts, max_new=10, scan=False)
    np.testing.assert_array_equal(np.asarray(greedy_scan),
                                  np.asarray(greedy_loop))
    hot_scan = eng.generate(prompts, max_new=6, temperature=0.8, seed=11)
    hot_loop = eng.generate(prompts, max_new=6, temperature=0.8, seed=11,
                            scan=False)
    np.testing.assert_array_equal(np.asarray(hot_scan), np.asarray(hot_loop))


def test_masked_scan_bucketed_executables_across_requests():
    """A bounded set of power-of-two-bucket decode executables serves every
    (max_new, temperature) mix — the recompile-per-(steps, temperature)
    problem is gone; tokens still match the loop oracle for each mix."""
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32")
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=40)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    for max_new, temp, seed in [(4, 0.0, 0), (9, 0.0, 0), (6, 0.9, 5),
                                (5, 1.3, 2)]:
        got = eng.generate(prompts, max_new=max_new, temperature=temp,
                           seed=seed)
        want = eng.generate(prompts, max_new=max_new, temperature=temp,
                            seed=seed, scan=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # trips 3, 8, 5, 4 → buckets {4, 8}: temperature/length changes reuse
    # executables instead of compiling per (steps, temperature) pair
    assert set(eng._decode_fns) == {4, 8}


def test_masked_scan_per_lane_budgets():
    """Per-lane length masks: a lane past its budget re-emits its frozen
    token while other lanes keep generating; tokens inside every lane's
    budget match the uniform run exactly."""
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32")
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=32)
    prompts = jax.random.randint(KEY, (2, 6), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    full = np.asarray(eng.generate(prompts, max_new=8))
    capped = np.asarray(eng.generate(prompts, max_new=8,
                                     max_new_per_lane=[3, 8]))
    np.testing.assert_array_equal(capped[1], full[1])     # uncapped lane
    np.testing.assert_array_equal(capped[0, :6 + 3], full[0, :6 + 3])
    assert (capped[0, 6 + 3:] == capped[0, 6 + 2]).all()  # frozen tail
    # the Python loop oracle applies the same per-lane freeze
    loop = np.asarray(eng.generate(prompts, max_new=8,
                                   max_new_per_lane=[3, 8], scan=False))
    np.testing.assert_array_equal(capped, loop)


def test_generate_rejects_cache_overflow():
    cfg = tiny_config("llama2-7b")
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=16)
    with pytest.raises(ValueError, match="cache horizon"):
        eng.generate(jnp.zeros((1, 8), jnp.int32), max_new=16)


def test_quantized_linears_route_through_mvdram_engine():
    """Quantized serving installs EngineLinear: every lane-batched
    bit-plane linear traces through MVDRAMEngine.linear (counted at trace
    time), and generation still matches the dense model at 8 bits."""
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                              weight_bits=8)
    params = init_params(param_defs(cfg), KEY)
    prompts = jax.random.randint(KEY, (2, 8), 0, cfg.vocab_size,
                                 dtype=jnp.int32)
    eng = ServeEngine(cfg, params, max_seq=32, quantized=True)
    assert eng.mvdram is not None
    toks = eng.generate(prompts, max_new=8)
    assert toks.shape == (2, 16)
    # prefill + decode traces each route the model's quantized linears
    assert eng.mvdram.routed_linears > 0
    dense_eng = ServeEngine(cfg, params, max_seq=32, quantized=False)
    assert dense_eng.mvdram is None
    agree = float((toks == dense_eng.generate(prompts, max_new=8)).mean())
    assert agree > 0.8, agree


def test_scan_decode_single_token_edge():
    cfg = tiny_config("llama2-7b")
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=24)
    out = eng.generate(jnp.zeros((1, 4), jnp.int32), max_new=1)
    assert out.shape == (1, 5)


def test_temperature_sampling_shape():
    cfg = tiny_config("llama2-7b")
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=24)
    out = eng.generate(jnp.zeros((1, 4), jnp.int32), max_new=4,
                       temperature=1.0, seed=7)
    assert out.shape == (1, 8)
    assert (np.asarray(out) >= 0).all()
    assert (np.asarray(out) < cfg.vocab_size).all()


def test_moe_experts_served_bitplane():
    """Routed experts swap to E-stacked bit-planes and the quantized model
    tracks the dense one at 8 bits (paper's per-expert GeMV case)."""
    cfg = dataclasses.replace(tiny_config("qwen2-moe-a2.7b"),
                              dtype="float32", weight_bits=8)
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = init_params(param_defs(cfg), KEY)
    batch = {"tokens": jax.random.randint(KEY, (2, 12), 0, cfg.vocab_size)}
    ref, _ = jax.jit(Model(cfg).forward)(params, batch)
    pq = quantize_params(params, bits=8)
    assert isinstance(pq["stages"]["0"]["moe"]["w_up"], BitplaneWeights)
    assert not isinstance(pq["stages"]["0"]["moe"]["router"],
                          BitplaneWeights)  # router stays fp
    out, _ = jax.jit(Model(cfg).forward)(pq, batch)
    rel = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < 0.05, rel


def test_placement_fallback_surfaced_in_residency_stats(monkeypatch):
    """A model that does not fit the DramPool serves program-less — and the
    fallback is now VISIBLE in residency_stats() (placement_fallback /
    resident_program), not just a construction-time warning."""
    import repro.serve.engine as serve_mod
    from repro.core.engine import MVDRAMEngine
    from repro.core.pud.gemv import PudGeometry
    from repro.core.pud.residency import DramPool

    orig = serve_mod.MVDRAMEngine

    def tiny_engine(**kw):
        # a pool with almost no resident rows: placement MUST overflow
        geom = PudGeometry()
        pool = DramPool(geom, compute_reserve=geom.bank_rows - 4)
        return orig(pool=pool, **kw)

    monkeypatch.setattr(serve_mod, "MVDRAMEngine", tiny_engine)
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                              weight_bits=8)
    params = init_params(param_defs(cfg), KEY)
    with pytest.warns(RuntimeWarning, match="does not fit the DramPool"):
        eng = ServeEngine(cfg, params, max_seq=32, quantized=True)
    assert eng.decode_program is None
    stats = eng.residency_stats()
    assert stats["placement_fallback"] is True
    assert stats["resident_program"] is False
    assert stats["placements"] == 0          # partial residency rolled back
    assert eng.price_decode_step() is None
    # the engine still serves through the jit path
    prompts = jnp.zeros((1, 4), jnp.int32)
    out = eng.generate(prompts, max_new=4)
    assert out.shape == (1, 8)


def test_resident_serving_reports_no_fallback():
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                              weight_bits=8)
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=32, quantized=True)
    stats = eng.residency_stats()
    assert stats["placement_fallback"] is False
    assert stats["resident_program"] is True
    assert stats["fault_corrupted"] == 0      # no fault model configured
    assert stats["degraded_layers"] == []
    assert ServeEngine(cfg, params, max_seq=32).residency_stats() is None


def test_decode_tick_energy_twin_of_tick_cost():
    """`decode_tick_energy_j` is the EnergyModel twin of
    `decode_tick_cost_s`: one pricing fills both cache slots, the Joules
    match a direct program pricing exactly, and dense engines get None."""
    cfg = dataclasses.replace(tiny_config("llama2-7b"), dtype="float32",
                              weight_bits=8)
    params = init_params(param_defs(cfg), KEY)
    eng = ServeEngine(cfg, params, max_seq=32, quantized=True)
    e1 = eng.decode_tick_energy_j(1)
    assert e1 is not None and e1 > 0.0
    # shares the seconds cache: the (occupancy, density) entry holds both
    key = (1, 0.5)
    assert eng._tick_price_cache[key] == (eng.decode_tick_cost_s(1), e1)
    cost = eng.decode_program.price(bit_density=0.5, batch=1)
    assert e1 == cost.e_total
    # more lanes bill more readout/host energy at the same resident waves
    assert eng.decode_tick_energy_j(2) > e1
    assert ServeEngine(cfg, params, max_seq=32).decode_tick_energy_j(1) is None
