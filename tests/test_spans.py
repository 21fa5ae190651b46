"""Spans and counters of the served path: the tick's device ops carry the
model's region names, the set-up phases and compile events are counted in
memory, and a repeat of a compiled trip bucket compiles nothing."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import tiny_config
from repro.models.model import param_defs
from repro.models.params import init_params
from repro.serve import spans
from repro.serve.engine import ServeEngine
from repro.serve.quantize import init_quantized_params
from repro.serve.scheduler import ContinuousBatcher, Request

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def batcher():
    cfg = dataclasses.replace(tiny_config("qwen2-7b"), dtype="float32",
                              weight_bits=2)
    return ContinuousBatcher(cfg, init_params(param_defs(cfg), KEY),
                             max_seq=32, lanes=2, quantized=True,
                             act_bits=4, prefill_chunk=4)


REGIONS = ("embed", "attention", "kv_write", "norm", "act_quant", "ffn",
           "lm_head", "freeze_lanes", "sample")


def test_tick_ops_carry_the_model_regions(batcher):
    b = batcher
    args = (jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.int32))
    text = b._tick_fn(4).lower(b.params, b.cache, *args).as_text(
        debug_info=True)
    paths = [p.split("/") for p in re.findall(r'loc\("([^"]*)"', text)]
    for region in REGIONS:
        assert any(region in p[:-1] for p in paths), region
    assert any(p[:2] == ["attention", "kv_write"] for p in paths)


def test_compiles_only_on_a_new_trip_bucket(batcher):
    def compiles():
        ev = spans.snapshot()["compiles"]
        return sum(ev.get(k, {}).get("count", 0)
                   for k in spans.COMPILE_EVENTS[:3])

    b = batcher
    b.submit(Request(rid=0, prompt=[3], max_new=2))
    b.run()                                     # trip 1 compiles here
    c0, wall0 = compiles(), spans.snapshot()["compile_wall_s"]
    b.submit(Request(rid=1, prompt=[5], max_new=2))
    b.run()                                     # trip 1 again
    assert compiles() == c0
    assert spans.snapshot()["compile_wall_s"] == wall0
    b.submit(Request(rid=2, prompt=[1, 2, 3, 4], max_new=1))
    b.run()                                     # trip 4 is new
    assert compiles() > c0
    assert spans.snapshot()["compile_wall_s"] > wall0


def test_set_up_phases_are_counted():
    cfg = dataclasses.replace(tiny_config("llama2-7b"), weight_bits=2)

    def count(snap, name):
        return snap["phases"].get(name, {}).get("count", 0)

    s0 = spans.snapshot()
    params = init_quantized_params(param_defs(cfg), KEY, 2)
    ServeEngine(cfg, params, max_seq=16, quantized=True, act_bits=4)
    s1 = spans.snapshot()
    for name in ("init", "quantize", "place"):
        assert count(s1, name) == count(s0, name) + 1, name
        assert s1["phases"][name]["s"] > s0["phases"].get(
            name, {}).get("s", 0.0)


def test_compile_cover_counts_nested_spans_once():
    c = spans._Cover()
    # an outer trace [0, 10] reports after the inner ones it made
    for a, b in ((1, 2), (5, 6), (0, 10), (12, 13), (12.5, 14), (11, 15)):
        c.add(a, b)
    assert c.total == pytest.approx(14.0)
    assert c.spans == [[0, 10], [11, 15]]
    d = spans._Cover()
    d.add(0, 4)
    d.add(3, 6)                 # overlaps the last without containing it
    assert d.total == pytest.approx(6.0)
