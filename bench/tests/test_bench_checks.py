"""The correctness check has teeth: at a size a test run can hold (a
four-layer, 128-wide model of the configuration's shape, on the CPU through
the program's jnp path), a sound run passes, a run whose timed path is
broken fails, and each control (the reference one weight bit or one
activation bit narrower, put in the program's place) fails the run's own
comparison."""
import _paths  # noqa: F401
import jax
import jax.numpy as jnp
import pytest

from harness import spec

bench_run = _paths.bench_run()

TINY = dict(layers=4, d_model=128, d_ff=256, vocab=1024, heads=8, kv_heads=2,
            head_dim=16)
# at this size sound runs read a mean rank of 3.3-10.7 (of 1024 tokens) on
# four seeds, the activation control (a3) 70-110, the weight control (w1)
# 497-762, and a broken path 370-605
TINY_LIMIT = 30


def tiny_cell(name):
    cell = spec.load_cell(name)
    cell.config["as_run"].update(TINY)
    cell.traffic.update(
        prompt={"median": 8, "sigma": 0.5, "min": 4, "max": 12},
        answer={"median": 12, "sigma": 0.5, "min": 8, "max": 16},
        max_seq=40, prefill_chunk=4, warm_ticks=2, check_requests=4)
    cell.limits = {"rank_mean": TINY_LIMIT, "tokens_compared": 24}
    return cell


def run_tiny(cell, patch=None):
    from repro.core import backends
    return bench_run.run_cell(cell, 2**31 + 5, 1.0, False,
                              backend=backends.JNP, patch=patch, cache=False)


def altered_token(batcher):
    """A token altered where it is produced: every tick's sampled token
    comes out one id higher."""
    tick_fn, vocab = batcher._tick_fn, batcher.cfg.vocab_size

    def patched(trip):
        f = tick_fn(trip)
        return lambda *a: (lambda c, n: (c, (n + 1) % vocab))(*f(*a))
    batcher._tick_fn = patched


def unchanged_state(batcher):
    """A step that returns its state unchanged: the tick computes on a copy
    of the cache and hands the old one back."""
    tick_fn = batcher._tick_fn

    def patched(trip):
        f = tick_fn(trip)

        def g(params, cache, *a):
            _, nxt = f(params, jax.tree_util.tree_map(jnp.copy, cache), *a)
            return cache, nxt
        return g
    batcher._tick_fn = patched


CELL = "qwen2-7b-w2a4.decode"


def test_sound_run_is_correct():
    line = run_tiny(tiny_cell(CELL))
    assert line["correct"], line["check"]
    assert line["check"]["rank_mean"]["value"] <= TINY_LIMIT / 2


@pytest.mark.parametrize("fault", [altered_token, unchanged_state],
                         ids=["altered_token", "unchanged_state"])
def test_broken_timed_path_is_not_correct(fault):
    line = run_tiny(tiny_cell(CELL), patch=fault)
    assert not line["correct"]
    assert line["check"]["rank_mean"]["value"] > TINY_LIMIT


def _control(bits):
    """The sound run's and one control's readings, and the control's
    verdict by the run's own comparison."""
    cell = tiny_cell(CELL)
    from repro.core import backends
    sv = bench_run.serve(cell, 2**31 + 5, 1.0, False, backends.JNP, 0.0)
    smp = bench_run.sample(sv, cell, 2**31 + 5)
    got = bench_run.readings(smp, cell, 2**31 + 5, (bits,))
    control = got["control w%d a%d" % bits]
    verdict = bench_run.judge(control, len(smp["served"]), 0, cell.limits)
    return got["program"]["rank_mean"], control["rank_mean"], verdict


def test_control_reads_above_the_limit():
    a = tiny_cell(CELL).config["as_run"]
    sound, control, verdict = _control((a["weight_bits"] - 1, a["act_bits"]))
    assert sound <= TINY_LIMIT < control
    assert control >= 3 * max(sound, 1)
    assert not verdict["correct"]


def test_activation_control_is_not_correct():
    a = tiny_cell(CELL).config["as_run"]
    sound, control, verdict = _control((a["weight_bits"], a["act_bits"] - 1))
    assert sound <= TINY_LIMIT < control
    assert not verdict["correct"]


def test_no_chip_no_result(capsys):
    """Off a TPU the command exits non-zero and prints no result line."""
    assert bench_run.main(["--workload", CELL, "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""
