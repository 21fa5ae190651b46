"""Block families: a configuration's `"family"` key finds
`families/<family>.py`, which holds everything the harness knows of a
block's shapes.

- The dense family's reference gives, bit for bit, the logits recorded from
  the reference as it stood before it was split into families
  (`testdata/logits.<config>.npz`: the seed, the TINY sizes of
  `test_bench_checks.py`, tokens, rows, variants and logits, on the CPU),
  and its counts give the recorded step sums (`testdata/step_counts.json`:
  synthetic windows of each cell, with the figures the same code gave).
- An unknown family is an error that names the families present.
- A family written under another root, named by a configuration there, is
  what `step_context` and the reference check call, with no harness file
  edited.
- The shared harness reads no key of a dense block.
"""
import ast
import json
import os
import tokenize

import _paths  # noqa: F401
import numpy as np
import pytest
from harness import spec

bench_run = _paths.bench_run()
DATA = os.path.join(_paths.BENCH, "testdata")
GOLDEN = sorted(f[len("logits."):-len(".npz")] for f in os.listdir(DATA)
                if f.startswith("logits.") and f.endswith(".npz"))
with open(os.path.join(DATA, "step_counts.json")) as f:
    STEPS = json.load(f)


@pytest.mark.parametrize("config", GOLDEN)
def test_dense_reference_matches_golden_logits(config):
    g = np.load(os.path.join(DATA, f"logits.{config}.npz"))
    with open(os.path.join(_paths.BENCH, "configs", f"{config}.json")) as f:
        c = json.load(f)
    assert c["family"] == "dense"
    a = dict(c["as_run"], **json.loads(str(g["sizes"])))
    variants = tuple(tuple(v) for v in g["variants"].tolist())
    got = spec.family("dense").forward_logits(
        a, int(g["seed"]), g["tokens"], g["lengths"], g["rows"], variants)
    assert len(got) == len(variants) == len(g["logits"])
    for lg, want in zip(got, g["logits"]):
        assert np.array_equal(np.asarray(lg), want)


def _served(ticks, lanes, finished=(), vocab=0):
    return bench_run.Served(
        setup_s=0.0, window_open=0.0, window_s=1.0, ticks=ticks, stamps={},
        finished=list(finished),
        counters={"occupancy_ticks": {}, "lanes": lanes},
        memory_peak_bytes=0, memory_in_use_bytes=0, vocab=vocab)


@pytest.mark.parametrize("name", sorted(STEPS["cells"]))
def test_dense_step_counts_match_the_recorded_figures(name):
    rec = STEPS["cells"][name]
    cell = spec.load_cell(name)
    ticks = [(float(i), [tuple(lane) for lane in lanes])
             for i, lanes in enumerate(rec["ticks"])]
    ctx = bench_run.step_context(_served(ticks, cell.traffic["lanes"]), cell,
                                 spec.peaks(STEPS["device_kind"]))
    assert ctx["step_roofline_s"] == rec["step_roofline_s"]
    assert ctx["kernels"] == {"bitplane_gemv": {
        "launches_per_step": rec["launches_per_step"],
        "kernel_step_s": rec["kernel_step_s"]}}


def test_unknown_family_names_the_families_present(tmp_path):
    with pytest.raises(KeyError, match="'dense'"):
        spec.family("no-such-family")
    where = tmp_path / "bench" / "families"
    where.mkdir(parents=True)
    for name in ("alpha", "beta"):
        (where / f"{name}.py").write_text("")
    with pytest.raises(KeyError) as err:
        spec.family("gamma", root=tmp_path)
    assert "'gamma'" in str(err.value)
    assert "['alpha', 'beta']" in str(err.value)


TOY = '''"""A toy block family: one square linear a layer, round counts, and a
reference whose best token follows each token by one id."""
import numpy as np

SEEN = []


def program_config(a):
    return ("toy", a["layers"])


def forward_logits(as_run, seed, tokens, lengths, rows, variants):
    SEEN.append(("forward_logits", seed, tuple(variants)))
    best = np.asarray(tokens).reshape(-1)[np.asarray(rows)] + 1
    out = []
    for i, _ in enumerate(variants):
        lg = np.zeros((len(rows), as_run["vocab"]), np.float32)
        lg[np.arange(len(rows)), (best + i) % as_run["vocab"]] = 1.0
        out.append(lg)
    return out


def step_flops(m, positions, counters=None):
    SEEN.append(("step_flops", counters))
    return 1000 * len(positions)


def step_bytes(m, bits, positions, counters=None):
    return 0


def kernel_calls(m, bits, act_bits, rows, counters=None):
    SEEN.append(("kernel_calls", rows, counters))
    return [("w#%d" % i, "toy_mm", 100, 0) for i in range(m["layers"])]
'''


def _toy_root(root):
    """A benchmark under `root` with one cell, `toy-1.decode`, whose
    configuration names the family `toy`."""
    bench = root / "bench"
    for d in ("configs", "families", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    (bench / "families" / "toy.py").write_text(TOY)
    (bench / "configs" / "toy-1.json").write_text(json.dumps({
        "family": "toy", "as_run": {"layers": 3, "width": 8, "vocab": 50,
                                    "weight_bits": 2, "act_bits": 4}}))
    with open(os.path.join(_paths.BENCH, "traffic", "decode.json")) as f:
        (bench / "traffic" / "decode.json").write_text(f.read())
    (bench / "limits" / "toy-1.decode.json").write_text(json.dumps(
        {"rank_mean": 0.05, "tokens_compared": 4}))
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy-1", "file": "bench/configs/toy-1.json"}],
        "workloads": [{"name": "toy-1.decode", "config": "toy-1",
                       "traffic": "decode", "chips": 1}],
        "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "model_step.mfu_pct", "unit": "%",
                       "moves": "tokens_per_s"},
                      {"name": "kernel.bitplane_gemv.roofline_pct",
                       "unit": "%", "moves": "tokens_per_s",
                       "workloads": []}]}))


def test_a_new_family_is_found_by_name(tmp_path):
    _toy_root(tmp_path)
    cell = spec.load_cell("toy-1.decode", root=tmp_path)
    toy = cell.family
    assert toy is spec.family("toy", root=tmp_path)
    assert toy.__file__ == str((tmp_path / "bench" / "families" /
                                "toy.py").resolve())

    # step_context: two lanes step twice and once, one lane idles
    sv = _served([(0.1, [(2, 5, True), (1, 9, False), (0, 0, False)])],
                 cell.traffic["lanes"])
    peak = {"flops_per_s": 1e6, "hbm_bytes_per_s": 1e4}
    ctx = bench_run.step_context(sv, cell, peak)
    assert ctx["step_roofline_s"] == pytest.approx(2e-3 + 1e-3)
    assert ctx["kernels"] == {"toy_mm": {"launches_per_step": 3,
                                         "kernel_step_s": pytest.approx(0.03)}}
    assert ("step_flops", sv.counters) in toy.SEEN
    assert ("kernel_calls", cell.traffic["lanes"], sv.counters) in toy.SEEN
    # the step's share of peak reads the family's sums; the cell does not
    # list the bit-plane kernels' roofline, whose reader refuses a family
    # that counts no launch of theirs
    read = {m["name"]: spec.metric_reader(m["name"])(
        {"steps": ctx, "window_s": 0.3, "trace": None})
        for m in cell.per_layer}
    assert read == {"model_step.mfu_pct": pytest.approx(1.0)}
    with pytest.raises(KeyError, match="bitplane_gemv"):
        spec.metric_reader("kernel.bitplane_gemv.roofline_pct")(
            {"steps": ctx, "window_s": 0.3, "trace": None})

    # the reference check: answers that follow each token by one id are
    # the toy reference's own choices, and one altered token is not
    def finished(alter):
        out = []
        for start in (3, 20, 40):
            prompt = [start, start + 1]
            ans = [(start + 2 + k) % 50 for k in range(3)]
            if alter and start == 20:
                ans[1] += 1
            out.append((0.5, prompt, ans, 3))
        return out
    sound = bench_run.check(_served([], 4, finished(False), 50), cell, 7)
    assert sound["correct"] and sound["numbers"]["rank_mean"]["value"] == 0
    assert ("forward_logits", 7, ((2, 4),)) in toy.SEEN
    broken = bench_run.check(_served([], 4, finished(True), 50), cell, 7)
    assert not broken["correct"]
    # a control variant runs through the same family
    smp = bench_run.sample(_served([], 4, finished(False), 50), cell, 7)
    got = bench_run.readings(smp, cell, 7, ((1, 4),))
    assert got["control w1 a4"]["rank_mean"] == 1


SHARED = ("run.py", "calibrate.py", "harness/counts.py",
          "harness/reference.py")
DENSE_KEYS = {"heads", "kv_heads", "head_dim", "d_ff", "qkv_bias", "ffn",
              "norm"}


@pytest.mark.parametrize("path", SHARED)
def test_shared_harness_reads_no_dense_key(path):
    with open(os.path.join(_paths.BENCH, path)) as f:
        strings = [ast.literal_eval(t.string)
                   for t in tokenize.generate_tokens(f.readline)
                   if t.type == tokenize.STRING]
    assert not [s for s in strings if s in DENSE_KEYS]
