"""Fault-injected PUD (ISSUE 6): ABFT verification, wave retry, quarantine.

Load-bearing contracts:

* `FaultModel.none()` produces NO session, so a fault-configured engine is
  BIT-IDENTICAL — outputs and per-(request, tile) OpCounts — to an engine
  with no fault layer at all, across random layouts, ragged chunks, mixed
  q/p and B > wave capacity (property-tested).
* Every injected corruption is a single bit-0 column flip, so the ABFT
  checksum (GeMV linearity) detects ALL of them: coverage is exactly 1.0.
* Bounded wave retries restore bit-exact outputs under transient faults;
  their op bills reconcile into `timing.price_program` as `t_retry`.
* Persistent weak banks escalate: strikes → pool quarantine (evict +
  restage on healthy banks) → host `jnp` recompute → permanent degradation
  past the budget, while every launch keeps returning correct results.
* No implicit global RNG anywhere in `core/pud/` (grep-enforced).
"""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import backends
from repro.core.engine import MVDRAMEngine
from repro.core.pud.device import BankArray, Subarray
from repro.core.pud.faults import (FaultModel, FaultPolicy, FaultSession,
                                   FaultTrace)
from repro.core.pud.gemv import PudGeometry
from repro.core.quant import QuantSpec

GEOM = PudGeometry(subarray_cols=32, n_sub_max=16,
                   channels=2, banks_per_channel=2)
KEY = jax.random.PRNGKey(0)


def _register_random(eng, rng, layers, geom=GEOM):
    hs = []
    for i in range(layers):
        q = int(rng.integers(2, 5))
        p = int(rng.integers(1, 4))
        n = int(rng.integers(3, 40))
        m = int(rng.integers(2, 3 * (geom.subarray_cols // q)))
        w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        hs.append(eng.register(f"l{i}", w, QuantSpec(bits=q),
                               a_spec=QuantSpec(bits=p)))
    return hs


def _tile_counts(report, B):
    return [[c.asdict() for c in report.requests[b].tile_runtime]
            for b in range(B)]


# ---------------------------------------------------------------------------
# FaultModel / FaultSession basics
# ---------------------------------------------------------------------------

def test_none_model_has_no_session():
    assert FaultModel.none().session() is None
    assert not FaultModel.none().enabled
    assert FaultModel(transient_ber=0.1).session() is not None


def test_model_validates_probabilities():
    for field in ("transient_ber", "weak_cell_rate", "weak_flip_prob"):
        with pytest.raises(ValueError, match="probability"):
            FaultModel(**{field: 1.5})
        with pytest.raises(ValueError, match="probability"):
            FaultModel(**{field: -0.1})


def test_session_requires_enabled_model():
    with pytest.raises(ValueError, match="enabled"):
        FaultSession(FaultModel.none())


def test_weak_maps_are_order_independent():
    """A bank's weak map is a pure function of (model, channel, bank) —
    independent of which bank a session touched first."""
    m = FaultModel(weak_cell_rate=0.2, seed=9)
    s1, s2 = m.session(), m.session()
    a1 = s1.weak_mask(0, 3, 64)
    b1 = s1.weak_mask(1, 0, 64)
    b2 = s2.weak_mask(1, 0, 64)   # opposite visit order
    a2 = s2.weak_mask(0, 3, 64)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)


def test_sessions_are_deterministic():
    m = FaultModel(transient_ber=0.1, seed=4)
    f1 = m.session().flip_columns(256)
    f2 = m.session().flip_columns(256)
    np.testing.assert_array_equal(f1, f2)


# ---------------------------------------------------------------------------
# Satellite (a): device shape errors carry shapes, not bare asserts
# ---------------------------------------------------------------------------

def test_majx_rejects_even_row_counts_with_message():
    sa = Subarray(rows=16, cols=8)
    with pytest.raises(ValueError, match="odd row count"):
        sa.majx([0, 1])
    ba = BankArray(tiles=2, rows=16, cols=8)
    with pytest.raises(ValueError, match="odd row count"):
        ba.majx([0, 1, 2, 3])


def test_host_write_shape_errors_carry_shapes():
    sa = Subarray(rows=16, cols=8)
    with pytest.raises(ValueError, match=r"\(8,\)"):
        sa.host_write_row(0, np.zeros(5, dtype=np.uint8))
    ba = BankArray(tiles=2, rows=16, cols=8)
    with pytest.raises(ValueError, match=r"\(8,\)"):
        ba.host_write_row(0, np.zeros((2, 8), dtype=np.uint8))
    with pytest.raises(ValueError, match=r"\(2, 3, 8\)"):
        ba.host_write_rows([0, 1, 2], np.zeros((2, 2, 8), dtype=np.uint8))


# ---------------------------------------------------------------------------
# Device-level injection (Subarray / BankArray majx hooks)
# ---------------------------------------------------------------------------

def test_subarray_majx_injects_on_reliable_columns_only():
    rng = np.random.default_rng(1)
    sa = Subarray(rows=16, cols=32)
    sa.data[:3] = rng.integers(0, 2, size=(3, 32)).astype(np.uint8)
    clean = Subarray(rows=16, cols=32)
    clean.data[:3] = sa.data[:3].copy()
    clean.majx([0, 1, 2])
    sa.fault_session = FaultModel(transient_ber=0.5, seed=2).session()
    sa.majx([0, 1, 2])
    diff = sa.data[0] != clean.data[0]
    assert diff.any()                       # something flipped
    assert not diff[~sa.reliable].any()     # never off the reliable mask


def test_bankarray_majx_uses_per_tile_fault_keys():
    """With a sticky weak map, only the tile keyed to the weak bank sees
    persistent flips — the fault keys address banks, not wave positions."""
    model = FaultModel(weak_cell_rate=0.04, weak_flip_prob=1.0, seed=6)
    session = model.session()
    weak_key = next((0, b) for b in range(64)
                    if session.bank_is_weak(0, b, 32))
    # and a bank with NO weak columns for the control tile
    healthy = next((0, b) for b in range(64)
                   if not session.bank_is_weak(0, b, 32))
    ba = BankArray(tiles=2, rows=16, cols=32)
    rng = np.random.default_rng(3)
    ba.data[:, :3] = rng.integers(0, 2, size=(2, 3, 32)).astype(np.uint8)
    clean = ba.data[:, :3].copy()
    ref = BankArray(tiles=2, rows=16, cols=32)
    ref.data[:, :3] = clean
    ref.majx([0, 1, 2])
    ba.fault_session = session
    ba.fault_keys = [weak_key, healthy]
    ba.majx([0, 1, 2])
    assert (ba.data[0, 0] != ref.data[0, 0]).any()
    np.testing.assert_array_equal(ba.data[1, 0], ref.data[1, 0])


# ---------------------------------------------------------------------------
# Satellite (c): faults-off bit-identity, property-tested
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)   # the first example compiles
@given(layers=st.integers(min_value=1, max_value=4),
       b=st.sampled_from([1, 2, 6]),
       seed=st.integers(min_value=0, max_value=50))
def test_none_model_is_bit_identical(layers, b, seed):
    """FaultModel.none() vs no fault layer at all: outputs AND per-(request,
    tile) OpCounts bit-identical, single launches and fused programs, across
    random ragged layouts, mixed q/p and B above the wave capacity."""
    rng0, rng1 = np.random.default_rng(seed), np.random.default_rng(seed)
    eng_plain = MVDRAMEngine(geom=GEOM)
    eng_none = MVDRAMEngine(geom=GEOM, fault_model=FaultModel.none(),
                            fault_policy=FaultPolicy())
    hs0 = _register_random(eng_plain, rng0, layers)
    hs1 = _register_random(eng_none, rng1, layers)
    assert eng_none._fault_session is None
    xs = [jnp.asarray(np.random.default_rng(seed + 99 + i)
                      .normal(size=(b, h.plan.n)), jnp.float32)
          for i, h in enumerate(hs0)]
    for h0, h1, x in zip(hs0, hs1, xs):
        o0, r0 = eng_plain.gemv(h0, x, backend=backends.SIM)
        o1, r1 = eng_none.gemv(h1, x, backend=backends.SIM)
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))
        assert r1.fault is None
        assert _tile_counts(r0, b) == _tile_counts(r1, b)
        assert r0.runtime.asdict() == r1.runtime.asdict()
    p0 = eng_plain.compile(hs0)
    p1 = eng_none.compile(hs1)
    outs0, rep0 = p0.run(xs)
    outs1, rep1 = p1.run(xs)
    assert rep1.fault is None and rep1.retry_wave_ops == ()
    for o0, o1 in zip(outs0, outs1):
        np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))
    for r0, r1 in zip(rep0.reports, rep1.reports):
        assert _tile_counts(r0, b) == _tile_counts(r1, b)
    c0 = eng_plain.price_program(p0, batch=b, executed=rep0)
    c1 = eng_none.price_program(p1, batch=b, executed=rep1)
    assert c0.asdict() == c1.asdict()
    assert c1.t_retry == 0.0 and c1.retry_waves == 0


# ---------------------------------------------------------------------------
# Satellite (b): no implicit global RNG in core/pud/
# ---------------------------------------------------------------------------

def test_no_global_rng_in_core_pud():
    """All randomness in the PUD layer flows through explicit seeded
    `np.random.default_rng` / `np.random.Generator` streams — the legacy
    global-state entry points (np.random.seed / np.random.random / the
    stdlib `random` module) are banned."""
    pud = pathlib.Path(__file__).resolve().parent.parent \
        / "src" / "repro" / "core" / "pud"
    banned = re.compile(
        r"np\.random\.(?!default_rng\b|Generator\b)\w+"
        r"|numpy\.random\.(?!default_rng\b|Generator\b)\w+"
        r"|^\s*import random\b|^\s*from random import\b",
        re.MULTILINE)
    offenders = []
    for path in sorted(pud.glob("*.py")):
        for m in banned.finditer(path.read_text()):
            offenders.append(f"{path.name}: {m.group(0)}")
    assert not offenders, f"implicit global RNG in core/pud/: {offenders}"


# ---------------------------------------------------------------------------
# ABFT detection + retry (transient faults)
# ---------------------------------------------------------------------------

def test_transient_faults_detected_and_retried_bit_exact():
    w = jax.random.normal(KEY, (48, 40))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 48))
    clean = MVDRAMEngine(geom=GEOM)
    h0 = clean.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    out0, _ = clean.gemv(h0, x, backend=backends.SIM)
    eng = MVDRAMEngine(geom=GEOM, fault_model=FaultModel(transient_ber=0.05,
                                                         seed=7))
    h = eng.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    out, rep = eng.gemv(h, x, backend=backends.SIM)
    tr = rep.fault
    assert tr is not None and tr.corrupted > 0
    assert tr.detected == tr.corrupted          # coverage is a theorem
    assert tr.coverage == 1.0
    assert tr.retries > 0 and not tr.unresolved
    assert len(tr.retry_wave_ops) == tr.retries
    assert all(ops > 0 for ops in tr.retry_wave_ops)
    # a transient fault re-draws on retry: the corrected launch is EXACT
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out))
    stats = eng.residency_stats()
    assert stats["fault_corrupted"] == tr.corrupted
    assert stats["fault_detected"] == tr.detected
    assert stats["fault_retries"] == tr.retries
    assert stats["transient_injections"] >= tr.corrupted


def test_detection_coverage_at_fixed_ber():
    """Acceptance: >= 99% of corrupted (request, tile) cells detected at a
    fixed BER (here: exactly 100%, across many launches)."""
    w = jax.random.normal(KEY, (64, 48))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    eng = MVDRAMEngine(geom=GEOM,
                       fault_model=FaultModel(transient_ber=0.02, seed=13),
                       fault_policy=FaultPolicy(max_wave_retries=3))
    h = eng.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    for _ in range(10):
        eng.gemv(h, x, backend=backends.SIM)
    stats = eng.residency_stats()
    assert stats["fault_corrupted"] >= 10       # the BER actually fired
    coverage = stats["fault_detected"] / stats["fault_corrupted"]
    assert coverage >= 0.99
    assert coverage == 1.0                      # single-bit flips: exact


def test_fused_program_retry_reconciles_into_price():
    rng = np.random.default_rng(12)
    eng = MVDRAMEngine(geom=GEOM,
                       fault_model=FaultModel(transient_ber=0.3, seed=5),
                       fault_policy=FaultPolicy(max_wave_retries=4,
                                                degrade_after=100))
    clean = MVDRAMEngine(geom=GEOM)
    hs = _register_random(eng, np.random.default_rng(12), 3)
    hc = _register_random(clean, np.random.default_rng(12), 3)
    prog, progc = eng.compile(hs), clean.compile(hc)
    xs = [jnp.asarray(rng.normal(size=(2, h.plan.n)), jnp.float32)
          for h in hs]
    outs, rep = prog.run(xs)
    outsc, repc = progc.run(xs)
    tr = rep.fault
    assert tr.corrupted > 0 and tr.detected == tr.corrupted
    assert rep.retry_wave_ops == tuple(tr.retry_wave_ops)
    for o, oc in zip(outs, outsc):
        if tr.unresolved:
            np.testing.assert_allclose(np.asarray(o), np.asarray(oc),
                                       rtol=2e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(o), np.asarray(oc))
    cost = eng.price_program(prog, batch=2, executed=rep)
    costc = clean.price_program(progc, batch=2, executed=repc)
    assert cost.retry_waves == len(tr.retry_wave_ops) > 0
    assert cost.t_retry == pytest.approx(
        sum(tr.retry_wave_ops) * eng.timing.t_op)
    # the retry term is EXACTLY the extra serialization over the clean run
    assert cost.t_total - cost.t_retry == pytest.approx(costc.t_total)
    d = cost.asdict()
    assert d["retry_waves"] == cost.retry_waves
    assert d["t_retry"] == cost.t_retry


# ---------------------------------------------------------------------------
# Quarantine + restage (persistent faults)
# ---------------------------------------------------------------------------

def test_persistent_fault_quarantines_and_restages_clean():
    """Sticky weak banks beat the retry budget; the engine quarantines
    them, the pool restages the matrix on healthy banks, and the NEXT
    launch is corruption-free and bit-exact."""
    w = jax.random.normal(KEY, (48, 40))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 48))
    clean = MVDRAMEngine(geom=GEOM)
    h0 = clean.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    out0, _ = clean.gemv(h0, x, backend=backends.SIM)
    # GEOM has 4 rank slots; rate chosen so SOME banks are weak, not all
    model = FaultModel(weak_cell_rate=0.004, weak_flip_prob=1.0, seed=11)
    geom_big = PudGeometry(subarray_cols=32, n_sub_max=16)
    clean_big = MVDRAMEngine(geom=geom_big)
    hb = clean_big.register("w", w, QuantSpec(bits=4),
                            a_spec=QuantSpec(bits=4))
    outb, _ = clean_big.gemv(hb, x, backend=backends.SIM)
    eng = MVDRAMEngine(geom=geom_big, fault_model=model,
                       fault_policy=FaultPolicy(max_wave_retries=1,
                                                quarantine_after=1,
                                                degrade_after=8))
    h = eng.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    out1, rep1 = eng.gemv(h, x, backend=backends.SIM)
    assert rep1.fault.unresolved            # retries could not fix sticky
    np.testing.assert_allclose(np.asarray(outb), np.asarray(out1),
                               rtol=2e-5, atol=1e-5)   # host recompute
    stats = eng.residency_stats()
    assert stats["fault_quarantines"] >= 1
    assert stats["quarantined_banks"] >= 1
    assert stats["fault_restages"] >= 1
    assert stats["quarantine_evictions"] >= 1
    assert eng.pool.quarantined()
    assert eng.pool.is_resident("w")        # restaged, not dropped
    # the restaged placement avoids every quarantined bank
    for cb in h.placement.banks:
        assert not eng.pool.is_quarantined(*cb)
    out2, rep2 = eng.gemv(h, x, backend=backends.SIM)
    assert rep2.fault.corrupted == 0        # healthy banks now
    np.testing.assert_array_equal(np.asarray(outb), np.asarray(out2))
    assert not eng.is_degraded(h)


def test_fault_storm_degrades_to_host_backend():
    """When every bank is weak, quarantine cannot help: past the fallback
    budget the linear degrades permanently to the host `jnp` backend and
    the sim backend keeps serving it (report None, jnp-exact outputs)."""
    w = jax.random.normal(KEY, (48, 40))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 48))
    model = FaultModel(weak_cell_rate=0.05, weak_flip_prob=1.0, seed=3)
    eng = MVDRAMEngine(geom=GEOM, fault_model=model,
                       fault_policy=FaultPolicy(max_wave_retries=1,
                                                quarantine_after=1,
                                                degrade_after=2))
    h = eng.register("w", w, QuantSpec(bits=4), a_spec=QuantSpec(bits=4))
    outj = backends.JNP.gemv(eng, h, x)
    for _ in range(3):
        out, rep = eng.gemv(h, x, backend=backends.SIM)
        np.testing.assert_allclose(np.asarray(outj), np.asarray(out),
                                   rtol=2e-5, atol=1e-5)
        if eng.is_degraded(h):
            break
    assert eng.is_degraded(h)
    stats = eng.residency_stats()
    assert stats["degraded_layers"] == ["w"]
    # degradation either exhausted the fallback budget or hit the
    # restage-failure fast path (every bank of the small rank quarantined)
    assert stats["fault_host_fallbacks"] >= 1
    assert (stats["fault_host_fallbacks"] >= 2
            or stats["quarantined_banks"] == GEOM.parallel_tiles)
    out, rep = eng.gemv(h, x, backend=backends.SIM)
    assert rep is None                      # no simulated stream anymore
    np.testing.assert_array_equal(np.asarray(outj), np.asarray(out))


def test_quarantine_bank_api():
    from repro.core.pud.residency import CapacityError, DramPool
    pool = DramPool(GEOM)
    eng = MVDRAMEngine(geom=GEOM, pool=pool)
    w = jax.random.normal(KEY, (20, 12))
    h = eng.register("w", w, QuantSpec(bits=2), a_spec=QuantSpec(bits=2))
    victim_bank = h.placement.banks[0]
    victims = pool.quarantine_bank(*victim_bank)
    assert victims == ["w"]
    assert pool.is_quarantined(*victim_bank)
    assert pool.quarantine_bank(*victim_bank) == []   # idempotent
    assert pool.stats()["quarantined_banks"] == 1
    assert pool.stats()["quarantine_evictions"] == 1
    # re-placement avoids the quarantined bank
    h2 = eng.register("w", w, QuantSpec(bits=2), a_spec=QuantSpec(bits=2))
    assert victim_bank not in set(h2.placement.banks)
    with pytest.raises(ValueError, match="no such bank"):
        pool.quarantine_bank(99, 99)
    # quarantining every slot leaves no healthy capacity
    for c in range(GEOM.channels):
        for b in range(GEOM.banks_per_channel):
            pool.quarantine_bank(c, b)
    with pytest.raises(CapacityError, match="quarantined"):
        pool.place("w2", [16], 1)


# ---------------------------------------------------------------------------
# Tier-1 smoke (satellite e): the whole ladder in one small run
# ---------------------------------------------------------------------------

def test_fault_injection_smoke():
    """Tier-1 smoke: transient injection fires, ABFT catches everything,
    retries restore exactness, the price carries the retry term."""
    w = jax.random.normal(KEY, (32, 24))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32))
    clean = MVDRAMEngine(geom=GEOM)
    hc = clean.register("w", w, QuantSpec(bits=3), a_spec=QuantSpec(bits=3))
    out0, _ = clean.gemv(hc, x, backend=backends.SIM)
    eng = MVDRAMEngine(geom=GEOM,
                       fault_model=FaultModel(transient_ber=0.2, seed=21),
                       fault_policy=FaultPolicy(max_wave_retries=6))
    h = eng.register("w", w, QuantSpec(bits=3), a_spec=QuantSpec(bits=3))
    out, rep = eng.gemv(h, x, backend=backends.SIM)
    tr = rep.fault
    assert tr.corrupted > 0 and tr.coverage == 1.0
    if not tr.unresolved:
        np.testing.assert_array_equal(np.asarray(out0), np.asarray(out))


def test_trace_merge():
    a = FaultTrace(corrupted=2, detected=2, retries=1, retry_wave_ops=[5],
                   unresolved=[(0, 0, 1)], unresolved_banks=[(0, 1)])
    b = FaultTrace(corrupted=1, detected=1, retries=2, retry_wave_ops=[7, 9],
                   unresolved=[(1, 2, 0)], unresolved_banks=[(0, 1), (1, 0)])
    a.merge(b)
    assert (a.corrupted, a.detected, a.retries) == (3, 3, 3)
    assert a.retry_wave_ops == [5, 7, 9]
    assert a.unresolved == [(0, 0, 1), (1, 2, 0)]
    assert a.unresolved_banks == [(0, 1), (1, 0)]   # deduped
