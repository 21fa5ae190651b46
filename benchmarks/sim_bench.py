"""Simulator + kernel-schedule benchmarks for the template architecture.

Measures (1) PUD-simulator GeMV wall-clock, naive micro-op oracle vs the
template-selected vectorized executor, on the paper-representative 512×256
q=4/p=4 shape — asserting the ≥20× acceptance floor and bit-identical
outputs/OpCounts; (2) wave-parallel BankArray dispatch vs the sequential
per-tile template path at banked geometry (256 tiles → 4 waves) — asserting
the ≥5× acceptance floor, bit-identical outputs AND per-tile OpCounts;
(3) cross-request wave sharing: one B=4 batched GeMV launch vs 4 sequential
launches at the same banked geometry — asserting the ≥2× amortization
floor, per-request outputs AND per-tile OpCounts bit-identical to the
sequential oracle, and `price_gemv_batched`'s amortized weight staging
reconciling with the simulator's shared-wave counts; (4) multi-layer
RESIDENT decode: a 4-layer block compiled into one `GemvProgram` (weights
staged once by the residency pool, q/k/v waves fused) vs per-layer
sequential staging — asserting the ≥1.5× wall-clock floor, bit-identical
outputs/per-tile runtime OpCounts, ZERO repeated weight staging, and exact
staging reconciliation against the pool placements; (5) FUSED wave-major
program execution (the simulator walks `schedule_program`'s fused slot
order directly, one batched step per global wave) vs the retained
layer-major oracle on the same 4-layer q4/p2 B=2 block — asserting the
≥1.3× floor, bit-identical outputs AND per-tile OpCounts, executed fused
waves == the compiled schedule's, and `price_program(executed=…)`
reconciling against the measured per-wave serialization; (6) per-command
ENERGY of the executed decode step (`EnergyModel`): `ProgramCost.e_total`
reconciled float-exactly against the simulator's per-command `OpCounts`
ledger on clean, faulted (`e_retry`) and CXL-spill (`e_spill`) runs, the
same step at the LPDDR5 (CD-PIM) geometry, the real-column energy ratio
vs the CPU baseline, and the speculative encode/wave overlap ratio
(layer k+1's host encode hidden under layer k's waves); and (7) the MXU
dots issued per tile by the bit-serial Pallas kernel's decomposed schedule
vs the §V-D code-dot fast path (q·p vs q), plus measured interpret-mode
wall-clock for both fidelities.

    PYTHONPATH=src python -m benchmarks.sim_bench --json
        runs everything and writes BENCH_sim.json (per-shape wall-clock +
        speedup ratios) so the perf trajectory is tracked across PRs.
    PYTHONPATH=src python -m benchmarks.sim_bench --json BENCH_new.json --smoke
        the pull-request gate: the (slow) Pallas-interpret kernel section
        is skipped. Benchmark SHAPES and the best-of-5 measurement are
        unchanged so every speedup/amortization row stays directly
        comparable to the committed full-run BENCH_sim.json baseline
        (`benchmarks/check_regression.py --max-drop`).
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.bitplane import make_bitplane_weights
from repro.core.engine import MVDRAMEngine
from repro.core.pud.gemv import PudGeometry, mvdram_gemv, mvdram_gemv_cost
from repro.core.pud.timing import (price_gemv, price_gemv_batched,
                                   simulated_wave_time)
from repro.core.quant import (QuantSpec, quantize_activations,
                              quantize_weights)

N, M, Q, P = 512, 256, 4, 4
# Banked geometry for the wave benchmark: 16 reduction chunks × 16 column
# chunks = 256 tiles over 64 concurrent subarrays → 4 waves.
BANKED = PudGeometry(subarray_cols=64, n_sub_max=32)

# measurement repetitions (best-of-N). The fast denominators (wave/fused
# paths, ~5-10 ms) are the noisy side of every ratio; best-of-5 converges
# them to the true min closely enough for the PR gate's 25% drop threshold
# (single-rep and best-of-3 measurements were observed to swing >25% under
# runner load). --smoke keeps N=5 so smoke rows compare like-for-like
# against the committed full-run baseline.
_REPS = 5


# Measured-timing floors are hard asserts on full runs. Under --smoke they
# are tolerated (printed, not fatal): the PR gate takes the per-row BEST
# of two independent smoke runs precisely because one run can hit a
# transient contention window — an in-run fatal assert would abort before
# the second run could absorb it. Correctness asserts (bit-identity,
# reconciliation) are ALWAYS fatal; only wall-clock floors soften.
_FLOORS_FATAL = True


def _assert_floor(value: float, floor: float, msg: str) -> None:
    if value >= floor:
        return
    if _FLOORS_FATAL:
        raise AssertionError(msg)
    print(f"# smoke: tolerated measured-floor miss ({msg}); "
          f"the cross-run regression gate decides")


def _best_of(fn, reps: int | None = None):
    best, ret = float("inf"), None
    for _ in range(reps if reps is not None else _REPS):
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        if dt < best:
            best, ret = dt, out
    return best, ret


def sim_vectorized_vs_naive(emit):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    wq = quantize_weights(w, QuantSpec(bits=Q))
    aq = quantize_activations(a, QuantSpec(bits=P))

    t0 = time.perf_counter()
    out_v, rep_v = mvdram_gemv(aq, wq)
    t_vec = time.perf_counter() - t0
    t0 = time.perf_counter()
    out_n, rep_n = mvdram_gemv(aq, wq, naive=True)
    t_naive = time.perf_counter() - t0

    bit_identical = (np.array_equal(np.asarray(out_v), np.asarray(out_n))
                     and rep_v.runtime.asdict() == rep_n.runtime.asdict())
    speedup = t_naive / t_vec
    emit("sim.naive_512x256_q4p4_ms", t_naive * 1e3)
    emit("sim.vectorized_512x256_q4p4_ms", t_vec * 1e3)
    emit("sim.vectorized_speedup_x", speedup,
         f"bit_identical={bit_identical} pud_ops={rep_v.runtime.pud_ops}")
    assert bit_identical, "vectorized sim diverged from the naive oracle"
    _assert_floor(speedup, 20.0,
                  f"speedup {speedup:.1f}x below the 20x floor")


def sim_wave_vs_sequential(emit):
    """Wave-parallel BankArray dispatch vs the sequential template path at
    banked geometry — the §VII channel/bank concurrency win on top of PR 1's
    template vectorization."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(N,)), jnp.float32)
    wq = quantize_weights(w, QuantSpec(bits=Q))
    aq = quantize_activations(a, QuantSpec(bits=P))

    mvdram_gemv(aq, wq, geom=BANKED)  # warm template/plan caches
    t_wave, (out_w, rep_w) = _best_of(lambda: mvdram_gemv(aq, wq, geom=BANKED))
    t_seq, (out_s, rep_s) = _best_of(
        lambda: mvdram_gemv(aq, wq, geom=BANKED, wave=False))

    bit_identical = (
        np.array_equal(np.asarray(out_w), np.asarray(out_s))
        and [c.asdict() for c in rep_w.tile_runtime]
            == [c.asdict() for c in rep_s.tile_runtime]
        and rep_w.runtime.asdict() == rep_s.runtime.asdict())
    speedup = t_seq / t_wave
    emit("sim.sequential_banked_512x256_q4p4_ms", t_seq * 1e3)
    emit("sim.wave_banked_512x256_q4p4_ms", t_wave * 1e3)
    emit("sim.wave_speedup_x", speedup,
         f"bit_identical={bit_identical} tiles={rep_w.tiles} "
         f"waves={rep_w.waves}")
    assert bit_identical, "wave sim diverged from the sequential oracle"
    assert rep_w.waves == 4, f"expected 4 waves, got {rep_w.waves}"
    _assert_floor(speedup, 5.0,
                  f"speedup {speedup:.1f}x below the 5x floor")


def sim_batched_wave_sharing(emit):
    """Cross-request wave sharing: B=4 activation vectors against one
    resident matrix in shared waves vs 4 independent sequential launches.
    The per-wave weight staging happens once for the batch; outputs and
    per-tile OpCounts of every request must be bit-identical to its
    sequential-oracle run, and the analytic `price_gemv_batched` must
    reconcile with the simulator's shared-wave staging counts."""
    B = 4
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    A = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    wq = quantize_weights(w, QuantSpec(bits=Q))
    aqb = quantize_activations(A, QuantSpec(bits=P))
    aqs = [quantize_activations(A[b], QuantSpec(bits=P)) for b in range(B)]

    mvdram_gemv(aqb, wq, geom=BANKED)   # warm template/plan caches
    mvdram_gemv(aqs[0], wq, geom=BANKED)
    t_batch, (out_b, rep) = _best_of(
        lambda: mvdram_gemv(aqb, wq, geom=BANKED))
    t_seq, seq = _best_of(
        lambda: [mvdram_gemv(a, wq, geom=BANKED) for a in aqs])

    bit_identical = all(
        np.array_equal(np.asarray(out_1), np.asarray(out_b[b]))
        and [c.asdict() for c in rep_1.tile_runtime]
            == [c.asdict() for c in rep.requests[b].tile_runtime]
        and rep_1.runtime.asdict() == rep.requests[b].runtime.asdict()
        and rep_1.preload.asdict() == rep.requests[b].preload.asdict()
        for b, (out_1, rep_1) in enumerate(seq))

    # analytic shared-wave pricing reconciles with the simulated counts
    cost = mvdram_gemv_cost(M, N, Q, P, geom=BANKED,
                            usable_cols=BANKED.subarray_cols)
    priced = price_gemv_batched(cost, B, geom=BANKED)
    staging_match = (rep.shared_preload.host_bits_written
                     == cost.weight_load_bits == priced.weight_load_bits)
    # non-tautological: the batch ledger must equal the INDEPENDENT
    # sequential-oracle runs' command totals
    runtime_match = rep.runtime.pud_ops == sum(
        r1.runtime.pud_ops for (_o, r1) in seq)

    amortization = t_seq / t_batch
    emit("sim.sequential_b4_banked_512x256_q4p4_ms", t_seq * 1e3)
    emit("sim.batched_b4_banked_512x256_q4p4_ms", t_batch * 1e3)
    emit("sim.batch_amortization_x", amortization,
         f"bit_identical={bit_identical} waves={rep.waves} "
         f"shared_preload_bits={rep.shared_preload.host_bits_written} "
         f"amortized_bits={rep.amortized_preload_bits}")
    emit("sim.batch_price_amortization_x", priced.amortization,
         f"staging_match={staging_match} runtime_match={runtime_match}")
    assert bit_identical, "batched GeMV diverged from the sequential oracle"
    assert staging_match, "analytic weight staging != simulated shared counts"
    assert runtime_match, "batch runtime != sum of per-request runtimes"
    assert rep.waves == 4, f"expected 4 waves, got {rep.waves}"
    assert rep.schedule.reuse_factor == B
    _assert_floor(amortization, 2.0,
                  f"amortization {amortization:.2f}x below the 2x floor")


def _resident_block(seed: int = 5, B: int = 2, q_b: int = 4, p_b: int = 2,
                    fault_model=None, fault_policy=None):
    """The 4-layer q4/p2 B=2 resident block (q/k/v-style group of three
    512→256 linears + a 256→512 down projection) shared by the resident,
    fused-execution and fault-injection benchmarks."""
    rng = np.random.default_rng(seed)
    eng = MVDRAMEngine(geom=BANKED, fault_model=fault_model,
                       fault_policy=fault_policy)
    shapes = [(N, M), (N, M), (N, M), (M, N)]
    hs = []
    for i, (n, m) in enumerate(shapes):
        w = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        hs.append(eng.register(f"layer{i}", w, QuantSpec(bits=q_b),
                               a_spec=QuantSpec(bits=p_b)))
    prog = eng.compile(hs, groups=[[0, 1, 2], [3]])
    X = [jnp.asarray(rng.normal(size=(B, n)), jnp.float32)
         for (n, _m) in shapes]
    return eng, hs, prog, X


def sim_resident_decode(emit):
    """Multi-layer resident decode (residency sessions, ISSUE 4): a 4-layer
    block — a q/k/v-style concurrency group of three 512→256 linears plus a
    256→512 down projection, q=4/p=2, B=2 lanes — compiled into one
    `GemvProgram` whose weights were staged ONCE at placement, vs the same
    four GeMVs launched sequentially with per-call staging. Outputs and
    per-tile runtime OpCounts must be bit-identical; the resident step must
    re-stage NOTHING (reconciled exactly against the pool placements and
    the per-call oracle's preload); measured wall-clock amortization and
    the priced residency speedup (real-DRAM columns, fused q/k/v waves)
    must clear the ≥1.5× floor."""
    B, p_b = 2, 2
    eng, hs, prog, X = _resident_block(B=B, p_b=p_b)
    aqs = [quantize_activations(x, QuantSpec(bits=p_b)) for x in X]

    def run_seq():
        return [mvdram_gemv(aq, h.wq, geom=BANKED, templates=h.templates)
                for aq, h in zip(aqs, hs)]

    prog.run(X)     # warm: staging done, caches hot
    run_seq()
    t_prog, (outs, prep) = _best_of(lambda: prog.run(X))
    t_seq, refs = _best_of(run_seq)

    bit_identical = all(
        np.array_equal(np.asarray(out), np.asarray(o_ref))
        and [c.asdict() for c in rep.requests[b].tile_runtime]
            == [c.asdict() for c in r_ref.requests[b].tile_runtime]
        for out, rep, (o_ref, r_ref) in zip(outs, prep.reports, refs)
        for b in range(B))
    zero_restaging = (prep.repeated_staging.host_bits_written == 0
                      and all(r.shared_preload.host_bits_written == 0
                              for r in prep.reports))
    # exact three-way staging reconciliation: program == pool placements ==
    # what the per-call oracle re-pays every launch
    staged = prep.staged.host_bits_written
    staging_match = (
        staged == sum(h.placement.staged.host_bits_written for h in hs)
        == sum(r_ref.shared_preload.host_bits_written for _o, r_ref in refs))
    priced = eng.price_program(prog, batch=B, usable_cols=BANKED.real_cols)

    amortization = t_seq / t_prog
    emit("sim.resident_seq_4layer_q4p2_b2_ms", t_seq * 1e3)
    emit("sim.resident_program_4layer_q4p2_b2_ms", t_prog * 1e3)
    emit("sim.resident_amortization_x", amortization,
         f"bit_identical={bit_identical} zero_restaging={zero_restaging} "
         f"staged_bits={staged} staging_match={staging_match}")
    emit("sim.resident_price_speedup_x", priced.residency_speedup,
         f"waves={priced.waves} waves_shared={priced.waves_shared} "
         f"weight_load_bits={priced.weight_load_bits}")
    assert bit_identical, "resident program diverged from per-layer oracle"
    assert zero_restaging, "resident decode step re-staged weight rows"
    assert staging_match, "placement staging != oracle preload accounting"
    assert priced.weight_load_bits == 0
    _assert_floor(amortization, 1.5,
                  f"amortization {amortization:.2f}x below the 1.5x floor")
    assert priced.residency_speedup >= 1.5, \
        f"priced speedup {priced.residency_speedup:.2f}x below the 1.5x floor"


def sim_fused_program(emit):
    """Fused cross-layer wave execution (ISSUE 5): the same 4-layer q4/p2
    B=2 resident block, decoded by walking the compiled `ProgramSchedule`'s
    fused slot order directly — one batched simulator step per global wave,
    heterogeneous layouts sharing boundary waves — vs the retained
    layer-major oracle. Outputs and per-tile OpCounts must be bit-identical,
    execution must run exactly the waves the schedule fused (reconciled into
    `price_program(executed=…)`), and the measured wall-clock speedup must
    clear the ≥1.3× floor."""
    B = 2
    eng, hs, prog, X = _resident_block(B=B)

    prog.run(X)                      # warm: staging + fused plan built
    prog.run(X, layer_major=True)
    t_fused, (outs_f, rep_f) = _best_of(lambda: prog.run(X))
    t_layer, (outs_l, rep_l) = _best_of(
        lambda: prog.run(X, layer_major=True))

    # bit-exactness vs the layer-major oracle: outputs AND per-(request,
    # tile) runtime OpCounts (report materialization is lazy — outside the
    # timed region for the fused path, as in a real decode loop)
    bit_identical = all(
        np.array_equal(np.asarray(of), np.asarray(ol))
        and [c.asdict() for c in rf.requests[b].tile_runtime]
            == [c.asdict() for c in rl.requests[b].tile_runtime]
        and rf.runtime.asdict() == rl.runtime.asdict()
        for of, rf, ol, rl in zip(outs_f, rep_f.reports, outs_l,
                                  rep_l.reports)
        for b in range(B))
    executed_match = rep_f.fused and rep_f.waves == prog.sched.waves
    # the program price's bank term now reconciles against the EXECUTED
    # fused-wave serialization, not the scheduled estimate
    priced = eng.price_program(prog, batch=B, executed=rep_f)
    t_sim = simulated_wave_time(rep_f)
    price_reconciles = priced.t_compute >= t_sim > 0.0

    speedup = t_layer / t_fused
    emit("sim.layer_major_4layer_q4p2_b2_ms", t_layer * 1e3)
    emit("sim.fused_wave_4layer_q4p2_b2_ms", t_fused * 1e3)
    emit("sim.fused_wave_speedup_x", speedup,
         f"bit_identical={bit_identical} waves={rep_f.waves} "
         f"scheduled={prog.sched.waves} shared={prog.sched.waves_shared} "
         f"t_sim_us={t_sim * 1e6:.1f}")
    assert bit_identical, "fused execution diverged from layer-major oracle"
    assert executed_match, (
        f"executed {rep_f.waves} fused waves, schedule has "
        f"{prog.sched.waves}")
    assert price_reconciles, "executed-wave pricing failed to reconcile"
    _assert_floor(speedup, 1.3,
                  f"fused speedup {speedup:.2f}x below the 1.3x floor")


def sim_fault_injection(emit):
    """Fault-injected PUD (ISSUE 6): seeded MAJX fault injection under the
    ABFT checksum verifier. Three rows: (1) detection coverage at a fixed
    transient BER over resident decode steps of the 4-layer block — every
    injection is a single-bit column flip, so the GeMV-linearity checksum
    must catch 100% (the ≥99% acceptance floor is a hard assert); (2) the
    priced retry overhead — faulty-step `t_total` (executed reconciliation
    including the `t_retry` term) over the clean step's; (3) degraded-mode
    throughput — a persistent fault storm degrades a linear to the host
    `jnp` backend through quarantine + fallback budgets, and the degraded
    step (still serving, correct results) is timed against the healthy
    simulated step."""
    from repro.core import backends
    from repro.core.pud.faults import FaultModel, FaultPolicy

    B, p_b = 2, 2
    # ① + ② transient BER on the resident block (~2048 (request, tile)
    # cells per decode step)
    fm = FaultModel(transient_ber=2e-3, seed=17)
    eng_f, _hs_f, prog_f, X = _resident_block(
        B=B, p_b=p_b, fault_model=fm,
        fault_policy=FaultPolicy(max_wave_retries=4, degrade_after=10**6))
    eng_c, _hs_c, prog_c, _ = _resident_block(B=B, p_b=p_b)
    outs_c, rep_c = prog_c.run(X)
    corrupted = detected = 0
    rep_retry = None
    for _ in range(12):
        outs, rep = prog_f.run(X)
        tr = rep.fault
        corrupted += tr.corrupted
        detected += tr.detected
        if tr.retries and not tr.unresolved:
            rep_retry = rep
            for o, oc in zip(outs, outs_c):
                assert np.array_equal(np.asarray(o), np.asarray(oc)), \
                    "retried decode step diverged from the clean block"
    assert corrupted > 0, "transient BER never fired — raise the cell count"
    coverage = detected / corrupted
    emit("sim.fault_detection_coverage", coverage,
         f"corrupted={corrupted} detected={detected} ber=2e-3 "
         f"(single-bit flips: coverage is exact)")
    assert coverage >= 0.99, \
        f"ABFT coverage {coverage:.4f} below the 0.99 acceptance floor"
    assert rep_retry is not None, "no fully-retried step to price"
    cost_c = eng_c.price_program(prog_c, batch=B, executed=rep_c)
    cost_f = eng_f.price_program(prog_f, batch=B, executed=rep_retry)
    assert cost_f.t_retry > 0.0
    assert abs((cost_f.t_total - cost_f.t_retry) - cost_c.t_total) \
        <= 1e-9 * cost_c.t_total, "retry term failed to reconcile"
    overhead = cost_f.t_total / cost_c.t_total
    emit("sim.fault_retry_overhead_x", overhead,
         f"retry_waves={cost_f.retry_waves} t_retry_us="
         f"{cost_f.t_retry * 1e6:.1f}")

    # ③ persistent fault storm → quarantine → host degradation, still serving
    storm = FaultModel(weak_cell_rate=0.05, weak_flip_prob=1.0, seed=23)
    pol = FaultPolicy(max_wave_retries=1, quarantine_after=1, degrade_after=1)
    eng_s = MVDRAMEngine(geom=BANKED, fault_model=storm, fault_policy=pol)
    rng = np.random.default_rng(29)
    w = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(B, N)), jnp.float32)
    h_s = eng_s.register("w", w, QuantSpec(bits=Q), a_spec=QuantSpec(bits=p_b))
    eng_s.gemv(h_s, x, backend=backends.SIM)        # trips the full ladder
    assert eng_s.is_degraded(h_s), "fault storm failed to degrade the linear"
    st = eng_s.residency_stats()
    eng_h = MVDRAMEngine(geom=BANKED)
    h_h = eng_h.register("w", w, QuantSpec(bits=Q), a_spec=QuantSpec(bits=p_b))
    eng_h.gemv(h_h, x, backend=backends.SIM)        # warm caches
    t_sim, (out_sim, _r) = _best_of(
        lambda: eng_h.gemv(h_h, x, backend=backends.SIM))
    eng_s.gemv(h_s, x, backend=backends.SIM)        # warm the jnp route
    t_deg, (out_deg, rep_deg) = _best_of(
        lambda: eng_s.gemv(h_s, x, backend=backends.SIM))
    assert rep_deg is None                          # host route, no sim stream
    np.testing.assert_allclose(np.asarray(out_sim), np.asarray(out_deg),
                               rtol=2e-5, atol=1e-5)
    ratio = t_sim / t_deg
    emit("sim.fault_degraded_throughput_x", ratio,
         f"degraded (host jnp) step vs healthy simulated step; "
         f"quarantined_banks={st['quarantined_banks']} "
         f"fallbacks={st['fault_host_fallbacks']} still_correct=True")
    assert ratio > 0.0


def sim_energy_overlap(emit):
    """Per-command energy accounting + speculative encode overlap (ISSUE
    10), four rows on the 4-layer q4/p2 B=2 resident block: (1) the
    DDR4-priced energy of one EXECUTED decode step (`ProgramCost.e_total`),
    reconciled EXACTLY — float-equal, not approximate — against the
    per-command `OpCounts` ledger the simulator billed (activate/precharge
    per MAJX/RowCopy, readout + staging bus bits, host encode ops, idle
    draw over the step); (2) the same executed ledger re-priced at the
    LPDDR5 (CD-PIM) energy geometry; (3) the paper-scale energy ratio —
    CPU-baseline step energy over the MVDRAM step priced at real DRAM
    columns (the tiny 64-col bench geometry would overstate the DRAM
    side); (4) the speculative encode/wave overlap — layer k+1's host
    activation encode runs under layer k's waves, so the measured pipeline
    exposes only `t_encode_extra` of the full `t_encode`, and
    `encode_overlap_speedup` is what a host that serialized every encode
    in front of compute would pay instead. Exact reconciliation is
    additionally asserted on a FAULTED run (the retry ledger re-bills
    per-command as `e_retry`) and a CXL SPILL run (page-in bits as
    `e_spill`)."""
    from benchmarks.fabric_bench import (SPILL_GEOM, SPILL_LAYERS,
                                         SPILL_RESERVE)
    from repro.core.pud.device import _COUNT_FIELDS, OpCounts
    from repro.core.pud.fabric import FabricPool
    from repro.core.pud.faults import FaultModel, FaultPolicy
    from repro.core.pud.timing import (DDR4_ENERGY, LPDDR5_CDPIM,
                                       CpuBaseline)

    def expected_energy(cost, rep, energy):
        # mirrors price_program's executed branch COMPONENT ORDER exactly,
        # so the equalities below are float-bit equality, not tolerance
        retry_c = rep.retry_counts
        base_c = OpCounts(*(getattr(rep.executed_counts, f)
                            - getattr(retry_c, f) for f in _COUNT_FIELDS))
        e_pud = energy.pud_energy(base_c)
        e_io = energy.io_energy(base_c.host_bits_read
                                + base_c.host_bits_written)
        e_host = (energy.host_energy(base_c.host_int_ops)
                  + energy.idle_power * cost.t_compute)
        e_retry = energy.ledger_energy(retry_c)
        e_spill = energy.io_energy(cost.spill_restage_bits)
        return e_pud + e_io + e_host + e_retry + e_spill

    B, q_b, p_b = 2, 4, 2
    eng, hs, prog, X = _resident_block(B=B, q_b=q_b, p_b=p_b)
    outs, rep = prog.run(X)
    assert rep.executed_counts is not None, "fused run must carry a ledger"
    cost = eng.price_program(prog, batch=B, executed=rep)
    assert cost.e_retry == 0.0 and cost.e_spill == 0.0
    assert cost.e_total == expected_energy(cost, rep, DDR4_ENERGY), \
        "priced e_total diverged from the executed per-command ledger"
    emit("sim.energy_step_ddr4_j", cost.e_total,
         f"per-command DDR4 ledger: e_pud={cost.e_pud:.3g} "
         f"e_io={cost.e_io:.3g} e_host={cost.e_host:.3g} (exact)")

    # ② the same executed ledger at the LPDDR5 (CD-PIM) energy geometry
    eng.energy = LPDDR5_CDPIM
    try:
        cost_lp = eng.price_program(prog, batch=B, executed=rep)
    finally:
        eng.energy = DDR4_ENERGY
    assert cost_lp.e_total == expected_energy(cost_lp, rep, LPDDR5_CDPIM)
    assert 0.0 < cost_lp.e_total < cost.e_total, \
        "LPDDR5 (CD-PIM) step energy should undercut DDR4"
    emit("sim.energy_step_lpddr5_j", cost_lp.e_total,
         "same executed ledger at the LPDDR5 (CD-PIM) energy geometry")

    # ③ paper-scale ratio vs the CPU baseline. The bench block's 512→256
    # layers fill 3% of a real 8192-column DRAM row, so at real geometry
    # their per-command energy honestly LOSES to the CPU — MVDRAM's win is
    # an LLM-scale effect. Price the paper's anchor GeMV shape (32000×4096,
    # the A2/A3 matrix) per-command at real columns instead: analytic and
    # registration-free, so paper scale costs nothing to evaluate.
    m_a, n_a = 32000, 4096
    mv = mvdram_gemv_cost(m_a, n_a, q_b, p_b, geom=BANKED)
    pc = price_gemv(mv, BANKED)
    e_mv = (DDR4_ENERGY.pud_energy(mv.runtime)
            + DDR4_ENERGY.io_energy(mv.runtime.host_bits_read
                                    + mv.runtime.host_bits_written)
            + DDR4_ENERGY.host_energy(mv.runtime.host_int_ops
                                      + mv.encode_host_ops)
            + DDR4_ENERGY.idle_power * pc.t_compute)
    e_cpu = CpuBaseline().gemv_energy(m_a, n_a, q_b, p_b)
    ratio = e_cpu / e_mv
    emit("sim.energy_ratio_vs_cpu", ratio,
         f"CPU {e_cpu:.3g} J / MVDRAM {e_mv:.3g} J on the paper-scale "
         f"{m_a}x{n_a} q{q_b}/p{p_b} anchor GeMV (per-command, real cols)")
    assert ratio > 1.0, \
        f"MVDRAM anchor-GeMV energy should beat the CPU, got {ratio:.3f}x"

    # ④ speculative encode overlap: deterministic priced pipeline ratio
    assert cost.t_encode > 0.0
    speedup = cost.encode_overlap_speedup
    emit("sim.overlap_speedup_x", speedup,
         f"t_encode={cost.t_encode * 1e6:.1f}us exposed="
         f"{cost.t_encode_extra * 1e6:.1f}us (layer k+1 encodes under "
         f"layer k's waves)")
    assert speedup > 1.0, \
        f"speculative encode overlap bought nothing: {speedup:.5f}x"

    # faulted run: the retry ledger re-bills per-command as e_retry
    fm = FaultModel(transient_ber=2e-3, seed=17)
    eng_f, _hs_f, prog_f, _ = _resident_block(
        B=B, q_b=q_b, p_b=p_b, fault_model=fm,
        fault_policy=FaultPolicy(max_wave_retries=4, degrade_after=10**6))
    rep_retry = None
    for _ in range(12):
        _outs_f, rep_f = prog_f.run(X)
        if rep_f.fault.retries and not rep_f.fault.unresolved:
            rep_retry = rep_f
            break
    assert rep_retry is not None, "transient BER never forced a retry"
    cost_f = eng_f.price_program(prog_f, batch=B, executed=rep_retry)
    assert cost_f.e_retry > 0.0
    assert cost_f.e_total == expected_energy(cost_f, rep_retry,
                                             DDR4_ENERGY), \
        "faulted-run e_total failed exact reconciliation (e_retry term)"

    # spill run: CXL page-in bits land as e_spill, still exact
    rng = np.random.default_rng(7)
    ws = [jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
          for _ in range(SPILL_LAYERS)]
    pool = FabricPool(geom=SPILL_GEOM, dimms=1,
                      compute_reserve=SPILL_RESERVE)
    eng_s = MVDRAMEngine(geom=SPILL_GEOM, pool=pool, on_full="spill")
    hs_s = [eng_s.register(f"l{i}", w, QuantSpec(bits=4),
                           a_spec=QuantSpec(bits=4))
            for i, w in enumerate(ws)]
    prog_s = eng_s.compile([h.name for h in hs_s])
    Xs = [jnp.asarray(rng.normal(size=(16,)), jnp.float32) for _ in ws]
    _outs_s, rep_s = prog_s.run(Xs)
    assert rep_s.spill_restage_bits > 0
    cost_s = prog_s.price(batch=1, executed=rep_s)
    assert cost_s.spill_restage_bits == rep_s.spill_restage_bits
    assert cost_s.e_spill == DDR4_ENERGY.io_energy(rep_s.spill_restage_bits)
    assert cost_s.e_spill > 0.0
    # per-PART exactness (the fabric total re-sums the parts in a
    # different float order, so the part is the bit-exact unit)
    for pc_k, rep_k in zip(cost_s.parts, rep_s.parts):
        assert rep_k.executed_counts is not None
        assert pc_k.e_total == expected_energy(pc_k, rep_k, DDR4_ENERGY), \
            "spill-part e_total failed exact reconciliation (e_spill term)"


def kernel_dots_issued(emit):
    from repro.kernels.bitplane_gemv import ops as bp
    from repro.kernels.bitplane_gemv.kernel import dots_per_tile

    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=(N, M)), jnp.float32)
    a = jnp.asarray(rng.normal(size=(4, N)), jnp.float32)
    bw = make_bitplane_weights(w, QuantSpec(bits=Q))
    spec = QuantSpec(bits=P)
    tn = bp._pick_blocks(N, M, q=Q, rows=4)[0]
    tile = dict(tn=tn, z_a=spec.zero_point)
    emit("kernel.bitserial_dots_per_tile",
         dots_per_tile(Q, P, "bitserial", **tile))
    emit("kernel.code_dots_per_tile", dots_per_tile(Q, P, "code", **tile),
         "the §V-D linearity collapse on both operands: 1 instead of q·p")
    outs = {}
    for fid in ("bitserial", "code"):
        def f(x, fid=fid):
            return bp.bitplane_gemv_bitserial(x, bw, spec,
                                              impl="pallas_interpret",
                                              fidelity=fid)
        f(a).block_until_ready()               # compile outside the timer
        t0 = time.perf_counter()
        for _ in range(5):
            out = f(a)
        out.block_until_ready()
        outs[fid] = out
        emit(f"kernel.{fid}_interpret_us", (time.perf_counter() - t0) / 5 * 1e6)
    rel = float(jnp.abs(outs["code"] - outs["bitserial"]).max()
                / (jnp.abs(outs["bitserial"]).max() + 1e-9))
    emit("kernel.code_vs_bitserial_relerr", rel, "must be <= 1e-4")
    assert rel <= 1e-4


def kernel_program(emit):
    """Fused whole-block Pallas decode kernel (ISSUE 8): a compiled
    program executed as ONE Pallas launch walking its schedule
    (`kernels/bitplane_gemv/program.py`, `GemvProgram.run_kernel`) vs the
    per-leaf path — one jitted `bitplane_gemv_bitserial` dispatch per
    weight, the ~L launches a decode block cost before.

    Correctness is asserted on the HETEROGENEOUS 4-layer resident block
    (ragged bn, grouped q/k/v, the hard case for the one-launch padding
    algebra): bit-identical outputs and exactly ONE trace-time launch.
    The speedup row is timed on a uniform 8-layer thin block (256->128,
    q2/p2, B=2) where the fused envelope pads nothing, so fused and
    per-leaf execute IDENTICAL integer work and the row isolates what
    fusion actually buys: L-1 avoided host dispatches per decode step
    plus one batched activation quantization — the B<=8 dispatch-bound
    decode regime the program path exists for. (The resident block's
    mixed bn would hide that behind envelope-padding MACs: its layer-3
    tiles pad 256->512 and interpret-mode compute swamps dispatch.)"""
    from repro.kernels.bitplane_gemv import ops as bp
    from repro.kernels.bitplane_gemv import program as bp_prog

    B, p_b = 2, 2
    eng, hs, prog, X = _resident_block(B=B, p_b=p_b)
    spec = QuantSpec(bits=p_b)

    def per_leaf():
        outs = [bp.bitplane_gemv_bitserial(x, h.weights, spec,
                                           impl="pallas_interpret")
                for x, h in zip(X, hs)]
        outs[-1].block_until_ready()
        return outs

    def fused():
        outs = prog.run_kernel(X, interpret=True)
        outs[-1].block_until_ready()
        return outs

    l0 = bp_prog.LAUNCHES
    outs_f = fused()                  # first call traces the ONE launch
    launches = bp_prog.LAUNCHES - l0
    outs_l = per_leaf()
    bit_identical = all(np.array_equal(np.asarray(a), np.asarray(b))
                        for a, b in zip(outs_f, outs_l))
    assert bit_identical, "fused program kernel != per-leaf outputs"
    assert launches == 1, f"{launches} launches for one decode block"

    # dispatch-bound timing block: uniform layers, zero envelope padding
    L_u, n_u, m_u = 8, 256, 128
    rng = np.random.default_rng(11)
    eng_u = MVDRAMEngine(geom=BANKED)
    hs_u, X_u = [], []
    for i in range(L_u):
        w = jnp.asarray(rng.normal(size=(n_u, m_u)), jnp.float32)
        hs_u.append(eng_u.register(f"uni{i}", w, QuantSpec(bits=2),
                                   a_spec=QuantSpec(bits=2)))
        X_u.append(jnp.asarray(rng.normal(size=(B, n_u)), jnp.float32))
    prog_u = eng_u.compile(hs_u, groups=[list(range(L_u))])
    spec_u = QuantSpec(bits=2)

    STEPS = 10                        # steady-state decode loop per rep:
                                      # single-step timings swing 2-3x with
                                      # host dispatch jitter; amortizing 10
                                      # steps per measurement stabilizes the
                                      # ratio the gate tracks

    def per_leaf_u():
        for _ in range(STEPS):
            outs = [bp.bitplane_gemv_bitserial(x, h.weights, spec_u,
                                               impl="pallas_interpret")
                    for x, h in zip(X_u, hs_u)]
        outs[-1].block_until_ready()
        return outs

    def fused_u():
        for _ in range(STEPS):
            outs = prog_u.run_kernel(X_u, interpret=True)
        outs[-1].block_until_ready()
        return outs

    outs_fu = fused_u()               # warm (pack weights + trace)
    outs_lu = per_leaf_u()
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(outs_fu, outs_lu)), \
        "uniform-block fused kernel != per-leaf outputs"

    t_fused, _ = _best_of(fused_u)
    t_leaf, _ = _best_of(per_leaf_u)
    t_fused, t_leaf = t_fused / STEPS, t_leaf / STEPS
    speedup = t_leaf / t_fused
    emit("kernel.program_launches_per_block", launches,
         "trace-time pallas_call count on the fused 4-layer resident block")
    emit("kernel.program_decode_ms", t_fused * 1e3,
         "one fused launch for the whole 8-layer uniform decode block")
    emit("kernel.program_perleaf_ms", t_leaf * 1e3,
         "the per-leaf path: one jitted dispatch per weight leaf")
    emit("kernel.program_fusion_speedup_x", speedup,
         "per-leaf dispatch / fused whole-block launch wall-clock")
    _assert_floor(speedup, 1.3,
                  f"program fusion speedup {speedup:.2f}x below 1.3x floor")


from benchmarks.fabric_bench import sim_fabric  # noqa: E402
from benchmarks.serve_traffic import sim_serve_traffic  # noqa: E402

ALL = [sim_vectorized_vs_naive, sim_wave_vs_sequential,
       sim_batched_wave_sharing, sim_resident_decode, sim_fused_program,
       sim_fault_injection, sim_energy_overlap, sim_serve_traffic,
       sim_fabric, kernel_dots_issued, kernel_program]

# skipped under --smoke: Pallas interpret-mode timing is the long pole and
# emits no gated ratio rows. The serve-traffic horizon stays in smoke:
# its rows are require-rows-guarded (not drop-gated), but its internal
# bit-exactness/price-reconciliation asserts surface as recorded errors
# the PR gate fails on. `kernel_program` also stays in smoke: its
# `kernel.program_fusion_speedup_x` row IS drop-gated, and the PR gate
# fails on a gated baseline row missing from the new runs.
_SLOW = {kernel_dots_issued}


# ---------------------------------------------------------------------------
# Machine-readable output: BENCH_sim.json tracks the perf trajectory
# ---------------------------------------------------------------------------

def main() -> None:
    import argparse
    import json
    import platform

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", nargs="?", const="BENCH_sim.json",
                    default=None, metavar="PATH",
                    help="write per-shape wall-clock + speedup rows as JSON "
                         "(default path: BENCH_sim.json)")
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark function names")
    ap.add_argument("--smoke", action="store_true",
                    help="pull-request gate config: the slow Pallas-"
                         "interpret kernel section is skipped; simulator "
                         "shapes and best-of-5 measurement are unchanged "
                         "so every speedup row stays directly comparable "
                         "to the committed full-run baseline")
    args = ap.parse_args()

    if args.smoke:
        global _FLOORS_FATAL
        _FLOORS_FATAL = False

    rows: list = []

    def emit(name, value, derived=""):
        rows.append({"name": name, "value": value, "derived": derived})
        v = f"{value:.6g}" if isinstance(value, float) else value
        print(f"{name},{v},{derived}")

    errors = []
    for fn in ALL:
        if args.only and args.only not in fn.__name__:
            continue
        if args.smoke and fn in _SLOW:
            continue
        try:
            fn(emit)
        except Exception as e:  # noqa: BLE001 — record and continue
            errors.append({"bench": fn.__name__, "error": repr(e)[:200]})
            print(f"{fn.__name__}.ERROR,0,{repr(e)[:200]}")
    if args.json:
        doc = {
            "schema": 1,
            "suite": "sim_bench",
            "platform": platform.platform(),
            "python": platform.python_version(),
            "rows": rows,
            "errors": errors,
            "speedups": {r["name"]: r["value"] for r in rows
                         if r["name"].endswith(("_x", "_speedup"))},
            "wall_clock_ms": {r["name"]: r["value"] for r in rows
                              if r["name"].endswith("_ms")},
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}: {len(rows)} rows, "
              f"{len(errors)} errors")
    if errors:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
