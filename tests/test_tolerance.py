"""repro.tolerance — the §V error-tolerant over-scaling tier (ISSUE-6).

Covers the four layers end to end: the live timing-fault model and seeded
injector (zero at the guard band, deterministic streams), the ABFT
row/column-checksummed matmul (Pallas-vs-ref parity under forced
injections, single-flip repair, aliasing escapes), the ``ErrorTolerant``
policy (budget -> 0 collapses to PowerSave bitwise; nonzero budgets buy
power below the guard band while the *predicted* escaped-SDC rate honors
the budget), and the closed loop (controller back-off hysteresis, the
``sdc_storm`` acceptance day, cooled-chip restore).  Plus the
``core/overscaling.error_profile`` edge cases the static tier never pinned.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import policy as pol
from repro import scenarios as SC
from repro.core import netlist as NL
from repro.core import overscaling as OS
from repro.core import runtime as RT
from repro.core import thermal
from repro.core import tpu_fleet as TF
from repro.core import vtr_benchmarks as vb
from repro.control import RailBackoff, Restore, SetRails, Snapshot
from repro.control.lut import sweep_points
from repro.kernels.abft_matmul import abft_matmul, checksum_refs
from repro.kernels.overscale_matmul import bit_probs_to_cdf
from repro.kernels.ref import abft_matmul_ref
from repro.tolerance import (AbftMatmul, FaultInjector, TimingFaultModel,
                             detect_and_correct, routed_matmuls,
                             topk_agreement)

TC12 = thermal.ThermalConfig(theta_ja=12.0)
T_KNOTS = sweep_points(20.0, 36.0, 5)
U_KNOTS = sweep_points(0.25, 1.0, 3)
BUDGET = 1e-5


@pytest.fixture(scope="module")
def profile():
    return TF.StepProfile.from_roofline(compute_s=0.8, memory_s=0.45,
                                        collective_s=0.2)


@pytest.fixture(scope="module")
def rt_ps(profile):
    return RT.EnergyAwareRuntime(profile, policy="power_save")


@pytest.fixture(scope="module")
def rt_et(profile):
    return RT.EnergyAwareRuntime(profile, policy=f"error_tolerant:{BUDGET}")


@pytest.fixture(scope="module")
def field_ps(rt_ps):
    return rt_ps.build_field(T_KNOTS, U_KNOTS)


@pytest.fixture(scope="module")
def field_et(rt_et):
    return rt_et.build_field(T_KNOTS, U_KNOTS)


# ===========================================================================
# core/overscaling.error_profile edge cases (the static FPGA tier)
# ===========================================================================


class TestErrorProfileEdges:
    @pytest.fixture(scope="class")
    def sweep(self):
        nl = NL.generate(vb.BY_NAME["raygentop"])
        return OS.sweep(nl, [1.0, 1.15, 1.3], t_amb=40.0, tc=TC12)

    def test_gamma_one_is_exactly_error_free(self, sweep):
        # the guard-band contract: no relaxation, no violating path, no
        # flipped bit — the probabilities are hard zeros, not small floats
        r = sweep[0]
        assert r.frac_violating == 0.0
        assert r.mean_overshoot == 0.0
        assert np.all(r.bit_probs == 0.0)

    def test_bit_probs_monotone_in_gamma(self, sweep):
        totals = [float(r.bit_probs.sum()) for r in sweep]
        assert totals[0] <= totals[1] <= totals[2]
        assert totals[2] > 0.0

    def test_bit_probs_monotone_in_temperature_at_fixed_rails(self):
        # hotter silicon = slower paths = deeper violations — at FIXED
        # rails (the solved operating point re-optimizes rails per
        # temperature, so only the fixed-rail profile is monotone)
        import repro.core.characterization as C
        nl = NL.generate(vb.BY_NAME["raygentop"])
        lib, nlj = C.default_library(), nl.as_jax()
        d_worst = float(NL.crit_delay(
            lib, nlj, jnp.full((nl.n_tiles,), 60.0),
            C.V_CORE_NOM, C.V_BRAM_NOM))
        out = []
        for t in (40.0, 60.0, 80.0):
            frac, overshoot, bp = OS.error_profile(
                lib, nlj, nl, jnp.full((nl.n_tiles,), t),
                0.70, 0.75, d_worst, 1.0)
            out.append((frac, overshoot, float(bp.sum())))
        fracs, overs, totals = zip(*out)
        assert fracs[0] <= fracs[1] <= fracs[2]
        assert totals[0] <= totals[1] <= totals[2]
        assert totals[2] > 0.0

    def test_cdf_round_trip(self, sweep):
        probs = sweep[2].bit_probs
        cdf = np.asarray(bit_probs_to_cdf(probs))
        assert cdf.shape == (33,)
        assert cdf[0] == 0.0
        np.testing.assert_allclose(np.diff(cdf), probs, atol=1e-7)
        assert cdf[-1] == pytest.approx(float(probs.sum()), abs=1e-6)
        assert np.all(np.diff(cdf) >= -1e-9)  # monotone


# ===========================================================================
# faults: the live model + seeded injector
# ===========================================================================


class TestTimingFaultModel:
    def test_zero_at_guard_band_rails(self):
        m = TimingFaultModel()
        assert float(m.overshoot(TF.V_CORE_NOM, TF.V_SRAM_NOM, 60.0)) == 0.0
        assert float(m.sdc_rate(TF.V_CORE_NOM, TF.V_SRAM_NOM, 60.0)) == 0.0
        assert np.all(m.bit_probs(TF.V_CORE_NOM, TF.V_SRAM_NOM, 60.0) == 0.0)

    def test_rate_monotone_in_undervolt_and_temperature(self):
        m = TimingFaultModel()
        r = [float(m.sdc_rate(vc, 0.80, 60.0))
             for vc in (0.66, 0.64, 0.62)]
        assert r[0] < r[1] < r[2]
        rt = [float(m.sdc_rate(0.64, 0.80, t)) for t in (40.0, 60.0, 80.0)]
        assert rt[0] < rt[1] < rt[2]

    def test_bit_profile_is_carry_tail_weighted(self):
        m = TimingFaultModel()
        bp = m.bit_probs(0.64, 0.80, 60.0)
        assert bp[:20].sum() == 0.0  # only the carry/MSB tail flips
        assert bp[31] > 0.0

    def test_shared_constants_close_the_prediction_loop(self):
        # the policy's inverse rate model and the injector's forward model
        # are the same curve: escaped_rate(overshoot_budget(b)) == b
        for b in (1e-6, 1e-5, 1e-4):
            x = float(pol.overshoot_budget(b))
            assert float(pol.escaped_sdc_rate(x)) == pytest.approx(b,
                                                                   rel=1e-5)
        m = TimingFaultModel()
        raw = m.sdc_rate(0.66, 0.80, 70.0)
        np.testing.assert_allclose(m.escaped_rate(0.66, 0.80, 70.0),
                                   pol.ABFT_ESCAPE * raw, rtol=1e-7)


class TestFaultInjector:
    def test_zero_injections_at_nominal(self):
        inj = FaultInjector(seed=3)
        c = inj.tick(0.0, TF.V_CORE_NOM, TF.V_SRAM_NOM, 60.0)
        assert c.injected == 0 and c.escaped == 0
        assert c.checked > 0  # traffic is still checksummed

    def test_deterministic_given_seed(self):
        a, b = FaultInjector(seed=11), FaultInjector(seed=11)
        seq = []
        for t in range(4):
            ca = a.tick(float(t), 0.64, 0.80, 70.0)
            cb = b.tick(float(t), 0.64, 0.80, 70.0)
            assert (ca.injected, ca.detected, ca.escaped, ca.checked) == \
                   (cb.injected, cb.detected, cb.escaped, cb.checked)
            seq.append(ca.injected)
        assert a.totals.injected == b.totals.injected
        a.reset()  # reset restarts the exact same stream
        assert [a.tick(float(t), 0.64, 0.80, 70.0).injected
                for t in range(4)] == seq

    def test_ledger_is_conserved(self):
        inj = FaultInjector(seed=5)
        c = inj.tick(0.0, 0.62, 0.78, 75.0)
        assert c.injected > 0
        assert c.detected + c.escaped == c.injected
        assert c.corrected == c.detected  # what ABFT catches, it repairs
        assert inj.totals.escape_rate == pytest.approx(
            c.escaped / c.checked)

    def test_noise_trace_scales_the_rate(self):
        quiet = FaultInjector(seed=9)
        noisy = FaultInjector(seed=9, noise=lambda now: 8.0)
        cq = quiet.tick(0.0, 0.64, 0.80, 70.0)
        cn = noisy.tick(0.0, 0.64, 0.80, 70.0)
        assert cn.injected > cq.injected


# ===========================================================================
# ABFT: kernel parity + detect/correct
# ===========================================================================


def _inputs(m, k, n, p_tail=0.02, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.integers(-30, 30, (m, k)).astype(np.int8)
    b = rng.integers(-30, 30, (k, n)).astype(np.int8)
    key = jax.random.PRNGKey(seed)
    u_gate = jax.random.bits(key, (m, n), jnp.uint32)
    u_bit = jax.random.bits(jax.random.fold_in(key, 1), (m, n), jnp.uint32)
    probs = np.zeros(32)
    probs[24:] = p_tail / 8.0
    return a, b, u_gate, u_bit, bit_probs_to_cdf(probs)


class TestAbftKernel:
    @pytest.mark.parametrize("shape", [(64, 96, 80), (200, 128, 130),
                                       (96, 72, 60)])
    def test_pallas_matches_ref_under_forced_injections(self, shape):
        a, b, ug, ub, cdf = _inputs(*shape, p_tail=0.05)
        c_k, rs_k, cs_k = jax.tree_util.tree_map(
            np.asarray, abft_matmul(a, b, ug, ub, cdf, interpret=True))
        c_r, rs_r, cs_r = jax.tree_util.tree_map(
            np.asarray, abft_matmul_ref(a, b, ug, ub, cdf))
        # forced flips actually happened, and both paths agree bit-exactly
        clean = a.astype(np.int64) @ b.astype(np.int64)
        assert np.count_nonzero(c_r.astype(np.int64) != clean) > 0
        np.testing.assert_array_equal(c_k, c_r)
        np.testing.assert_array_equal(rs_k, rs_r)
        np.testing.assert_array_equal(cs_k, cs_r)

    def test_fused_checksums_sum_the_corrupted_product(self):
        # the kernel checksums C' (post-injection), so syndromes against
        # the protected references see exactly the injected deltas
        a, b, ug, ub, cdf = _inputs(64, 96, 80, p_tail=0.05)
        c, rs, cs = jax.tree_util.tree_map(
            np.asarray, abft_matmul(a, b, ug, ub, cdf, interpret=True))
        np.testing.assert_array_equal(
            rs, c.sum(axis=1, dtype=np.int64).astype(np.int32))
        np.testing.assert_array_equal(
            cs, c.sum(axis=0, dtype=np.int64).astype(np.int32))

    def test_clean_checksums_equal_protected_references(self):
        a, b, ug, ub, _ = _inputs(64, 96, 80)
        cdf0 = bit_probs_to_cdf(np.zeros(32))
        c, rs, cs = abft_matmul(a, b, ug, ub, cdf0, interpret=True)
        row_ref, col_ref = checksum_refs(a, b)
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(row_ref))
        np.testing.assert_array_equal(np.asarray(cs), np.asarray(col_ref))
        clean = a.astype(np.int64) @ b.astype(np.int64)
        np.testing.assert_array_equal(np.asarray(c), clean.astype(np.int32))


class TestDetectAndCorrect:
    def _clean(self, m=16, k=12, n=20, seed=2):
        rng = np.random.default_rng(seed)
        a = rng.integers(-4, 4, (m, k)).astype(np.int8)
        b = rng.integers(-4, 4, (k, n)).astype(np.int8)
        c = (a.astype(np.int32) @ b.astype(np.int32))
        return a, b, c

    @staticmethod
    def _sums(c):
        return (c.sum(axis=1, dtype=np.int64).astype(np.int32),
                c.sum(axis=0, dtype=np.int64).astype(np.int32))

    def test_single_flip_is_repaired_exactly(self):
        a, b, clean = self._clean()
        bad = clean.copy()
        bad[3, 5] += np.int32(1 << 20)  # one carry-tail flip
        rs, cs = self._sums(bad)
        fixed, detected, corrected = detect_and_correct(bad, rs, cs, a, b)
        assert detected == 1 and corrected == 1
        np.testing.assert_array_equal(fixed, clean)

    def test_distinct_double_flips_both_repaired(self):
        a, b, clean = self._clean()
        bad = clean.copy()
        bad[1, 2] += np.int32(1 << 18)
        bad[7, 9] -= np.int32(1 << 22)  # distinct rows, cols AND deltas
        rs, cs = self._sums(bad)
        fixed, detected, corrected = detect_and_correct(bad, rs, cs, a, b)
        assert detected == 2 and corrected == 2
        np.testing.assert_array_equal(fixed, clean)

    def test_aliased_flips_detected_but_escape(self):
        # two flips in one row: the row syndrome is their sum, neither
        # column syndrome matches it — detected, not uniquely localizable
        a, b, clean = self._clean()
        bad = clean.copy()
        bad[3, 5] += np.int32(1 << 20)
        bad[3, 9] += np.int32(1 << 20)
        rs, cs = self._sums(bad)
        fixed, detected, corrected = detect_and_correct(bad, rs, cs, a, b)
        assert detected == 2
        assert corrected == 0  # no healthy cell was "repaired"
        assert np.count_nonzero(fixed != clean) == 2  # the escapes

    def test_ambiguous_syndrome_never_corrupts_a_healthy_cell(self):
        # same delta at (2,4) and (6,8): the syndrome match matrix pairs
        # rows {2,6} x cols {4,8} four ways — repair must decline
        a, b, clean = self._clean()
        bad = clean.copy()
        bad[2, 4] += np.int32(1 << 19)
        bad[6, 8] += np.int32(1 << 19)
        rs, cs = self._sums(bad)
        fixed, detected, corrected = detect_and_correct(bad, rs, cs, a, b)
        assert detected == 2 and corrected == 0
        np.testing.assert_array_equal(fixed != clean, bad != clean)


class TestAbftMatmulWrapper:
    def test_sparse_flips_fully_repaired(self):
        # at realistic per-call flip counts (a couple of cells) the
        # syndromes localize every one — output error is quantization only
        probs = np.zeros(32)
        probs[20:] = 0.0008 / 12.0
        mm = AbftMatmul(probs, jax.random.PRNGKey(7), use_pallas=True)
        rng = np.random.default_rng(1)
        a = rng.standard_normal((48, 64)).astype(np.float32)
        b = rng.standard_normal((64, 40)).astype(np.float32)
        out = np.asarray(mm(a, b))
        c = mm.counters
        assert c.checked == 48 * 40
        assert c.injected >= 1
        assert c.corrected == c.injected
        assert c.escaped == 0
        rel = np.linalg.norm(out - a @ b) / np.linalg.norm(a @ b)
        assert rel < 0.05

    def test_heavy_flips_ledger_invariants(self):
        # pile on flips until rows/columns collide: repairs decline, the
        # residue is counted as escapes, and the ledger stays consistent
        probs = np.zeros(32)
        probs[26:] = 0.02 / 6.0
        mm = AbftMatmul(probs, jax.random.PRNGKey(7), use_pallas=True)
        rng = np.random.default_rng(1)
        a = rng.standard_normal((48, 64)).astype(np.float32)
        b = rng.standard_normal((64, 40)).astype(np.float32)
        mm(a, b)
        c = mm.counters
        assert c.injected > 5
        assert 0 < c.corrected < c.injected  # aliasing declined some
        assert c.detected <= c.injected  # cancellation can hide syndromes
        # corrections never touch healthy cells, so what remains wrong is
        # exactly the uncorrected injections
        assert c.escaped == c.injected - c.corrected
        assert 0.0 < c.escape_rate < c.injected / c.checked

    def test_zero_probs_is_plain_quantized_matmul(self):
        mm = AbftMatmul(np.zeros(32), jax.random.PRNGKey(0))
        rng = np.random.default_rng(4)
        a = rng.standard_normal((32, 48)).astype(np.float32)
        b = rng.standard_normal((48, 24)).astype(np.float32)
        out = np.asarray(mm(a, b))
        assert mm.counters.injected == 0
        assert mm.counters.escaped == 0
        rel = np.linalg.norm(out - a @ b) / np.linalg.norm(a @ b)
        assert rel < 0.02  # int8 quantization error only

    def test_routed_matmuls_installs_and_restores_the_hook(self):
        from repro.models import layers
        calls = []

        def spy(a, b):
            calls.append((a.shape, b.shape))
            return a @ b

        assert layers.MATMUL is None
        x = jnp.ones((2, 3, 4), jnp.bfloat16)
        w = jnp.ones((4, 5), jnp.bfloat16)
        with routed_matmuls(spy):
            y = layers.matmul(x, w)
        assert layers.MATMUL is None  # restored
        assert calls == [((6, 4), (4, 5))]  # 2D-flattened f32 routing
        assert y.shape == (2, 3, 5) and y.dtype == jnp.bfloat16

    def test_topk_agreement_bounds(self):
        logits = np.asarray(np.random.default_rng(0)
                            .standard_normal((4, 7, 50)), np.float32)
        assert topk_agreement(logits, logits, k=1) == 1.0
        assert topk_agreement(logits, logits, k=4) == 1.0
        shuffled = logits[..., ::-1].copy()
        assert topk_agreement(logits, shuffled, k=1) < 1.0


# ===========================================================================
# the ErrorTolerant policy
# ===========================================================================


class TestErrorTolerantPolicy:
    def test_from_spec(self):
        p = pol.from_spec("error_tolerant:1e-5")
        assert isinstance(p, pol.ErrorTolerant)
        assert p.budget == pytest.approx(1e-5)
        assert pol.from_spec("error_tolerant").budget == 0.0
        with pytest.raises(ValueError):
            pol.from_spec("error_tolerant:lots")

    def test_budget_zero_is_powersave_bitwise(self, rt_ps, profile):
        rt0 = RT.EnergyAwareRuntime(profile, policy="error_tolerant")
        ps, _ = rt_ps.planner.plan_at(28.0, None)
        et, _ = rt0.planner.plan_at(28.0, None)
        np.testing.assert_array_equal(et.v_core, ps.v_core)
        np.testing.assert_array_equal(et.v_sram, ps.v_sram)
        assert et.pod_power_w == pytest.approx(ps.pod_power_w)

    def test_budget_buys_power_below_the_guard_band(self, rt_ps, rt_et):
        ps, _ = rt_ps.planner.plan_at(28.0, None)
        et, T = rt_et.planner.plan_at(28.0, None)
        assert et.saving > ps.saving + 0.02  # strictly beyond PowerSave
        assert float(np.median(et.v_core)) < float(np.median(ps.v_core))
        # ... and the model the injector runs agrees the rails fit the
        # budget: predicted escaped rate at the solved (rails, T) field
        m = TimingFaultModel(rt_et.lib)
        pred = m.escaped_rate(et.v_core, et.v_sram,
                              np.asarray(T).reshape(-1))
        assert float(np.max(pred)) <= BUDGET * 1.05

    def test_runtime_spec_round_trip(self, rt_et):
        assert isinstance(rt_et.policy_obj, pol.ErrorTolerant)
        assert rt_et.policy_obj.budget == pytest.approx(BUDGET)
        assert rt_et.policy == "error_tolerant"  # the reported spec name


# ===========================================================================
# the closed loop: back-off hysteresis, sdc_storm, restore
# ===========================================================================


def _sdc_snap(t_amb=28.0, escaped=0, checked=10**9, **kw):
    return Snapshot(t_amb=t_amb, sdc_escaped=escaped,
                    sdc_detected=escaped, sdc_corrected=0,
                    sdc_checked=checked, **kw)


def _rails(actions):
    (s,) = [a for a in actions if isinstance(a, SetRails)]
    return np.asarray(s.v_core, np.float32)


class TestBackoffHysteresis:
    def test_retreat_and_redescend(self, rt_et, field_et):
        c = rt_et.controller(field=field_et, sdc_budget=BUDGET,
                             sdc_hysteresis=2)
        c.reset()
        vc0 = _rails(c.decide(_sdc_snap()))  # clean cold start
        hot = c.decide(_sdc_snap(escaped=30_000))  # 3e-5 > budget
        assert any(isinstance(a, RailBackoff) for a in hot)
        vc1 = _rails(hot)
        np.testing.assert_allclose(
            vc1, np.minimum(vc0 + 0.010, TF.V_CORE_NOM), atol=1e-6)
        # a second over-budget tick deepens the retreat
        vc2 = _rails(c.decide(_sdc_snap(escaped=30_000)))
        np.testing.assert_allclose(
            vc2, np.minimum(vc0 + 0.020, TF.V_CORE_NOM), atol=1e-6)
        # clean ticks: hold, hold ... then one step back down per window
        vc3 = _rails(c.decide(_sdc_snap()))
        np.testing.assert_allclose(vc3, vc2, atol=1e-6)
        vc4 = _rails(c.decide(_sdc_snap()))  # 2nd clean: backoff 2 -> 1
        np.testing.assert_allclose(vc4, vc1, atol=1e-6)
        c.decide(_sdc_snap())
        vc6 = _rails(c.decide(_sdc_snap()))  # 4th clean: backoff 1 -> 0
        np.testing.assert_allclose(vc6, vc0, atol=1e-6)
        assert c.stats.backoffs == 2

    def test_disabled_by_default(self, rt_et, field_et):
        c = rt_et.controller(field=field_et)
        c.reset()
        vc0 = _rails(c.decide(_sdc_snap()))
        acts = c.decide(_sdc_snap(escaped=10**6))
        assert not any(isinstance(a, RailBackoff) for a in acts)
        np.testing.assert_allclose(_rails(acts), vc0, atol=1e-6)

    def test_reset_clears_the_retreat(self, rt_et, field_et):
        c = rt_et.controller(field=field_et, sdc_budget=BUDGET)
        c.reset()
        c.decide(_sdc_snap())
        c.decide(_sdc_snap(escaped=10**5))
        assert c._backoff == 1
        c.reset()
        assert c._backoff == 0 and c._sdc_clean == 0


class TestSdcStorm:
    @pytest.fixture(scope="class")
    def storm(self, rt_ps, rt_et, field_ps, field_et):
        scn = SC.sdc_storm()
        r_ps = SC.replay(scn, runtime=rt_ps,
                         controller=rt_ps.controller(field=field_ps,
                                                     guard_band_c=3.0))
        inj = FaultInjector(TimingFaultModel(rt_et.lib), seed=7)
        c_et = rt_et.controller(field=field_et, guard_band_c=3.0,
                                sdc_budget=BUDGET)
        r_et = SC.replay(scn, runtime=rt_et, controller=c_et, injector=inj)
        return r_ps, r_et

    def test_saves_beyond_powersave_at_declared_budget(self, storm):
        r_ps, r_et = storm
        assert r_et.mean_saving > r_ps.mean_saving  # strictly greater
        assert r_et.energy_j < r_ps.energy_j
        assert r_et.t_max < TF.T_MAX_CHIP

    def test_escape_rate_lands_inside_the_budget(self, storm):
        _, r_et = storm
        assert r_et.sdc_checked > 0
        assert r_et.sdc_injected > 0  # the storm was real
        assert r_et.escape_rate <= BUDGET
        assert r_et.sdc_detected == r_et.sdc_corrected
        assert (r_et.sdc_detected + r_et.sdc_escaped == r_et.sdc_injected)

    def test_spike_forces_observable_backoff(self, storm):
        _, r_et = storm
        assert r_et.backoffs >= 1
        # the retreat shows in the rail trace: spike-era rails sit above
        # the quiet-era rails on at least one tick
        quiet = r_et.rails[10, 0]
        spike = r_et.rails[22, 0]
        assert float(np.min(spike - quiet)) >= 0.0
        assert float(np.max(spike - quiet)) > 0.005

    def test_powersave_day_stays_error_free(self, rt_ps, field_ps):
        # at-or-above guard band rails inject nothing, storm or not
        inj = FaultInjector(TimingFaultModel(rt_ps.lib), seed=7)
        r = SC.replay(SC.sdc_storm(ticks=8), runtime=rt_ps,
                      controller=rt_ps.controller(field=field_ps,
                                                  guard_band_c=3.0),
                      injector=inj)
        assert r.sdc_injected == 0
        assert r.escape_rate == 0.0

    def test_deterministic_replay(self, rt_et, field_et, storm):
        _, r_et = storm
        inj = FaultInjector(TimingFaultModel(rt_et.lib), seed=7)
        c = rt_et.controller(field=field_et, guard_band_c=3.0,
                             sdc_budget=BUDGET)
        again = SC.replay(SC.sdc_storm(), runtime=rt_et, controller=c,
                          injector=inj)
        assert again.fingerprint == r_et.fingerprint
        assert again.sdc_escaped == r_et.sdc_escaped
        assert again.backoffs == r_et.backoffs


class TestRestore:
    def test_cool_down_hysteresis_then_restore(self, rt_ps, field_ps):
        chips = rt_ps.substrate.n_domains
        c = rt_ps.controller(field=field_ps, restore_after=2,
                             restore_below_c=70.0)
        c.reset()
        shares = np.ones(chips, np.float32)
        shares[0] = 0.0
        cool = np.full(chips, 55.0, np.float32)
        hot = cool.copy()
        hot[0] = 80.0
        s = dict(t_amb=28.0, shares=shares)
        assert not any(isinstance(a, Restore)
                       for a in c.decide(Snapshot(t_chip=cool, **s)))
        # a hot tick resets the cool-down counter
        assert not any(isinstance(a, Restore)
                       for a in c.decide(Snapshot(t_chip=hot, **s)))
        assert not any(isinstance(a, Restore)
                       for a in c.decide(Snapshot(t_chip=cool, **s)))
        acts = c.decide(Snapshot(t_chip=cool, **s))
        assert any(isinstance(a, Restore) and a.chip == 0 for a in acts)
        assert c.stats.restores == 1

    def test_disabled_by_default(self, rt_ps, field_ps):
        chips = rt_ps.substrate.n_domains
        c = rt_ps.controller(field=field_ps)
        c.reset()
        shares = np.ones(chips, np.float32)
        shares[0] = 0.0
        cool = np.full(chips, 50.0, np.float32)
        for _ in range(5):
            acts = c.decide(Snapshot(t_amb=28.0, shares=shares,
                                     t_chip=cool))
            assert not any(isinstance(a, Restore) for a in acts)

    def test_storm_restore_migrates_work_back(self, rt_ps, field_ps):
        # the straggler storm condemns the hot chip; with restore enabled
        # the loop re-admits it once the TSD reads it cool again
        scn = SC.straggler_storm(ticks=24, storm_at=8)
        c = rt_ps.controller(field=field_ps, guard_band_c=3.0,
                             restore_after=3, restore_below_c=70.0)
        r = SC.replay(scn, runtime=rt_ps, controller=c)
        assert r.rebalances >= 1
        assert r.restores >= 1
        # after the restore the chip carries work again (it may be
        # re-condemned by the still-running storm; either way the restore
        # actually moved shares through the elastic assignment)
        assert r.restores <= r.rebalances


class TestUnrolledStack:
    """scan_layers=False unrolls the block stack into a python loop (the
    host-side ABFT routing can't execute under a lax.scan trace) — the two
    paths must agree bitwise for every stacked family."""

    @pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-780m",
                                      "mixtral-8x7b"])
    def test_loop_matches_scan(self, arch):
        from repro.configs import registry
        from repro.models.model import Model

        cfg = registry.get(arch).reduced()
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = (np.arange(2 * 16, dtype=np.int32).reshape(2, 16)
                  % cfg.vocab_size)
        logits_scan, aux_scan = Model(cfg.replace(scan_layers=True)).apply(
            params, {"tokens": tokens})
        logits_loop, aux_loop = Model(cfg.replace(scan_layers=False)).apply(
            params, {"tokens": tokens})
        # same function, different reduction order: only a few ulps of
        # bf16 output rounding are allowed between the two paths — except
        # moe, where near-tied router probs make top-k expert selection
        # chaotically sensitive to that rounding
        if not cfg.is_moe:
            np.testing.assert_allclose(np.asarray(logits_scan, np.float32),
                                       np.asarray(logits_loop, np.float32),
                                       rtol=0.0, atol=0.06)
            assert topk_agreement(np.asarray(logits_loop, np.float32),
                                  np.asarray(logits_scan, np.float32),
                                  k=1) > 0.95
        assert np.asarray(logits_loop).shape == np.asarray(logits_scan).shape
        assert np.all(np.isfinite(np.asarray(logits_loop, np.float32)))
        for k in aux_scan:
            # moe aux is routing-sensitive at random init; same order of
            # magnitude is the strongest portable claim
            assert np.isfinite(float(aux_loop[k]))
            if not cfg.is_moe:
                np.testing.assert_allclose(float(aux_scan[k]),
                                           float(aux_loop[k]),
                                           rtol=0.05, atol=1e-4)

    def test_routed_abft_under_unrolled_stack(self):
        # the motivating composition: clean-profile ABFT matmuls routed
        # through the unrolled model reproduce the plain forward logits
        from repro.configs import registry
        from repro.models.model import Model

        cfg = registry.get("llama3.2-1b").reduced().replace(
            scan_layers=False)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        tokens = (np.arange(2 * 12, dtype=np.int32).reshape(2, 12)
                  % cfg.vocab_size)
        ref = np.asarray(model.apply(params, {"tokens": tokens})[0])
        mm = AbftMatmul(np.zeros(32), jax.random.PRNGKey(3),
                        use_pallas=False)
        with routed_matmuls(mm):
            out = np.asarray(model.apply(params, {"tokens": tokens})[0])
        assert mm.counters.checked > 0
        assert mm.counters.injected == 0
        assert mm.counters.escaped == 0
        assert topk_agreement(out, ref, k=1) > 0.9
