"""repro.reliability: retention decay, the scrub kernel, scrub policies,
serve/checkpoint integration, and the Δ(T) single-source regression.

Heavy lane: the serve-level cases compile real decode bursts and the
decay sampler is a Monte-Carlo model — keep this module in the CI heavy
shard (.github/workflows/ci.yml HEAVY_TESTS).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import memory
from repro.core import approx_store as aps
from repro.core import mtj, wer
from repro.core.extent_table import ExtentTable
from repro.core.priority import Priority, kv_cache_policy
from repro.kernels.extent_write.ops import level_vectors
from repro.kernels.scrub import scrub_write
from repro.reliability import (MIN_P_STEP, LifetimePlan, RestoreIntegrity,
                               decay_tensor, make_scrub_policy,
                               retention_delta, retention_flip_p,
                               scrub_tree)

#: modeled dwell per decode step for the serve-level tests: large enough
#: that 400 K LOW planes rot visibly, small enough that 300 K stays below
#: the MIN_P_STEP clamp (bit-stable by construction).
DWELL = 1000.0


# ---------------------------------------------------------------------------
# Δ(T) single source (satellite: fig6_thermal + wer share mtj.delta_of_t)
# ---------------------------------------------------------------------------

class TestDeltaSingleSource:
    @pytest.mark.parametrize("t_k", [300.0, 350.0, 400.0])
    def test_wer_delta_pins_mtj_delta(self, t_k):
        a = float(wer.delta_of_t(jnp.asarray(t_k)))
        b = float(mtj.delta_of_t(mtj.DEFAULT_MTJ, jnp.asarray(t_k)))
        assert a == b, (t_k, a, b)

    def test_fig6_sources_the_same_delta(self):
        from benchmarks import fig6_thermal
        out = fig6_thermal.run()
        for t_k, d in zip(out["temps_K"], out["delta"]):
            assert d == float(mtj.delta_of_t(mtj.DEFAULT_MTJ,
                                             jnp.asarray(t_k))), t_k

    def test_wer_thermal_at_consistent_with_wer_thermal(self):
        for t_k in (300.0, 350.0, 400.0):
            d = float(wer.delta_of_t(jnp.asarray(t_k)))
            a = float(wer.wer_thermal_at(1e-8, 1.4, t_k))
            b = float(wer.wer_thermal(1e-8, 1.4, d,
                                      h_k=mtj.DEFAULT_MTJ.h_k * wer.MU_0,
                                      alpha=mtj.DEFAULT_MTJ.alpha))
            assert a == b

    def test_no_duplicated_constants(self):
        assert wer.MU_0 == mtj.MU_0
        assert wer.GAMMA_GYRO == mtj.GAMMA
        assert wer.ALPHA_DAMPING == mtj.DEFAULT_MTJ.alpha


# ---------------------------------------------------------------------------
# retention rates
# ---------------------------------------------------------------------------

class TestRetentionRates:
    def test_floor_orders_decay(self):
        """Lower priority -> lower effective Delta -> faster rot."""
        deltas = [retention_delta(l, 400.0)
                  for l in (Priority.LOW, Priority.MID, Priority.HIGH,
                            Priority.EXACT)]
        assert deltas == sorted(deltas)
        ps = [retention_flip_p(l, 400.0, DWELL)
              for l in (Priority.LOW, Priority.MID, Priority.HIGH)]
        assert ps[0] > ps[1] > ps[2] >= 0.0

    def test_cold_clamps_to_exact_zero(self):
        """300 K at Δ=60: below MIN_P_STEP, the probability is EXACTLY 0 —
        the no-spurious-decay guarantee."""
        for l in Priority:
            assert retention_flip_p(l, 300.0, DWELL) == 0.0
        assert retention_flip_p(Priority.LOW, 400.0, DWELL) >= MIN_P_STEP

    def test_decay_layout_invariant(self):
        """Counter RNG over flat element indices: reshaping the tensor
        reshapes the decay pattern but never changes it."""
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (8, 32)).astype(jnp.bfloat16)
        d1, m1, n1 = decay_tensor(key, x, level=Priority.LOW,
                                  ambient_k=400.0, dwell_s=1e5)
        d2, m2, n2 = decay_tensor(key, x.reshape(256), level=Priority.LOW,
                                  ambient_k=400.0, dwell_s=1e5)
        assert int(n1) == int(n2) > 0
        np.testing.assert_array_equal(np.asarray(d1).reshape(-1),
                                      np.asarray(d2))
        np.testing.assert_array_equal(np.asarray(m1).reshape(-1),
                                      np.asarray(m2))

    def test_exponent_planes_protected(self):
        """EXACT-coded bit planes (sign/exponent) never decay: damage is
        bounded, a rotted value cannot become inf/NaN."""
        x = jnp.ones((64, 64), jnp.float32)
        d, _, n = decay_tensor(jax.random.PRNGKey(1), x,
                               level=Priority.LOW, ambient_k=400.0,
                               dwell_s=1e6)
        assert int(n) > 0
        dev = jnp.abs(d - 1.0)
        assert bool(jnp.all(jnp.isfinite(d)))
        assert float(jnp.max(dev)) < 1.0


# ---------------------------------------------------------------------------
# scrub kernel: pallas vs ref parity + semantics
# ---------------------------------------------------------------------------

class TestScrubKernel:
    def _mk(self, shape=(33, 17), dtype=jnp.bfloat16, seed=0):
        x = jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)
        d, mask, _ = decay_tensor(jax.random.PRNGKey(seed + 1), x,
                                  level=Priority.LOW, ambient_k=400.0,
                                  dwell_s=1e6)
        return x, d, mask

    @pytest.mark.parametrize("shape", [(33, 17), (256,), (5, 7, 11)])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_pallas_matches_ref_bit_exact(self, shape, dtype):
        x, d, mask = self._mk(shape, dtype)
        vec = level_vectors(jnp.dtype(dtype), Priority.MID)
        key = jax.random.PRNGKey(9)
        s_k, r_k, st_k = scrub_write(key, d, mask, vectors=vec,
                                     use_kernel=True, interpret=True)
        s_r, r_r, st_r = scrub_write(key, d, mask, vectors=vec,
                                     use_kernel=False)
        np.testing.assert_array_equal(
            np.asarray(s_k).view(np.uint8), np.asarray(s_r).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(r_k), np.asarray(r_r))
        # per-plane integer counts priced by one function: every stat,
        # energy included, agrees exactly
        for k in ("flips01", "flips10", "errors", "energy_pj"):
            assert float(st_k[k]) == float(st_r[k]), k

    def test_perfect_scrub_restores_golden(self):
        """With zero failure thresholds every correction lands: the
        scrubbed tensor is bit-identical to the pre-decay value and the
        residual mask is empty."""
        x, d, mask = self._mk()
        thr01, thr10, e01, e10 = level_vectors(jnp.dtype(jnp.bfloat16),
                                               Priority.MID)
        vec = (jnp.zeros_like(thr01), jnp.zeros_like(thr10), e01, e10)
        s, residual, st = scrub_write(jax.random.PRNGKey(2), d, mask,
                                      vectors=vec, use_kernel=False)
        np.testing.assert_array_equal(
            np.asarray(s).view(np.uint8), np.asarray(x).view(np.uint8))
        assert int(jnp.sum(residual.astype(jnp.uint32))) == 0
        assert int(st["flips01"]) + int(st["flips10"]) == int(jnp.sum(
            jax.lax.population_count(mask).astype(jnp.int32)))
        assert float(st["energy_pj"]) > 0.0

    def test_empty_mask_is_free(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (64,)
                              ).astype(jnp.bfloat16)
        mask = jnp.zeros((64,), jnp.uint16)
        vec = level_vectors(jnp.dtype(jnp.bfloat16), Priority.LOW)
        s, r, st = scrub_write(jax.random.PRNGKey(4), x, mask, vectors=vec,
                               use_kernel=True, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(s).view(np.uint8), np.asarray(x).view(np.uint8))
        assert float(st["energy_pj"]) == 0.0
        assert int(st["flips01"]) == int(st["flips10"]) == 0

    def test_every_backend_scrubs(self):
        """Backend.leaf_scrub is total over the registry; counter-RNG
        backends agree bit-exactly (shared scrub RNG contract)."""
        x, d, mask = self._mk()
        lv = memory.leaf_vectors(jnp.bfloat16, Priority.MID)
        outs = {}
        for name in memory.available_backends():
            be = memory.get_backend(name)
            s, r, st = be.leaf_scrub(jax.random.PRNGKey(5), d, mask, lv)
            outs[name] = (np.asarray(s).view(np.uint16), np.asarray(r),
                          st.host_dict())
        for name in ("oracle", "lanes_ref", "pallas"):
            np.testing.assert_array_equal(outs[name][0],
                                          outs["lanes_ref"][0])
            np.testing.assert_array_equal(outs[name][1],
                                          outs["lanes_ref"][1])
        # exact backend: perfect free correction
        np.testing.assert_array_equal(
            outs["exact"][0], np.asarray(x).view(np.uint16))
        assert outs["exact"][2]["energy_pj"] == 0.0


# ---------------------------------------------------------------------------
# scrub policies
# ---------------------------------------------------------------------------

class TestScrubPolicies:
    LEVELS = (Priority.HIGH, Priority.MID, Priority.LOW, None)

    def test_periodic_cadence_and_idle_opportunism(self):
        p = make_scrub_policy("periodic", interval=8)
        assert p.plan_pass(4, self.LEVELS) is None
        assert p.plan_pass(4, self.LEVELS, idle=True) is not None  # >= 1/2
        p.record(4)
        assert p.plan_pass(8, self.LEVELS) is None
        mask = p.plan_pass(12, self.LEVELS)
        assert mask == (True, True, True, False)

    def test_wear_aware_backs_off(self):
        p = make_scrub_policy("wear_aware", interval=4)
        due_clocks = []
        clock = 0
        for _ in range(3):
            while p.plan_pass(clock, self.LEVELS) is None:
                clock += 1
            due_clocks.append(clock)
            p.record(clock)
        gaps = np.diff([0] + due_clocks)
        assert list(gaps) == sorted(gaps) and gaps[-1] > gaps[0]

    def test_quality_floor_lets_low_rot(self):
        p = make_scrub_policy("quality_floor", interval=8)
        # HIGH leaves scrub at interval/4, LOW only at 4x interval
        assert p.plan_pass(2, self.LEVELS) == (True, False, False, False)
        assert p.plan_pass(3, self.LEVELS) is None  # HIGH just scrubbed
        m = p.plan_pass(8, self.LEVELS)
        assert m == (True, True, False, False)
        m = p.plan_pass(32, self.LEVELS)
        assert m == (True, True, True, False)

    def test_none_never_scrubs(self):
        p = make_scrub_policy("none", interval=1)
        assert p.plan_pass(10**6, self.LEVELS, idle=True) is None

    def test_reset_restarts_pass_history(self):
        """A reused scheduler restarts the serving clock at 0 — without
        reset(), last_pass from the previous stream makes `since` negative
        and the next stream never scrubs."""
        for name in ("periodic", "wear_aware", "quality_floor"):
            p = make_scrub_policy(name, interval=4)
            clock = 0
            while p.plan_pass(clock, self.LEVELS) is None:
                clock += 1
            p.record(clock)
            end_of_run = clock + 100
            p.record(end_of_run)
            p.reset()
            assert p.last_pass == 0 and p.passes == 0
            # the fresh stream scrubs within one base interval again
            assert any(p.plan_pass(c, self.LEVELS) is not None
                       for c in range(0, 5)), name

    def test_unknown_name_raises(self):
        with pytest.raises(KeyError):
            make_scrub_policy("hourly")


# ---------------------------------------------------------------------------
# serve integration: the acceptance contract
# ---------------------------------------------------------------------------

def _mk_engine(**kw):
    from repro.configs import get_config
    from repro.serve import ServeConfig, ServingEngine
    cfg = get_config("qwen2.5-3b").reduced()
    return ServingEngine(cfg, ServeConfig(max_seq=32, max_new_tokens=6,
                                          **kw)), cfg


class TestServeRetention:
    def _prompt(self, cfg, b=2):
        return {"tokens": jax.random.randint(jax.random.PRNGKey(0),
                                             (b, 8), 0, cfg.vocab_size)}

    def test_300k_bit_identical_to_retention_off(self):
        """Retention enabled at 300 K with scrub-interval -> infinity is
        bit-identical to a retention-disabled run: same tokens, same
        stats (all decay thresholds clamp to exactly zero)."""
        eng_off, cfg = _mk_engine()
        tok_off, rep_off = eng_off.generate(self._prompt(cfg))
        eng_on, _ = _mk_engine(retention_scale=DWELL, ambient_k=300.0)
        tok_on, rep_on = eng_on.generate(self._prompt(cfg))
        np.testing.assert_array_equal(np.asarray(tok_off),
                                      np.asarray(tok_on))
        for k in ("energy_pj", "bits_written", "bit_errors", "bits_total"):
            assert rep_off["total"][k] == rep_on["total"][k], k
        assert rep_on["retention"]["flips"] == 0
        assert rep_on["retention"]["decayed_bits"] == 0

    def test_400k_low_floor_rots_no_host_sync_in_scan(self):
        """At 400 K the LOW-floor (V) planes decay measurably; the burst
        that advances the lifetime state performs ZERO host transfers
        (asserted via jax.transfer_guard around the compiled call)."""
        from repro.core.energy_model import zero_slot_stats
        from repro.memory import WriteStats
        eng, cfg = _mk_engine(retention_scale=DWELL, ambient_k=400.0)
        prompt = self._prompt(cfg)
        eng.generate(prompt)  # warm: compiles prefill + burst

        key = jax.random.PRNGKey(eng.scfg.seed + 1)
        vectors = eng.vectors_for_floor(Priority.LOW)
        rvec = eng.retention_vectors_for(Priority.LOW)
        tok, cache, key, _ = eng._prefill_fused(eng.params, prompt, key,
                                                vectors)
        B = prompt["tokens"].shape[0]
        pos = jnp.full((B,), 8, jnp.int32)
        active = jnp.ones((B,), bool)
        acc = WriteStats.zero()
        slot_acc = zero_slot_stats(B)
        life = eng.life_plan.init_state(cache)
        jax.block_until_ready((tok, cache, life))
        with jax.transfer_guard("disallow"):
            out = eng._burst(eng.params, tok, cache, pos, key, acc,
                             slot_acc, active, vectors, life, rvec, n=5)
        jax.block_until_ready(out)
        life = out[6]
        assert int(life.retention_flips) > 0
        assert int(life.decayed_bits()) > 0
        assert int(life.step) == 5

    def test_lifetime_ledger_write_plus_scrub(self):
        """Scheduler + periodic scrub at 400 K: lifetime energy is exactly
        write energy + scrub energy, retention flips are nonzero, and the
        scrub stream shows up in the meter."""
        from repro.serve import ContinuousScheduler, synthetic_requests
        eng, cfg = _mk_engine(retention_scale=DWELL, ambient_k=400.0)
        reqs = synthetic_requests(cfg, 4, prompt_len=8, new_tokens=6,
                                  arrival_every=2, app_ids=["app"], seed=1)
        sch = ContinuousScheduler(
            eng, capacity=2,
            scrub_policy=make_scrub_policy("periodic", interval=2))
        rep = sch.run(reqs)
        lt = rep["lifetime"]
        assert lt["retention_flips"] > 0
        assert lt["scrub_passes"] > 0
        assert lt["scrub_energy_pj"] > 0.0
        np.testing.assert_allclose(
            lt["lifetime_energy_pj"],
            lt["write_energy_pj"] + lt["scrub_energy_pj"], rtol=1e-7)
        np.testing.assert_allclose(
            lt["write_energy_pj"],
            rep["streams"]["kv_prefill"]["energy_pj"]
            + rep["streams"]["kv_decode"]["energy_pj"], rtol=1e-7)
        assert rep["streams"]["kv_scrub"]["energy_pj"] == \
            lt["scrub_energy_pj"]

    def test_scrub_table_traffic_scoped(self):
        """Scrub-time quality re-resolution through the LRU lands in the
        'scrub' scope — the serve hit-rate is not double-counted."""
        from repro.serve import ContinuousScheduler, synthetic_requests
        eng, cfg = _mk_engine(retention_scale=DWELL, ambient_k=400.0)
        reqs = synthetic_requests(cfg, 2, prompt_len=8, new_tokens=5,
                                  app_ids=["app"], seed=0)
        sch = ContinuousScheduler(
            eng, capacity=2, max_burst=2,  # scrub while requests are live
            scrub_policy=make_scrub_policy("periodic", interval=2))
        rep = sch.run(reqs)
        scopes = rep["extent_table"]["scopes"]
        # serve traffic: one miss (install) + one hit — as without scrub
        assert scopes["serve"] == {"hits": 1, "misses": 1, "evictions": 0}
        assert scopes["scrub"]["hits"] > 0
        assert scopes["scrub"]["misses"] == 0

    def test_rewrite_voids_stale_decay_record(self):
        """A decay flip on a column that is LATER re-written must not
        leave a stale mask bit behind — a scrub would XOR it into the
        fresh data, corrupting live state while reporting a fix.
        clear_written zeroes exactly the written (active slot, column)
        and keeps inactive slots' real decay."""
        eng, cfg = _mk_engine(retention_scale=DWELL, ambient_k=400.0)
        cache = eng.api.init_cache(2, eng.scfg.max_seq)
        life = eng.life_plan.init_state(cache)
        # plant a synthetic "decayed bit" at column 3 of every masked leaf
        # for both slots
        masks = tuple(
            None if m is None else jnp.moveaxis(
                jnp.moveaxis(jnp.zeros_like(m), ax, 0).at[3].set(1), 0, ax)
            for m, ax in zip(life.masks, eng.plan.leaf_seq_axis))
        life = dataclasses.replace(life, masks=masks)
        planted = int(life.decayed_bits())
        assert planted > 0
        # slot 0 writes column 3; slot 1 is inactive
        pos = jnp.asarray([3, 3], jnp.int32)
        active = jnp.asarray([True, False])
        life2 = eng.life_plan.clear_written(life, pos, active)
        # exactly slot 0's planted bits vanished, slot 1's survived
        assert int(life2.decayed_bits()) == planted // 2
        # writing a different column leaves the planted bits alone
        life3 = eng.life_plan.clear_written(life, pos + 1, active)
        assert int(life3.decayed_bits()) == planted

    def test_region_write_voids_decay_and_books_wear(self):
        golden = {"v": jax.random.normal(jax.random.PRNGKey(0), (64, 64)
                                         ).astype(jnp.bfloat16)}
        r = memory.MemoryRegion.create(
            jax.tree.map(jnp.zeros_like, golden), level=Priority.LOW,
            ambient_k=400.0, retention_scale=1e4)
        r = r.write(jax.random.PRNGKey(1), golden)
        r = r.age(jax.random.PRNGKey(2), steps=4)
        assert r.report()["residual_decayed_bits"] > 0
        assert int(r.life.step) == 4  # the clock counts dwell steps
        # aging books NO write wear; the two writes book exactly 2
        assert int(r.life.write_count[0]) == 1
        r = r.write(jax.random.PRNGKey(3), golden)
        assert int(r.life.write_count[0]) == 2
        # the full re-write re-drove/confirmed every bit: record voided
        assert r.report()["residual_decayed_bits"] == 0

    def test_ambient_schedule_bounds_bursts(self):
        """A temperature breakpoint mid-request must split the burst —
        otherwise the hot phase decays with the cold phase's (all-zero)
        thresholds and samples nothing."""
        from repro.serve import ContinuousScheduler, synthetic_requests
        eng, cfg = _mk_engine(retention_scale=DWELL, ambient_k=300.0)
        reqs = synthetic_requests(cfg, 1, prompt_len=8, new_tokens=6,
                                  seed=2)
        sch = ContinuousScheduler(
            eng, capacity=1, ambient_schedule=[(0, 300.0), (2, 400.0)])
        rep = sch.run(reqs)
        # cold phase: zero decay by construction; hot phase must show up
        assert rep["lifetime"]["retention_flips"] > 0
        assert rep["bursts"] >= 2  # the breakpoint ended a burst

    def test_column_scoped_scrub_matches_full(self):
        """Column-window scrubbing with zero-failure thresholds restores
        a decayed cache as completely as a full pass once the cursor has
        covered the ring."""
        eng, cfg = _mk_engine(retention_scale=DWELL, ambient_k=400.0)
        cache = eng.api.init_cache(2, eng.scfg.max_seq)
        cache = jax.tree.map(
            lambda l: jax.random.normal(
                jax.random.PRNGKey(l.size % 97), l.shape).astype(l.dtype)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, cache)
        life = eng.life_plan.init_state(cache)
        rvec = eng.retention_vectors_for(Priority.LOW,
                                         ambient_k=400.0)
        decayed, life = eng.life_plan.advance(jax.random.PRNGKey(0),
                                              cache, life, rvec)
        assert int(life.retention_flips) > 0
        vectors = eng.vectors_for_floor(Priority.EXACT)  # tiny WER
        C = eng.scfg.max_seq
        out, life2 = decayed, life
        for i in range(4):  # 4 windows of C//4 cover the whole ring
            out, life2, st = scrub_tree(
                jax.random.fold_in(jax.random.PRNGKey(1), i), out, life2,
                eng.life_plan, vectors, cols=C // 4,
                cursor=jnp.asarray(i * (C // 4), jnp.int32))
        # EXACT-floor corrections essentially never fail -> decay cleared
        assert int(life2.decayed_bits()) <= int(life.decayed_bits()) // 50
        assert int(jnp.sum(life2.scrub_count)) > 0


# ---------------------------------------------------------------------------
# checkpoint pre-restore integrity pass
# ---------------------------------------------------------------------------

class TestCheckpointIntegrity:
    @staticmethod
    def _policy(path, leaf):
        """Moments approximate (m@MID, v@LOW), weights exact — the
        checkpoint_policy contract over this test's dict paths."""
        s = str(path)
        if "'v'" in s:
            return Priority.LOW
        if "'m'" in s:
            return Priority.MID
        return Priority.EXACT

    def _roundtrip(self, tmp_path, integrity):
        from repro.train.checkpoint import Checkpointer
        state = {
            "w": jax.random.normal(jax.random.PRNGKey(0), (32, 8)),
            "opt": {"m": jax.random.normal(jax.random.PRNGKey(1), (32, 8)),
                    "v": jax.random.normal(jax.random.PRNGKey(2), (32, 8))},
        }
        ck = Checkpointer(str(tmp_path), async_save=False,
                          extent_policy=self._policy,
                          extent_backend="lanes_ref")
        ck.save(3, state)
        got, _ = ck.restore(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state),
            integrity=integrity)
        return state, got, ck

    def test_plain_restore_bit_identical(self, tmp_path):
        state, got, ck = self._roundtrip(tmp_path, None)
        saved, _ = ck.restore(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state))
        for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert ck.last_restore_report["leaves_checked"] == 0

    def test_integrity_pass_decays_and_scrubs(self, tmp_path):
        integ = RestoreIntegrity(ambient_k=400.0, dwell_s=1e5, scrub=True)
        state, got, ck = self._roundtrip(tmp_path, integ)
        rep = ck.last_restore_report
        # weights are EXACT (never checked); the two moments are
        assert rep["leaves_checked"] == 2
        assert rep["retention_flips"] > 0
        assert rep["scrub_energy_pj"] > 0.0
        # scrubbed moments: close to the stored values (ECC corrected),
        # weights bit-identical
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      np.asarray(got["w"]))

    def test_cold_integrity_pass_is_free(self, tmp_path):
        integ = RestoreIntegrity(ambient_k=300.0, dwell_s=DWELL,
                                 scrub=True)
        state, got, ck = self._roundtrip(tmp_path, integ)
        rep = ck.last_restore_report
        assert rep["leaves_checked"] == 2
        assert rep["retention_flips"] == 0
        assert rep["residual_decayed_bits"] == 0


# ---------------------------------------------------------------------------
# MemoryRegion lifetime + the ApproxStore shim (immortal by default)
# ---------------------------------------------------------------------------

class TestRegionLifetime:
    def test_default_region_is_immortal_and_pr3_identical(self):
        """No retention knobs -> the lifetime plan is immortal: age() is
        identity and write/report numbers are bit-identical to a plain
        PR 3 region (same plan, same RNG, same stats)."""
        data = {"a": jnp.zeros((16, 16), jnp.float32)}
        new = {"a": jnp.ones((16, 16), jnp.float32)}
        r = memory.MemoryRegion.create(data, level=Priority.MID)
        assert r.life_plan.immortal
        r = r.write(jax.random.PRNGKey(0), new)
        aged = r.age(jax.random.PRNGKey(1), steps=100)
        assert aged is r  # identity, not merely equal
        rep = r.report()
        assert "retention_flips" not in rep  # ledger stays PR 3-shaped
        # bit-identical to an explicit plan-level write (the PR 3 path)
        plan = memory.WritePlan.for_tree(
            data, policy=lambda p, l: Priority.MID,
            approx_if=lambda leaf, tag: tag != Priority.EXACT)
        stored, st = plan.jitted_write()(
            jax.random.PRNGKey(0), data, new,
            plan.vectors_for(Priority.LOW))
        np.testing.assert_array_equal(np.asarray(r.read()["a"]),
                                      np.asarray(stored["a"]))
        assert rep["energy_pj"] == float(st.energy_pj)

    def test_shim_regions_immortal(self):
        """ApproxStore (the PR 3 deprecation shim) under the lifetime
        state: stays bit-identical to PR 3 behavior — the substrate write
        path has no decay applied to it."""
        store = aps.ApproxStore(backend="lanes_ref")
        k = jax.random.PRNGKey(12)
        x = jnp.ones((64,), jnp.float32)
        store, got1 = store.write(k, "w", x, Priority.LOW)
        _, expect = memory.write(k, jnp.zeros_like(x), x,
                                 level=Priority.LOW, backend="lanes_ref")
        stored2, _ = memory.write(k, jnp.zeros_like(x), x,
                                  level=Priority.LOW, backend="lanes_ref")
        np.testing.assert_array_equal(np.asarray(got1), np.asarray(stored2))
        # reading later never shows decay: the stored bits are stable
        np.testing.assert_array_equal(np.asarray(store.read("w")),
                                      np.asarray(got1))

    def test_mortal_region_rots_and_scrubs(self):
        golden = {"v": jax.random.normal(jax.random.PRNGKey(3), (64, 64)
                                         ).astype(jnp.bfloat16)}
        r = memory.MemoryRegion.create(
            jax.tree.map(jnp.zeros_like, golden), level=Priority.LOW,
            ambient_k=400.0, retention_scale=1e4)
        r = r.write(jax.random.PRNGKey(4), golden)
        r = r.age(jax.random.PRNGKey(5), steps=4)
        rep_rotted = r.report()
        assert rep_rotted["retention_flips"] > 0
        assert rep_rotted["residual_decayed_bits"] > 0
        r = r.scrub(jax.random.PRNGKey(6))
        rep = r.report()
        assert rep["scrub_energy_pj"] > 0.0
        np.testing.assert_allclose(
            rep["lifetime_energy_pj"],
            rep["energy_pj"] + rep["scrub_energy_pj"], rtol=1e-7)
        assert rep["residual_decayed_bits"] < \
            rep_rotted["residual_decayed_bits"]


# ---------------------------------------------------------------------------
# ExtentTable scopes (satellite: serve vs scrub traffic accounting)
# ---------------------------------------------------------------------------

class TestExtentTableScopes:
    def test_scoped_counters_separate(self):
        t = ExtentTable(capacity=8)
        t.update("a", Priority.LOW)
        t.lookup("a")                       # serve hit
        with t.scope("scrub"):
            t.lookup("a")                   # scrub hit — same entry
            t.lookup("b")                   # scrub miss
        assert t.stats(scope="serve")["hits"] == 1
        assert t.stats(scope="scrub") == {
            "hits": 1, "misses": 1, "evictions": 0, "hit_rate": 0.5,
            "occupancy": 2}
        # aggregate view sums the scopes
        assert t.hits == 2 and t.misses == 1
        assert t.stats()["scopes"]["scrub"]["misses"] == 1

    def test_scope_is_reentrant_and_resets_fully(self):
        t = ExtentTable()
        with t.scope("scrub"):
            with t.scope("inner"):
                t.lookup("x")
            t.lookup("x")
        t.lookup("x")
        assert t.stats()["scopes"].keys() == {"inner", "scrub", "serve"}
        t.reset_stats()
        assert t.hits == 0 and t.misses == 0 and t.evictions == 0
        assert t.lookup("x") == t.default  # entries survived
        assert t.hits == 1
