"""repro.memory substrate: WritePlan resolve-once semantics, the backend
registry, MemoryRegion, the ApproxStore deprecation shim, the soft-error
hook, ExtentTable.reset_stats, and the compression wire path."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import memory
from repro.core import approx_store as aps
from repro.core.extent_table import ExtentTable
from repro.core.priority import Priority, kv_cache_policy
from repro.train import compression as comp


def _tree(key, n=32):
    k1, k2 = jax.random.split(key)
    return {"kv": {"k": jax.random.normal(k1, (2, n)).astype(jnp.bfloat16),
                   "v": jax.random.normal(k2, (2, n)).astype(jnp.bfloat16)},
            "state": jnp.zeros((2, 4), jnp.float32),
            "pos": jnp.zeros((2,), jnp.int32)}


class TestWritePlan:
    def test_policy_resolution(self):
        tree = _tree(jax.random.PRNGKey(0))
        plan = memory.WritePlan.for_tree(tree, policy=kv_cache_policy,
                                         backend="lanes_ref")
        # K@MID, V@LOW, recurrent state EXACT (None), ints excluded
        by_level = dict(zip(["k", "v", "pos", "state"], plan.leaf_levels))
        assert by_level["k"] == Priority.MID
        assert by_level["v"] == Priority.LOW
        assert by_level["state"] is None and by_level["pos"] is None

    def test_floor_composition_raises_never_lowers(self):
        tree = _tree(jax.random.PRNGKey(0))
        plan = memory.WritePlan.for_tree(tree, policy=kv_cache_policy)
        lo = plan.vectors_for(Priority.LOW)
        hi = plan.vectors_for(Priority.HIGH)
        # same pytree structure across floors (operand-swap, no retrace)
        assert (jax.tree.structure(lo, is_leaf=lambda x: x is None)
                == jax.tree.structure(hi, is_leaf=lambda x: x is None))
        # a HIGH floor strictly reduces the LOW-tagged leaf's failure prob
        i_v = [i for i, l in enumerate(plan.leaf_levels)
               if l == Priority.LOW][0]
        assert float(hi[i_v].wer01[0]) < float(lo[i_v].wer01[0])

    def test_floor_swap_does_not_retrace(self):
        tree = _tree(jax.random.PRNGKey(1))
        plan = memory.WritePlan.for_tree(tree, policy=kv_cache_policy)
        traces = {"n": 0}

        @jax.jit
        def step(key, old, new, vectors):
            traces["n"] += 1
            return plan.write(key, old, new, vectors)

        new = _tree(jax.random.PRNGKey(2))
        for floor in (Priority.LOW, Priority.MID, Priority.HIGH,
                      Priority.EXACT):
            step(jax.random.PRNGKey(3), tree, new,
                 plan.vectors_for(floor))
        assert traces["n"] == 1, "floor change retraced the write"

    def test_write_skips_exact_leaves(self):
        tree = _tree(jax.random.PRNGKey(4))
        new = _tree(jax.random.PRNGKey(5))
        plan = memory.WritePlan.for_tree(tree, policy=kv_cache_policy)
        stored, st = plan.write(jax.random.PRNGKey(6), tree, new)
        # EXACT/int leaves pass through bit-exactly, no accounting
        np.testing.assert_array_equal(np.asarray(stored["state"]),
                                      np.asarray(new["state"]))
        np.testing.assert_array_equal(np.asarray(stored["pos"]),
                                      np.asarray(new["pos"]))
        kv_bits = sum(l.size * 16 for l in jax.tree.leaves(new["kv"]))
        assert float(st.bits_total) == kv_bits

    def test_backend_instance_accepted(self):
        tree = _tree(jax.random.PRNGKey(0))
        be = memory.get_backend("oracle")
        plan = memory.WritePlan.for_tree(tree, policy=kv_cache_policy,
                                         backend=be)
        assert plan.backend is be


class TestSoftErrors:
    def test_hook_strikes_and_schema(self):
        x = {"kv": {"k": jnp.ones((64, 64), jnp.float32),
                    "v": jnp.ones((64, 64), jnp.float32)}}
        plan = memory.WritePlan.for_tree(
            x, policy=lambda p, l: Priority.EXACT if "'k'" in str(p)
            else Priority.LOW,
            approx_if=lambda leaf, tag: tag != Priority.EXACT,
            soft_error_ber=1e-3, soft_error_hardened=True)
        old = jax.tree.map(jnp.zeros_like, x)
        stored, st = plan.write(jax.random.PRNGKey(0), old, x)
        assert int(st.soft_strikes) > 0
        # hardened driver: sign/exponent protected, damage bounded < 1.0
        assert float(jnp.max(jnp.abs(stored["kv"]["v"] - 1.0))) < 1.0

    def test_unhardened_can_strike_exponent(self):
        x = {"v": jnp.ones((256, 256), jnp.float32)}
        mk = lambda hard: memory.WritePlan.for_tree(
            x, policy=lambda p, l: Priority.EXACT,
            approx_if=lambda leaf, tag: True,
            soft_error_ber=1e-3, soft_error_hardened=hard)
        old = jax.tree.map(jnp.zeros_like, x)
        s_hard, _ = mk(True).write(jax.random.PRNGKey(1), old, x)
        s_soft, _ = mk(False).write(jax.random.PRNGKey(1), old, x)
        assert float(jnp.max(jnp.abs(s_hard["v"] - 1.0))) < 1.0
        # an exponent strike is catastrophic: huge deviation or NaN/inf
        dev = jnp.abs(s_soft["v"] - 1.0)
        assert bool(jnp.any(~jnp.isfinite(dev) | (dev > 1.0)))

    def test_off_by_default_is_bitfree(self):
        x = {"v": jnp.ones((32,), jnp.float32)}
        plan = memory.WritePlan.for_tree(
            x, policy=lambda p, l: Priority.LOW,
            approx_if=lambda leaf, tag: True)
        _, st = plan.write(jax.random.PRNGKey(2),
                           jax.tree.map(jnp.zeros_like, x), x)
        assert int(st.soft_strikes) == 0


class TestMemoryRegion:
    def test_functional_write_and_report(self):
        data = {"a": jnp.zeros((16, 16), jnp.float32)}
        r = memory.MemoryRegion.create(data, level=Priority.MID,
                                       backend="lanes_ref")
        r = r.write(jax.random.PRNGKey(0),
                    {"a": jnp.ones((16, 16), jnp.float32)})
        r2 = r.write(jax.random.PRNGKey(1),
                     {"a": jnp.ones((16, 16), jnp.float32)})  # redundant
        rep1, rep2 = r.report(), r2.report()
        assert rep2["energy_pj"] == rep1["energy_pj"]  # CMP: free rewrite
        assert rep2["bits_total"] == 2 * rep1["bits_total"]
        assert rep2["backend"] == "lanes_ref"
        np.testing.assert_array_equal(np.asarray(r2.read()["a"]),
                                      np.asarray(r.read()["a"]))

    def test_stats_stay_on_device_until_report(self):
        r = memory.MemoryRegion.create({"a": jnp.zeros((8,), jnp.float32)})
        r = r.write(jax.random.PRNGKey(0), {"a": jnp.ones((8,),
                                                          jnp.float32)})
        assert all(isinstance(v, jax.Array)
                   for v in jax.tree.leaves(r.stats))


class TestApproxStoreShim:
    def test_cumulative_accounting_device_resident(self):
        store = aps.ApproxStore()
        k = jax.random.PRNGKey(12)
        x = jnp.ones((64,), jnp.float32)
        store, _ = store.write(k, "w", x, Priority.EXACT)
        # stats accumulate as device arrays; properties sync on read-out
        assert all(isinstance(v, jax.Array)
                   for v in jax.tree.leaves(store.stats))
        e1 = store.energy_pj
        store, _ = store.write(k, "w", x, Priority.EXACT)  # redundant
        assert store.energy_pj == e1
        store, got = store.write(k, "w", x * 2, Priority.EXACT)
        assert store.energy_pj > e1
        assert store.bits_written > 0 and store.bit_errors == 0
        assert bool(jnp.all(store.read("w") == got))

    def test_shim_accepts_backend(self):
        store = aps.ApproxStore(backend="lanes_ref")
        store, _ = store.write(jax.random.PRNGKey(0), "x",
                               jnp.ones((33,), jnp.bfloat16), Priority.LOW)
        assert store.bits_written > 0


class TestExtentTableReset:
    def test_reset_stats_keeps_entries(self):
        t = ExtentTable(capacity=4)
        t.update("a", Priority.LOW)
        t.lookup("a")
        t.lookup("b")  # miss installs default
        assert t.hits == 1 and t.misses == 1
        t.reset_stats()
        assert t.hits == 0 and t.misses == 0 and t.evictions == 0
        # cached entries survive: "a" still resolves LOW as a hit
        assert t.lookup("a") == Priority.LOW
        assert t.hits == 1

    def test_scheduler_reports_per_run_table_traffic(self):
        """Two runs on ONE engine: the second report must not aggregate the
        first stream's table counters."""
        from repro.configs import get_config
        from repro.serve import (ContinuousScheduler, ServeConfig,
                                 ServingEngine, synthetic_requests)
        cfg = get_config("qwen2.5-3b").reduced()
        eng = ServingEngine(cfg, ServeConfig(max_seq=32, max_new_tokens=4))
        reqs = synthetic_requests(cfg, 2, prompt_len=8, new_tokens=3,
                                  app_ids=["app"], seed=0)
        rep1 = ContinuousScheduler(eng, capacity=2).run(reqs)
        rep2 = ContinuousScheduler(eng, capacity=2).run(reqs)
        # run 1: one miss (install) + one hit; run 2: both hit the cached
        # block — and neither report carries the other's counters
        assert rep1["extent_table"]["misses"] == 1
        assert rep1["extent_table"]["hits"] == 1
        assert rep2["extent_table"]["misses"] == 0
        assert rep2["extent_table"]["hits"] == 2


class TestCompressionWirePath:
    def test_wire_backend_exercises_int8_lanes(self):
        g = {"w": jax.random.normal(jax.random.PRNGKey(0), (64, 8)) * 1e-3}
        cfg = comp.CompressionConfig(wire_backend="lanes_ref",
                                     wire_level=Priority.HIGH)
        out, ef, st = comp.compress_grads(
            g, comp.init_state(g), cfg, key=jax.random.PRNGKey(1),
            with_stats=True)
        assert isinstance(st, memory.WriteStats)
        assert float(st.bits_total) == 64 * 8 * 8  # int8 codes
        assert int(st.bits_written) > 0
        assert out["w"].shape == g["w"].shape

    def test_wire_upsets_washed_out_by_error_feedback(self):
        """With the EF residual, the accumulated applied gradient tracks
        the true gradient even when the wire buffer errs (HIGH level)."""
        cfg = comp.CompressionConfig(wire_backend="lanes_ref",
                                     wire_level=Priority.HIGH)
        key = jax.random.PRNGKey(1)
        g_true = {"w": jax.random.normal(key, (32,)) * 1e-3}
        ef = comp.init_state(g_true)
        applied = jnp.zeros((32,))
        for i in range(50):
            out, ef = comp.compress_grads(g_true, ef, cfg,
                                          key=jax.random.fold_in(key, i))
            applied = applied + out["w"]
        total_true = 50 * g_true["w"]
        rel = float(jnp.linalg.norm(applied - total_true)
                    / jnp.linalg.norm(total_true))
        assert rel < 0.05, f"wire-write bias not absorbed by EF: {rel}"

    def test_disabled_wire_path_unchanged(self):
        g = {"w": jnp.ones((16,))}
        cfg = comp.CompressionConfig()
        assert cfg.wire_backend is None
        out, ef = comp.compress_grads(g, comp.init_state(g), cfg)
        assert out["w"].shape == (16,)


class TestServeBackendSelection:
    @pytest.mark.parametrize("backend", ["oracle", "lanes_ref", "exact"])
    def test_engine_runs_on_every_backend(self, backend):
        from repro.configs import get_config
        from repro.serve import ServeConfig, ServingEngine
        cfg = get_config("qwen2.5-3b").reduced()
        eng = ServingEngine(cfg, ServeConfig(max_seq=32, max_new_tokens=3,
                                             backend=backend))
        prompt = {"tokens": jax.random.randint(
            jax.random.PRNGKey(42), (2, 8), 0, cfg.vocab_size)}
        toks, report = eng.generate(prompt)
        assert toks.shape == (2, 3)
        tot = report["total"]
        if backend == "exact":
            assert tot["energy_pj"] == 0.0 and tot["bit_errors"] == 0
            assert tot["bits_total"] > 0
        else:
            assert tot["energy_pj"] > 0

    def test_lanes_vs_oracle_same_flips_and_energy(self):
        """Engine-level parity: the SAME generate() on two backends agrees
        on every RNG-independent quantity (same key schedule + greedy
        sampling => identical write streams... unless an approximate-read
        divergence changes the trajectory; energy/flips equality holds for
        the prefill stream which precedes any divergence)."""
        from repro.configs import get_config
        from repro.serve import ServeConfig, ServingEngine
        cfg = get_config("qwen2.5-3b").reduced()
        prompt = {"tokens": jax.random.randint(
            jax.random.PRNGKey(7), (2, 8), 0, cfg.vocab_size)}
        reports = {}
        for backend in ("oracle", "lanes_ref"):
            eng = ServingEngine(cfg, ServeConfig(max_seq=32,
                                                 max_new_tokens=2,
                                                 backend=backend))
            _, raw = eng.generate(prompt, sync_stats=False)
            reports[backend] = raw["device_stats"]["kv_prefill"].host_dict()
        a, b = reports["oracle"], reports["lanes_ref"]
        assert a["flips01"] == b["flips01"]
        assert a["flips10"] == b["flips10"]
        assert a["bits_total"] == b["bits_total"]
        np.testing.assert_allclose(a["energy_pj"], b["energy_pj"],
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# the column write's column addressing (WritePlan.write_columns)
# ---------------------------------------------------------------------------

_KV_AXES = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
#: qwen2.5-3b-like (2 KV heads of 128) and h2o-danube-1.8b-like (8 of 80)
_COLUMN_SHAPES = {"qwen": (3, 4, 16, 2, 128), "danube": (2, 4, 16, 8, 80)}
#: each cell's decode pool: 32 slots x 512 positions at full depth
_CELL_SHAPES = {"qwen2.5-3b": (36, 32, 512, 2, 128),
                "h2o-danube-1.8b": (24, 32, 512, 8, 80)}


def _element_column(leaf, col, ax, batch_axis):
    """The column as one index per element (``take_along_axis`` over an
    index broadcast to the whole column): the reference the column write
    must match bit for bit."""
    ishape = [1] * leaf.ndim
    ishape[batch_axis] = col.shape[0]
    gshape = leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:]
    return jnp.take_along_axis(
        leaf, jnp.broadcast_to(col.reshape(ishape), gshape), axis=ax)


def _kv_plan(tree, backend="lanes_ref", spec=None):
    return memory.WritePlan.for_tree(
        tree, policy=kv_cache_policy, backend=backend,
        axes={"k": _KV_AXES, "v": _KV_AXES}, address_spec=spec)


def _bits16(tree):
    return [np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16))
            for a in jax.tree.leaves(tree)]


def _elementwise_ring_gathers(text, rank):
    """Gathers in a lowered (StableHLO) or compiled (HLO) module that read
    a rank-``rank`` operand one element per index: all-ones
    ``slice_sizes``."""
    sizes = (re.findall(r"slice_sizes = array<i64: ([\d, ]+)>", text)
             + re.findall(r"slice_sizes=\{([\d,]+)\}", text))
    return [s for s in sizes
            if [int(x) for x in s.replace(" ", "").split(",")]
            == [1] * rank]


class TestColumnWrite:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("shape,ax,batch_axis", [
        ((3, 4, 16, 2, 8), 2, 1), ((16, 4, 3, 8), 0, 1),
        ((4, 3, 16), 2, 0), ((3, 16, 4, 8), 1, 2)])
    def test_ring_column_is_the_element_gather(self, shape, ax,
                                               batch_axis, dtype):
        """Any ring and slot axis, any word width, and the words a float
        sum or move would alter: -0.0, NaNs with payloads, subnormals."""
        from repro.core.priority import uint_type
        from repro.memory.plan import _ring_column
        ut = uint_type(dtype)
        words = jax.random.bits(jax.random.PRNGKey(0), shape, ut)
        sign = ut(1 << (8 * jnp.dtype(ut).itemsize - 1))
        words = words.at[(0,) * len(shape)].set(sign)   # -0.0
        leaf = jax.lax.bitcast_convert_type(words, dtype)
        col = jax.random.randint(jax.random.PRNGKey(1),
                                 (shape[batch_axis],), 0, shape[ax])
        col = col.at[0].set(0)
        got = _ring_column(leaf, col, ax, batch_axis)
        # gathered as integers: a float gather may quiet a NaN (the CPU
        # backend moves bf16 through f32)
        want = _element_column(words, col, ax, batch_axis)
        assert got.shape == want.shape
        np.testing.assert_array_equal(
            np.asarray(jax.lax.bitcast_convert_type(got, ut)),
            np.asarray(want))

    @pytest.mark.parametrize("arm", ["plain", "alias", "worn"])
    @pytest.mark.parametrize("backend", ["lanes_ref", "pallas"])
    @pytest.mark.parametrize("name", sorted(_COLUMN_SHAPES))
    def test_matches_the_element_gather(self, monkeypatch, name, backend,
                                        arm):
        """Stored bits and every WriteStats field equal the element-gather
        column write's: ring wrap (pos >= C), inactive slots (new rows
        equal to old, as ``mask_rows`` leaves them), a prefix alias, and
        one worn row group."""
        from repro.memory import plan as plan_mod
        from repro.memory.address import AddressSpec
        shape = _COLUMN_SHAPES[name]
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        old = {n: jax.random.normal(k, shape).astype(jnp.bfloat16)
               for n, k in zip("kv", ks[:2])}
        active = jnp.asarray([True, False, True, True])
        new = jax.tree.map(lambda o: jnp.where(
            active[None, :, None, None, None],
            jax.random.normal(ks[2], shape).astype(jnp.bfloat16), o), old)
        pos = jnp.asarray([3, 9, 16, 37], jnp.int32)   # 16, 37 wrap
        spec = AddressSpec(group_cols=4) if arm == "worn" else None
        plan = _kv_plan(old, backend, spec)
        kw = {}
        if arm == "alias":
            kw["alias_cols"] = jnp.asarray([5, 0, 20, 0], jnp.int32)
        if arm == "worn":
            # slot 3's column 37 % 16 = 5 lies in its row group 1 of 4
            worn = jnp.zeros((2, spec.n_groups(shape, 2, 1)), bool)
            kw["addr"] = (plan.identity_address().shifts,
                          worn.at[:, 3 * 4 + 1].set(True))
        key = jax.random.PRNGKey(9)
        got, st = plan.write_columns(key, old, new, pos, **kw)
        monkeypatch.setattr(plan_mod, "_ring_column", _element_column)
        want, st_ref = plan.write_columns(key, old, new, pos, **kw)
        for a, b in zip(_bits16(got), _bits16(want)):
            np.testing.assert_array_equal(a, b)
        assert st.host_dict() == st_ref.host_dict()
        if arm == "worn":
            assert st.errors > 0

    @pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
    def test_reads_no_element_gather_at_cell_shapes(self, monkeypatch,
                                                    cell):
        """Lowered at each cell's pool shape (abstract inputs, nothing
        allocated), the column write reads no ring leaf one element per
        index; the element gather it replaced does."""
        from repro.memory import plan as plan_mod
        shape = _CELL_SHAPES[cell]
        leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        tree = {"k": leaf, "v": leaf}
        plan = _kv_plan(tree)
        vec = plan.vectors_for()
        args = (jax.ShapeDtypeStruct((2,), jnp.uint32), tree, tree,
                jax.ShapeDtypeStruct((shape[1],), jnp.int32))

        def lowered():
            return jax.jit(lambda k, o, n, p: plan.write_columns(
                k, o, n, p, vec)).lower(*args).as_text()

        assert _elementwise_ring_gathers(lowered(), len(shape)) == []
        monkeypatch.setattr(plan_mod, "_ring_column", _element_column)
        assert _elementwise_ring_gathers(lowered(), len(shape))
