"""Dry-run internals + roofline model unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, all_cells, get_config
from repro.launch import roofline as rl
from repro.launch.dryrun import _collective_bytes, input_specs
from repro.models import get_model


class TestInputSpecs:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_every_cell_has_specs(self, arch):
        for shape in SHAPES:
            specs = input_specs(arch, shape)
            assert "tokens" in specs or "frames" in specs
            for v in specs.values():
                assert isinstance(v, jax.ShapeDtypeStruct)
                assert v.shape[0] == SHAPES[shape].global_batch

    def test_cell_count(self):
        cells = list(all_cells())
        assert len(cells) == 34  # 40 assigned − 6 documented long_500k skips


class TestCollectiveParser:
    HLO = """
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}
  %ag = bf16[64]{0} all-gather(%y), replica_groups=[2,8]<=[16]
  %tup = (f32[16]{0}, f32[16]{0}) all-to-all(%a, %b), replica_groups={{0,1}}
"""

    def test_parses_kinds_and_bytes(self):
        out = _collective_bytes(self.HLO, 16)
        assert out["all-reduce"]["count"] == 1
        assert out["all-reduce"]["result_bytes"] == 128 * 256 * 4
        # ring all-reduce wire = 2*(g-1)/g * bytes, g=4
        np.testing.assert_allclose(out["all-reduce"]["wire_bytes"],
                                   2 * 3 / 4 * 128 * 256 * 4)
        assert out["all-gather"]["result_bytes"] == 64 * 2
        assert out["all-to-all"]["result_bytes"] == 2 * 16 * 4

    def test_empty_hlo(self):
        assert _collective_bytes("ENTRY main { ROOT %r = f32[] }", 8) == {}


class TestRooflineModel:
    def test_terms_positive_for_all_cells(self):
        for arch, shape in all_cells():
            r = rl.analyze(arch, shape)
            assert r.compute_s > 0 and r.memory_s > 0, (arch, shape)
            assert r.collective_s >= 0
            assert 0 < r.useful_ratio <= 1.2, (arch, shape, r.useful_ratio)
            assert r.bottleneck in ("compute", "memory", "collective")

    def test_train_flops_close_to_6nd(self):
        """Dense train cells: analytic total within [6ND, 10ND] (attention
        + remat overhead on top of the matmul floor)."""
        for arch in ("qwen2.5-3b", "mistral-nemo-12b"):
            r = rl.analyze(arch, "train_4k")
            assert 1.0 <= r.total_flops / r.model_flops <= 1.8, arch

    def test_moe_uses_active_params(self):
        r = rl.analyze("llama4-scout-17b-a16e", "train_4k")
        api = get_model(get_config("llama4-scout-17b-a16e"))
        n_act, n_tot = api.active_params_per_token(), api.num_params()
        assert n_act < 0.3 * n_tot
        # MODEL_FLOPS built from active params
        T = 4096 * 256
        np.testing.assert_allclose(r.model_flops, 6 * n_act * T, rtol=1e-6)

    def test_decode_memory_includes_kv(self):
        base = rl.analyze("qwen2.5-3b", "decode_32k")
        kvq = rl.analyze("qwen2.5-3b", "decode_32k",
                         rl.STRATEGIES["serve_tp_only_kvq8"])
        assert kvq.memory_s < base.memory_s

    def test_strategies_change_collectives(self):
        base = rl.analyze("mamba2-2.7b", "train_4k")
        wide = rl.analyze("mamba2-2.7b", "train_4k",
                          rl.STRATEGIES["dp64_tp4"])
        assert wide.collective_s < 0.5 * base.collective_s

    def test_windowed_attention_cheaper(self):
        """h2o-danube (SWA-4096) must pay less attention flops than a full-
        attention model of equal shape at 32k prefill."""
        import dataclasses
        cfg = get_config("h2o-danube-1.8b")
        full = dataclasses.replace(cfg, window_pattern=(0,))
        shp = SHAPES["prefill_32k"]
        swa_fl = rl.cell_flops(cfg, shp)["total"]
        full_fl = rl.cell_flops(full, shp)["total"]
        assert swa_fl < full_fl


def test_dryrun_import_is_side_effect_free():
    """Importing launch.dryrun (this module did, at collection time) must
    not stage the CLI's 512-device XLA_FLAGS: pytest imports test modules
    before the jax backend initializes, so an import-time mutation would
    put the ENTIRE suite on 512 fake CPU devices — conftest.py's contract
    is that smoke tests see the real single device. (Found the hard way:
    the sharded-serve bit-invariance test folds dies onto real devices,
    and a partitioned f32 energy reduction reassociates by a few ULP.)"""
    import os
    assert "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", "")


class TestStrategyRules:
    def test_all_named_strategies_resolve(self):
        from repro.sharding import make_host_mesh
        from repro.sharding.rules import strategy_rules
        mesh = make_host_mesh()
        for name in ("baseline", "serve_tp_only", "serve_moe_2d"):
            rules = strategy_rules(mesh, name)
            assert "embed" in rules
        with pytest.raises(KeyError):
            strategy_rules(mesh, "nope")


class TestPeaks:
    def test_v5e_peaks_by_device_kind(self):
        from repro.launch import hw
        p = hw.peaks("TPU v5 lite")
        assert p.flops_bf16 == 197e12 and p.hbm_bw == 819e9

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
    def test_unknown_device_kind_raises(self, kind):
        from repro.launch import hw
        with pytest.raises(KeyError, match="no peak figures"):
            hw.peaks(kind)

    def test_running_device_is_looked_up(self):
        from repro.launch import hw
        dev = jax.devices()[0]
        if dev.device_kind in hw.PEAKS:
            assert hw.device_peaks() is hw.PEAKS[dev.device_kind]
        else:
            with pytest.raises(KeyError):
                hw.device_peaks()


KEY_FLAGS = ("jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")


@pytest.fixture
def key_flags():
    """Restores the cache-key flags ``use_compile_cache`` sets."""
    was = {k: getattr(jax.config, k) for k in KEY_FLAGS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


class TestCompileCache:
    def test_fixed_path_in_the_checkout_is_ignored_by_git(self, monkeypatch,
                                                          key_flags):
        from pathlib import Path
        from repro.launch import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            assert cc.use_compile_cache() == str(cc.CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == str(cc.CACHE_DIR)
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        repo = Path(__file__).resolve().parents[1]
        assert cc.CACHE_DIR.parent == repo
        ignored = (repo / ".gitignore").read_text().split()
        assert f"{cc.CACHE_DIR.name}/" in ignored

    def test_env_directory_is_left_to_jax(self, monkeypatch, tmp_path,
                                          key_flags):
        from repro.launch import compile_cache as cc
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        was = jax.config.jax_compilation_cache_dir
        assert cc.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was

    def test_cache_key_keeps_scopes_without_the_checkout_path(
            self, monkeypatch, tmp_path, key_flags):
        """A program with a device scope never loads an executable
        compiled without it (JAX's default key strips op metadata), and
        the key does not depend on where the checkout lies."""
        import hashlib

        import jax.numpy as jnp
        from jax._src import cache_key
        from repro.launch import compile_cache as cc
        from repro.telemetry.scopes import scope
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        cc.use_compile_cache()

        def step(with_scope):
            def f(x):
                if not with_scope:
                    return x * 2
                with scope("decode_model"):
                    return x * 2
            return f

        x = jnp.ones(4)
        hlo = jax.jit(step(True)).lower(x).as_text(debug_info=True)
        assert "decode_model" in hlo
        assert str(cc.CHECKOUT) not in hlo
        assert "tests/test_launch_tools.py" in hlo

        def key(fn):
            h = hashlib.sha256()
            mod = jax.jit(fn).lower(x).compiler_ir("stablehlo")
            cache_key._hash_computation(h, mod, cache_key.IgnoreCallbacks.NO)
            return h.hexdigest()
        assert key(step(False)) != key(step(True))
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        assert key(step(False)) == key(step(True))
