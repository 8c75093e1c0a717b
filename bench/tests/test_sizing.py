"""Each cell's largest programs, compiled for a described TPU v5e (the
compiler is installed here; no chip is needed): the decode burst over the
whole pool and a one-request admission of the longest prompt. Their
``memory_analysis`` is the sizing of the cells (PERF.md, section 4): the
weights, the pool and the larger of the two programs' working sets must fit
one chip's 16 GB."""
import importlib
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import run
from harness import traffic

ROOT = Path(__file__).resolve().parents[2]
HBM = 16e9
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", CELLS)
def test_cell_fits_one_chip(one_chip, name):
    from repro.core.energy_model import zero_slot_stats
    from repro.memory import WriteStats
    from repro.models import get_model
    from repro.serve import ServeConfig, ServingEngine

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.cell_of(bench, name)
    conf = json.loads((ROOT / cell["config_entry"]["file"]).read_text())
    sizes, ref = conf["model"], importlib.import_module(
        f"references.{conf['reference']}")
    mix = traffic.Mix.load(ROOT / "bench" / "mixes" / f"{cell['traffic']}.json")
    cfg = run.model_config(sizes)
    api = get_model(cfg)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = sds(jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    eng = ServingEngine(cfg, ServeConfig(max_seq=mix.max_seq,
                                         max_new_tokens=mix.max_new_tokens,
                                         **mix.serve), params=params)
    C, S = mix.capacity, mix.max_seq
    cache = sds(jax.eval_shape(lambda: api.init_cache(C, S)))
    vec = sds(eng.vectors_for_floor())
    key = sds(jax.random.PRNGKey(0))
    burst = eng._burst.lower(
        params, i32(C), cache, i32(C), key, sds(WriteStats.zero()),
        sds(zero_slot_stats(C)),
        jax.ShapeDtypeStruct((C,), bool, sharding=one_chip), vec,
        n=mix.max_burst).compile().memory_analysis()
    rows = sds(jax.eval_shape(lambda: api.init_cache(1, S)))
    admit = eng._admit_fused.lower(
        params, {"tokens": i32(1, max(mix.prompt_lengths))}, rows, key,
        vec).compile().memory_analysis()

    weights = ref.param_count(sizes) * 2
    pool = ref.cache_bytes(sizes, C, S)
    assert sum(x.size * x.dtype.itemsize
               for x in jax.tree.leaves(cache)) == pool
    burst_peak = (burst.argument_size_in_bytes + burst.output_size_in_bytes
                  + burst.temp_size_in_bytes)
    admit_peak = (admit.argument_size_in_bytes + admit.temp_size_in_bytes
                  + pool)
    print(f"{name}: weights {weights / 1e9:.2f} GB, pool {pool / 1e9:.2f} GB,"
          f" burst temp {burst.temp_size_in_bytes / 1e9:.2f} GB (peak "
          f"{burst_peak / 1e9:.2f}), admission temp "
          f"{admit.temp_size_in_bytes / 1e9:.2f} GB (peak "
          f"{admit_peak / 1e9:.2f})")
    assert burst.argument_size_in_bytes >= weights + pool
    assert max(burst_peak, admit_peak) < HBM
