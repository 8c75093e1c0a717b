"""The Pallas kernels compile natively for a v5e, at full width.

Interpret mode cannot see what the chip's compiler refuses (scalar stores
to VMEM, blocks off the (8, 128) tiling, more VMEM than a kernel may use,
a Mosaic call XLA would have to partition), so these tests hand the
installed TPU compiler a *described* v5e and compile the kernels with
``interpret=False``:

  * the write and scrub kernels (and the int8 KV quantize-store) at
    qwen2.5-3b's cache shapes (36 layers, 2 KV heads of 128, bf16): the
    decode column written once per token for 8 slots, and the admission
    write of a 128-token prompt, which spans several default
    (256, 512)-lane blocks;
  * the write and scrub kernels over the four chips of the described
    v5e:2x2, mapped over a 4-slot pool's slot axis as the die-sharded
    scheduler runs them: they compile, and the column write gathers
    nothing across chips;
  * the sliding-window attention kernel at gemma2-9b's local layers;
  * the decode column write at both benchmark cells' pool shapes, which
    must read the written column without an element-wise gather.

Nothing runs: a compile that passes is not a chip run. The topology is
described inside a fixture, never at import, so every test worker
collects the same tests and only the one given this file loads the TPU
library.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.priority import Priority
from repro.kernels.extent_write.ops import extent_write, level_vectors
from repro.kernels.kv_quant import kv_quant_store
from repro.kernels.local_attention import local_attention
from repro.kernels.scrub.ops import scrub_write

SHAPES = {
    "decode_column": (36, 8, 1, 2, 128),
    "admission_128": (36, 1, 128, 2, 128),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    # compiled programs for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the way
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _args(one_chip, shape, mask: bool):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    vec = tuple(sds(v.shape, v.dtype)
                for v in level_vectors(jnp.dtype(jnp.bfloat16), Priority.LOW))
    second = jnp.uint16 if mask else jnp.bfloat16
    return (sds((2,), jnp.uint32), sds(shape, jnp.bfloat16),
            sds(shape, second)) + vec


def _native_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_extent_write_compiles_for_v5e(one_chip, name):
    text = _native_text(
        lambda key, old, new, *vec: extent_write(
            key, old, new, vectors=vec, use_kernel=True, interpret=False),
        _args(one_chip, SHAPES[name], mask=False))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_scrub_compiles_for_v5e(one_chip, name):
    text = _native_text(
        lambda key, stored, mask, *vec: scrub_write(
            key, stored, mask, vectors=vec, use_kernel=True,
            interpret=False),
        _args(one_chip, SHAPES[name], mask=True))
    assert "tpu_custom_call" in text


#: ops that move float words without computing on them
_MOVES = {"parameter", "constant", "bitcast-convert", "bitcast", "reshape",
          "copy", "copy-start", "copy-done", "fusion", "slice",
          "dynamic-slice", "pad", "concatenate", "transpose", "tuple",
          "get-tuple-element", "opt-barrier"}


@pytest.mark.parametrize("shape", [(1472,), (3, 4, 5, 2, 8),
                                   SHAPES["decode_column"]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stored_words_move_only_as_data(one_chip, dtype, shape):
    """A stored word may be a NaN with a payload or a subnormal (a failed
    write or a decayed bit). Compiled for the v5e, the write and the scrub
    hold no float arithmetic: XLA for a TPU can compile the lane
    unpacking's reshape into an f32 add-reduce, which canonicalizes such
    words on the chip and breaks pallas == lanes_ref for f32."""
    from repro.core.priority import uint_type
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    vec = tuple(sds(v.shape, v.dtype)
                for v in level_vectors(jnp.dtype(dtype), Priority.LOW))
    key, data = sds((2,), jnp.uint32), sds(shape, dtype)
    texts = (
        _native_text(lambda k, o, n, *v: extent_write(
            k, o, n, vectors=v, interpret=False)[0],
            (key, data, data) + vec),
        _native_text(lambda k, s, m, *v: scrub_write(
            k, s, m, vectors=v, interpret=False)[0],
            (key, data, sds(shape, uint_type(dtype))) + vec))
    for text in texts:
        ops = set(re.findall(r"= (?:f32|bf16)\[[^\]]*\]\S* ([a-z\-]+)\(",
                             text))
        assert ops and ops <= _MOVES, ops - _MOVES


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_kv_quant_compiles_for_v5e(one_chip, name):
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    text = _native_text(
        lambda key, kv: kv_quant_store(key, kv, interpret=False),
        (sds((2,), jnp.uint32), sds(SHAPES[name], jnp.bfloat16)))
    assert "tpu_custom_call" in text


def test_local_attention_compiles_for_v5e(one_chip):
    """gemma2-9b's local layers: 16 query / 8 KV heads of 256, a 4096
    window, logit softcap 50, over an 8192-token prefill."""
    from repro.configs import get_config
    cfg = get_config("gemma2-9b")
    S = 8192
    sds = lambda h: jax.ShapeDtypeStruct((1, S, h, cfg.head_dim),
                                         jnp.bfloat16, sharding=one_chip)
    text = _native_text(
        lambda q, k, v: local_attention(
            q, k, v, window=cfg.window_pattern[0], softcap=50.0,
            interpret=False),
        (sds(cfg.num_heads), sds(cfg.num_kv_heads), sds(cfg.num_kv_heads)))
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def four_chip_plan(one_chip, topo):
    """qwen2.5-3b's 4-slot pool cache, its slot axis split over the four
    described chips, and the pallas write plan that maps over them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core.priority import kv_cache_policy
    from repro.memory import WritePlan
    from repro.memory.backends import LaneBackend
    from repro.models import get_model
    api = get_model(get_config("qwen2.5-3b"))
    mesh = Mesh(np.asarray(topo.devices[:4]), ("die",))
    tree = jax.eval_shape(lambda: api.init_cache(4, 256))
    # the registry's "pallas" resolves interpret for this host's platform
    native = LaneBackend("pallas", use_kernel=True, interpret=False)
    plan = WritePlan.for_tree(tree, policy=kv_cache_policy, backend=native,
                              axes=api.cache_axes(), batch_axis=1,
                              slot_mesh=mesh)
    split = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=(
        NamedSharding(mesh, P(None, "die")) if a.ndim > 1
        else NamedSharding(mesh, P())))
    return plan, jax.tree.map(split, tree), split


def _collectives(text):
    return {c: len(re.findall(rf"\b{c}(-start)?\(", text))
            for c in ("all-gather", "all-reduce")}


def test_sharded_column_write_compiles_for_four_chips(four_chip_plan):
    """The decode column write over the die-sharded pool: native kernels,
    no all-gather (each chip writes its own slots); the per-plane counts
    are summed across chips."""
    plan, cache, split = four_chip_plan
    vec = plan.vectors_for()
    text = jax.jit(lambda k, o, n, p: plan.write_columns(
        k, o, n, p, vec)).lower(
            split(jax.ShapeDtypeStruct((2,), jnp.uint32)), cache, cache,
            split(jax.ShapeDtypeStruct((4,), jnp.int32))).compile().as_text()
    assert "tpu_custom_call" in text
    n = _collectives(text)
    assert n["all-gather"] == 0, n
    assert n["all-reduce"] >= 1, n


#: each cell's decode pool: 32 slots x 512 positions at full depth
CELL_POOLS = {"qwen2.5-3b": (36, 32, 512, 2, 128),
              "h2o-danube-1.8b": (24, 32, 512, 8, 80)}


@pytest.mark.parametrize("cell", sorted(CELL_POOLS))
def test_column_write_reads_no_element_gather_for_v5e(one_chip, monkeypatch,
                                                     cell):
    """Compiled for one v5e at each cell's pool shape, the decode column
    write reads no ring leaf one element per index (all-ones
    ``slice_sizes``): such a gather costs ~7 ms per K or V leaf on the
    chip, where the column is under 1 MB. The element gather it replaced
    shows there."""
    from repro.core.priority import kv_cache_policy
    from repro.memory import WritePlan
    from repro.memory import plan as plan_mod
    shape = CELL_POOLS[cell]
    axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    tree = {"k": leaf, "v": leaf}
    plan = WritePlan.for_tree(tree, policy=kv_cache_policy,
                              axes={"k": axes, "v": axes})
    vec = plan.vectors_for()
    args = (jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
            tree, tree,
            jax.ShapeDtypeStruct((shape[1],), jnp.int32, sharding=one_chip))
    ones = "slice_sizes={%s}" % ",".join(["1"] * len(shape))

    def text():
        return _native_text(
            lambda k, o, n, p: plan.write_columns(k, o, n, p, vec), args)

    assert ones not in text()

    def element_column(leaf, col, ax, batch_axis):
        idx = col.reshape([-1 if d == batch_axis else 1
                           for d in range(leaf.ndim)])
        return jnp.take_along_axis(leaf, jnp.broadcast_to(
            idx, leaf.shape[:ax] + (1,) + leaf.shape[ax + 1:]), axis=ax)

    monkeypatch.setattr(plan_mod, "_ring_column", element_column)
    assert ones in text()


def test_sharded_scrub_compiles_for_four_chips(four_chip_plan):
    """A whole-leaf scrub over the die-sharded pool: native kernel, no
    all-gather."""
    from repro.core.priority import uint_type
    plan, cache, split = four_chip_plan
    leaf = jax.tree.leaves(cache)[0]
    mask = split(jax.ShapeDtypeStruct(leaf.shape, uint_type(leaf.dtype)))
    lv = plan.vectors_for()[0]
    text = jax.jit(lambda k, a, m: plan.leaf_call(
        "leaf_scrub", k, a, m, lv)).lower(
            split(jax.ShapeDtypeStruct((2,), jnp.uint32)), leaf,
            mask).compile().as_text()
    assert "tpu_custom_call" in text
    assert _collectives(text)["all-gather"] == 0
