"""The Pallas write and scrub kernels over a slot mesh of four devices.

Run in a process that has four devices (tests/test_kernels.py starts it
on four virtual CPU devices; ``chip_smoke.py --chips 4`` makes the same
comparison on chips):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python tests/sharded_write_check.py

A ``WritePlan`` with a slot mesh maps the kernels over the pool's slot
axis; the same plan without one writes on one device. On equal inputs the
column write, the whole-tree write, a one-slot admission write and a
scrub must store the same bits and count the same stats, and the compiled
column write must hold no all-gather (each device writes its own slots).
The last line is a JSON object of the comparisons.
"""
from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.priority import kv_cache_policy, uint_type
from repro.memory import WritePlan
from repro.models import get_model
from repro.sharding.mesh import die_device_mesh

SLOTS, SEQ, DIES = 4, 16, 4
BATCH_AXIS = 1


def _bits(tree):
    return [np.asarray(a).view(np.uint8) for a in jax.tree.leaves(tree)]


def _same(a, b) -> bool:
    *da, sa = a
    *db, sb = b
    return (all(np.array_equal(x, y) for x, y in zip(_bits(da), _bits(db)))
            and sa.host_dict() == sb.host_dict())


def main() -> dict:
    assert len(jax.devices()) >= DIES, jax.devices()
    api = get_model(get_config("qwen2.5-3b").reduced())
    tree = api.init_cache(SLOTS, SEQ)
    mesh = die_device_mesh(DIES)
    plans = {n: WritePlan.for_tree(
        tree, policy=kv_cache_policy, backend="pallas",
        axes=api.cache_axes(), batch_axis=BATCH_AXIS, slot_mesh=m)
        for n, m in ((1, None), (DIES, mesh))}
    vec = plans[1].vectors_for()

    def rand(key, t):
        leaves, treedef = jax.tree.flatten(t)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([
            jax.random.normal(k, a.shape).astype(a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a
            for k, a in zip(keys, leaves)])

    k_old, k_new, k_mask, key = jax.random.split(jax.random.PRNGKey(0), 4)
    old, new = rand(k_old, tree), rand(k_new, tree)
    pos = jnp.array([3, 7, 0, 15], jnp.int32)
    rows = jnp.array([2], jnp.int32)

    def place(t):
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = lambda a: P(*([None] * BATCH_AXIS), "die") if (
            a.ndim > BATCH_AXIS) else P()
        return jax.tree.map(lambda a: jax.device_put(
            a, NamedSharding(mesh, spec(a))), t)

    def ops(plan):
        def scrub(key, t, masks):
            out, acc = [], None
            for i, (a, m) in enumerate(zip(jax.tree.leaves(t), masks)):
                if plan.leaf_levels[i] is None:
                    continue
                s, r, st = plan.leaf_call("leaf_scrub", key, a, m, vec[i])
                out += [s, r]
                acc = st if acc is None else acc + st
            return (*out, acc)

        return {
            "write_columns": jax.jit(lambda k, o, n, p: plan.write_columns(
                k, o, n, p, vec)),
            "write": jax.jit(lambda k, o, n: plan.write(k, o, n, vec)),
            "admission_row": jax.jit(lambda k, o, n, r: plan.write(
                k, jax.tree.map(lambda a: jnp.take(a, r, BATCH_AXIS), o),
                jax.tree.map(lambda a: jnp.take(a, r, BATCH_AXIS), n),
                vec)),
            "scrub": jax.jit(scrub),
        }

    masks = []
    for i, a in enumerate(jax.tree.leaves(tree)):
        ut = uint_type(a.dtype)
        bits = [jax.random.bits(k, a.shape, ut)
                for k in jax.random.split(jax.random.fold_in(k_mask, i), 3)]
        masks.append(bits[0] & bits[1] & bits[2])  # ~1/8 of bits decayed

    args = {"write_columns": (key, old, new, pos),
            "write": (key, old, new),
            "admission_row": (key, old, new, rows),
            "scrub": (key, old, masks)}
    one, four = ops(plans[1]), ops(plans[DIES])
    result = {}
    for name, a in args.items():
        a4 = tuple(place(x) if i else x for i, x in enumerate(a))
        result[name] = _same(one[name](*a), four[name](*a4))
    hlo = four["write_columns"].lower(
        key, place(old), place(new), place(pos)).compile().as_text()
    result["all_gathers_in_column_write"] = len(
        re.findall(r"\ball-gather(-start)?\(", hlo))
    return result


if __name__ == "__main__":
    print(json.dumps(main()))
