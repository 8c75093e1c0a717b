"""Fixture tests for ``repro.analysis`` — each rule must fire on a
seeded violation (true positive), stay silent on conforming code (true
negative), and honor the inline waiver protocol.

The fixtures are tiny synthetic trees under ``tmp_path`` (the engine
resolves paths against an explicit ``root``, so the zone/boundary rules
see the same ``src/repro/...`` prefixes they see in the real repo). The
final tests run the engine over THIS repo and pin the RNG registry
values the bit-parity suites depend on.
"""
import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import run_analysis
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.engine import WAIVER_DISCIPLINE, PARSE_ERROR

REPO_ROOT = Path(__file__).resolve().parents[1]


def lint(tmp_path, files, rules=None, paths=None):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return run_analysis(paths=paths or ["src"], root=tmp_path, rules=rules)


def rules_of(report):
    return sorted({f.rule for f in report.violations})


# ------------------------------------------------------------------ R1
class TestOperandDiscipline:
    def test_fires_on_prngkey_and_literal_table_in_jit(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def f(x):
                k = jax.random.PRNGKey(0)
                t = jnp.asarray([1.0, 2.0, 3.0])
                return x * t
        """})
        msgs = [f.message for f in rep.violations
                if f.rule == "operand-discipline"]
        assert len(msgs) == 2
        assert any("PRNGKey" in m for m in msgs)
        assert any("literal constant table" in m for m in msgs)

    def test_fires_on_closure_and_self_state(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax
            import jax.numpy as jnp

            def make(scale):
                @jax.jit
                def h(x):
                    return x * jnp.asarray(scale)
                return h

            class Writer:
                @jax.jit
                def m(self, x):
                    return x * jnp.asarray(self.scale)
        """})
        msgs = [f.message for f in rep.violations
                if f.rule == "operand-discipline"]
        assert len(msgs) == 2
        assert any("closes over an enclosing function" in m for m in msgs)
        assert any("self" in m for m in msgs)

    def test_silent_on_operands_and_module_constants(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax
            import jax.numpy as jnp

            SCALE = [1.0, 2.0]

            @jax.jit
            def f(x, t):
                return x * t * jnp.asarray(SCALE)

            def host(scale):
                return jnp.asarray(scale)  # not traced: fine
        """})
        assert not [f for f in rep.violations
                    if f.rule == "operand-discipline"]

    def test_waiver_silences_with_justification(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            @jax.jit
            def f(x):
                # repro: allow(operand-discipline): fixture bends it
                k = jax.random.PRNGKey(0)
                return x
        """})
        assert rep.ok
        assert len(rep.waived) == 1
        assert rep.waived[0].justification == "fixture bends it"


# ------------------------------------------------------------------ R2
class TestHostSync:
    def test_fires_inside_scan_body(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            def run(xs):
                def body(c, x):
                    v = x.item()
                    return c + v, x
                return jax.lax.scan(body, 0.0, xs)
        """})
        v = [f for f in rep.violations if f.rule == "no-host-sync-in-scan"]
        assert len(v) == 1 and ".item()" in v[0].message

    def test_fires_through_local_call_graph(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax
            import numpy as np

            def helper(x):
                return np.asarray(x)

            @jax.jit
            def entry(x):
                return helper(x)
        """})
        v = [f for f in rep.violations if f.rule == "no-host-sync-in-scan"]
        assert len(v) == 1 and "np.asarray" in v[0].message

    def test_coercion_of_traced_param_kwonly_exempt(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import functools
            import jax

            @functools.partial(jax.jit, static_argnames=("n",))
            def f(x, *, n):
                return x * int(n) + int(x)
        """})
        v = [f for f in rep.violations if f.rule == "no-host-sync-in-scan"]
        assert len(v) == 1 and "'x'" in v[0].message

    def test_zone_flags_host_path_sync(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/sched.py": """
            import jax

            def report(acc):
                return jax.device_get(acc)
        """})
        v = [f for f in rep.violations if f.rule == "no-host-sync-in-scan"]
        assert len(v) == 1 and "zero-sync serving zone" in v[0].message

    def test_silent_outside_zone_and_trace(self, tmp_path):
        rep = lint(tmp_path, {"src/tools/host.py": """
            import jax
            import numpy as np

            def dump(acc):
                print(np.asarray(jax.device_get(acc)))
        """})
        assert not [f for f in rep.violations
                    if f.rule == "no-host-sync-in-scan"]

    def test_zone_waiver(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/sched.py": """
            import jax

            def report(acc):
                # repro: allow(no-host-sync-in-scan): once per run
                return jax.device_get(acc)
        """})
        assert rep.ok and len(rep.waived) == 1


# ------------------------------------------------------------------ R3
REGISTRY_FIXTURE = """
    from typing import NamedTuple

    class Stream(NamedTuple):
        name: str
        offset: int
        domain: str
        doc: str

    A_OFFSET = 1_000_003
    B_OFFSET = 1_000_003
    ORPHAN_OFFSET = 5_000

    STREAMS = (
        Stream("a", A_OFFSET, "root", "a's stream"),
        Stream("b", B_OFFSET, "root", "collides with a"),
    )
"""


class TestMetricsDiscipline:
    def test_fires_on_adhoc_module_accumulator(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            TOTAL_WRITES = 0

            def record(n):
                global TOTAL_WRITES
                TOTAL_WRITES += n
        """})
        v = [f for f in rep.violations if f.rule == "metrics-discipline"]
        assert len(v) == 1 and "TOTAL_WRITES" in v[0].message
        assert "registry" in v[0].message

    def test_fires_on_drain_in_traced_region(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            def run(ins, xs):
                def body(c, x):
                    row = ins.drain()
                    return c + x, x
                return jax.lax.scan(body, 0.0, xs)
        """})
        v = [f for f in rep.violations if f.rule == "metrics-discipline"]
        assert len(v) == 1 and ".drain()" in v[0].message

    def test_silent_on_constants_and_host_drains(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            WRITE_LEAF_OFFSET = 0
            SCALE = 1.5

            def event(tele, clock):
                return tele.event(clock)

            def scaled(x):
                return x * SCALE
        """})
        assert "metrics-discipline" not in rules_of(rep)

    def test_waiver_suppresses(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            # repro: allow(metrics-discipline): legacy counter, migrating in PR 10
            HITS = 0

            def bump():
                global HITS
                HITS += 1
        """})
        assert "metrics-discipline" not in rules_of(rep)
        assert any(w.rule == "metrics-discipline" for w in rep.waived)

    def test_fires_on_bare_trace_marks_outside_telemetry(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/mod.py": """
            import jax
            from jax.profiler import TraceAnnotation

            def step(x):
                with jax.named_scope("decode"):
                    return x + 1

            def host():
                with jax.profiler.TraceAnnotation("burst"):
                    pass
        """})
        v = [f for f in rep.violations if f.rule == "metrics-discipline"]
        assert len(v) == 3
        assert all("repro.telemetry.scopes" in f.message for f in v)

    def test_silent_on_trace_marks_inside_telemetry(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/telemetry/scopes.py": """
            import jax

            def scope(name):
                return jax.named_scope(name)

            class Phase(jax.profiler.TraceAnnotation):
                pass
        """, "src/repro/serve/mod.py": """
            from repro.telemetry.scopes import phase, scope

            def step(x):
                with scope("decode_model"):
                    return x + 1
        """})
        assert "metrics-discipline" not in rules_of(rep)


class TestRngStreamHygiene:
    def test_fires_on_magic_constant_and_offset_assign(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            LOCAL_KEY_OFFSET = 9_000_001

            def fork(key, i):
                return jax.random.fold_in(key, 7_000_019 + i)
        """})
        msgs = [f.message for f in rep.violations
                if f.rule == "rng-stream-hygiene"]
        assert len(msgs) == 2
        assert any("LOCAL_KEY_OFFSET" in m for m in msgs)
        assert any("7000019" in m for m in msgs)

    def test_fires_on_physical_fold(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            def fork(key, phys_col):
                return jax.random.fold_in(key, phys_col)
        """})
        v = [f for f in rep.violations if f.rule == "rng-stream-hygiene"]
        assert len(v) == 1 and "LOGICAL" in v[0].message

    def test_registry_collision_and_orphan(self, tmp_path):
        rep = lint(tmp_path,
                   {"src/repro/memory/rng_streams.py": REGISTRY_FIXTURE})
        msgs = [f.message for f in rep.violations
                if f.rule == "rng-stream-hygiene"]
        assert len(msgs) == 2
        assert any("collides" in m for m in msgs)
        assert any("ORPHAN_OFFSET" in m for m in msgs)

    def test_unknown_registry_attribute(self, tmp_path):
        rep = lint(tmp_path, {
            "src/repro/memory/rng_streams.py": REGISTRY_FIXTURE,
            "src/mod.py": """
                import jax
                from repro.memory import rng_streams

                def fork(key):
                    return jax.random.fold_in(key, rng_streams.NOT_REAL)
            """})
        v = [f for f in rep.violations
             if f.rule == "rng-stream-hygiene" and f.path == "src/mod.py"]
        assert len(v) == 1 and "NOT_REAL" in v[0].message

    def test_silent_on_registry_reference_and_small_folds(self, tmp_path):
        rep = lint(tmp_path, {
            "src/repro/memory/rng_streams.py": """
                from typing import NamedTuple

                class Stream(NamedTuple):
                    name: str
                    offset: int
                    domain: str
                    doc: str

                GOOD_OFFSET = 1_000_003
                STREAMS = (Stream("good", GOOD_OFFSET, "root", "ok"),)
            """,
            "src/mod.py": """
                import jax
                from repro.memory import rng_streams

                def fork(key, i):
                    k = jax.random.fold_in(key,
                                           rng_streams.GOOD_OFFSET + i)
                    return jax.random.fold_in(k, i)
            """})
        assert not [f for f in rep.violations
                    if f.rule == "rng-stream-hygiene"]

    def test_waiver(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            def fork(key):
                # repro: allow(rng-stream-hygiene): fixture constant
                return jax.random.fold_in(key, 7_000_019)
        """})
        assert rep.ok and len(rep.waived) == 1


# ------------------------------------------------------------------ R4
class TestRegistryDiscipline:
    def test_fires_outside_boundary(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/bad.py": """
            import repro.kernels.scrub.kernel as sk
            from repro.kernels.extent_write.ops import approx_write_lanes

            def f(key, dst, src, vec):
                out = approx_write_lanes(key, dst, src, vec,
                                         use_kernel=True)
                return sk.scrub(out, interpret=False)
        """})
        msgs = [f.message for f in rep.violations
                if f.rule == "registry-discipline"]
        assert len(msgs) == 5  # 2 imports + 2 kwargs + 1 direct call
        assert any("repro.kernels.extent_write.ops" in m for m in msgs)
        assert any("use_kernel" in m for m in msgs)
        assert any("interpret" in m for m in msgs)

    def test_silent_inside_boundary_and_for_public_kernels(self, tmp_path):
        rep = lint(tmp_path, {
            "src/repro/memory/backend.py": """
                from repro.kernels.extent_write.ops import (
                    approx_write_lanes)

                def write(key, dst, src, vec):
                    return approx_write_lanes(key, dst, src, vec,
                                              use_kernel=True)
            """,
            "src/repro/serve/ok.py": """
                from repro.kernels.kv_quant import quantize
                from repro.memory import get_backend

                def f(x):
                    return get_backend("pallas"), quantize(x)
            """})
        assert not [f for f in rep.violations
                    if f.rule == "registry-discipline"]

    def test_waiver(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/bench.py": """
            # repro: allow(registry-discipline): measures the raw kernel
            from repro.kernels.extent_write.ops import approx_write_lanes
        """})
        assert rep.ok and len(rep.waived) == 1


# ------------------------------------------------------------------ R5
class TestPytreeCarry:
    def test_fires_on_unfrozen_registered_dataclass(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import dataclasses
            import jax

            @jax.tree_util.register_pytree_node_class
            @dataclasses.dataclass
            class Carry:
                x: int

            @dataclasses.dataclass
            class Stats:
                n: int

            jax.tree_util.register_dataclass(
                Stats, data_fields=["n"], meta_fields=[])
        """})
        msgs = [f.message for f in rep.violations
                if f.rule == "pytree-carry-discipline"]
        assert len(msgs) == 2
        assert any("Carry" in m for m in msgs)
        assert any("Stats" in m for m in msgs)

    def test_fires_on_register_dataclass_of_non_dataclass(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            class Plain:
                pass

            jax.tree_util.register_dataclass(
                Plain, data_fields=[], meta_fields=[])
        """})
        v = [f for f in rep.violations
             if f.rule == "pytree-carry-discipline"]
        assert len(v) == 1 and "not declared as a dataclass" in v[0].message

    def test_silent_on_frozen(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import dataclasses
            import jax

            @jax.tree_util.register_pytree_node_class
            @dataclasses.dataclass(frozen=True)
            class Carry:
                x: int

            @dataclasses.dataclass(frozen=True)
            class Stats:
                n: int

            jax.tree_util.register_dataclass(
                Stats, data_fields=["n"], meta_fields=[])
        """})
        assert not [f for f in rep.violations
                    if f.rule == "pytree-carry-discipline"]

    def test_waiver(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import dataclasses
            import jax

            @jax.tree_util.register_pytree_node_class
            @dataclasses.dataclass
            # repro: allow(pytree-carry-discipline): fixture mutability
            class Carry:
                x: int
        """})
        assert rep.ok and len(rep.waived) == 1


# ------------------------------------------------------------------ R8
class TestShardLocality:
    def test_fires_on_collective_in_traced_zone_code(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/engine.py": """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def burst(acc):
                return jax.lax.psum(acc, axis_name="die")
        """})
        v = [f for f in rep.violations if f.rule == "shard-locality"]
        assert len(v) == 1 and "jax.lax.psum" in v[0].message

    def test_fires_inside_scan_body(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/reliability/scrub.py": """
            import jax
            from jax import lax

            def run(xs):
                def body(c, x):
                    g = lax.all_gather(x, axis_name="die")
                    return c + g.sum(), x
                return jax.lax.scan(body, 0.0, xs)
        """})
        v = [f for f in rep.violations if f.rule == "shard-locality"]
        assert len(v) == 1 and "lax.all_gather" in v[0].message

    def test_silent_on_host_paths_and_outside_zone(self, tmp_path):
        rep = lint(tmp_path, {
            # host-path reduction in the zone: the once-per-run ledger
            # merge is exactly the sanctioned place for cross-die math
            "src/repro/serve/sched.py": """
                import numpy as np

                def merge(per_slot, n_dies):
                    return per_slot.reshape(n_dies, -1).sum(axis=1)
            """,
            # traced collective OUTSIDE the serving zone: not this rule's
            # business (launch-time replication uses them legitimately)
            "src/repro/launch/train.py": """
                import jax

                @jax.jit
                def mean_grads(g):
                    return jax.lax.pmean(g, axis_name="batch")
            """})
        assert not [f for f in rep.violations
                    if f.rule == "shard-locality"]

    def test_waiver(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/engine.py": """
            import jax

            @jax.jit
            def report(acc):
                # repro: allow(shard-locality): off the per-token path
                return jax.lax.psum(acc, axis_name="die")
        """})
        assert rep.ok and len(rep.waived) == 1


# -------------------------------------------------------------- engine
class TestEngine:
    def test_unjustified_waiver_is_a_violation(self, tmp_path):
        rep = lint(tmp_path, {"src/mod.py": """
            import jax

            def report(acc):
                # repro: allow(no-host-sync-in-scan)
                return jax.device_get(acc)
        """})
        assert [f.rule for f in rep.violations] == [WAIVER_DISCIPLINE]

    def test_star_waiver_covers_all_rules(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/x.py": """
            import jax

            def report(acc):
                # repro: allow(*): fixture silences everything
                return jax.device_get(acc)
        """})
        assert rep.ok and len(rep.waived) == 1

    def test_waiver_only_covers_adjacent_line(self, tmp_path):
        rep = lint(tmp_path, {"src/repro/serve/x.py": """
            import jax

            # repro: allow(no-host-sync-in-scan): too far away
            def report(acc):
                return jax.device_get(acc)
        """})
        assert len(rep.violations) == 1

    def test_parse_error_is_reported_not_fatal(self, tmp_path):
        rep = lint(tmp_path, {"src/bad.py": "def broken(:\n"})
        assert [f.rule for f in rep.violations] == [PARSE_ERROR]

    def test_rule_subset_and_unknown_rule(self, tmp_path):
        files = {"src/repro/serve/bad.py": """
            import jax
            from repro.kernels.extent_write.ops import approx_write_lanes

            def f(acc):
                return jax.device_get(acc)
        """}
        rep = lint(tmp_path, files, rules=["registry-discipline"])
        assert rules_of(rep) == ["registry-discipline"]
        with pytest.raises(KeyError):
            lint(tmp_path, {}, rules=["not-a-rule"])


# ----------------------------------------------------------------- CLI
class TestCli:
    def _tree(self, tmp_path, text):
        p = tmp_path / "src" / "mod.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
        return tmp_path

    def test_exit_zero_on_clean_tree(self, tmp_path, capsys):
        root = self._tree(tmp_path, "X = 1\n")
        assert analysis_main(["--root", str(root)]) == 0
        assert "0 violation(s)" in capsys.readouterr().out

    def test_exit_one_on_violation_and_json_artifact(self, tmp_path,
                                                     capsys):
        root = self._tree(tmp_path, """
            import jax

            @jax.jit
            def f(x):
                return jax.random.PRNGKey(0)
        """)
        out = tmp_path / "report.json"
        assert analysis_main(["--root", str(root),
                              "--json", str(out)]) == 1
        data = json.loads(out.read_text())
        assert data["counts"]["violations"] == 1
        assert data["violations"][0]["rule"] == "operand-discipline"

    def test_exit_two_on_unknown_rule(self, tmp_path, capsys):
        root = self._tree(tmp_path, "X = 1\n")
        assert analysis_main(["--root", str(root),
                              "--rule", "not-a-rule"]) == 2


# ---------------------------------------------------------- this repo
class TestRepoInvariants:
    def test_repo_is_clean(self):
        """The acceptance gate: the engine over src/ + benchmarks/ of THIS
        repo reports zero unwaived violations, and every waiver carries a
        justification."""
        rep = run_analysis(root=REPO_ROOT)
        assert rep.ok, "\n".join(f.location + " " + f.message
                                 for f in rep.violations)
        assert all(f.justification for f in rep.waived)

    def test_rng_registry_values_are_pinned(self):
        """The migrated constants keep their pre-registry values — the
        RNG schedule (and with it every bit-parity contract) must not
        move when a constant changes address."""
        from repro.memory import rng_streams as rs
        rs.validate()
        assert rs.WRITE_LEAF_OFFSET == 0
        assert rs.SOFT_ERROR_OFFSET == 1_000_003
        assert rs.RETENTION_OFFSET == 2_000_003
        assert rs.SCRUB_OFFSET == 3_000_017
        assert rs.SCHEDULER_SCRUB_PASS_OFFSET == 1_000_000
        assert rs.CHECKPOINT_RESTORE_OFFSET == 4_000_037
        assert rs.RESTORE_SCRUB_OFFSET == 1_000_003
        # ISSUE 8: the workload-event stream joined the registry (and
        # validate() grew range-overlap checking) — existing pinned
        # values above must not have moved
        assert rs.WORKLOAD_OFFSET == 5_000_011
        assert rs.INDEX_SPAN == 1_000_000
