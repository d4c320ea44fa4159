"""Per-rule `hvt-lint` units over fixture snippets (ISSUE 6 satellite).

Each rule gets positive fixtures seeded with the bug shape it encodes —
including the PR 2 one-sided-shutdown reproduction for HVT001 — plus
negatives for the shapes it must NOT flag, and the suppression paths
(``# hvt: noqa[RULE]``, committed baseline) end to end through
`lint_paths` and the CLI.
"""

import json
import os
import textwrap

import pytest

from horovod_tpu.analysis import callgraph, cli, core, registry
from horovod_tpu.analysis.rules import (
    CheckpointWriteAtomicity,
    MetricRegistryDiscipline,
    CollectiveOrderDivergence,
    CollectiveSymmetry,
    DataLayerSeededRng,
    EnvKnobRegistry,
    ExpertAllToAllDiscipline,
    ReductionComposition,
    ScheduleDivergence,
    TeardownDiscipline,
    TracingHazards,
    TunableKnobResolverOnly,
)


def findings_of(rule_cls, src, relpath="horovod_tpu/fake.py"):
    """Run ONE rule over a source snippet (no noqa/baseline filtering —
    that layer is covered through `lint_paths` below)."""
    module = core.ModuleSource(
        "/fake/" + relpath, relpath, textwrap.dedent(src)
    )
    return list(rule_cls().check(module))


def lint_tree(tmp_path, files, **kwargs):
    """Write `files` ({relpath: source}) under tmp_path and lint the tree
    with the full pipeline (noqa + baseline)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    kwargs.setdefault("baseline_path", None)
    return core.lint_paths([str(tmp_path)], root=str(tmp_path), **kwargs)


class TestHVT001CollectiveSymmetry:
    def test_rank_gated_psum_flagged(self):
        found = findings_of(CollectiveSymmetry, """
            from horovod_tpu.parallel.collectives import psum
            def step(x):
                if rank() == 0:
                    return psum(x)
                return x
        """)
        assert len(found) == 1
        assert found[0].rule == "HVT001" and "psum" in found[0].message

    def test_pr2_one_sided_shutdown_shape(self):
        """The seeded PR 2 fixture: `runtime.shutdown` is a BARRIER; a
        rank-gated call tears down one side and SIGABRTs the survivors
        (CHANGES.md PR 2) — exactly the shape HVT001 exists for."""
        found = findings_of(CollectiveSymmetry, """
            from horovod_tpu import runtime

            def leave_early(world):
                if runtime.process_rank() != 0:
                    runtime.shutdown()
        """)
        assert [f.rule for f in found] == ["HVT001"]
        assert "runtime.shutdown" in found[0].message

    def test_attribute_rank_gate_and_while(self):
        found = findings_of(CollectiveSymmetry, """
            def f(world, x):
                while world.process_index == 0:
                    barrier()
        """)
        assert len(found) == 1

    def test_boolop_short_circuit_gate(self):
        flagged = findings_of(CollectiveSymmetry, """
            def f(x):
                ok = rank() == 0 and broadcast_object(x)
        """)
        assert len(flagged) == 1
        # Operand BEFORE the rank test is unconditionally evaluated.
        clean = findings_of(CollectiveSymmetry, """
            def f(x):
                ok = broadcast_object(x) and rank() == 0
        """)
        assert clean == []

    def test_else_branch_of_rank_gate_flagged(self):
        # Either arm of a rank-conditional is rank-asymmetric.
        found = findings_of(CollectiveSymmetry, """
            def f(x):
                if is_primary():
                    pass
                else:
                    allgather_object(x)
        """)
        assert len(found) == 1

    def test_ungated_collective_clean(self):
        assert findings_of(CollectiveSymmetry, """
            def step(x):
                y = psum(x)
                if rank() == 0:
                    print(y)
                return y
        """) == []

    def test_def_under_gate_is_not_execution(self):
        # A function DEFINED under a rank gate is not thereby CALLED
        # under it (tracking call sites needs dataflow; documented limit).
        assert findings_of(CollectiveSymmetry, """
            def f(x):
                if rank() == 0:
                    def helper(y):
                        return psum(y)
                return x
        """) == []

    def test_qualified_shutdown_needs_runtime_like_owner(self):
        # `httpd.shutdown()` under a rank gate is a same-name method on an
        # unrelated object — must not be flagged.
        assert findings_of(CollectiveSymmetry, """
            def stop(httpd):
                if rank() == 0:
                    httpd.shutdown()
        """) == []

    def test_elastic_state_sync_qualified_forms(self):
        found = findings_of(CollectiveSymmetry, """
            def agree(self, x):
                if process_index() == 0:
                    self.state.sync(x)
        """)
        assert len(found) == 1
        assert findings_of(CollectiveSymmetry, """
            def f(conn):
                if rank() == 0:
                    conn.sync()
        """) == []


class TestHVT001Interprocedural:
    """The PR 9 tentpole: rank-taint propagation through the call graph.
    A collective reached only through a rank-gated HELPER — one or more
    hops deep, across modules — is the seeded PR 2 shape the lexical
    rule deliberately missed."""

    def test_two_hops_in_one_module(self):
        """The acceptance fixture: gate -> helper -> inner -> psum, two
        call hops between the gate and the collective."""
        found = findings_of(CollectiveSymmetry, """
            from horovod_tpu.parallel.collectives import psum

            def inner(x):
                return psum(x)

            def helper(x):
                return inner(x)

            def step(x):
                if rank() == 0:
                    helper(x)
        """)
        assert len(found) == 1
        assert "helper -> inner -> psum" in found[0].message
        assert "rank-conditional" in found[0].message

    def test_cross_module_helper(self, tmp_path):
        """The same shape split across files: resolution rides the
        import-alias map and the module-set call graph."""
        res = lint_tree(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/helpers.py": """
                from pkg.deep import inner
                def helper(x):
                    return inner(x)
            """,
            "pkg/deep.py": """
                def inner(x):
                    return psum(x)
            """,
            "pkg/main.py": """
                from pkg import helpers
                def step(x):
                    if rank() == 0:
                        helpers.helper(x)
            """,
        }, select=["HVT001"])
        assert [f.path for f in res.findings] == ["pkg/main.py"]
        assert "helpers.helper -> inner -> psum" in res.findings[0].message

    def test_self_method_resolution(self):
        found = findings_of(CollectiveSymmetry, """
            class Agreement:
                def _announce(self, x):
                    return broadcast_object(x)

                def maybe(self, x):
                    if self.is_primary:
                        self._announce(x)
        """)
        assert len(found) == 1
        assert "self._announce" in found[0].message

    def test_ungated_transitive_call_clean(self):
        assert findings_of(CollectiveSymmetry, """
            def helper(x):
                return psum(x)

            def step(x):
                helper(x)
                if rank() == 0:
                    print(x)
        """) == []

    def test_gated_inside_callee_does_not_taint_call_site(self):
        """A helper that gates its own collective is flagged AT the
        internal site (that finding stands on its own); calling such a
        helper under a gate adds no second finding — its effect summary
        is rank-gated, not issues-collective."""
        found = findings_of(CollectiveSymmetry, """
            def helper(x):
                if rank() == 0:
                    psum(x)

            def step(x):
                if is_primary():
                    helper(x)
        """)
        assert len(found) == 1
        assert found[0].line == 4  # the psum inside helper, not the call

    def test_unresolvable_call_never_taints(self):
        # A call the module set cannot resolve (stdlib, dynamic) must
        # not propagate taint — no guessing.
        assert findings_of(CollectiveSymmetry, """
            import os
            def step(x):
                if rank() == 0:
                    os.listdir(".")
        """) == []

    def test_redefined_function_body_still_scanned(self):
        """A fallback redefinition (the try-import shape) must not put
        the second def's body in the dark: the clash gets a synthetic
        non-addressable unit and its gated collective is still a
        finding — lexical-rule parity."""
        found = findings_of(CollectiveSymmetry, """
            def save(x):
                return x

            def save(x):
                if rank() == 0:
                    barrier()
        """)
        assert len(found) == 1
        assert "barrier" in found[0].message

    def test_noqa_suppresses_call_site(self, tmp_path):
        res = lint_tree(tmp_path, {"m.py": """
            def helper(x):
                return psum(x)

            def step(x):
                if rank() == 0:
                    helper(x)  # hvt: noqa[HVT001]
        """}, select=["HVT001"])
        assert res.findings == []

    def test_effect_classification_summary(self):
        """The callgraph's three-way classification is observable."""
        m = core.ModuleSource("/fake/m.py", "m.py", textwrap.dedent("""
            def issues(x):
                return psum(x)
            def gated(x):
                if rank() == 0:
                    barrier()
            def clean(x):
                return x + 1
            def transitive(x):
                return issues(x)
        """))
        g = callgraph.CallGraph([m])
        s = g.summary()
        assert s["m:issues"] == callgraph.ISSUES
        assert s["m:gated"] == callgraph.RANK_GATED
        assert s["m:clean"] == callgraph.CLEAN
        assert s["m:transitive"] == callgraph.ISSUES
        assert g.witness("m:transitive") == ["issues", "psum"]


class TestHVT002TeardownDiscipline:
    def test_direct_jax_distributed_shutdown_flagged(self):
        found = findings_of(TeardownDiscipline, """
            import jax
            def cleanup():
                jax.distributed.shutdown()
        """)
        assert [f.rule for f in found] == ["HVT002"]

    def test_import_alias_resolved(self):
        found = findings_of(TeardownDiscipline, """
            from jax import distributed
            def cleanup():
                distributed.shutdown()
        """)
        assert len(found) == 1

    def test_clear_backends_flagged(self):
        found = findings_of(TeardownDiscipline, """
            from horovod_tpu import compat
            def reset():
                compat.clear_backends()
        """)
        assert len(found) == 1 and "clear_backends" in found[0].message

    def test_sanctioned_modules_exempt(self):
        src = """
            import jax
            def _teardown_and_interrupt():
                jax.distributed.shutdown()
        """
        for rel in ("horovod_tpu/elastic/rescale.py",
                    "horovod_tpu/elastic/state.py",
                    "horovod_tpu/runtime.py",
                    "horovod_tpu/compat.py"):
            assert findings_of(TeardownDiscipline, src, relpath=rel) == []
        assert len(findings_of(
            TeardownDiscipline, src, relpath="horovod_tpu/training/x.py"
        )) == 1

    def test_runtime_shutdown_wrapper_clean(self):
        # The sanctioned wrapper is the REPLACEMENT, not a violation.
        assert findings_of(TeardownDiscipline, """
            from horovod_tpu import runtime
            def cleanup():
                runtime.shutdown()
        """) == []


class TestHVT003TracingHazards:
    def test_time_in_jitted_function(self):
        found = findings_of(TracingHazards, """
            import time
            import jax
            @jax.jit
            def step(x):
                t = time.time()
                return x + t
        """)
        assert [f.rule for f in found] == ["HVT003"]
        assert "trace time" in found[0].message

    def test_seed_free_numpy_random(self):
        found = findings_of(TracingHazards, """
            import numpy as np
            from jax import jit
            @jit
            def noise(x):
                return x + np.random.rand()
        """)
        assert len(found) == 1 and "numpy.random.rand" in found[0].message

    def test_jax_random_with_key_clean(self):
        assert findings_of(TracingHazards, """
            from jax import jit, random
            @jit
            def noise(x, key):
                return x + random.normal(key, x.shape)
        """) == []

    def test_environ_read_inside_shard_map(self):
        found = findings_of(TracingHazards, """
            import os
            from jax.experimental.shard_map import shard_map
            @shard_map
            def step(x):
                if os.environ.get("HVT_FAULT"):
                    return x
                return x * 2
        """)
        assert len(found) == 1 and "os.environ" in found[0].message

    def test_scan_body_lambda_and_named(self):
        found = findings_of(TracingHazards, """
            import time
            from jax import lax
            def body(c, x):
                return c, x * time.perf_counter()
            def run(xs):
                lax.scan(body, 0.0, xs)
                lax.scan(lambda c, x: (c, print(x)), 0.0, xs)
        """)
        assert len(found) == 2

    def test_host_effects_outside_trace_clean(self):
        assert findings_of(TracingHazards, """
            import time
            def host_loop(step_fn, xs):
                t0 = time.time()
                for x in xs:
                    step_fn(x)
                print(time.time() - t0)
        """) == []


class TestHVT004EnvKnobRegistry:
    def test_undeclared_literal_flagged(self):
        found = findings_of(EnvKnobRegistry, """
            KNOB = "HVT_DEFINITELY_NOT_DECLARED"
        """)
        assert [f.rule for f in found] == ["HVT004"]

    def test_inline_reads_flagged_even_for_declared_knobs(self):
        found = findings_of(EnvKnobRegistry, """
            import os
            a = os.environ.get("HVT_FAULT")
            b = os.getenv("HVT_FAULT")
            c = os.environ["HVT_FAULT"]
        """)
        assert len(found) == 3
        assert all("registry" in f.message for f in found)

    def test_registry_accessor_and_plain_literal_clean(self):
        assert findings_of(EnvKnobRegistry, """
            from horovod_tpu.analysis import registry
            a = registry.get_str("HVT_FAULT")
            DOC = "set HVT_FAULT to inject faults"  # not a bare knob literal
        """) == []

    def test_non_hvt_env_reads_out_of_scope(self):
        assert findings_of(EnvKnobRegistry, """
            import os
            p = os.environ.get("PS_MODEL_PATH", "./models")
        """) == []

    def test_every_declared_knob_passes(self):
        src = "NAMES = [" + ",".join(
            repr(name) for name in registry.KNOBS
        ) + "]"
        assert findings_of(EnvKnobRegistry, src) == []


class TestHVT005CheckpointWriteAtomicity:
    def test_truncating_open_flagged(self):
        found = findings_of(CheckpointWriteAtomicity, """
            def save(path, data):
                with open(path, "w") as f:
                    f.write(data)
        """)
        assert [f.rule for f in found] == ["HVT005"]

    def test_mode_kwarg_and_update_modes(self):
        found = findings_of(CheckpointWriteAtomicity, """
            def f(path):
                a = open(path, mode="wb")
                b = open(path, "r+b")
        """)
        assert len(found) == 2

    def test_reads_and_appends_clean(self):
        assert findings_of(CheckpointWriteAtomicity, """
            def f(path):
                a = open(path)
                b = open(path, "rb")
                c = open(path, "a")  # append streams cannot tear history
        """) == []

    def test_atomic_write_helper_sanctioned(self):
        assert findings_of(CheckpointWriteAtomicity, """
            import os
            def _atomic_write(path, data):
                tmp = path + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
        """) == []


class TestHVT006DataLayerSeededRng:
    """HVT006: unseeded RNG inside horovod_tpu/data/ — the determinism
    invariant the durable stream cursors depend on (ISSUE 8 satellite)."""

    DATA = "horovod_tpu/data/fake.py"

    def test_global_numpy_rng_flagged(self):
        found = findings_of(DataLayerSeededRng, """
            import numpy as np
            def order(n):
                return np.random.permutation(n)
        """, relpath=self.DATA)
        assert [f.rule for f in found] == ["HVT006"]
        assert "numpy.random.permutation" in found[0].message

    def test_stdlib_global_rng_flagged(self):
        found = findings_of(DataLayerSeededRng, """
            import random
            def pick(xs):
                random.shuffle(xs)
                return random.randint(0, 9)
        """, relpath=self.DATA)
        assert len(found) == 2

    def test_seedless_generator_ctors_flagged(self):
        found = findings_of(DataLayerSeededRng, """
            import numpy as np
            rng1 = np.random.RandomState()
            rng2 = np.random.default_rng()
        """, relpath=self.DATA)
        assert len(found) == 2

    def test_seeded_generators_clean(self):
        assert findings_of(DataLayerSeededRng, """
            import numpy as np
            def order(seed, epoch, n):
                rng = np.random.RandomState(seed)
                g = np.random.default_rng(seed=epoch)
                s = np.random.SeedSequence([seed, epoch])
                return rng.permutation(n), g, s
        """, relpath=self.DATA) == []

    def test_method_calls_on_local_generators_clean(self):
        # rng.shuffle/rng.randint resolve through the LOCAL name, not
        # the numpy.random global module — never flagged.
        assert findings_of(DataLayerSeededRng, """
            import numpy as np
            def draw(seed):
                rng = np.random.RandomState(seed)
                rng.shuffle([1, 2])
                return rng.randint(3)
        """, relpath=self.DATA) == []

    def test_outside_data_layer_not_scoped(self):
        assert findings_of(DataLayerSeededRng, """
            import numpy as np
            x = np.random.permutation(8)
        """, relpath="horovod_tpu/training/fake.py") == []


class TestHVT007CollectiveOrderDivergence:
    """Sibling branches issuing different collective sequences — the
    cross-rank mismatched-submission-order deadlock class."""

    def test_direct_order_divergence_flagged(self):
        found = findings_of(CollectiveOrderDivergence, """
            def step(x, phase):
                if phase:
                    psum(x)
                    allgather(x)
                else:
                    allgather(x)
                    psum(x)
        """)
        assert [f.rule for f in found] == ["HVT007"]
        assert "['psum', 'allgather']" in found[0].message
        assert "['allgather', 'psum']" in found[0].message

    def test_divergence_through_helpers_flagged(self):
        """Callee sequences are inlined: the branches LOOK symmetric
        (one call each) but the helpers issue different collectives."""
        found = findings_of(CollectiveOrderDivergence, """
            def path_a(x):
                psum(x)

            def path_b(x):
                broadcast(x)

            def step(x, phase):
                if phase:
                    path_a(x)
                else:
                    path_b(x)
        """)
        assert len(found) == 1
        assert "['psum']" in found[0].message
        assert "['broadcast']" in found[0].message

    def test_same_sequence_both_arms_clean(self):
        assert findings_of(CollectiveOrderDivergence, """
            def step(x, phase):
                if phase:
                    y = psum(x)
                else:
                    y = psum(x * 2)
        """) == []

    def test_collective_free_branch_is_hvt001_territory(self):
        # One silent arm is only a bug under a rank-varying condition —
        # exactly what HVT001's gate detection covers; HVT007 stays out.
        assert findings_of(CollectiveOrderDivergence, """
            def step(x, phase):
                if phase:
                    psum(x)
                else:
                    log(x)
        """) == []

    def test_repeat_count_divergence_flagged(self):
        """A helper called TWICE in one arm vs once in the other submits
        a different number of collectives — the cycle guard must pop
        after inlining (recursion-only), not swallow sibling repeats."""
        found = findings_of(CollectiveOrderDivergence, """
            def helper(x):
                psum(x)

            def step(x, phase):
                if phase:
                    helper(x)
                    helper(x)
                else:
                    helper(x)
        """)
        assert len(found) == 1
        assert "['psum', 'psum']" in found[0].message

    def test_recursive_helper_terminates(self):
        found = findings_of(CollectiveOrderDivergence, """
            def loop(x, n):
                psum(x)
                return loop(x, n - 1)

            def step(x, phase):
                if phase:
                    loop(x, 3)
                else:
                    broadcast(x)
        """)
        assert len(found) == 1  # and no RecursionError

    def test_uniform_config_branch_noqa(self, tmp_path):
        res = lint_tree(tmp_path, {"m.py": """
            def reduce(x, quantized):
                if quantized:  # hvt: noqa[HVT007] config-uniform branch
                    allgather(x)
                else:
                    psum(x)
        """}, select=["HVT007"])
        assert res.findings == []


class TestHVT008ReductionComposition:
    """Per-leaf gradient reductions in the accumulation/ZeRO surface
    must route through `collectives.reduce_gradients` (ROADMAP item 3's
    pinned guardrail)."""

    def test_tree_mapped_psum_lambda_flagged(self):
        found = findings_of(ReductionComposition, """
            # wires backward_passes_per_step into the step
            import jax
            def reduce(grads):
                return jax.tree.map(lambda g: psum(g, 'data'), grads)
        """)
        assert [f.rule for f in found] == ["HVT008"]
        assert "reduce_gradients" in found[0].message

    def test_tree_mapped_named_local_fn_flagged(self):
        found = findings_of(ReductionComposition, """
            # wires backward_passes_per_step into the step
            import jax
            def _one(g):
                return hierarchical_psum(g, 'data', 2)
            def reduce(grads):
                return jax.tree.map(_one, grads)
        """)
        assert len(found) == 1

    def test_raw_psum_scatter_flagged(self):
        found = findings_of(ReductionComposition, """
            from jax import lax
            def shard_update_reduce(grads, spec):
                return lax.psum_scatter(grads, 'data')
        """)
        assert len(found) == 1
        assert "psum_scatter" in found[0].message

    def test_outside_surface_module_not_scoped(self):
        assert findings_of(ReductionComposition, """
            import jax
            def reduce(grads):
                return jax.tree.map(lambda g: psum(g, 'data'), grads)
        """) == []

    def test_metric_pmean_tree_map_clean(self):
        # Scalar-metric bookkeeping (trainer.py's sown-metrics pmean) is
        # not gradient reduction — pmean per leaf stays legal.
        assert findings_of(ReductionComposition, """
            # wires backward_passes_per_step into the step
            import jax
            def metrics(sm):
                return jax.tree.map(lambda v: jax.lax.pmean(v, 'data'), sm)
        """) == []

    def test_entry_point_module_exempt(self):
        src = """
            # wires backward_passes_per_step into the step
            import jax
            def reduce_gradients(grads):
                return jax.tree.map(lambda g: psum(g, 'data'), grads)
        """
        assert findings_of(
            ReductionComposition, src,
            relpath="horovod_tpu/parallel/collectives.py",
        ) == []
        assert len(findings_of(
            ReductionComposition, src,
            relpath="horovod_tpu/training/zero1.py",
        )) == 1

    def test_routed_through_entry_point_clean(self):
        assert findings_of(ReductionComposition, """
            # wires backward_passes_per_step into the step
            from horovod_tpu.parallel import collectives
            def boundary(grads, k):
                return collectives.reduce_gradients(grads, reverse=True)
        """) == []


class TestHVT010ScheduleDivergence:
    """Whole-program schedule verification (ISSUE 14 tentpole): every
    rank-feasible path through a unit must submit the same collective
    sequence. The matrix seeds the shapes the first two layers cannot
    see — and the rank-gated-but-agreeing shapes that must NOT fire."""

    def test_rank_gated_early_return_flagged(self):
        """The canonical HVT001/HVT007-invisible deadlock: no collective
        under the gate, no sibling arm — rank 0 just skips the psum
        every other rank blocks in."""
        found = findings_of(ScheduleDivergence, """
            def step(x):
                if rank() == 0:
                    return x
                return psum(x)
        """)
        assert [f.rule for f in found] == ["HVT010"]
        assert "DIVERGENT" in found[0].message
        assert "`psum`" in found[0].message
        assert "first mismatched submission at op 0" in found[0].message
        # Anchored at the rank fork, where the noqa belongs.
        assert found[0].line == 3

    def test_two_hop_cross_module_divergent_schedule(self, tmp_path):
        """The 2-hop cross-module case: the gate lives in the entry
        module, the collective two call hops away in another — the
        witness chain still names the fork and the mismatched op."""
        res = lint_tree(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/helpers.py": """
                from pkg.deep import inner
                def finish(x):
                    return inner(x)
            """,
            "pkg/deep.py": """
                def inner(x):
                    return psum(x)
            """,
            "pkg/main.py": """
                from pkg import helpers
                def step(x):
                    if rank() == 0:
                        return x
                    return helpers.finish(x)
            """,
        }, select=["HVT010"])
        assert [f.path for f in res.findings] == ["pkg/main.py"]
        msg = res.findings[0].message
        assert "['psum']" in msg and "[]" in msg

    def test_loop_count_divergence_flagged(self):
        """A loop whose trip count reads the rank submits a different
        NUMBER of collectives per rank — no gate for HVT001, no sibling
        arm for HVT007; the {0, 1}-iteration bound witnesses it."""
        found = findings_of(ScheduleDivergence, """
            from horovod_tpu import runtime
            def drain(x):
                for _ in range(runtime.rank()):
                    psum(x)
                return x
        """)
        assert [f.rule for f in found] == ["HVT010"]
        assert "0-iterations" in found[0].message

    def test_rank_gated_but_agreeing_arms_clean(self):
        """Both arms submit the SAME sequence (the root/non-root
        broadcast idiom): rank-feasible paths agree — no finding."""
        assert findings_of(ScheduleDivergence, """
            def pick(x):
                if rank() == 0:
                    cfg = broadcast_object(x)
                else:
                    cfg = broadcast_object(None)
                return cfg
        """) == []

    def test_uniform_config_pick_clean(self):
        """elastic/state.py's transport pick, in miniature: the branch
        reads an ALLGATHERED vote — uniform across ranks — so the two
        transports are separate configurations, never compared (the
        false positive the rank-predicate awareness exists to avoid)."""
        assert findings_of(ScheduleDivergence, """
            def sync(self, root):
                votes = allgather_object(self._vote())
                if all(v == votes[root] for v in votes):
                    return
                if votes[root][0] is not None:
                    self._c = broadcast_pytree(self._c, root=root)
                else:
                    self._c = broadcast_object(self._c, root=root)
        """) == []

    def test_hvt007_invisible_cross_function_case(self):
        """The gate travels as an ARGUMENT: `step` passes `rank() == 0`
        into a helper whose one-armed branch on that parameter issues an
        extra collective. HVT007 needs both arms of one `if` to carry
        collectives; HVT001 needs a syntactic rank read at the gate —
        both stay silent, the path pair diverges."""
        src = """
            def phase(x, flag):
                if flag:
                    psum(x)
                allgather(x)

            def step(x):
                phase(x, rank() == 0)
        """
        assert findings_of(CollectiveOrderDivergence, src) == []
        assert findings_of(CollectiveSymmetry, src) == []
        found = findings_of(ScheduleDivergence, src)
        assert len(found) == 1
        msg = found[0].message
        assert "['psum', 'allgather']" in msg
        assert "['allgather']" in msg
        assert "`psum` vs `allgather`" in msg

    def test_rank_returning_helper_gates_the_branch(self):
        """Rank taint through RETURN VALUES: branching on a helper that
        returns `rank() == 0` is a rank fork, however many modules away
        the rank read lives."""
        found = findings_of(ScheduleDivergence, """
            def is_root():
                return rank() == 0

            def step(x):
                if is_root():
                    return x
                return broadcast_object(x)
        """)
        assert len(found) == 1

    def test_rebound_uniform_local_clears_taint(self):
        """Taint soundness direction: a local once bound to a rank read
        but REBOUND to a uniform value must not keep gating — stale
        taint would invent divergences on provably-uniform branches."""
        assert findings_of(ScheduleDivergence, """
            def step(x):
                flag = rank() == 0
                flag = False
                if flag:
                    return x
                return psum(x)
        """) == []
        # AugAssign keeps the taint (the old rank value still feeds it).
        found = findings_of(ScheduleDivergence, """
            def step(x):
                n = rank()
                n += 1
                if n:
                    return x
                return psum(x)
        """)
        assert len(found) == 1

    def test_divergent_helper_reported_once(self):
        """A divergent helper is ITS finding; callers inline one
        representative path and do not re-report it."""
        found = findings_of(ScheduleDivergence, """
            def helper(x):
                if rank() == 0:
                    return x
                return psum(x)

            def caller_a(x):
                return helper(x)

            def caller_b(x):
                return helper(x)
        """)
        assert len(found) == 1

    def test_noqa_suppresses_at_fork_line(self, tmp_path):
        res = lint_tree(tmp_path, {"m.py": """
            def step(x):
                if rank() == 0:  # hvt: noqa[HVT010] single-proc test path
                    return x
                return psum(x)
        """}, select=["HVT010"])
        assert res.findings == []

    def test_entry_report_on_fixture_project(self):
        """`schedule.entry_report` summarizes the real entry automata
        (the hvt-sched check banner): path/configuration counts and the
        agree verdict are observable per entry."""
        import textwrap

        from horovod_tpu.analysis import callgraph, schedule

        m = core.ModuleSource(
            "/fake/horovod_tpu/elastic/state.py",
            "horovod_tpu/elastic/state.py",
            textwrap.dedent("""
                class ElasticState:
                    def sync(self, root):
                        votes = allgather_object(self._vote())
                        if votes:
                            self._c = broadcast_object(self._c, root=root)
                        else:
                            self._c = broadcast_object(None, root=root)
            """),
        )
        graph = callgraph.CallGraph([m])
        rows = schedule.entry_report(graph)
        assert [r["unit"] for r in rows] == [
            "horovod_tpu.elastic.state:ElasticState.sync"
        ]
        assert rows[0]["agree"]
        assert rows[0]["sequence"][0] == "allgather_object"


class TestHVT011ExpertAllToAllDiscipline:
    """EP dispatch/combine all-to-alls route through the collectives
    entry point (ROADMAP item 4's wire discipline)."""

    EP_SRC = """
        from jax import lax
        from horovod_tpu.parallel.mesh import EXPERT_AXIS
        def dispatch(x):
            return lax.all_to_all(x, EXPERT_AXIS, 0, 0, tiled=True)
    """

    def test_raw_lax_all_to_all_flagged(self):
        found = findings_of(ExpertAllToAllDiscipline, self.EP_SRC)
        assert [f.rule for f in found] == ["HVT011"]
        assert "collectives.all_to_all" in found[0].message

    def test_routed_through_entry_point_clean(self):
        assert findings_of(ExpertAllToAllDiscipline, """
            from horovod_tpu.parallel import collectives
            def dispatch(x, n_experts):
                return collectives.all_to_all(x, 'expert')
        """) == []

    def test_outside_ep_surface_not_scoped(self):
        # A quantized-wire all-to-all in a module with no EP vocabulary
        # is HVT008/entry-point territory, not this rule's.
        assert findings_of(ExpertAllToAllDiscipline, """
            from jax import lax
            def shuffle(x):
                return lax.all_to_all(x, 'data', 0, 0)
        """) == []

    def test_entry_module_exempt(self):
        assert findings_of(
            ExpertAllToAllDiscipline, self.EP_SRC,
            relpath="horovod_tpu/parallel/collectives.py",
        ) == []


class TestHVT012TunableKnobResolverOnly:
    """Raw environ reads of knobs carrying `tunable=` domain metadata are
    autotuning blind spots (ISSUE 19): `hvt-tune` writes the
    resolver-visible env surface, so a bypassing read sees values the
    tuner can neither observe nor override."""

    def test_tunable_knob_raw_reads_flagged_all_shapes(self):
        found = findings_of(TunableKnobResolverOnly, """
            import os
            a = os.environ.get("HVT_BUCKET_BYTES", "0")
            b = os.getenv("HVT_OVERLAP_REDUCTION")
            c = os.environ["HVT_COMPRESSION"]
        """)
        assert [f.rule for f in found] == ["HVT012"] * 3
        assert all("tuning blind spot" in f.message for f in found)

    def test_non_tunable_registered_knob_out_of_scope(self):
        # HVT_FAULT has no tunable= domain — an inline read is HVT004's
        # generic finding, not this rule's.
        assert findings_of(TunableKnobResolverOnly, """
            import os
            a = os.environ.get("HVT_FAULT")
        """) == []

    def test_registry_accessor_and_literal_clean(self):
        assert findings_of(TunableKnobResolverOnly, """
            from horovod_tpu.analysis import registry
            a = registry.get_int("HVT_BUCKET_BYTES")
            DOC = "tune HVT_BUCKET_BYTES via hvt-tune"  # bare literal: fine
        """) == []

    def test_registry_resolver_module_exempt(self):
        assert findings_of(TunableKnobResolverOnly, """
            import os
            raw = os.environ.get("HVT_BUCKET_BYTES")
        """, relpath="horovod_tpu/analysis/registry.py") == []

    def test_every_tunable_knob_is_in_scope(self):
        # The rule's key set IS the registry's tunable set — a knob
        # gaining tunable= metadata gains the protection automatically.
        names = sorted(registry.tunable_knobs())
        src = "import os\n" + "\n".join(
            f"v{i} = os.getenv({n!r})" for i, n in enumerate(names)
        )
        found = findings_of(TunableKnobResolverOnly, src)
        assert len(found) == len(names) == 5


class TestRulesDocAndExplain:
    def test_generated_doc_covers_every_rule(self):
        doc = core.generate_rules_doc()
        for cls in core.iter_rules():
            assert f"## {cls.rule_id}" in doc
            assert cls.title in doc

    def test_explain_prints_rationale(self, capsys):
        assert cli.main(["--explain", "HVT007"]) == 0
        out = capsys.readouterr().out
        assert "HVT007" in out and "Why:" in out and "Provenance:" in out

    def test_explain_unknown_rule_is_usage_error(self, capsys):
        assert cli.main(["--explain", "HVT999"]) == 2
        assert "unknown rule" in capsys.readouterr().err


class TestSuppressionsAndBaseline:
    SRC = """
        import os
        a = os.environ.get("HVT_FAULT")
    """

    def test_noqa_rule_scoped(self, tmp_path):
        res = lint_tree(tmp_path, {"m.py": """
            import os
            a = os.environ.get("HVT_FAULT")  # hvt: noqa[HVT004]
            b = os.environ.get("HVT_FAULT")  # hvt: noqa[HVT001]
            c = os.environ.get("HVT_FAULT")  # hvt: noqa
        """})
        # a suppressed (right rule), b NOT (wrong rule), c suppressed (all).
        assert [f.line for f in res.findings] == [4]

    def test_baseline_matches_by_snippet_not_line(self, tmp_path):
        baseline = tmp_path / "baseline.json"
        baseline.write_text(json.dumps({"findings": [{
            "rule": "HVT004", "path": "m.py",
            "snippet": 'a = os.environ.get("HVT_FAULT")',
            "justification": "grandfathered for the test",
        }]}))
        # Extra lines ABOVE the finding: line number moved, snippet same.
        res = lint_tree(tmp_path, {"m.py": """
            import os

            # comment pushing the read down some lines
            a = os.environ.get("HVT_FAULT")
        """}, baseline_path=str(baseline))
        assert res.findings == [] and len(res.baselined) == 1

        # Editing the flagged LINE invalidates the baseline entry.
        res2 = lint_tree(tmp_path, {"m.py": """
            import os
            a = os.environ.get("HVT_FAULT") or "edited"
        """}, baseline_path=str(baseline))
        assert len(res2.findings) == 1 and res2.baselined == []

    def test_baseline_requires_justification(self, tmp_path):
        bad = tmp_path / "baseline.json"
        bad.write_text(json.dumps({"findings": [{
            "rule": "HVT004", "path": "m.py", "snippet": "x",
        }]}))
        with pytest.raises(ValueError, match="justification"):
            core.load_baseline(str(bad))

    def test_syntax_error_is_a_finding(self, tmp_path):
        res = lint_tree(tmp_path, {"broken.py": "def f(:\n"})
        assert [f.rule for f in res.findings] == [core.PARSE_ERROR_RULE]

    def test_out_of_root_paths_anchor_at_package_dir(self, tmp_path):
        """Absolute inputs from another cwd (editor/CI integrations) must
        key the HVT002 sanctioned set and the baseline by the SAME
        package-relative paths as a repo-root run — not by raw absolute
        paths that match nothing."""
        pkg = tmp_path / "checkout" / "horovod_tpu"
        (pkg / "elastic").mkdir(parents=True)
        (pkg / "elastic" / "rescale.py").write_text(textwrap.dedent("""
            import jax
            def _teardown_and_interrupt():
                jax.distributed.shutdown()
        """))
        elsewhere = tmp_path / "elsewhere"
        elsewhere.mkdir()
        res = core.lint_paths(
            [str(pkg)], root=str(elsewhere), baseline_path=None
        )
        assert res.findings == []  # sanctioned module still recognized

    def test_select_subset(self, tmp_path):
        res = lint_tree(tmp_path, {"m.py": self.SRC}, select=["HVT001"])
        assert res.findings == []
        res = lint_tree(tmp_path, {"m.py": self.SRC}, select=["HVT004"])
        assert len(res.findings) == 1


class TestCLI:
    def test_exit_codes_and_write_baseline(self, tmp_path, capsys):
        (tmp_path / "m.py").write_text(
            'import os\na = os.environ.get("HVT_FAULT")\n'
        )
        baseline = tmp_path / "baseline.json"
        argv = [str(tmp_path), "--root", str(tmp_path),
                "--baseline", str(baseline)]
        assert cli.main(argv) == 1  # finding, no baseline yet
        assert "HVT004" in capsys.readouterr().out

        assert cli.main(argv + ["--write-baseline"]) == 0
        capsys.readouterr()
        assert baseline.exists()
        assert cli.main(argv) == 0  # grandfathered now
        assert "1 baselined" in capsys.readouterr().out
        assert cli.main(argv + ["--no-baseline"]) == 1
        capsys.readouterr()

        (tmp_path / "clean.py").write_text("x = 1\n")
        assert cli.main([str(tmp_path / "clean.py")]) == 0

    def test_missing_or_empty_paths_are_usage_errors(self, tmp_path,
                                                     capsys):
        """A gate that lints NOTHING must not report clean: a typo'd
        path and a .py-free directory both exit 2, not 0."""
        assert cli.main([str(tmp_path / "no_such_dir")]) == 2
        assert "no such file" in capsys.readouterr().err
        empty = tmp_path / "empty"
        empty.mkdir()
        assert cli.main([str(empty)]) == 2
        assert "nothing was linted" in capsys.readouterr().err

    def test_write_baseline_preserves_justifications(self, tmp_path,
                                                     capsys):
        """Re-running --write-baseline must keep hand-written
        justifications for findings that still fire, and a --select run
        must carry other rules' entries over instead of dropping them."""
        (tmp_path / "m.py").write_text(
            'import os\n'
            'a = os.environ.get("HVT_FAULT")\n'
            'def f(p):\n'
            '    return open(p, "w")\n'
        )
        baseline = tmp_path / "baseline.json"
        argv = [str(tmp_path), "--root", str(tmp_path),
                "--baseline", str(baseline)]
        assert cli.main(argv + ["--write-baseline"]) == 0
        entries = json.loads(baseline.read_text())["findings"]
        assert {e["rule"] for e in entries} == {"HVT004", "HVT005"}
        for e in entries:
            if e["rule"] == "HVT004":
                e["justification"] = "hand-written reason"
        baseline.write_text(json.dumps({"findings": entries}))

        # Full rewrite keeps the hand-written justification.
        assert cli.main(argv + ["--write-baseline"]) == 0
        entries = json.loads(baseline.read_text())["findings"]
        just = {e["rule"]: e["justification"] for e in entries}
        assert just["HVT004"] == "hand-written reason"

        # A rule-subset rewrite must not drop the other rules' entries.
        assert cli.main(
            argv + ["--select", "HVT004", "--write-baseline"]
        ) == 0
        entries = json.loads(baseline.read_text())["findings"]
        assert {e["rule"] for e in entries} == {"HVT004", "HVT005"}
        assert cli.main(argv) == 0  # everything still grandfathered
        capsys.readouterr()

    def test_json_format(self, tmp_path, capsys):
        (tmp_path / "m.py").write_text(
            'import os\na = os.environ.get("HVT_FAULT")\n'
        )
        code = cli.main([str(tmp_path), "--root", str(tmp_path),
                         "--format", "json", "--no-baseline"])
        assert code == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["findings"][0]["rule"] == "HVT004"
        assert payload["findings"][0]["path"] == "m.py"

    def test_list_rules(self, capsys):
        assert cli.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("HVT001", "HVT002", "HVT003", "HVT004", "HVT005"):
            assert rid in out


class TestRegistryAccessors:
    def test_unknown_knob_refused(self):
        with pytest.raises(registry.UnknownKnobError):
            registry.get_str("HVT_NOT_A_KNOB")

    def test_empty_string_is_unset(self):
        env = {"HVT_COMMIT_EVERY": ""}
        assert registry.get_int("HVT_COMMIT_EVERY", environ=env) == 1
        env = {"HVT_COMMIT_EVERY": "5"}
        assert registry.get_int("HVT_COMMIT_EVERY", environ=env) == 5
        assert registry.get_int("HVT_DCN_FACTOR", environ={}) is None

    def test_flag_spellings(self):
        for off in ("", "0", "false", "FALSE", "no", "No"):
            assert not registry.get_flag(
                "HVT_NO_NATIVE", environ={"HVT_NO_NATIVE": off}
            )
        for on in ("1", "true", "yes", "anything"):
            assert registry.get_flag(
                "HVT_NO_NATIVE", environ={"HVT_NO_NATIVE": on}
            )

    def test_float_and_default_types(self):
        assert registry.get_float(
            "HVT_RESTART_LOG_MAX_MB", environ={}
        ) == 64.0
        assert registry.get_float(
            "HVT_RESTART_LOG_MAX_MB",
            environ={"HVT_RESTART_LOG_MAX_MB": "0.5"},
        ) == 0.5

    def test_runtime_env_flag_delegates(self):
        # runtime.env_flag and registry.flag_like are the SAME contract
        # by construction (delegation, not duplication).
        from horovod_tpu import runtime

        assert runtime.env_flag.__doc__  # still documented
        os.environ["HVT_FAST_RNG"] = "no"
        try:
            assert not runtime.env_flag("HVT_FAST_RNG")
            os.environ["HVT_FAST_RNG"] = "on"
            assert runtime.env_flag("HVT_FAST_RNG")
        finally:
            del os.environ["HVT_FAST_RNG"]

    def test_generate_doc_covers_every_knob(self):
        doc = registry.generate_doc()
        for name in registry.KNOBS:
            assert f"`{name}`" in doc


class TestHVT009MetricRegistryDiscipline:
    def test_undeclared_metric_name_flagged(self):
        found = findings_of(MetricRegistryDiscipline, """
            from horovod_tpu import obs
            def publish(v):
                obs.gauge("hvt_stpe_ms", v)
        """)
        assert len(found) == 1
        assert found[0].rule == "HVT009"
        assert "hvt_stpe_ms" in found[0].message
        assert "MetricSpec" in found[0].message

    def test_declared_names_clean_across_aliases(self):
        found = findings_of(MetricRegistryDiscipline, """
            from horovod_tpu import obs
            from horovod_tpu.obs import core as obs_core
            def publish(reg, v):
                obs.gauge("hvt_mfu", v)
                obs_core.counter("hvt_scrapes_total")
                obs.histogram("hvt_step_seconds", v)
        """)
        assert found == []

    def test_registry_method_sites_checked_by_convention(self):
        # A Registry instance can't be typed statically; the hvt_ naming
        # convention discriminates emission sites (obs/core naming rule).
        found = findings_of(MetricRegistryDiscipline, """
            def collect(reg):
                reg.counter_set("hvt_not_declared_total", 3)
                reg.gauge("hvt_fleet_size", 2)       # declared — clean
                other.counter("unrelated_api", 1)    # not hvt_ — skipped
        """)
        assert len(found) == 1
        assert "hvt_not_declared_total" in found[0].message

    def test_dynamic_names_skipped(self):
        found = findings_of(MetricRegistryDiscipline, """
            from horovod_tpu import obs
            def publish(name, v):
                obs.gauge(name, v)
        """)
        assert found == []

    def test_obs_call_inside_jit_flagged(self):
        found = findings_of(MetricRegistryDiscipline, """
            import jax
            from horovod_tpu import obs
            @jax.jit
            def step(x):
                obs.counter("hvt_optimizer_steps_total")
                return x
        """)
        assert len(found) == 1
        assert "trace time" in found[0].message

    def test_obs_call_inside_shard_map_and_scan_flagged(self):
        found = findings_of(MetricRegistryDiscipline, """
            import jax
            from horovod_tpu import obs
            from jax import lax
            def local(x):
                obs.gauge("hvt_mfu", 0.5)
                return x
            f = jax.shard_map(local, mesh=None, in_specs=(), out_specs=())
            def body(c, t):
                obs.gauge("hvt_mfu", 0.5)
                return c, t
            lax.scan(body, 0, None)
        """)
        assert len(found) == 2

    def test_host_side_emission_clean(self):
        found = findings_of(MetricRegistryDiscipline, """
            import jax
            from horovod_tpu import obs
            @jax.jit
            def step(x):
                return x + 1
            def loop(x):
                x = step(x)
                obs.counter("hvt_optimizer_steps_total")
                return x
        """)
        assert found == []

    def test_trace_span_inside_jit_flagged(self):
        # ISSUE 15: a span entered inside a traced body clocks the TRACE
        # and fires once at compile time — a frozen span poisoning the
        # merged timeline's clock anchors.
        found = findings_of(MetricRegistryDiscipline, """
            import jax
            from horovod_tpu import trace
            @jax.jit
            def step(x):
                with trace.span("step"):
                    x = x + 1
                return x
        """)
        assert len(found) == 1
        assert "clocks the TRACE" in found[0].message

    def test_trace_span_alias_inside_scan_flagged(self):
        found = findings_of(MetricRegistryDiscipline, """
            from jax import lax
            from horovod_tpu import trace as trace_lib
            def body(c, t):
                trace_lib.emit_span("decode", 0.0, 0.1)
                return c, t
            lax.scan(body, 0, None)
        """)
        assert len(found) == 1
        assert "emit_span" in found[0].message

    def test_trace_span_on_host_side_clean(self):
        found = findings_of(MetricRegistryDiscipline, """
            import jax
            from horovod_tpu import trace
            @jax.jit
            def step(x):
                return x + 1
            def loop(x):
                with trace.span("step", epoch=0):
                    x = step(x)
                return x
        """)
        assert found == []

    def test_serving_tier_names_declared_clean(self):
        # PR 17: the serving tier's scheduler/router series are declared
        # in obs/core like every other subsystem — the scrape collectors
        # and the router's pre-materialized zero-500s series lint clean,
        # and a typo'd serve series is caught like any other.
        found = findings_of(MetricRegistryDiscipline, """
            def collect(reg, s):
                reg.counter_set("hvt_serve_admitted_total", s["a"])
                reg.counter_set("hvt_serve_retired_total", s["r"])
                reg.counter_set("hvt_serve_rejected_total", s["x"])
                reg.gauge("hvt_serve_live_seqs", s["live"])
                reg.gauge("hvt_serve_kv_blocks_free", s["free"])
                reg.gauge("hvt_serve_replica_inflight", 1, replica="r0")
                reg.histogram("hvt_serve_ttft_seconds", 0.05)
                reg.counter("hvt_serve_swaps_total")
        """)
        assert found == []
        found = findings_of(MetricRegistryDiscipline, """
            def collect(reg):
                reg.gauge("hvt_serve_kv_block_free", 3)  # typo'd: block
        """)
        assert len(found) == 1
        assert "hvt_serve_kv_block_free" in found[0].message

    def test_engine_tick_span_shape_clean_but_not_inside_cont(self):
        # The continuous-batching engine's tick emits a `decode` span
        # with a caller-timed `step` child (admitted/evicted attrs) —
        # legal exactly because both wrap the HOST-side dispatch of the
        # compiled cont program. The same emit_span moved INSIDE the
        # compiled body would clock the trace once and freeze.
        found = findings_of(MetricRegistryDiscipline, """
            import time
            from horovod_tpu import trace as trace_lib
            def tick(decoder, state):
                with trace_lib.span("decode", rows=2):
                    t0w, t0p = time.time(), time.perf_counter()
                    tokens, state = decoder.step(state)
                    trace_lib.emit_span(
                        "step", t0w, time.perf_counter() - t0p,
                        admitted=1, evicted=0, live=2,
                    )
                return tokens, state
        """)
        assert found == []
        found = findings_of(MetricRegistryDiscipline, """
            import jax
            from horovod_tpu import trace as trace_lib
            @jax.jit
            def cont(params, state):
                trace_lib.emit_span("step", 0.0, 0.1, admitted=1)
                return state
        """)
        assert len(found) == 1
        assert "emit_span" in found[0].message

    def test_noqa_suppresses(self, tmp_path):
        res = lint_tree(tmp_path, {
            "pkg/mod.py": """
                from horovod_tpu import obs
                def publish(v):
                    obs.gauge("hvt_bespoke", v)  # hvt: noqa[HVT009] why
            """,
        })
        assert [f for f in res.findings if f.rule == "HVT009"] == []
